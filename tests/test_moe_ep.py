"""Expert-parallel MoE correctness on 8 virtual devices (subprocess):

* the replicated-token shard_map path == GSPMD MoE (logits + grads);
* the true EP dispatch (``models/moe_ep.py``: tokens sharded over the
  EP axes, dispatch/combine as explicit all-to-all) matches the dense
  reference to fp32 tolerance under both the bare-lax single-shot and
  the engine-routed exchange, on the ("data","model") mesh and the
  folded ("pod","data") expert mesh, logits and grads."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.multidev, pytest.mark.slow]

_SCRIPT = r"""
import json, dataclasses
import numpy as np, jax
from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh
from repro.models import init_params, forward_train
from repro.train.step import loss_fn

cfg = get_config("olmoe-1b-7b").reduced()
cfg = dataclasses.replace(cfg, dtype="float32", num_experts=8,
                          experts_per_token=2)
key = jax.random.PRNGKey(0)
params = init_params(key, cfg)
batch = {"tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
         "labels": jax.random.randint(jax.random.fold_in(key, 1), (4, 32),
                                      0, cfg.vocab_size)}
cfg_ep = dataclasses.replace(cfg, moe_shardmap_ep=True)
mesh = make_debug_mesh()

lp, _ = forward_train(params, cfg, batch)
with jax.set_mesh(mesh):
    le, _ = jax.jit(lambda p, b: forward_train(p, cfg_ep, b))(params, batch)
logit_err = float(np.max(np.abs(np.asarray(lp) - np.asarray(le))))

g1 = jax.grad(lambda p: loss_fn(p, cfg, batch)[0])(params)
with jax.set_mesh(mesh):
    g2 = jax.jit(jax.grad(lambda p: loss_fn(p, cfg_ep, batch)[0]))(params)
grad_err = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                   - np.asarray(b, np.float32))))
               for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
print("JSON" + json.dumps({"logit_err": logit_err, "grad_err": grad_err}))
"""


def _run_sub(script: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON")][-1]
    return json.loads(line[4:])


def test_moe_ep_matches_gspmd():
    res = _run_sub(_SCRIPT)
    assert res["logit_err"] < 1e-3, res
    assert res["grad_err"] < 5e-3, res


_EP_SCRIPT = r"""
import json, dataclasses, functools
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.models.moe import moe_ffn
from repro.models.moe_ep import moe_ffn_ep

key = jax.random.PRNGKey(0)
G, gs, D, E, F, K = 8, 16, 12, 8, 24, 2
ks = jax.random.split(key, 5)
x = jax.random.normal(ks[0], (G, gs, D), jnp.float32)
router = jax.random.normal(ks[1], (D, E)) * 0.5
wg = jax.random.normal(ks[2], (E, D, F)) * 0.1
wu = jax.random.normal(ks[3], (E, D, F)) * 0.1
wd = jax.random.normal(ks[4], (E, F, D)) * 0.1
ref, _ = moe_ffn(x, router, wg, wu, wd, top_k=K)
ref = np.asarray(ref)

res = {}
for mesh_shape, mesh_axes in (((2, 4), ("data", "model")),
                              ((2, 4), ("pod", "data"))):
    mesh = make_mesh(mesh_shape, mesh_axes)
    outs = {}
    for algo in ("lax", "auto", "hierarchical", "flat"):
        with jax.set_mesh(mesh):
            out, _ = jax.jit(functools.partial(
                moe_ffn_ep, top_k=K, algorithm=algo))(x, router, wg,
                                                      wu, wd)
        outs[algo] = np.asarray(out)
    tag = "x".join(mesh_axes)
    res[f"dense_err_{tag}"] = max(
        float(np.max(np.abs(o - ref))) for o in outs.values())
    res[f"lax_vs_engine_{tag}"] = float(
        np.max(np.abs(outs["auto"] - outs["lax"])))

# gradient flow through the engine exchange == through bare lax
mesh = make_mesh((2, 4), ("pod", "data"))
def loss(params, algo):
    r, a, b, c = params
    out, _ = moe_ffn_ep(x, r, a, b, c, top_k=K, algorithm=algo)
    return jnp.sum(out ** 2)
with jax.set_mesh(mesh):
    g_lax = jax.jit(jax.grad(lambda p: loss(p, "lax")))((router, wg, wu, wd))
    g_eng = jax.jit(jax.grad(lambda p: loss(p, "auto")))((router, wg, wu, wd))
res["grad_err"] = max(
    float(np.max(np.abs(np.asarray(u) - np.asarray(v))))
    for u, v in zip(jax.tree.leaves(g_lax), jax.tree.leaves(g_eng)))
print("JSON" + json.dumps(res))
"""


def test_moe_ep_engine_matches_bare_lax():
    """Acceptance: the engine-routed EP forward matches the bare-lax EP
    path (and the dense moe_ffn reference) to fp32 tolerance on 8
    devices, on both the ("data","model") and the folded ("pod","data")
    expert mesh; gradients agree through the exchange."""
    res = _run_sub(_EP_SCRIPT)
    for tag in ("dataxmodel", "podxdata"):
        assert res[f"dense_err_{tag}"] < 1e-4, res
        assert res[f"lax_vs_engine_{tag}"] < 1e-5, res
    assert res["grad_err"] < 1e-4, res
