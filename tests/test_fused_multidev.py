"""Fused matmul+reduce-scatter execution on 8 virtual devices, via a
subprocess (tests must not set xla_force_host_platform_device_count
globally).

Covers the Pallas ring kernel against the einsum oracle and the
serialized GEMM-then-RS on the 1D ``model`` axis and the folded
``(pod, data)`` FSDP layout, at FFN-sized and MQA-decode-sized shapes;
the engine executor's ``auto`` / forced-``fused`` / forced-``unfused``
agreement and its ``w=None`` grad-sync degenerate; the fused swiglu
down-projection (forward and gradients) against the GSPMD reference;
and the engine's one-shot latency dispatch (result + stats counter)."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.multidev, pytest.mark.slow]

_SCRIPT = r"""
import functools, json
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.collectives.api import get_engine
from repro.kernels.fused_matmul_rs import fused_matmul_rs, matmul_then_rs
from repro.kernels.ref import fused_matmul_rs_ref

results = {}
eng = get_engine()
key = jax.random.PRNGKey(7)

# ------------------------------------------------------------------ #
# kernel vs oracle: 1D model axis, FFN-sized and MQA-sized shapes
# ------------------------------------------------------------------ #
mesh = make_mesh((8,), ("model",))
for tag, (m, k, n) in (("ffn", (64, 512, 48)), ("mqa", (16, 64, 24))):
    kx, kw, key = *jax.random.split(key, 2), key
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "model")))
    ws = jax.device_put(w, NamedSharding(mesh, P("model", None)))
    want = fused_matmul_rs_ref(
        np.asarray(x).reshape(m, 8, k // 8).transpose(1, 0, 2),
        np.asarray(w).reshape(8, k // 8, n)).reshape(m, n)
    for name, body in (
            ("fused", lambda xl, wl: fused_matmul_rs(xl, wl, "model")),
            ("unfused", lambda xl, wl: matmul_then_rs(xl, wl, "model"))):
        fn = shard_map(body, mesh=mesh,
                       in_specs=(P(None, "model"), P("model", None)),
                       out_specs=P("model", None), check_vma=False)
        out = np.asarray(jax.jit(fn)(xs, ws))
        results[f"kernel_{tag}_{name}"] = bool(
            np.allclose(out, want, rtol=1e-5, atol=1e-5))

# ------------------------------------------------------------------ #
# folded (pod, data) FSDP layout
# ------------------------------------------------------------------ #
mesh2 = make_mesh((2, 4), ("pod", "data"))
m, k, n = 64, 512, 32
kx, kw, key = *jax.random.split(key, 2), key
x = jax.random.normal(kx, (m, k), jnp.float32)
w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
xs = jax.device_put(x, NamedSharding(mesh2, P(None, ("pod", "data"))))
ws = jax.device_put(w, NamedSharding(mesh2, P(("pod", "data"), None)))
want = np.asarray(x, np.float32) @ np.asarray(w, np.float32)
fn = shard_map(lambda xl, wl: fused_matmul_rs(xl, wl, ("pod", "data")),
               mesh=mesh2,
               in_specs=(P(None, ("pod", "data")), P(("pod", "data"), None)),
               out_specs=P(("pod", "data"), None), check_vma=False)
out = np.asarray(jax.jit(fn)(xs, ws))
results["kernel_folded_fsdp"] = bool(
    np.allclose(out, want, rtol=1e-4, atol=1e-4))

# ------------------------------------------------------------------ #
# engine executor: auto / forced-fused / forced-unfused agree; the
# w=None grad-sync degenerate equals psum_scatter
# ------------------------------------------------------------------ #
mesh = make_mesh((8,), ("model",))
xs = jax.device_put(x, NamedSharding(mesh, P(None, "model")))
ws = jax.device_put(w, NamedSharding(mesh, P("model", None)))
for algo in ("auto", "fused", "unfused"):
    fn = shard_map(
        lambda xl, wl, a=algo: eng.fused_matmul_reduce_scatter(
            xl, wl, "model", algorithm=a),
        mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
        out_specs=P("model", None), check_vma=False)
    out = np.asarray(jax.jit(fn)(xs, ws))
    results[f"engine_{algo}"] = bool(
        np.allclose(out, want, rtol=1e-4, atol=1e-4))

g = jax.random.normal(key, (64, 16), jnp.float32)
gs = jax.device_put(g, NamedSharding(mesh, P(None, None)))
def degenerate(gl):
    a = eng.fused_matmul_reduce_scatter(gl, None, ("model",))
    b = lax.psum_scatter(gl, "model", tiled=True)
    return a, b
fn = shard_map(degenerate, mesh=mesh, in_specs=P(None, None),
               out_specs=(P("model", None), P("model", None)),
               check_vma=False)
a, b = jax.jit(fn)(gs)
results["engine_w_none_degenerate"] = bool(
    np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5))

# ------------------------------------------------------------------ #
# fused swiglu down-projection: forward + grads vs GSPMD reference
# ------------------------------------------------------------------ #
from repro.models import layers

mesh_tp = make_mesh((2, 4), ("data", "model"))
b_, s_, d_, f_ = 8, 16, 32, 64
ks = jax.random.split(jax.random.PRNGKey(3), 4)
xin = jax.random.normal(ks[0], (b_, s_, d_), jnp.float32)
wg = jax.random.normal(ks[1], (d_, f_), jnp.float32) / np.sqrt(d_)
wu = jax.random.normal(ks[2], (d_, f_), jnp.float32) / np.sqrt(d_)
wd = jax.random.normal(ks[3], (f_, d_), jnp.float32) / np.sqrt(f_)

def loss(params, x):
    y = layers.swiglu(x, *params)
    return jnp.sum(y * y)

with jax.set_mesh(mesh_tp):
    layers.set_fused_tp(False)
    ref_l, ref_g = jax.value_and_grad(loss)((wg, wu, wd), xin)
    layers.set_fused_tp(True)
    fus_l, fus_g = jax.value_and_grad(loss)((wg, wu, wd), xin)
    layers.set_fused_tp(False)
results["swiglu_fused_forward"] = bool(
    np.allclose(float(ref_l), float(fus_l), rtol=1e-5))
results["swiglu_fused_grads"] = all(
    bool(np.allclose(np.asarray(r), np.asarray(f), rtol=1e-4, atol=1e-4))
    for r, f in zip(ref_g, fus_g))

# ------------------------------------------------------------------ #
# one-shot latency dispatch: correct result, counted in stats
# ------------------------------------------------------------------ #
mesh = make_mesh((8,), ("data",))
v = jax.random.normal(jax.random.PRNGKey(11), (8, 8), jnp.float32)
vs = jax.device_put(v, NamedSharding(mesh, P("data", None)))
before = eng.stats["latency_dispatches"]
fn = shard_map(
    lambda x: eng.allreduce_inside(x, "data", algorithm="oneshot"),
    mesh=mesh, in_specs=P("data", None), out_specs=P("data", None),
    check_vma=False)
out = np.asarray(jax.jit(fn)(vs))
want = np.tile(np.asarray(v).sum(0), (8, 1))
results["oneshot_allreduce_value"] = bool(
    np.allclose(out, want, rtol=1e-4, atol=1e-4))
results["oneshot_counted"] = eng.stats["latency_dispatches"] > before

print("JSON" + json.dumps(results))
"""


def test_fused_on_8_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON")][-1]
    results = json.loads(line[4:])
    for key, ok in results.items():
        assert ok, (key, results)
