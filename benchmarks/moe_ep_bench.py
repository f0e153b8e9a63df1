"""Expert-parallel MoE dispatch microbenchmark: the model-priced
AllToAll subsystem against the bare-lax single-shot, on the 8-device
("pod", "data") expert mesh.  Emits ``BENCH_moe_ep.json``.

Two measurement layers, both from compiled per-device HLO:

* **a2a sweep** -- one dispatch-shaped exchange per payload size and
  backend (``lax`` single-shot, the planner shapes ``flat`` /
  ``sequential`` / ``hierarchical`` plus their chunk-pipelined
  variants, and ``auto``): collective bytes/device + op count
  (sequential-depth proxy).
* **moe_forward** -- a full ``moe_ffn_ep`` forward (dispatch + combine)
  under the bare-lax and engine paths.

The ``model`` section reports, per payload size, the planner's joint
predictions, the Theta(B*(P-1)/P) lower bound, and the modeled per-axis
wire bytes from ``CollectivePlan.cost_terms`` -- modeled vs compiled
bytes per dispatch, side by side.  ``check()`` asserts the acceptance
properties: every candidate >= the lower bound, hierarchical moves
strictly fewer modeled cross-pod bytes than the flat single-shot, and
``auto`` compiles to the argmin's byte profile.  With ``--fabric
pod=slow`` the slow cross-pod link must drive the argmin to the
hierarchical 2-phase decomposition.

Runs itself in a subprocess so the XLA_FLAGS device-count override
never leaks into the parent.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import emit

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, functools
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.collectives.api import all_to_all_multi_inside, get_engine
from repro.launch.roofline import parse_collective_bytes, collective_total

FABRIC_SPEC = %(fabric_spec)r
if FABRIC_SPEC:
    from repro.launch.train import install_fabric_topology
    install_fabric_topology(FABRIC_SPEC)

mesh = make_mesh((2, 4), ("pod", "data"))
AXES = ("pod", "data")
P_WORLD = 8

def compiled_counters(fn, x):
    smfn = shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                     check_vma=False)
    with jax.set_mesh(mesh):
        compiled = jax.jit(smfn).lower(
            jax.ShapeDtypeStruct(x.shape, x.dtype)).compile()
    coll = parse_collective_bytes(compiled.as_text())
    return {"bytes_per_dev": collective_total(coll),
            "ops": int(sum(v["count"] for v in coll.values()))}

results = {}
for nbytes in %(payload_sizes)s:
    n = nbytes // 4
    n -= n %% P_WORLD
    x = jax.ShapeDtypeStruct((n,), jnp.float32)
    per = {}
    for name in ("lax", "flat", "sequential", "hierarchical",
                 "sequential_pipelined", "hierarchical_pipelined",
                 "auto"):
        per[name] = compiled_counters(
            functools.partial(all_to_all_multi_inside, axes=AXES,
                              algorithm=name), x)
    results[str(nbytes)] = per

# full EP forward: dispatch + combine through one MoE layer
from repro.models.moe_ep import moe_ffn_ep
G, gs, D, E, F, K = 8, 32, 64, 8, 128, 2
key = jax.random.PRNGKey(0)
ks = jax.random.split(key, 5)
args = (jax.random.normal(ks[0], (G, gs, D), jnp.float32),
        jax.random.normal(ks[1], (D, E)) * 0.5,
        jax.random.normal(ks[2], (E, D, F)) * 0.1,
        jax.random.normal(ks[3], (E, D, F)) * 0.1,
        jax.random.normal(ks[4], (E, F, D)) * 0.1)
fwd = {}
for name in ("lax", "auto"):
    with jax.set_mesh(mesh):
        compiled = jax.jit(functools.partial(
            moe_ffn_ep, top_k=K, algorithm=name)).lower(*args).compile()
    coll = parse_collective_bytes(compiled.as_text())
    fwd[name] = {"bytes_per_dev": collective_total(coll),
                 "ops": int(sum(v["count"] for v in coll.values()))}
results["moe_forward"] = fwd
print("JSON" + json.dumps(results))
"""


def _model_plans(payload_sizes, fabric_spec: str | None = None):
    """Planner-side view: per-size joint predictions, the lower bound,
    and modeled per-axis wire bytes (no devices needed)."""
    from repro.collectives.engine import CollectiveEngine

    if fabric_spec:
        from repro.core.model import parse_fabric_topology
        eng = CollectiveEngine(fabric=parse_fabric_topology(fabric_spec),
                               persist=False)
    else:
        eng = CollectiveEngine(persist=False)
    out = {}
    for nbytes in payload_sizes:
        plan = eng.plan_multi("all_to_all", ("pod", "data"), (2, 4),
                              nbytes)
        out[str(nbytes)] = {
            "plan": plan.describe(),
            "n_chunks": plan.n_chunks,
            "predictions": plan.predictions,
            "lower_bound": plan.lower_bound,
            "axis_bytes": {shape: entry["axis_bytes"]
                           for shape, entry in plan.cost_terms.items()},
        }
    return out


def run(verbose: bool = True, fabric_spec: str | None = None):
    payload_sizes = (1 << 16, 1 << 20, 4 << 20)
    child = _CHILD % {"payload_sizes": list(payload_sizes),
                      "fabric_spec": fabric_spec}
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    # gated counters must not depend on a machine-local calibration:
    # the child prices with the declared constants only, matching the
    # stock-fabric engine _model_plans compares against
    env["REPRO_RESTORE_TOPOLOGY"] = "0"
    # compiled-HLO counters on virtual CPU devices: the child never
    # reaches for an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=1500)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("JSON")][-1]
    results = json.loads(line[4:])
    results["mesh"] = {"pod": 2, "data": 4}
    results["fabric_spec"] = fabric_spec
    results["model"] = _model_plans(payload_sizes, fabric_spec)
    if verbose:
        for nbytes in payload_sizes:
            per = results[str(nbytes)]
            for name, r in per.items():
                emit(f"moe_ep/{nbytes}/{name}", 0.0,
                     f"{r['bytes_per_dev'] / 1e6:.2f}MB/dev,{r['ops']}ops")
            emit(f"moe_ep/{nbytes}/plan", 0.0,
                 results["model"][str(nbytes)]["plan"])
        for name, r in results["moe_forward"].items():
            emit(f"moe_ep/forward/{name}", 0.0,
                 f"{r['bytes_per_dev'] / 1e6:.2f}MB/dev,{r['ops']}ops")
    return results


def check(results):
    """Invariants the perf trajectory must keep."""
    hetero = bool(results.get("fabric_spec"))
    for nbytes, model in results["model"].items():
        per = results[nbytes]
        # no shape beats the Theta(B*(P-1)/P) bound
        assert all(t >= model["lower_bound"] - 1e-6
                   for t in model["predictions"].values()), nbytes
        # the 2-phase decomposition moves strictly fewer modeled
        # cross-pod bytes than the flat single-shot exchange
        ab = model["axis_bytes"]
        assert ab["hierarchical"]["pod"] < ab["flat"]["pod"], nbytes
        # `auto` executes the modeled argmin's compiled byte profile
        best = min(model["predictions"], key=model["predictions"].get)
        assert (per["auto"]["bytes_per_dev"]
                == per[best]["bytes_per_dev"]), (nbytes, best)
        # a slow cross-pod link must keep the argmin on the
        # hierarchical intra-pod/inter-pod decomposition
        # (chunk-pipelined or not)
        if hetero:
            base = (best[:-len("_pipelined")]
                    if best.endswith("_pipelined") else best)
            assert base == "hierarchical", (nbytes, best)
    # the engine forward exchanges no more wire bytes than bare lax
    # (same B per device; the engine path may add ops, not volume);
    # generous 2x headroom keeps CPU-backend HLO layout noise out
    fwd = results["moe_forward"]
    assert fwd["auto"]["bytes_per_dev"] <= 2 * fwd["lax"]["bytes_per_dev"], fwd


def main(out_path: str = "BENCH_moe_ep.json",
         fabric_spec: str | None = None):
    results = run(fabric_spec=fabric_spec)
    check(results)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    emit("moe_ep/json", 0.0, out_path)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fabric", default=None, metavar="SPEC",
                    help="heterogeneous topology spec "
                         "('pod=slow,data=fast' or a JSON path)")
    ap.add_argument("--out", default="BENCH_moe_ep.json")
    args = ap.parse_args()
    main(out_path=args.out, fabric_spec=args.fabric)
