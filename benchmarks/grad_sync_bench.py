"""Gradient-synchronization microbenchmark: the paper's model-driven
reduction scheduling applied to DP gradient AllReduce, now with the
topology planner's joint multi-axis plans.

Two tiers, both emitting ``BENCH_grad_sync.json``:

* **big** (default): the 512-chip multi-pod mesh
  (pod=2 x data=16 x model=16).  Compares, from compiled HLO:

    psum_flat   -- XLA-native AllReduce over the flattened (pod, data)
    psum_hier   -- XLA AllReduce over 'data' then 'pod'
    two_phase / ring / tree -- per-axis ppermute ladders
    sequential / hierarchical / flat -- the planner's joint shapes
    auto        -- the planner's argmin for the topology

* **small** (``--small``; CI): the 8-device (pod=2 x data=4) debug
  mesh, sweeping every plan shape (incl. 2d_xy / 2d_snake) across
  bucket sizes -- the per-bucket heatmap of the multi-axis selector.

``--fabric 'pod=slow,data=fast'`` (or a JSON topology file) prices the
mesh with heterogeneous per-axis link constants; ``check()`` then also
asserts the slow cross-pod link drives every bandwidth-bound bucket to
the hierarchical composition.

Metrics per variant: collective bytes/device from the per-device HLO,
collective op count (sequential depth proxy), plus the spatial model's
per-shape predictions and per-axis modeled wire bytes from
``CollectivePlan.cost_terms``.  Runs itself in a subprocess so the
XLA_FLAGS device-count override never leaks into the parent.
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
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(devices)d"
import json, functools
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.collectives.api import (allreduce_inside, allreduce_multi_inside,
                                   select_algorithm)
from repro.launch.roofline import parse_collective_bytes, collective_total

FABRIC_SPEC = %(fabric_spec)r
if FABRIC_SPEC:
    from repro.launch.train import install_fabric_topology
    install_fabric_topology(FABRIC_SPEC)

mesh = make_mesh(%(mesh_shape)s, %(mesh_axes)s)
AXES = ("pod", "data")
PLAN_SHAPES = %(plan_shapes)s

def variant(name, nbytes):
    if name == "psum_flat":
        return lambda g: jax.lax.psum(g, AXES)
    if name == "psum_hier":
        def f(g):
            return jax.lax.psum(jax.lax.psum(g, "data"), "pod")
        return f
    if name == "auto" or name in PLAN_SHAPES:
        return functools.partial(allreduce_multi_inside, axes=AXES,
                                 algorithm=name)
    def f(g):   # legacy per-axis ladder with a fixed 1D backend
        g = allreduce_inside(g, "data", algorithm=name)
        return allreduce_inside(g, "pod", algorithm=name)
    return f

results = {}
spec = P()   # gradient replicated over all axes (pure-DP layout)
for nbytes in %(bucket_sizes)s:
    n = nbytes // 4
    per_size = {}
    for name in %(variants)s:
        fn = shard_map(variant(name, nbytes), mesh=mesh, in_specs=spec,
                       out_specs=spec, check_vma=False)
        with jax.set_mesh(mesh):
            compiled = jax.jit(fn).lower(
                jax.ShapeDtypeStruct((n,), jnp.float32)).compile()
        coll = parse_collective_bytes(compiled.as_text())
        per_size[name] = {
            "bytes_per_dev": collective_total(coll),
            "ops": int(sum(v["count"] for v in coll.values())),
        }
    results[str(nbytes)] = per_size
results["selector_choice"] = {
    "data_axis": select_algorithm(1 << 26, mesh.shape["data"]),
    "pod_axis": select_algorithm(1 << 26, mesh.shape["pod"]),
}
print("JSON" + json.dumps(results))
"""

BIG_VARIANTS = ("psum_flat", "psum_hier", "two_phase", "ring", "tree",
                "sequential", "hierarchical", "hierarchical_pipelined",
                "flat", "auto")
SMALL_VARIANTS = ("psum_flat", "sequential", "hierarchical",
                  "sequential_pipelined", "hierarchical_pipelined",
                  "2d_xy", "2d_snake", "flat", "auto")

PLAN_SHAPES = ("sequential", "hierarchical", "2d_xy", "2d_snake",
               "flat", "sequential_pipelined", "hierarchical_pipelined")


def _base(shape: str) -> str:
    suffix = "_pipelined"
    return shape[:-len(suffix)] if shape.endswith(suffix) else shape


def _model_plans(pod: int, data: int, bucket_sizes,
                 fabric_spec: str | None = None):
    """Planner-side view: per-bucket joint predictions + per-axis
    modeled wire bytes (no devices needed)."""
    from repro.collectives.engine import CollectiveEngine

    if fabric_spec:
        from repro.core.model import parse_fabric_topology
        eng = CollectiveEngine(fabric=parse_fabric_topology(fabric_spec),
                              persist=False)
    else:
        eng = CollectiveEngine(persist=False)
    out = {}
    for nbytes in bucket_sizes:
        plan = eng.plan_multi("allreduce", ("pod", "data"), (pod, data),
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


def run(small: bool = False, verbose: bool = True,
        fabric_spec: str | None = None):
    if small:
        devices, mesh_shape, mesh_axes = 8, (2, 4), ("pod", "data")
        bucket_sizes = (1 << 16, 1 << 20, 16 << 20)
        variants = SMALL_VARIANTS
    else:
        devices, mesh_shape = 512, (2, 16, 16)
        mesh_axes = ("pod", "data", "model")
        bucket_sizes = (64 << 20,)
        variants = BIG_VARIANTS
    child = _CHILD % {
        "devices": devices, "mesh_shape": mesh_shape,
        "mesh_axes": mesh_axes, "bucket_sizes": list(bucket_sizes),
        "variants": list(variants), "fabric_spec": fabric_spec,
        "plan_shapes": list(PLAN_SHAPES),
    }
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    # gated counters must not depend on a machine-local calibration:
    # the child prices with the declared constants only
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
    pod, data = mesh_shape[0], mesh_shape[1]
    results["mesh"] = {"pod": pod, "data": data}
    results["fabric_spec"] = fabric_spec
    results["model"] = _model_plans(pod, data, bucket_sizes, fabric_spec)
    if verbose:
        for nbytes in bucket_sizes:
            per = results[str(nbytes)]
            for name, r in per.items():
                emit(f"grad_sync/{nbytes}/{name}", 0.0,
                     f"{r['bytes_per_dev'] / 1e6:.1f}MB/dev,{r['ops']}ops")
            emit(f"grad_sync/{nbytes}/plan", 0.0,
                 results["model"][str(nbytes)]["plan"])
    return results


def check(results):
    """Invariants the perf trajectory must keep."""
    hetero = bool(results.get("fabric_spec"))
    for nbytes, model in results["model"].items():
        per = results[nbytes]
        # hierarchical moves strictly fewer modeled cross-pod bytes
        # than the sequential per-axis path
        ab = model["axis_bytes"]
        assert ab["hierarchical"]["pod"] < ab["sequential"]["pod"], nbytes
        # ... and than the flat folded schedule
        assert ab["hierarchical"]["pod"] < ab["flat"]["pod"], nbytes
        # no shape beats the 2D lower bound
        assert all(t >= model["lower_bound"] - 1e-6
                   for t in model["predictions"].values()), nbytes
        # in the bandwidth-bound region (>= 1 MiB buckets: every phase
        # rides ring) the hierarchical composition also compiles to
        # strictly fewer wire bytes per device than the sequential
        # per-axis ladder; below that, latency-optimal per-phase picks
        # make raw byte counts incomparable
        if int(nbytes) >= 1 << 20:
            assert (per["hierarchical"]["bytes_per_dev"]
                    < per["sequential"]["bytes_per_dev"]), nbytes
        # `auto` executes the modeled argmin's byte profile
        best = min(model["predictions"], key=model["predictions"].get)
        assert (per["auto"]["bytes_per_dev"]
                == per[best]["bytes_per_dev"]), (nbytes, best)
        # a slow cross-pod link must drive the joint argmin to the
        # hierarchical composition (chunk-pipelined or not) at
        # bandwidth-bound bucket sizes
        if hetero and int(nbytes) >= 1 << 20:
            assert _base(best) == "hierarchical", (nbytes, best)
    if not hetero:
        assert results["selector_choice"]["data_axis"] == "ring"


def main(out_path: str = "BENCH_grad_sync.json", small: bool = False,
         fabric_spec: str | None = None):
    results = run(small=small, fabric_spec=fabric_spec)
    check(results)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    emit("grad_sync/json", 0.0, out_path)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="8-device debug mesh, full shape sweep (CI)")
    ap.add_argument("--fabric", default=None, metavar="SPEC",
                    help="heterogeneous topology spec "
                         "('pod=slow,data=fast' or a JSON path)")
    ap.add_argument("--out", default="BENCH_grad_sync.json")
    args = ap.parse_args()
    main(out_path=args.out, small=args.small, fabric_spec=args.fabric)
