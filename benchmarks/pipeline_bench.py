"""Chunk-pipelined vs phase-sequential plan execution: the A/B harness.

For each fabric config (uniform ICI vs the heterogeneous ``pod=slow``
4x-slower cross-pod link) and each bucket size, compiles the gradient
AllReduce over the 8-device (pod=2 x data=4) debug mesh three ways --
the serial ``hierarchical`` composition, the forced
``hierarchical_pipelined`` variant, and ``auto`` -- and records the
deterministic counters from per-device HLO: collective bytes/device and
collective op count.  The bucket-size sweep doubles as the chunk-count
sweep: the planner's closed form picks ``n_chunks`` per size (1 below
the launch-overhead cutoff, rising with the payload), reported per
point in the ``model`` section alongside the per-shape predictions,
per-axis modeled wire bytes, the overlap-aware lower bound, and the
modeled overlap savings.

``check()`` asserts the acceptance ordering: on ``pod=slow`` at
>= 1 MiB the argmin is a pipelined plan strictly below the best
phase-sequential candidate and still >= ``lower_bound_multi``; on the
compiled counters, ``auto`` executes exactly the argmin's byte/op
profile, pipelining multiplies the phase count by ``n_chunks`` without
inflating wire bytes (measured phase fan-out vs the modeled chunk
count), and tiny buckets fall back to the serial plan.

Emits ``BENCH_pipeline.json``.  Runs itself in a subprocess so the
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
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, functools
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.collectives.api import allreduce_multi_inside
from repro.launch.roofline import parse_collective_bytes, collective_total

FABRIC_SPEC = %(fabric_spec)r
if FABRIC_SPEC:
    from repro.launch.train import install_fabric_topology
    install_fabric_topology(FABRIC_SPEC)

mesh = make_mesh((2, 4), ("pod", "data"))
AXES = ("pod", "data")

results = {}
for nbytes in %(bucket_sizes)s:
    n = nbytes // 4
    per = {}
    for name in %(variants)s:
        fn = shard_map(functools.partial(allreduce_multi_inside,
                                         axes=AXES, algorithm=name),
                       mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
        with jax.set_mesh(mesh):
            compiled = jax.jit(fn).lower(
                jax.ShapeDtypeStruct((n,), jnp.float32)).compile()
        coll = parse_collective_bytes(compiled.as_text())
        per[name] = {
            "bytes_per_dev": collective_total(coll),
            "ops": int(sum(v["count"] for v in coll.values())),
        }
    results[str(nbytes)] = per
print("JSON" + json.dumps(results))
"""

BUCKET_SIZES = (1 << 14, 1 << 20, 4 << 20)
VARIANTS = ("hierarchical", "hierarchical_pipelined", "auto")
FABRIC_CONFIGS = (("uniform", None), ("pod_slow", "pod=slow"))


def _base(shape: str) -> str:
    suffix = "_pipelined"
    return shape[:-len(suffix)] if shape.endswith(suffix) else shape


def _model_plans(bucket_sizes, fabric_spec: str | None):
    """Planner-side view per bucket size: the argmin plan, its chunk
    count, modeled overlap savings, and every candidate's price (no
    devices needed)."""
    from repro.collectives.engine import CollectiveEngine

    if fabric_spec:
        from repro.core.model import parse_fabric_topology
        eng = CollectiveEngine(fabric=parse_fabric_topology(fabric_spec),
                               persist=False)
    else:
        eng = CollectiveEngine(persist=False)
    out = {}
    for nbytes in bucket_sizes:
        plan = eng.plan_multi("allreduce", ("pod", "data"), (2, 4),
                              nbytes)
        entry = plan.cost_terms.get(plan.shape, {})
        out[str(nbytes)] = {
            "plan": plan.describe(),
            "n_chunks": plan.n_chunks,
            "overlap_saved": entry.get("overlap_saved", 0.0),
            "predictions": plan.predictions,
            "lower_bound": plan.lower_bound,
            "axis_bytes": {shape: e["axis_bytes"]
                           for shape, e in plan.cost_terms.items()},
        }
    return out


def run(verbose: bool = True):
    results = {"mesh": {"pod": 2, "data": 4}}
    for tag, fabric_spec in FABRIC_CONFIGS:
        child = _CHILD % {"bucket_sizes": list(BUCKET_SIZES),
                          "variants": list(VARIANTS),
                          "fabric_spec": fabric_spec}
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__),
                                         "..", "src")
        # gated counters must not depend on a machine-local
        # calibration: the child prices with the declared constants
        env["REPRO_RESTORE_TOPOLOGY"] = "0"
        # compiled-HLO counters on virtual CPU devices: the child never
        # reaches for an accelerator the parent may hold
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run([sys.executable, "-c", child], env=env,
                              capture_output=True, text=True,
                              timeout=1500)
        if proc.returncode != 0:
            raise RuntimeError(proc.stderr[-2000:])
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("JSON")][-1]
        compiled = json.loads(line[4:])
        compiled["model"] = _model_plans(BUCKET_SIZES, fabric_spec)
        compiled["fabric_spec"] = fabric_spec
        results[tag] = compiled
        if verbose:
            for nbytes in BUCKET_SIZES:
                per = compiled[str(nbytes)]
                model = compiled["model"][str(nbytes)]
                for name, r in per.items():
                    emit(f"pipeline/{tag}/{nbytes}/{name}", 0.0,
                         f"{r['bytes_per_dev'] / 1e6:.2f}MB/dev,"
                         f"{r['ops']}ops")
                emit(f"pipeline/{tag}/{nbytes}/plan", 0.0,
                     f"{model['plan']} saved={model['overlap_saved']:g}")
    return results


def check(results):
    """The acceptance ordering, on model prices and compiled counters."""
    for tag, _ in FABRIC_CONFIGS:
        part = results[tag]
        for nbytes_s, model in part["model"].items():
            nbytes = int(nbytes_s)
            per = part[nbytes_s]
            preds = model["predictions"]
            best = min(preds, key=preds.get)
            # nothing undercuts the overlap-aware lower bound
            assert all(t >= model["lower_bound"] - 1e-6
                       for t in preds.values()), (tag, nbytes)
            # pipelining conserves wire volume: the chunked plan ships
            # the same compiled bytes as its serial base (pow2 buckets
            # split evenly, so no padding slack either)
            assert (per["hierarchical_pipelined"]["bytes_per_dev"]
                    == per["hierarchical"]["bytes_per_dev"]), (tag,
                                                               nbytes)
            # `auto` executes exactly the argmin's compiled profile
            if best in per:
                assert per["auto"] == per[best], (tag, nbytes, best)
            if tag == "pod_slow" and nbytes >= 1 << 20:
                # the argmin is pipelined, strictly below the best
                # phase-sequential candidate
                assert best.endswith("_pipelined"), (nbytes, preds)
                serial_best = min(t for s, t in preds.items()
                                  if not s.endswith("_pipelined"))
                assert preds[best] < serial_best, (nbytes, preds)
                assert model["n_chunks"] >= 2
                assert model["overlap_saved"] > 0.0
                # measured phase fan-out matches the modeled chunks
                assert (per["hierarchical_pipelined"]["ops"]
                        > per["hierarchical"]["ops"]), nbytes
            if nbytes < 1 << 16:
                # launch overhead: tiny buckets fall back to serial
                assert model["n_chunks"] == 1, (tag, nbytes, model)
                assert not best.endswith("_pipelined"), (tag, nbytes)


def main(out_path: str = "BENCH_pipeline.json"):
    results = run()
    check(results)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    emit("pipeline/json", 0.0, out_path)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_pipeline.json")
    args = ap.parse_args()
    main(out_path=args.out)
