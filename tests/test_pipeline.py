"""Chunk-pipelined plan execution: planner pricing + engine runner.

Fast tier covers the pipelined candidates' pricing properties (they
win on a heterogeneous `pod=slow` topology at bandwidth-bound bucket
sizes, lose below the launch-overhead cutoff, never undercut the
overlap-aware ``lower_bound_multi``, and report their chunk count and
modeled overlap savings in ``cost_terms``).  The multidev tier checks
the wavefront runner's numerical equivalence against the ``jax.lax``
references for every op on the (2, 4) and (2, 2, 2) debug meshes,
including the odd-length pad paths and the compress=True
error-feedback bucketed path over a folded axis tuple.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.collectives import planner
from repro.collectives.engine import CollectiveEngine
from repro.core.model import parse_fabric_topology


def _slow_engine():
    return CollectiveEngine(fabric=parse_fabric_topology("pod=slow"),
                            persist=False)


# --------------------------- planner pricing -------------------------- #
def test_pipelined_wins_on_slow_pod_at_large_buckets():
    """Acceptance: on a pod=slow topology at >= 1 MiB the argmin is a
    pipelined plan, strictly below the best phase-sequential candidate
    and still >= lower_bound_multi."""
    eng = _slow_engine()
    cases = (("allreduce", (2, 4), 1 << 20),
             ("allreduce", (2, 4), 16 << 20),
             ("all_to_all", (2, 4), 1 << 20),
             ("reduce_scatter", (2, 4), 4 << 20),
             ("allgather", (2, 4), 4 << 20))
    for op, sizes, nbytes in cases:
        plan = eng.plan_multi(op, ("pod", "data"), sizes, nbytes)
        assert plan.shape.endswith("_pipelined"), (op, nbytes,
                                                   plan.predictions)
        serial_best = min(t for s, t in plan.predictions.items()
                          if not s.endswith("_pipelined"))
        assert plan.predicted < serial_best, (op, nbytes)
        assert plan.predicted >= plan.lower_bound - 1e-6
        assert plan.n_chunks >= 2
        entry = plan.cost_terms[plan.shape]
        assert entry["n_chunks"] == plan.n_chunks
        assert entry["overlap_saved"] > 0.0
        assert f"[chunks={plan.n_chunks}]" in plan.describe()


def test_pipelined_loses_below_launch_cutoff():
    """Per-chunk launch overhead makes tiny payloads fall back: the
    pipelined variant is priced but loses to its serial base."""
    eng = _slow_engine()
    for op in ("allreduce", "all_to_all"):
        plan = eng.plan_multi(op, ("pod", "data"), (2, 4), 1 << 12)
        assert not plan.shape.endswith("_pipelined"), (op,
                                                       plan.predictions)
        assert (plan.predictions["hierarchical_pipelined"]
                > plan.predictions["hierarchical"])


def test_single_effective_axis_has_no_pipelined_candidates():
    """One effective axis means one link class -- nothing to overlap,
    so no pipelined candidate is priced."""
    eng = _slow_engine()
    for op in ("allreduce", "reduce_scatter", "allgather",
               "all_to_all"):
        plan = eng.plan_multi(op, ("pod", "data"), (1, 8), 1 << 20)
        assert not any(s.endswith("_pipelined")
                       for s in plan.predictions), (op, plan.predictions)
        assert plan.n_chunks == 1


def test_overlap_savings_consistent_with_serial_base():
    """cost_terms reports overlap_saved == serial base predicted minus
    the pipelined predicted, and the pipelined plan ships at least the
    serial plan's wire bytes per axis (chunk quantization only adds)."""
    eng = _slow_engine()
    plan = eng.plan_multi("allreduce", ("pod", "data"), (2, 4), 4 << 20)
    for name, entry in plan.cost_terms.items():
        if not name.endswith("_pipelined"):
            assert "n_chunks" not in entry
            continue
        base = planner.base_shape(name)
        saved = (plan.cost_terms[base]["predicted"]
                 - entry["predicted"])
        assert entry["overlap_saved"] == pytest.approx(saved)
        for ax, b in plan.cost_terms[base]["axis_bytes"].items():
            assert entry["axis_bytes"][ax] >= b - 1e-6, (name, ax)


def test_forced_pipelined_shape_and_chunk_count():
    """Forcing a *_pipelined shape works on a uniform fabric too, and
    the plan carries the model-chosen chunk count."""
    eng = CollectiveEngine(persist=False)
    plan = eng.plan_multi("allreduce", ("pod", "data"), (2, 4), 1 << 20,
                          shape="hierarchical_pipelined")
    assert plan.shape == "hierarchical_pipelined"
    assert plan.n_chunks in planner.PIPELINE_CHUNK_CANDIDATES
    rec = eng.plan_multi("all_to_all", ("pod", "data"), (2, 4), 1 << 20,
                         shape="sequential_pipelined")
    assert rec.n_chunks >= 2
    assert [s.axes[0] for s in rec.steps] == ["pod", "data"]


def test_pipelined_plan_survives_cache_roundtrip(tmp_path):
    """n_chunks and the extra cost_terms keys persist through the plan
    cache (flush + reload)."""
    path = str(tmp_path / "decisions.json")
    eng = CollectiveEngine(fabric=parse_fabric_topology("pod=slow"),
                          cache_path=path)
    p1 = eng.plan_multi("allreduce", ("pod", "data"), (2, 4), 1 << 20)
    assert p1.shape.endswith("_pipelined") and p1.n_chunks >= 2
    eng.flush()
    eng2 = CollectiveEngine(fabric=parse_fabric_topology("pod=slow"),
                            cache_path=path)
    p2 = eng2.plan_multi("allreduce", ("pod", "data"), (2, 4), 1 << 20)
    assert eng2.stats["plan_hits"] == 1
    assert p2.shape == p1.shape and p2.n_chunks == p1.n_chunks
    assert (p2.cost_terms[p2.shape]["overlap_saved"]
            == pytest.approx(p1.cost_terms[p1.shape]["overlap_saved"]))


# ----------------------- multidev equivalence ------------------------- #
_SCRIPT = r"""
import functools, json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.collectives.api import (allreduce_multi_inside,
                                   reduce_scatter_multi_inside,
                                   allgather_multi_inside,
                                   all_to_all_multi_inside)
from repro.collectives.overlap import bucketed_allreduce

results = {}
mesh24 = make_mesh((2, 4), ("pod", "data"))
mesh222 = make_mesh((2, 2, 2), ("pod", "data", "model"))

def run(mesh, fn, x, in_spec, out_spec):
    f = shard_map(fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                  check_vma=False)
    with jax.set_mesh(mesh):
        return np.asarray(jax.jit(f)(x))

for mesh, axes, tag in ((mesh24, ("pod", "data"), "24"),
                        (mesh222, ("pod", "data"), "222sub"),
                        (mesh222, ("pod", "data", "model"), "222")):
    # odd length exercises every chunk/phase pad path
    x = jax.random.normal(jax.random.PRNGKey(1), (13,))
    ref = run(mesh, lambda v: jax.lax.psum(v, axes), x, P(), P())
    for shape in ("sequential_pipelined", "hierarchical_pipelined"):
        out = run(mesh, functools.partial(allreduce_multi_inside,
                                          axes=axes, algorithm=shape),
                  x, P(), P())
        results[f"ar_{tag}_{shape}"] = bool(
            np.allclose(out, ref, rtol=1e-4, atol=1e-4))

    p = 1
    for a in axes:
        p *= mesh.shape[a]
    xs = jax.random.normal(jax.random.PRNGKey(2), (p * 3, 5))
    ref = run(mesh, lambda v: jax.lax.psum_scatter(
        v, axes, scatter_dimension=0, tiled=True), xs, P(), P(axes))
    out = run(mesh, functools.partial(reduce_scatter_multi_inside,
                                      axes=axes,
                                      algorithm="cascade_pipelined"),
              xs, P(), P(axes))
    results[f"rs_{tag}"] = bool(np.allclose(out, ref, rtol=1e-4,
                                            atol=1e-4))

    ref = run(mesh, lambda v: jax.lax.all_gather(v, axes, tiled=True),
              xs, P(axes), P())
    out = run(mesh, functools.partial(allgather_multi_inside, axes=axes,
                                      algorithm="cascade_pipelined"),
              xs, P(axes), P())
    results[f"ag_{tag}"] = bool(np.allclose(out, ref))

    ref = run(mesh, lambda v: jax.lax.all_to_all(
        v, axes if len(axes) > 1 else axes[0], 0, 0, tiled=True),
        xs, P(), P())
    for shape in ("hierarchical_pipelined", "sequential_pipelined"):
        out = run(mesh, functools.partial(all_to_all_multi_inside,
                                          axes=axes, algorithm=shape),
                  xs, P(), P())
        results[f"a2a_{tag}_{shape}"] = bool(
            np.allclose(out, ref, rtol=1e-4, atol=1e-4))

# compress=True error feedback through a forced pipelined plan over the
# folded ("pod", "data") tuple
grads = {"a": jnp.ones((1000,)) * 0.5, "b": jnp.full((64, 32), 2.0)}
reduced, ef = bucketed_allreduce(
    grads, mesh222, axes=("pod", "data"),
    algorithm="hierarchical_pipelined", bucket_bytes=2048,
    compress=True,
    error_feedback=jax.tree.map(jnp.zeros_like, grads))
results["bucketed_pipelined_compressed"] = (
    bool(np.allclose(np.asarray(reduced["a"]), 0.5, rtol=1e-2))
    and bool(np.allclose(np.asarray(reduced["b"]), 2.0, rtol=1e-2))
    and ef is not None)
print("JSON" + json.dumps(results))
"""


@pytest.mark.multidev
@pytest.mark.slow
def test_pipelined_execution_on_8_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("JSON")][-1]
    results = json.loads(line[4:])
    for key, ok in results.items():
        assert ok, (key, results)
