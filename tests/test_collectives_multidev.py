"""Multi-device collective correctness, via a subprocess with 8 virtual
CPU devices (tests must not set xla_force_host_platform_device_count
globally).

Covers the 1D backends, and the topology planner's joint multi-axis
plans (hierarchical / 2D xy / 2D snake / flat / sequential) against the
jax.lax references on the (2,2,2) and (2,4) debug meshes -- including
the compress=True error-feedback path over an axis tuple, the FSDP
GradSyncConfig mode against the GSPMD baseline, and every all_to_all
backend/plan shape against ``jax.lax.all_to_all`` (single axis, (2,4)
and (2,2,2) axis tuples, fp32 + bf16)."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.multidev, pytest.mark.slow]

_SCRIPT = r"""
import functools, json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.collectives.api import allreduce_inside, select_algorithm
from repro.collectives.overlap import bucketed_allreduce, bucket_algorithm_plan

results = {}
mesh = make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 96))
vals = jax.device_put(x, NamedSharding(mesh, P("data", None)))
expected = np.tile(np.asarray(x).sum(0), (8, 1))

for algo in ("psum", "chain", "tree", "two_phase", "star", "ring", "autogen", "autogen_pipelined", "auto"):
    fn = shard_map(functools.partial(allreduce_inside, axis="data", algorithm=algo),
                   mesh=mesh, in_specs=P("data", None), out_specs=P("data", None),
                   check_vma=False)
    out = np.asarray(jax.jit(fn)(vals))
    results[f"allreduce_{algo}"] = bool(np.allclose(out, expected, rtol=1e-4, atol=1e-4))

# 2-axis hierarchy (two-phase across pod x data)
mesh2 = make_mesh((2, 4), ("pod", "data"))
v2 = jax.device_put(x, NamedSharding(mesh2, P(("pod", "data"), None)))
def hier(v):
    v = allreduce_inside(v, "data", algorithm="chain")
    v = allreduce_inside(v, "pod", algorithm="chain")
    return v
fn2 = shard_map(hier, mesh=mesh2, in_specs=P(("pod", "data"), None),
                out_specs=P(("pod", "data"), None), check_vma=False)
out2 = np.asarray(jax.jit(fn2)(v2))
results["hierarchical_two_phase"] = bool(np.allclose(out2, expected, rtol=1e-4, atol=1e-4))

# bucketed allreduce with compression + error feedback
grads = {"a": jnp.ones((1000,)) * 0.5, "b": jnp.full((64, 32), 2.0)}
reduced, ef = bucketed_allreduce(grads, mesh, axes=("data",), algorithm="ring",
                                 bucket_bytes=2048, compress=True,
                                 error_feedback=jax.tree.map(jnp.zeros_like, grads))
ok_a = bool(np.allclose(np.asarray(reduced["a"]), 0.5, rtol=1e-2))
ok_b = bool(np.allclose(np.asarray(reduced["b"]), 2.0, rtol=1e-2))
results["bucketed_compressed"] = ok_a and ok_b
results["error_feedback_exists"] = ef is not None

plan = bucket_algorithm_plan(grads, mesh, bucket_bytes=2048)
results["plan_nonempty"] = len(plan) > 1

# ---------------- topology planner: joint multi-axis plans ------------ #
from repro.collectives.api import (allreduce_multi_inside,
                                   reduce_scatter_multi_inside,
                                   allgather_multi_inside)

mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
x3 = jax.random.normal(jax.random.PRNGKey(2), (16, 6))

def run3(fn, in_spec, out_spec):
    f = shard_map(fn, mesh=mesh3, in_specs=in_spec, out_specs=out_spec,
                  check_vma=False)
    return np.asarray(jax.jit(f)(x3))

for axes in (("pod", "data"), ("pod", "data", "model")):
    ref = run3(lambda v: jax.lax.psum(v, axes), P(), P())
    shapes = ("auto", "sequential", "hierarchical", "flat")
    if len(axes) == 2:
        shapes += ("2d_xy", "2d_snake")
    for shape in shapes:
        out = run3(functools.partial(allreduce_multi_inside, axes=axes,
                                     algorithm=shape), P(), P())
        results[f"ar_multi_{len(axes)}ax_{shape}"] = bool(
            np.allclose(out, ref, rtol=1e-4, atol=1e-4))

    ref = run3(lambda v: jax.lax.psum_scatter(v, axes,
                                              scatter_dimension=0,
                                              tiled=True), P(), P(axes))
    for shape in ("auto", "cascade", "flat"):
        out = run3(functools.partial(reduce_scatter_multi_inside,
                                     axes=axes, algorithm=shape),
                   P(), P(axes))
        results[f"rs_multi_{len(axes)}ax_{shape}"] = bool(
            np.allclose(out, ref, rtol=1e-4, atol=1e-4))

    ref = run3(lambda v: jax.lax.all_gather(v, axes, tiled=True),
               P(axes), P())
    for shape in ("auto", "cascade", "flat"):
        out = run3(functools.partial(allgather_multi_inside, axes=axes,
                                     algorithm=shape), P(axes), P())
        results[f"ag_multi_{len(axes)}ax_{shape}"] = bool(
            np.allclose(out, ref))

# (2, 4) debug mesh: planner plans over ("data", "model"), odd vector
# length exercising the hierarchical pad path
mesh24 = make_mesh((2, 4), ("data", "model"))
y = jax.random.normal(jax.random.PRNGKey(3), (13,))
def run24(fn):
    f = shard_map(fn, mesh=mesh24, in_specs=P(), out_specs=P(),
                  check_vma=False)
    return np.asarray(jax.jit(f)(y))
ref = run24(lambda v: jax.lax.psum(v, ("data", "model")))
for shape in ("auto", "hierarchical", "2d_xy", "2d_snake", "flat"):
    out = run24(functools.partial(allreduce_multi_inside,
                                  axes=("data", "model"),
                                  algorithm=shape))
    results[f"ar_multi_24_{shape}"] = bool(
        np.allclose(out, ref, rtol=1e-4, atol=1e-4))

# multi-axis bucketed allreduce: compress=True error-feedback over the
# ("pod", "data") tuple routes each bucket through the planner
mesh22 = make_mesh((2, 2, 2), ("pod", "data", "model"))
reduced, ef = bucketed_allreduce(
    grads, mesh22, axes=("pod", "data"), bucket_bytes=2048,
    compress=True,
    error_feedback=jax.tree.map(jnp.zeros_like, grads))
results["bucketed_multi_compressed"] = (
    bool(np.allclose(np.asarray(reduced["a"]), 0.5, rtol=1e-2))
    and bool(np.allclose(np.asarray(reduced["b"]), 2.0, rtol=1e-2))
    and ef is not None)

mplan = bucket_algorithm_plan(grads, mesh22, axes=("pod", "data"),
                              bucket_bytes=2048)
results["multi_plan_reports_shapes"] = len(mplan) > 1 and all(
    "(" in desc for _, desc in mplan)

# ------------------- all_to_all vs the lax references ------------------ #
from repro.collectives.api import all_to_all_inside, all_to_all_multi_inside

def a2a_check(mesh_shape, mesh_axes, axes, dtype, tag):
    mesh_a = make_mesh(mesh_shape, mesh_axes)
    p = 1
    for a in axes:
        p *= mesh_a.shape[a]
    xa = jax.random.normal(jax.random.PRNGKey(7),
                           (p * 3, 5)).astype(dtype)
    axis_ref = axes if len(axes) > 1 else axes[0]
    ref_fn = shard_map(
        lambda v: jax.lax.all_to_all(v, axis_ref, 0, 0, tiled=True),
        mesh=mesh_a, in_specs=P(), out_specs=P(), check_vma=False)
    with jax.set_mesh(mesh_a):
        ref = np.asarray(jax.jit(ref_fn)(xa), np.float32)
    algos = (("auto", "ring", "halving") if len(axes) == 1 else
             ("auto", "hierarchical", "sequential", "flat", "ring",
              "halving"))
    for algo in algos:
        if len(axes) == 1:
            body = functools.partial(all_to_all_inside, axis=axes[0],
                                     algorithm=algo)
        else:
            body = functools.partial(all_to_all_multi_inside, axes=axes,
                                     algorithm=algo)
        fn = shard_map(body, mesh=mesh_a, in_specs=P(), out_specs=P(),
                       check_vma=False)
        with jax.set_mesh(mesh_a):
            out = np.asarray(jax.jit(fn)(xa), np.float32)
        results[f"a2a_{tag}_{algo}"] = bool(
            np.allclose(out, ref, rtol=1e-4, atol=1e-4))

for dtype, dtag in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
    a2a_check((8,), ("data",), ("data",), dtype, f"1d_{dtag}")
    a2a_check((2, 4), ("pod", "data"), ("pod", "data"), dtype,
              f"24_{dtag}")
    a2a_check((2, 2, 2), ("pod", "data", "model"),
              ("pod", "data", "model"), dtype, f"222_{dtag}")
print("JSON" + json.dumps(results))
"""


def test_collectives_on_8_devices():
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
