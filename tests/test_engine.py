"""CollectiveEngine: cached model-driven dispatch.

Fast tier: decision-cache hit/miss + persistence, calibration
round-trip, selection sanity -- no devices needed.  Multidev tier: the
new reduce_scatter/allgather/broadcast backends against their jax.lax
references on 8 virtual devices, plus trace-level cache behavior and
the engine-backed train/serve wiring.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.collectives.engine import (CollectiveEngine, SCHEMA_VERSION,
                                      fit_fabric, load_topology,
                                      ICI_ELEMENT_BYTES)
from repro.core.model import FabricTopology, TPU_V5E_AXIS, Fabric


# ------------------------------ decision cache ------------------------ #
def _engine(tmp_path, **kw):
    return CollectiveEngine(cache_path=str(tmp_path / "decisions.json"),
                            **kw)


def test_selection_cache_hit_miss(tmp_path):
    eng = _engine(tmp_path)
    d1 = eng.select("allreduce", 1 << 20, 8)
    assert eng.stats == {"hits": 0, "misses": 1, "dp_runs": 0,
                         "persisted_loads": 0, "plan_hits": 0,
                         "plan_misses": 0, "latency_dispatches": 0}
    d2 = eng.select("allreduce", 1 << 20, 8)
    assert eng.stats["hits"] == 1 and eng.stats["misses"] == 1
    assert d1 == d2
    # a different shape is a fresh miss
    eng.select("allreduce", 1 << 10, 8)
    assert eng.stats["misses"] == 2
    # a different op for the same shape too
    eng.select("broadcast", 1 << 20, 8)
    assert eng.stats["misses"] == 3


def test_autogen_dp_runs_once_per_shape(tmp_path):
    eng = _engine(tmp_path)
    r1 = eng.tree_rounds(8, 64)
    assert eng.stats["dp_runs"] == 1
    r2 = eng.tree_rounds(8, 64)
    assert eng.stats["dp_runs"] == 1 and r1 is r2
    eng.tree_rounds(8, 4096)
    assert eng.stats["dp_runs"] == 2
    # ops whose candidate set includes autogen reuse the cached DP
    eng.select("reduce", 64 * ICI_ELEMENT_BYTES, 8)
    eng.select("allgather", 64 * ICI_ELEMENT_BYTES, 8)
    assert eng.stats["dp_runs"] <= 3


def test_decisions_persist_across_engines(tmp_path):
    eng = _engine(tmp_path)
    d1 = eng.select("allreduce", 1 << 22, 8)
    d2 = eng.select("broadcast", 1 << 12, 8)
    eng.flush()   # saves are write-behind; force the tail out

    eng2 = _engine(tmp_path)
    e1 = eng2.select("allreduce", 1 << 22, 8)
    e2 = eng2.select("broadcast", 1 << 12, 8)
    assert eng2.stats["misses"] == 0, "persisted decisions were recomputed"
    assert eng2.stats["hits"] == 2
    assert eng2.stats["persisted_loads"] >= 2
    assert (e1.algorithm, e2.algorithm) == (d1.algorithm, d2.algorithm)
    assert e1.predictions == pytest.approx(d1.predictions)
    # autogen schedules survive the round-trip intact
    if e2.rounds is not None:
        assert e2.rounds == d2.rounds


def test_selection_matches_model_argmin(tmp_path):
    from repro.core import selector
    eng = _engine(tmp_path)
    for op in ("reduce_scatter", "allgather", "broadcast"):
        for nbytes in (1 << 10, 1 << 24):
            d = eng.select(op, nbytes, 8)
            b = max(1, nbytes // ICI_ELEMENT_BYTES)
            preds = selector.predict_collective(op, 8, b, TPU_V5E_AXIS)
            assert d.algorithm == min(preds, key=preds.get)
            assert d.predictions == pytest.approx(preds)


def test_identity_on_single_device(tmp_path):
    eng = _engine(tmp_path)
    assert eng.select("allreduce", 1 << 20, 1).algorithm == "identity"


# ------------------------------ calibration --------------------------- #
def test_calibration_round_trip(tmp_path):
    true = Fabric(name="truth", t_r=42.0, store_cost=1.0)
    cycle = 11.4e-9  # seconds per element, arbitrary
    sizes = [1 << 12, 1 << 16, 1 << 20, 1 << 22]
    meas = [(nb, (2 * true.t_r + nb // ICI_ELEMENT_BYTES) * cycle)
            for nb in sizes]
    fitted = fit_fabric(meas, base=TPU_V5E_AXIS)
    assert fitted.t_r == pytest.approx(true.t_r, rel=1e-6)

    eng = _engine(tmp_path)
    eng.select("allreduce", 1 << 20, 8)
    assert eng.stats["misses"] == 1
    out = eng.calibrate(measurements=meas)
    assert out.t_r == pytest.approx(true.t_r, rel=1e-6)
    assert eng.fabric is out
    # stale decisions dropped: same query is a fresh miss under the new
    # constants
    eng.select("allreduce", 1 << 20, 8)
    assert eng.stats["misses"] == 2


def _synthetic_measurements(t_r: float, bw: float, cycle: float = 11.4e-9):
    """Per-axis ppermute timings for a link with the given constants:
    seconds = 2*t_r*cycle + B * (cycle / bw)."""
    return [(nb, 2 * t_r * cycle + max(1, nb // ICI_ELEMENT_BYTES)
             * cycle / bw)
            for nb in (1 << 12, 1 << 16, 1 << 20, 1 << 22)]


def test_per_axis_calibration_round_trip(tmp_path):
    """Fit two axes from synthetic timings with different link speeds:
    the topology recovers both sets of constants on a shared time base
    (fast axis anchors link_bw=1), and the planner flips the 128 KiB
    (2, 16) plan from sequential to hierarchical -- the slow cross-pod
    link is exactly what makes the hierarchy pay."""
    eng = _engine(tmp_path)
    before = eng.plan_multi("allreduce", ("pod", "data"), (2, 16),
                            1 << 17)
    assert before.shape != "hierarchical"

    topo = eng.calibrate(measurements={
        "pod": _synthetic_measurements(t_r=300.0, bw=1.0 / 8.0),
        "data": _synthetic_measurements(t_r=88.0, bw=1.0),
    })
    assert isinstance(topo, FabricTopology)
    assert eng.topology is topo
    data_f, pod_f = topo.for_axis("data"), topo.for_axis("pod")
    assert data_f != pod_f
    assert data_f.t_r == pytest.approx(88.0, rel=1e-6)
    assert data_f.link_bw == pytest.approx(1.0, rel=1e-6)
    assert pod_f.t_r == pytest.approx(300.0, rel=1e-6)
    assert pod_f.link_bw == pytest.approx(1.0 / 8.0, rel=1e-6)

    after = eng.plan_multi("allreduce", ("pod", "data"), (2, 16), 1 << 17)
    assert after.shape == "hierarchical", after.predictions
    # and the modeled cross-pod bytes of the winner stay strictly below
    # the volume-shipping shapes'
    ab = after.cost_terms
    assert (ab["hierarchical"]["axis_bytes"]["pod"]
            < ab["flat"]["axis_bytes"]["pod"])


def test_per_axis_calibration_persists_v3_topology(tmp_path):
    """The v3 cache file records the calibrated per-axis fabrics, and
    ``load_topology`` restores them for a fresh process."""
    eng = _engine(tmp_path)
    topo = eng.calibrate(measurements={
        "pod": _synthetic_measurements(t_r=300.0, bw=0.25),
        "data": _synthetic_measurements(t_r=88.0, bw=1.0),
    })
    eng.plan_multi("allreduce", ("pod", "data"), (2, 4), 1 << 20)
    eng.flush()
    path = str(tmp_path / "decisions.json")
    with open(path) as f:
        payload = json.load(f)
    assert payload["schema"] == SCHEMA_VERSION == 3
    axes = payload["topology"]["axes"]
    assert set(axes) == {"pod", "data"}
    assert axes["pod"]["link_bw"] != axes["data"]["link_bw"]

    restored = load_topology(path)
    assert restored == topo
    # an engine rebuilt on the restored topology serves the persisted
    # plans as hits
    eng2 = CollectiveEngine(cache_path=path, fabric=restored)
    eng2.plan_multi("allreduce", ("pod", "data"), (2, 4), 1 << 20)
    assert eng2.stats["plan_hits"] == 1
    assert eng2.stats["plan_misses"] == 0


def test_get_engine_auto_restores_calibrated_topology(tmp_path,
                                                      monkeypatch):
    """A per-axis calibration persisted under REPRO_CACHE_DIR is
    auto-restored by ``api.get_engine()`` in a fresh process: the
    default engine comes up on the calibrated constants without the
    caller re-installing them.  ``REPRO_RESTORE_TOPOLOGY=0`` opts out."""
    from repro.collectives import api

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("REPRO_RESTORE_TOPOLOGY", raising=False)
    eng = CollectiveEngine()
    topo = eng.calibrate(measurements={
        "pod": _synthetic_measurements(t_r=300.0, bw=0.25),
        "data": _synthetic_measurements(t_r=88.0, bw=1.0),
    })
    eng.select("allreduce", 1 << 20, 8)
    eng.flush()

    # fresh process: empty engine registry, stock default requested
    monkeypatch.setattr(api, "_ENGINES", {})
    restored = api.get_engine()
    assert restored.topology == topo
    assert not restored.topology.is_uniform
    # the registry caches the restored engine under the stock key
    assert api.get_engine() is restored

    # env opt-out: the stock constants, calibration file ignored
    monkeypatch.setattr(api, "_ENGINES", {})
    monkeypatch.setenv("REPRO_RESTORE_TOPOLOGY", "0")
    stock = api.get_engine()
    assert stock.topology.is_uniform
    assert stock.topology.default == TPU_V5E_AXIS

    # an explicitly requested FabricTopology key is never overridden
    monkeypatch.delenv("REPRO_RESTORE_TOPOLOGY")
    monkeypatch.setattr(api, "_ENGINES", {})
    explicit = FabricTopology.uniform(TPU_V5E_AXIS)
    assert api.get_engine(explicit).topology == explicit


def test_find_calibrated_topology_ignores_other_fabric_families(
        tmp_path, monkeypatch):
    """A cache written under different base constants (say WSE2) must
    not leak into the TPU default engine."""
    from repro.collectives.engine import find_calibrated_topology
    from repro.core.model import WSE2

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("REPRO_RESTORE_TOPOLOGY", raising=False)
    eng = CollectiveEngine(fabric=WSE2)
    eng.calibrate(measurements={
        "pod": _synthetic_measurements(t_r=300.0, bw=0.25),
        "data": _synthetic_measurements(t_r=88.0, bw=1.0),
    })
    eng.select("allreduce", 1 << 20, 8)
    eng.flush()
    assert find_calibrated_topology(base=TPU_V5E_AXIS) is None
    assert find_calibrated_topology(base=WSE2) is not None


def test_find_calibrated_topology_ignores_declared_specs(tmp_path,
                                                         monkeypatch):
    """A topology installed from a --fabric spec (declared, not
    measured) persists with the cache but must not auto-restore into
    unrelated processes."""
    from repro.collectives.engine import find_calibrated_topology
    from repro.core.model import parse_fabric_topology

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("REPRO_RESTORE_TOPOLOGY", raising=False)
    eng = CollectiveEngine(fabric=parse_fabric_topology("pod=slow"))
    eng.select("allreduce", 1 << 20, 8)
    eng.flush()
    assert find_calibrated_topology(base=TPU_V5E_AXIS) is None


def test_per_axis_calibration_rejects_noise_dominated_axis(tmp_path):
    """A flat-line (or inverted) timing fit has no bandwidth signal;
    anchoring the shared time base on its clamped slope would hand
    every axis absurd constants -- calibrate must fail loudly
    instead, naming the axis, and leave the engine untouched."""
    eng = _engine(tmp_path)
    before = eng.topology
    flat = [(nb, 1e-6) for nb in (1 << 12, 1 << 16, 1 << 20, 1 << 22)]
    with pytest.raises(ValueError, match="pod"):
        eng.calibrate(measurements={
            "pod": flat,
            "data": _synthetic_measurements(t_r=88.0, bw=1.0)})
    assert eng.topology is before
    with pytest.raises(ValueError, match="empty"):
        eng.calibrate(measurements={})


def test_schema_v2_cache_migrates(tmp_path):
    """A v2 file (schema 2, no topology section, single-fabric tag)
    loads into the v3 engine without error: a uniform topology's tag
    equals the v2 tag and the keys are unchanged."""
    eng = _engine(tmp_path)
    d = eng.select("allreduce", 1 << 20, 8)
    eng.plan_multi("allreduce", ("pod", "data"), (2, 8), 1 << 20)
    eng.flush()
    path = str(tmp_path / "decisions.json")
    with open(path) as f:
        payload = json.load(f)
    legacy = {"schema": 2, "fabric": payload["fabric"],
              "decisions": payload["decisions"],
              "plans": payload["plans"]}
    with open(path, "w") as f:
        json.dump(legacy, f)

    eng2 = _engine(tmp_path)
    d2 = eng2.select("allreduce", 1 << 20, 8)
    eng2.plan_multi("allreduce", ("pod", "data"), (2, 8), 1 << 20)
    assert eng2.stats["misses"] == 0, "v2 decisions were not served"
    assert eng2.stats["plan_misses"] == 0, "v2 plans were not served"
    assert d2.algorithm == d.algorithm
    # a file from a newer schema than this build is ignored, not crashed
    legacy["schema"] = SCHEMA_VERSION + 1
    with open(path, "w") as f:
        json.dump(legacy, f)
    eng3 = _engine(tmp_path)
    eng3.select("allreduce", 1 << 20, 8)
    assert eng3.stats["misses"] == 1


def test_calibration_shifts_selection(tmp_path):
    """Higher measured launch latency pushes `auto` away from deep
    chains toward low-depth patterns -- the selector actually adapts."""
    nbytes = 1 << 19
    fast = CollectiveEngine(
        fabric=Fabric(name="fast", t_r=1.0, store_cost=1.0), persist=False)
    slow = CollectiveEngine(
        fabric=Fabric(name="slow", t_r=5e4, store_cost=1.0), persist=False)
    d_fast = fast.select("allreduce", nbytes, 64)
    d_slow = slow.select("allreduce", nbytes, 64)
    assert d_fast.algorithm == "chain"
    assert d_slow.algorithm != "chain"


# --------------------- multidev: numerics + wiring -------------------- #
_SCRIPT = r"""
import functools, json
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.collectives.engine import CollectiveEngine

results = {}
eng = CollectiveEngine(persist=False)
mesh = make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (64, 24))

def run(fn, in_spec, out_spec):
    f = shard_map(fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                  check_vma=False)
    return np.asarray(jax.jit(f)(x))

# reduce_scatter vs lax.psum_scatter
ref = run(lambda v: lax.psum_scatter(v, "data", scatter_dimension=0,
                                     tiled=True), P(), P("data"))
for algo in ("ring", "autogen", "auto"):
    out = run(functools.partial(eng.reduce_scatter_inside, axis="data",
                                algorithm=algo), P(), P("data"))
    results[f"reduce_scatter_{algo}"] = bool(
        np.allclose(out, ref, rtol=1e-4, atol=1e-4))

# allgather vs lax.all_gather
ref = run(lambda v: lax.all_gather(v, "data", tiled=True), P("data"), P())
for algo in ("ring", "doubling", "autogen", "auto"):
    out = run(functools.partial(eng.allgather_inside, axis="data",
                                algorithm=algo), P("data"), P())
    results[f"allgather_{algo}"] = bool(np.allclose(out, ref))

# broadcast from a non-zero root: everyone must end with root's value
def bc(v, algo):
    idx = lax.axis_index("data")
    seeded = jnp.where(idx == 3, v, jnp.zeros_like(v))
    return eng.broadcast_inside(seeded, "data", root=3, algorithm=algo)
for algo in ("doubling", "chain", "autogen", "auto"):
    out = run(functools.partial(bc, algo=algo), P(), P("data", None))
    results[f"broadcast_{algo}"] = bool(
        np.allclose(out, np.tile(np.asarray(x), (8, 1))))

# allreduce auto vs psum
ref = run(lambda v: lax.psum(v, "data"), P(), P())
out = run(functools.partial(eng.allreduce_inside, axis="data",
                            algorithm="auto"), P(), P())
results["allreduce_auto"] = bool(np.allclose(out, ref, rtol=1e-4,
                                             atol=1e-4))

# trace-level caching: a second trace of the same shape must not re-run
# selection or the Auto-Gen DP
eng2 = CollectiveEngine(persist=False)
g = shard_map(functools.partial(eng2.allreduce_inside, axis="data",
                                algorithm="auto"),
              mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
jax.jit(g).lower(x)
first = dict(eng2.stats)
g2 = shard_map(functools.partial(eng2.allreduce_inside, axis="data",
                                 algorithm="auto"),
               mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
jax.jit(g2).lower(x)
results["retrace_no_new_miss"] = (eng2.stats["misses"] == first["misses"])
results["retrace_hits_cache"] = (eng2.stats["hits"] > first["hits"])
h = shard_map(functools.partial(eng2.allreduce_inside, axis="data",
                                algorithm="autogen"),
              mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
jax.jit(h).lower(x)
dp_after_first = eng2.stats["dp_runs"]
jax.jit(h).lower(x * 2.0)
results["autogen_dp_once"] = (eng2.stats["dp_runs"] == dp_after_first)

# engine-backed gradient sync must land on the same updated params as
# the plain GSPMD step (the allreduce+mean over the DP axis is exactly
# the sync GSPMD's sharding-implied reductions perform; a sum-vs-mean
# or axis bug would show up as an 8x-scaled update)
from repro.configs.base import ArchConfig
from repro.optim.adamw import AdamWConfig
from repro.train.state import init_train_state
from repro.train.step import GradSyncConfig, make_train_step

cfg = ArchConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                 num_heads=4, num_kv_heads=4, d_ff=64, vocab_size=64,
                 dtype="float32")
from repro.models import init_params
params = init_params(jax.random.PRNGKey(0), cfg)
key = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
         "labels": jax.random.randint(key, (8, 16), 0, cfg.vocab_size)}
opt = AdamWConfig(warmup_steps=1, total_steps=10)
state = init_train_state(params)

ref_state, ref_metrics = jax.jit(make_train_step(cfg, opt))(
    init_train_state(params), batch)

sharded = {k: jax.device_put(v, NamedSharding(mesh, P("data")))
           for k, v in batch.items()}
step = make_train_step(cfg, opt, grad_sync=GradSyncConfig(mesh=mesh))
with jax.set_mesh(mesh):
    state2, metrics = jax.jit(step)(init_train_state(params), sharded)
results["grad_sync_finite"] = bool(np.isfinite(float(metrics["loss"])))
ref_leaves = jax.tree.leaves(ref_state.params)
got_leaves = jax.tree.leaves(state2.params)
results["grad_sync_matches_gspmd"] = all(
    np.allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
    for a, b in zip(ref_leaves, got_leaves))

# FSDP mode: reduce-scatter grads -> flat-shard AdamW -> allgather
# params, over the hierarchical (pod, data) topology.  Loss and params
# must track the GSPMD baseline at fp32 tolerance across steps (incl.
# the step-0 tree->flat optimizer-state conversion).
mesh_h = make_mesh((2, 4), ("pod", "data"))
sharded_h = {k: jax.device_put(v, NamedSharding(mesh_h, P(("pod", "data"))))
             for k, v in batch.items()}
fsdp_step = make_train_step(cfg, opt, grad_sync=GradSyncConfig(
    mesh=mesh_h, axes=("pod", "data"), mode="fsdp"))
state_ref = init_train_state(params)
state_f = init_train_state(params)
ref_jit = jax.jit(make_train_step(cfg, opt))
ok_loss, ok_gnorm = True, True
for _ in range(2):
    state_ref, m_ref = ref_jit(state_ref, batch)
    with jax.set_mesh(mesh_h):
        state_f, m_f = jax.jit(fsdp_step)(state_f, sharded_h)
    ok_loss &= bool(np.allclose(float(m_ref["loss"]), float(m_f["loss"]),
                                rtol=1e-5, atol=1e-6))
    ok_gnorm &= bool(np.allclose(float(m_ref["grad_norm"]),
                                 float(m_f["grad_norm"]),
                                 rtol=1e-4, atol=1e-6))
results["fsdp_loss_matches_gspmd"] = ok_loss
results["fsdp_gnorm_matches_gspmd"] = ok_gnorm
results["fsdp_params_match_gspmd"] = all(
    np.allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(state_ref.params),
                    jax.tree.leaves(state_f.params)))
results["fsdp_state_is_flat_shards"] = (
    getattr(state_f.opt.mu, "ndim", None) == 1)

# FSDP + fp32 master weights (bf16 params): must track the GSPMD
# master-weights baseline, with the master living as one flat fp32
# shard instead of a param-shaped tree
params_bf = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
fsdp_m_step = make_train_step(cfg, opt, grad_sync=GradSyncConfig(
    mesh=mesh_h, axes=("pod", "data"), mode="fsdp"))
state_mref = init_train_state(params_bf, master_weights=True)
state_mf = init_train_state(params_bf, master_weights=True)
ref_jit_m = jax.jit(make_train_step(cfg, opt))
for _ in range(2):
    state_mref, _ = ref_jit_m(state_mref, batch)
    with jax.set_mesh(mesh_h):
        state_mf, _ = jax.jit(fsdp_m_step)(state_mf, sharded_h)
results["fsdp_master_params_match_gspmd"] = all(
    np.allclose(np.asarray(a, dtype=np.float32),
                np.asarray(b, dtype=np.float32), rtol=1e-2, atol=1e-2)
    for a, b in zip(jax.tree.leaves(state_mref.params),
                    jax.tree.leaves(state_mf.params)))
results["fsdp_master_params_stay_bf16"] = all(
    l.dtype == jnp.bfloat16 for l in jax.tree.leaves(state_mf.params))
results["fsdp_master_is_flat_fp32_shard"] = (
    getattr(state_mf.opt.master, "ndim", None) == 1
    and state_mf.opt.master.dtype == jnp.float32)
# masters hold fp32 state the bf16 params cannot: the flat master must
# differ from the recast params (strictly more precision retained)
flat_masters = np.asarray(state_mf.opt.master)
results["fsdp_master_keeps_fp32_precision"] = bool(
    np.any(flat_masters[:64]
           != np.asarray(jax.tree.leaves(state_mf.params)[0],
                         dtype=np.float32).reshape(-1)[:64]))

# per-axis calibration on the real (2, 4) debug mesh: one fitted
# fabric per mesh axis, persisted under the v3 cache schema
import tempfile
from repro.collectives.engine import load_topology
cal_path = tempfile.mktemp(suffix=".json")
eng_cal = CollectiveEngine(cache_path=cal_path)
topo = eng_cal.calibrate(mesh=mesh_h,
                         sizes_bytes=(1 << 12, 1 << 14, 1 << 16, 1 << 18))
fpod, fdata = topo.for_axis("pod"), topo.for_axis("data")
results["calibrate_mesh_per_axis_fabrics"] = (
    len(dict(topo.axis_fabrics)) == 2
    and (fpod.t_r, fpod.link_bw) != (fdata.t_r, fdata.link_bw)
    and max(fpod.link_bw, fdata.link_bw) == 1.0)
eng_cal.select("allreduce", 1 << 20, 8)
eng_cal.plan_multi("allreduce", ("pod", "data"), (2, 4), 1 << 20)
eng_cal.flush()
with open(cal_path) as fh:
    payload = json.load(fh)
results["calibrate_v3_persisted"] = (
    payload["schema"] == 3
    and set(payload["topology"]["axes"]) == {"pod", "data"})
results["calibrate_topology_reloads"] = (load_topology(cal_path) == topo)

# engine-backed DP serving: tokens identical to single-device greedy
from repro.launch.serve import BatchedServer, Request
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
           for _ in range(8)]
outs = {}
for m in (None, mesh):
    srv = BatchedServer(cfg, params, batch_size=8, max_len=64, mesh=m)
    for rid, pr in enumerate(prompts):
        srv.submit(Request(rid=rid, prompt=pr, max_new_tokens=4))
    outs[m is not None] = srv.run(max_steps=8)
results["serve_dp_matches_local"] = (outs[True] == outs[False])
print("JSON" + json.dumps(results))
"""


@pytest.mark.multidev
@pytest.mark.slow
def test_engine_collectives_on_8_devices():
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
