"""Traced engine collectives on 8 virtual devices, via a subprocess
(tests must not set xla_force_host_platform_device_count globally).

The acceptance scenario of the observability layer: run every engine
collective family over a (2, 4) mesh with tracing on, backfill wall
time by measured replay, and assert the exported Chrome trace loads
back with every collective span carrying predicted cost, measured wall
time, plan description, and cache status -- plus nested phase spans
under the multi-axis plans."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.multidev, pytest.mark.slow]

_SCRIPT = r"""
import json, numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro import obs
from repro.collectives.api import get_engine
from repro.collectives.engine import CollectiveEngine
from repro.collectives.api import set_engine
from repro.obs import replay

results = {}
eng = CollectiveEngine(cache_path=None)
set_engine(eng)
tracer = obs.enable_tracing(measure=True)

mesh = make_mesh((2, 4), ("pod", "data"))
axes = ("pod", "data")

def run(fn, x, in_spec, out_spec):
    w = shard_map(fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                  check_vma=False)
    return jax.block_until_ready(jax.jit(w)(x))

x = jnp.arange(4096, dtype=jnp.float32)
run(lambda v: eng.allreduce_multi(v, axes), x, P(), P())
run(lambda v: eng.reduce_scatter_multi(v, axes), x, P(), P(axes))
xs = jnp.arange(512, dtype=jnp.float32)
run(lambda v: eng.allgather_inside(v, "data"), xs, P("data"), P())
xa = jnp.arange(128, dtype=jnp.float32)
run(lambda v: eng.all_to_all_multi(v, axes), xa, P(axes), P(axes))

spans = tracer.spans
coll = [s for s in spans if s.cat == obs.CAT_COLLECTIVE]
phases = [s for s in spans if s.cat == "phase"]
results["has_collective_spans"] = len(coll) >= 4
results["ops_covered"] = {s.args["op"] for s in coll} >= {
    "allreduce", "reduce_scatter", "allgather", "all_to_all"}
results["traced_mode"] = all(s.args["mode"] == "traced" for s in coll)
results["no_wall_time_yet"] = all(
    s.args["measured_s"] is None for s in coll)

# top-level spans carry the model's decision; the multi-axis ones a
# full plan description, the 1D allgather a bare algorithm
tops = [s for s in coll if s.parent_id is None]
results["top_spans_decided"] = all(
    s.args["predicted"] is not None
    and s.args["cache"] in ("hit", "miss") for s in tops)
multi_tops = [s for s in tops if s.name.endswith("_multi")]
results["multi_spans_have_plan"] = len(multi_tops) >= 3 and all(
    s.args["plan"] is not None and s.args["n_chunks"] >= 1
    for s in multi_tops)
results["phase_spans_nest"] = bool(phases) and all(
    p.parent_id is not None for p in phases)

# measured replay backfills wall time into every replayable span
measured = replay.measure_spans(spans, mesh, engine=eng)
results["replay_measured"] = len(measured) >= 4
results["all_backfilled"] = all(
    s.args["measured_s"] is not None and s.args["measured_s"] > 0
    for s in coll)
results["replay_tagged"] = all(
    s.args.get("measured_via") == "replay" for s in coll)

# the exported trace conforms and loads back identically
results["validates"] = obs.validate_spans(spans) == []
path = "trace_multidev.json"
n = tracer.export_chrome(path)
loaded = obs.load_chrome_trace(path)
results["export_count"] = n == len(spans)
results["roundtrip_ids"] = (
    [s.span_id for s in loaded] == [s.span_id for s in spans])
results["roundtrip_parents"] = (
    [s.parent_id for s in loaded] == [s.parent_id for s in spans])
results["roundtrip_validates"] = obs.validate_spans(loaded) == []
results["roundtrip_measured"] = all(
    s.args["measured_s"] is not None
    for s in loaded if s.cat == obs.CAT_COLLECTIVE)

print("JSON" + json.dumps(results))
"""


def test_traced_collectives_on_8_devices(tmp_path):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          cwd=str(tmp_path), capture_output=True,
                          text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("JSON")][-1]
    results = json.loads(line[4:])
    for key, ok in results.items():
        assert ok, (key, results)
