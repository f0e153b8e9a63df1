"""Observability layer: metrics registry, span tracing, model-error
monitoring, telemetry export.

Fast tier: registry semantics (kinds, labels, collectors, exporters),
engine stats snapshot/export, Chrome-trace round-trip (ordering,
nesting, args preserved), model-error drift firing at/below threshold,
TTFT sample counting and low-confidence marking, and the
``obs_report.py --check`` schema gate.  Multidev tier
(``test_obs_multidev.py``): traced engine collectives on 8 virtual
devices with measured replay.
"""

import json
import os
import subprocess
import sys
import threading

import pytest

from repro.collectives.engine import CollectiveEngine
from repro.obs import trace as obs_trace
from repro.obs.model_error import (DEFAULT_THRESHOLD, ModelErrorMonitor,
                                   bytes_decile)
from repro.obs.registry import (EXPORT_SCHEMA, MetricsRegistry,
                                export_engine_stats, validate_export)
from repro.serving.telemetry import (TTFT_LOW_CONFIDENCE, Telemetry,
                                     export_to_registry,
                                     ttft_low_confidence)


# ------------------------------ registry ------------------------------ #
def test_counter_monotonic():
    reg = MetricsRegistry()
    c = reg.counter("requests")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create returns the same object
    assert reg.counter("requests") is c


def test_gauge_and_labels_key_separately():
    reg = MetricsRegistry()
    reg.gauge("occupancy", labels={"pool": "kv"}).set(0.5)
    reg.gauge("occupancy", labels={"pool": "host"}).set(0.9)
    snap = reg.snapshot()
    assert snap["gauges"]['occupancy{pool="kv"}'] == 0.5
    assert snap["gauges"]['occupancy{pool="host"}'] == 0.9


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("n")
    with pytest.raises(TypeError):
        reg.gauge("n")


def test_histogram_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in range(1, 101):
        h.observe(float(v))
    exp = h.export()
    assert exp["count"] == 100 and exp["sum"] == 5050
    assert 49 <= exp["p50"] <= 52
    assert exp["min"] == 1.0 and exp["max"] == 100.0


def test_collector_runs_at_export():
    reg = MetricsRegistry()
    calls = []

    def collect(r):
        calls.append(1)
        r.gauge("fresh").set(len(calls))

    reg.register_collector("src", collect)
    assert reg.snapshot()["gauges"]["fresh"] == 1
    assert reg.snapshot()["gauges"]["fresh"] == 2
    # same key replaces, not stacks
    reg.register_collector("src", lambda r: r.gauge("fresh").set(-1))
    assert reg.snapshot()["gauges"]["fresh"] == -1


def test_export_json_schema_and_validation():
    reg = MetricsRegistry()
    reg.counter("a").inc()
    reg.histogram("h").observe(1.0)
    blob = reg.export_json()
    assert blob["schema"] == EXPORT_SCHEMA
    assert validate_export(blob) == []
    # round-trips through JSON text
    assert validate_export(json.loads(reg.export_json_str())) == []
    # broken blobs produce problems, not exceptions
    assert validate_export({"schema": "nope"})
    assert validate_export([1, 2])
    bad = reg.export_json()
    bad["counters"]["a"] = "NaN-ish"
    assert any("not numeric" in p for p in validate_export(bad))


def test_export_prometheus_format():
    reg = MetricsRegistry()
    reg.counter("reqs", labels={"code": "200"}, help="requests").inc(3)
    reg.histogram("lat").observe(2.0)
    text = reg.export_prometheus()
    assert "# HELP reqs requests" in text
    assert "# TYPE reqs counter" in text
    assert 'reqs{code="200"} 3' in text
    assert "lat_count 1" in text and "lat_sum 2" in text
    assert 'lat{quantile="0.50"} 2' in text


def test_export_prometheus_label_escaping_round_trips():
    """Label values containing backslash, double quote, and newline
    must escape per the exposition format -- and unescape back to the
    original value (tenant ids are label values under the serving
    fleet, and they are client-controlled strings)."""
    import re
    hostile = 'a"b\\c\nd'
    reg = MetricsRegistry()
    reg.gauge("fleet_rejected_by_tenant",
              labels={"tenant": hostile}).set(2)
    text = reg.export_prometheus()
    # a raw newline inside the label value would split the sample line
    # in two, so exactly one parseable line proves the escaping
    (line,) = [ln for ln in text.splitlines()
               if ln.startswith("fleet_rejected_by_tenant{")]
    m = re.fullmatch(r'fleet_rejected_by_tenant\{tenant="((?:[^"\\]|'
                     r'\\.)*)"\} 2', line)
    assert m, f"label pair not parseable: {line!r}"
    unescaped = re.sub(r"\\(.)",
                       lambda e: "\n" if e.group(1) == "n" else e.group(1),
                       m.group(1))
    assert unescaped == hostile


def test_registry_snapshot_is_atomic_copy():
    reg = MetricsRegistry()
    g = reg.gauge("x")
    g.set(1)
    snap = reg.snapshot()
    g.set(2)
    assert snap["gauges"]["x"] == 1


# ------------------------------ engine stats -------------------------- #
def _engine(tmp_path):
    return CollectiveEngine(cache_path=str(tmp_path / "decisions.json"))


def test_engine_stats_snapshot_counters(tmp_path):
    eng = _engine(tmp_path)
    s0 = eng.stats_snapshot()
    assert s0 == {"hits": 0, "misses": 0, "dp_runs": 0,
                  "persisted_loads": 0, "plan_hits": 0,
                  "plan_misses": 0, "latency_dispatches": 0}
    eng.select("allreduce", 1 << 20, 8)
    eng.select("allreduce", 1 << 20, 8)
    s_sel = eng.stats_snapshot()
    assert s_sel["misses"] == 1 and s_sel["hits"] == 1
    # planning scores candidates through select(), so only the plan
    # counters are exact here
    eng.plan_multi("allreduce", ("pod", "data"), (2, 4), 1 << 16)
    eng.plan_multi("allreduce", ("pod", "data"), (2, 4), 1 << 16)
    s1 = eng.stats_snapshot()
    assert s1["plan_misses"] == 1 and s1["plan_hits"] == 1
    # the snapshot is a copy: mutating it does not touch the engine
    s1["hits"] = 999
    assert eng.stats_snapshot()["hits"] != 999
    # select() still returns bare Decisions and _select_meta the hit bit
    d, hit = eng._select_meta("allreduce", 1 << 20, 8)
    assert hit and d.algorithm == eng.select("allreduce", 1 << 20, 8
                                             ).algorithm


def test_export_engine_stats_gauges(tmp_path):
    eng = _engine(tmp_path)
    eng.select("allreduce", 1 << 20, 8)
    reg = MetricsRegistry()
    export_engine_stats(eng, reg)
    gauges = reg.snapshot()["gauges"]
    key = [k for k in gauges if k.startswith("engine_misses")]
    assert key and gauges[key[0]] == 1
    assert any(k.startswith("engine_hits") for k in gauges)


def test_select_meta_hit_bit(tmp_path):
    eng = _engine(tmp_path)
    _, hit1 = eng._select_meta("allgather", 1 << 18, 4)
    _, hit2 = eng._select_meta("allgather", 1 << 18, 4)
    assert (hit1, hit2) == (False, True)
    d, hit = eng._select_meta("allreduce", 123, 1)
    assert not hit and d.algorithm == "identity"


# ------------------------------ trace round-trip ---------------------- #
def _fresh_tracer(**kw):
    return obs_trace.Tracer(enabled=True, **kw)


def test_trace_chrome_roundtrip(tmp_path):
    tracer = _fresh_tracer()
    prev = obs_trace.set_tracer(tracer)
    try:
        with tracer.span("allreduce_multi", op="allreduce",
                         axes=("pod", "data"), bytes=4096,
                         plan="hierarchical(rs:ring->ar:ring->ag:ring)",
                         cache="miss", predicted=123.0,
                         measured_s=0.0015, mode="eager") as root:
            with tracer.span("rs:ring@data", cat=obs_trace.CAT_PHASE,
                             op="allreduce", phase=0):
                pass
            with tracer.span("ar:ring@pod", cat=obs_trace.CAT_PHASE,
                             op="allreduce", phase=1):
                pass
            root.set(n_chunks=2)
    finally:
        obs_trace.set_tracer(prev)
    path = str(tmp_path / "trace.json")
    assert tracer.export_chrome(path) == 3

    loaded = obs_trace.load_chrome_trace(path)
    orig = tracer.spans
    assert [s.name for s in loaded] == [s.name for s in orig]
    assert [s.span_id for s in loaded] == [s.span_id for s in orig]
    by_id = {s.span_id: s for s in loaded}
    # nesting survives: both phases hang off the collective span
    root_l = [s for s in loaded if s.cat == obs_trace.CAT_COLLECTIVE][0]
    phases = [s for s in loaded if s.cat == obs_trace.CAT_PHASE]
    assert len(phases) == 2
    assert all(p.parent_id == root_l.span_id for p in phases)
    assert root_l.parent_id is None
    # args round-trip, including the late .set()
    assert root_l.args["plan"].startswith("hierarchical")
    assert root_l.predicted == 123.0
    assert root_l.measured_s == 0.0015
    assert root_l.args["n_chunks"] == 2
    assert by_id[phases[0].span_id].args["phase"] == 0
    # file metadata carries the schema tag
    with open(path) as f:
        payload = json.load(f)
    assert payload["metadata"]["schema"] == obs_trace.TRACE_SCHEMA


def test_tracer_disabled_is_noop_and_max_spans_drops():
    tracer = obs_trace.Tracer(enabled=False)
    sp = tracer.span("x", op="allreduce")
    # off, a span is its profiler annotation alone: it records nothing
    assert isinstance(sp, obs_trace.Annotation)
    with sp:
        sp.set(a=1)
        sp.finish_result(None)
    assert tracer.spans == []

    tracer = _fresh_tracer(max_spans=1)
    with tracer.span("kept"):
        pass
    with tracer.span("dropped"):
        pass
    assert [s.name for s in tracer.spans] == ["kept"]
    assert tracer.dropped == 1


def test_finish_result_measure_blocks_eager_only():
    import jax.numpy as jnp
    tracer = obs_trace.Tracer(enabled=True, measure=True)
    with tracer.span("coll", op="allreduce") as sp:
        sp.finish_result(jnp.zeros((4,)))
    (span,) = tracer.spans
    assert span.args["mode"] == "eager"
    assert span.args["measured_s"] == span.dur > 0

    # phase spans opt out of blocking regardless of measure mode
    tracer2 = obs_trace.Tracer(enabled=True, measure=True)
    with tracer2.span("phase", cat=obs_trace.CAT_PHASE) as sp:
        sp.finish_result(jnp.zeros((4,)), block=False)
    (span2,) = tracer2.spans
    assert span2.args["measured_s"] is None

    # measure=False never blocks: measured_s stays null
    tracer3 = obs_trace.Tracer(enabled=True, measure=False)
    with tracer3.span("coll", op="allreduce") as sp:
        sp.finish_result(jnp.zeros((4,)))
    (span3,) = tracer3.spans
    assert span3.args["measured_s"] is None
    assert span3.args["mode"] == "eager"


def test_span_stack_is_thread_local():
    tracer = _fresh_tracer()
    seen = {}

    def worker():
        with tracer.span("child_b") as sp:
            seen["parent_b"] = sp.span.parent_id

    with tracer.span("root_a") as root:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        with tracer.span("child_a") as sp:
            seen["parent_a"] = sp.span.parent_id
    assert seen["parent_a"] == root.span.span_id
    assert seen["parent_b"] is None


def test_validate_spans_contract():
    tracer = _fresh_tracer()
    with tracer.span("good", op="allreduce", axes=("d",), bytes=8,
                     plan=None, cache="hit", predicted=1.0,
                     measured_s=None, mode="traced"):
        pass
    assert obs_trace.validate_spans(tracer.spans) == []
    # missing keys flagged
    tracer2 = _fresh_tracer()
    with tracer2.span("bad", op="allreduce"):
        pass
    problems = obs_trace.validate_spans(tracer2.spans)
    assert problems and "missing" in problems[0]
    # null prediction only allowed when forced
    tracer3 = _fresh_tracer()
    with tracer3.span("forced", op="allreduce", axes=("d",), bytes=8,
                      plan=None, cache="forced", predicted=None,
                      measured_s=None, mode="traced",
                      algorithm_forced=True):
        pass
    assert obs_trace.validate_spans(tracer3.spans) == []
    assert obs_trace.validate_spans([]) == ["no collective spans in trace"]


# ------------------------------ model error --------------------------- #
def test_bytes_decile_bins():
    assert bytes_decile(1) == 0
    assert bytes_decile(999) == 2
    assert bytes_decile(1 << 20) == 6


def _feed(mon, err, n=32, predicted=1000.0, scale=1e-6):
    """Anchor a bin at ``scale`` seconds/cycle, then feed ``n`` samples
    measuring ``err`` relative error against the anchor."""
    for _ in range(mon.min_samples):
        mon.observe("allreduce", "2x4", 1 << 20, predicted,
                    predicted * scale)
    for _ in range(n):
        mon.observe("allreduce", "2x4", 1 << 20, predicted,
                    predicted * scale * (1.0 + err))


def test_drift_fires_above_threshold_only():
    quiet = ModelErrorMonitor(threshold=DEFAULT_THRESHOLD, min_samples=4)
    _feed(quiet, err=0.02)
    assert not quiet.should_recalibrate
    assert quiet.recommendation() is None
    assert all(not b.drifted for b in quiet.bins.values())

    drifted = ModelErrorMonitor(threshold=DEFAULT_THRESHOLD,
                                min_samples=4)
    _feed(drifted, err=0.10)
    assert drifted.should_recalibrate
    assert len(drifted.drifted_bins()) == 1
    rec = drifted.recommendation()
    assert "calibrate" in rec
    assert "DRIFT" in drifted.render_table()
    assert "!!" in drifted.render_table()


def test_drift_needs_min_scored_samples():
    mon = ModelErrorMonitor(min_samples=8)
    # anchor (8) + 3 scored samples of huge error: not enough to flag
    for _ in range(8):
        mon.observe("allgather", "8", 1 << 16, 100.0, 100e-6)
    for _ in range(3):
        mon.observe("allgather", "8", 1 << 16, 100.0, 200e-6)
    assert not mon.should_recalibrate


def test_explicit_seconds_per_cycle_skips_anchoring():
    mon = ModelErrorMonitor(min_samples=2, seconds_per_cycle=1e-6)
    for _ in range(4):
        mon.observe("allreduce", "4", 1 << 12, 500.0, 500e-6 * 1.2)
    assert mon.should_recalibrate


def test_monitor_observe_spans_filters():
    mon = ModelErrorMonitor(min_samples=2)
    tracer = _fresh_tracer()
    with tracer.span("ar", op="allreduce", axes=("d",), axis_sizes=(8,),
                     bytes=1 << 16, predicted=100.0, measured_s=1e-4):
        pass
    with tracer.span("no_measure", op="allreduce", axes=("d",),
                     bytes=1 << 16, predicted=100.0, measured_s=None):
        pass
    with tracer.span("phase", cat=obs_trace.CAT_PHASE, op="allreduce"):
        pass
    fed = mon.observe_spans(tracer.spans)
    assert fed == 1 and mon.skipped == 1
    assert list(mon.bins) == [("allreduce", "8", bytes_decile(1 << 16))]
    blob = mon.report()
    assert blob["observed"] == 1 and blob["bins"][0]["op"] == "allreduce"


# ------------------------------ telemetry ----------------------------- #
class _StubAllocator:
    capacity = 10
    num_used = 3
    num_evictable = 0
    occupancy = 0.3
    evictions = 0

    @staticmethod
    def internal_fragmentation(block_usage):
        return 0


def _snap_with_ttfts(n):
    tel = Telemetry(clock=iter(range(1000)).__next__)
    for _ in range(n):
        tel.record_first_token(0.0)
    return tel.snapshot(queue_depth=0, active=0,
                        allocator=_StubAllocator, block_usage=[])


def test_ttft_samples_and_low_confidence():
    snap = _snap_with_ttfts(4)
    assert snap.ttft_samples == 4
    assert ttft_low_confidence(snap)
    snap = _snap_with_ttfts(TTFT_LOW_CONFIDENCE)
    assert snap.ttft_samples == TTFT_LOW_CONFIDENCE
    assert not ttft_low_confidence(snap)
    assert _snap_with_ttfts(0).ttft_samples == 0


def test_export_to_registry_marks_confidence():
    snap = _snap_with_ttfts(3)
    reg = MetricsRegistry()
    export_to_registry(snap, reg)
    gauges = reg.snapshot()["gauges"]
    assert gauges["serve_ttft_samples"] == 3
    assert gauges["serve_ttft_low_confidence"] == 1
    assert "serve_ttft_p50_ms" in gauges
    assert validate_export(reg.export_json()) == []

    snap = _snap_with_ttfts(TTFT_LOW_CONFIDENCE + 1)
    reg2 = MetricsRegistry()
    export_to_registry(snap, reg2)
    assert reg2.snapshot()["gauges"]["serve_ttft_low_confidence"] == 0


def test_export_to_registry_skips_null_percentiles():
    snap = _snap_with_ttfts(0)
    reg = MetricsRegistry()
    export_to_registry(snap, reg)
    gauges = reg.snapshot()["gauges"]
    assert "serve_ttft_p50_ms" not in gauges
    assert gauges["serve_ttft_samples"] == 0


# ------------------------------ obs_report CLI ------------------------ #
_REPORT = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                       "obs_report.py")


def _run_report(args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run([sys.executable, _REPORT, *args], env=env,
                          capture_output=True, text=True, timeout=240)


def _write_trace(path, spans_args):
    events = []
    for i, args in enumerate(spans_args):
        args = dict(args)
        args.setdefault("span_id", i)
        args.setdefault("parent_id", None)
        events.append({"name": f"s{i}", "cat": "collective", "ph": "X",
                       "ts": i * 10.0, "dur": 5.0, "pid": 1, "tid": 0,
                       "args": args})
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "metadata": {"schema": obs_trace.TRACE_SCHEMA}}, f)


@pytest.mark.slow
def test_obs_report_check_gate(tmp_path):
    good = str(tmp_path / "good.json")
    _write_trace(good, [{"op": "allreduce", "axes": ["d"], "bytes": 64,
                         "plan": "flat(ar:ring)", "cache": "hit",
                         "predicted": 10.0, "measured_s": 1e-5,
                         "mode": "eager"}])
    proc = _run_report([good, "--check"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "conform" in proc.stdout

    bad = str(tmp_path / "bad.json")
    _write_trace(bad, [{"op": "allreduce"}])
    proc = _run_report([bad, "--check"])
    assert proc.returncode == 1
    assert "missing" in proc.stderr

    # report mode renders the table from the same trace
    proc = _run_report([good])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "allreduce" in proc.stdout
