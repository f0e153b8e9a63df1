"""The readers of the server's spans (``host_step_ms``, ``host_idle_ms``,
``decode_occupancy``, ``prefill_p50_ms``) on planes built by hand, and on
the recorded v5e trace, which holds no ``serve.*`` event."""

import dataclasses
import types

import pytest

from bench import harness, session
from bench.metrics import _spans
from bench.tests.test_trace_reduce import DATA, Line, Plane

READERS = ("host_step_ms", "host_idle_ms", "decode_occupancy", "prefill_p50_ms")


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: int
    duration_ns: int
    stats: tuple = ()


def ev(name, a, b, **stats):
    return Ev(name, a, b - a, tuple(stats.items()))


def planes():
    host = Plane(
        "/host:CPU",
        [
            Line(
                "python",
                [
                    ev("bench.step", 1000, 2000),
                    ev("serve.step", 1000, 1900, step=0),
                    ev("serve.schedule", 1000, 1100, queued=0),
                    ev("serve.decode.batch", 1100, 1200, rows=3),
                    ev("serve.decode", 1200, 1300, rows=3, batch=4),
                    ev("serve.sample", 1300, 1350, rows=3),
                    ev("serve.wait", 1350, 1800),
                    ev("serve.append", 1800, 1900, tokens=3),
                    ev("bench.step", 2500, 3000),
                    ev("serve.step", 2500, 2950, step=1),
                    ev("serve.prefill", 2500, 2600, rid=7, tokens=32, ctx=0),
                    ev("serve.decode", 2600, 2650, rows=2, batch=4),
                    ev("serve.wait", 2650, 2850),
                    ev("_server.py:293_decode_batch", 1100, 1200),
                ],
            )
        ],
    )
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [Ev("fusion", 1250, 500), Ev("harness_op", 2000, 100), Ev("paged_attention", 2620, 180)])])
    return [host, dev]


def fake_run(requests=(), trace=True):
    w = harness.Window(100.0, 10.0, [], {}, [], 0, 0.0)
    w.requests = list(requests)
    return types.SimpleNamespace(window=w, trace=object() if trace else None)


@pytest.fixture
def hand_built(monkeypatch):
    monkeypatch.setattr(_spans, "spans", lambda run: _spans.from_planes(planes()))


def test_host_step_less_waits(hand_built):
    # step 0: 900 ns less a 450 ns wait; step 1: 450 less 200
    assert session.load_reader("host_step_ms")(fake_run()) == pytest.approx((450 + 250) / 2 / 1e6)


def test_host_idle_per_step_and_by_span(hand_built, capsys):
    # step 0 [1000, 1900] busy 500; step 1 [2500, 2950] busy 180
    assert session.load_reader("host_idle_ms")(fake_run()) == pytest.approx((400 + 270) / 2 / 1e6)
    line = capsys.readouterr().err
    # gaps [1000, 1250] mid 1125, [1750, 2000] mid 1875, [2100, 2620] mid 2360, [2800, 3000] mid 2900
    got = dict(kv.rsplit(" ", 1) for kv in line.split(": ", 2)[2].strip().split(", "))
    assert {k: float(v) for k, v in got.items()} == pytest.approx({"harness": 520e-9, "serve.decode.batch": 250e-9, "serve.append": 250e-9, "serve.step": 200e-9})


def test_decode_occupancy(hand_built):
    assert session.load_reader("decode_occupancy")(fake_run()) == pytest.approx(100 * (3 / 4 + 2 / 4) / 2)


def test_spans_window_and_order():
    sp = _spans.from_planes(iter(planes()))  # ProfileData's planes read once
    assert (sp.lo, sp.hi) == (1000, 3000)
    assert sp.busy == [(1250, 1750), (2000, 2100), (2620, 2800)]
    assert [e[0] for e in sp.events[:2]] == ["serve.step", "serve.schedule"]
    assert all(e[0].startswith("serve.") for e in sp.events)
    assert sp.named("serve.decode")[1][3] == {"rows": 2, "batch": 4}


def test_prefill_p50_from_admission_in_window():
    r = lambda admit, first: types.SimpleNamespace(admit_t=admit, first_token_t=first)
    reqs = [r(101.0, 101.2), r(105.0, 105.5), r(103.0, 103.3), r(99.0, 100.1), r(109.0, None), r(111.0, 111.1)]
    assert session.load_reader("prefill_p50_ms")(fake_run(reqs)) == pytest.approx(300.0)


def test_readers_none_without_serving_spans(monkeypatch):
    # the recorded chip trace has bench.step spans and device ops, and
    # no serve.* event (the program predates them); requests with no
    # admit_t are what such a program hands the reader
    monkeypatch.setattr(_spans, "trace_dir", lambda: DATA)
    _spans._load.cache_clear()
    old = types.SimpleNamespace(first_token_t=101.0)
    for name in READERS:
        assert session.load_reader(name)(fake_run([old])) is None, name
        assert session.load_reader(name)(fake_run([old], trace=False)) is None, name


def four_chip_planes():
    """Two traced decode steps and a prefill-only step on four chips.
    Chip i's collectives: step 0 an async all-gather over [1300, 1400 +
    10 i] and an all-reduce nested in a ``while``, 120 + 10 i ns; step
    1 an overlapping collective-permute pair, 100 ns, and on chip 0 a
    reduce-scatter, 40 ns more; an all-to-all in the prefill step and
    one between steps are not counted."""
    host = Plane(
        "/host:CPU",
        [
            Line(
                "python",
                [
                    ev("bench.step", 1000, 2000),
                    ev("serve.step", 1000, 1900, step=0),
                    ev("serve.decode", 1200, 1300, rows=3, batch=8),
                    ev("bench.step", 2500, 3000),
                    ev("serve.step", 2500, 2950, step=1),
                    ev("serve.decode", 2600, 2650, rows=5, batch=8),
                    ev("serve.step", 2955, 2990, step=2),
                    ev("serve.prefill", 2955, 2980, rid=9, tokens=16, ctx=0),
                ],
            )
        ],
    )
    chips = []
    for i in range(4):
        ops = [
            Ev("all-gather-start.1", 1300, 20),
            Ev("all-gather-done.1", 1320, 80 + 10 * i),
            Ev("fusion.2", 1400 + 10 * i, 100 - 10 * i),
            Ev("while.1", 1450, 150),
            Ev("all-reduce.3", 1500, 20),
            Ev("all-to-all.6", 2100, 100),
            Ev("collective-permute-start.4", 2700, 50),
            Ev("collective-permute-done.4", 2740, 60),
            Ev("paged_attention.9", 2650, 50),
            Ev("all-to-all.7", 2960, 10),
        ]
        if i == 0:
            ops.append(Ev("reduce-scatter.5", 2800, 40))
        chips.append(Plane(f"/device:TPU:{i}", [Line("XLA Ops", ops)]))
    return [host] + chips


def test_dp_collective_ms_on_four_chips(monkeypatch):
    monkeypatch.setattr(_spans, "spans", lambda run: _spans.from_planes(four_chip_planes()))
    run = fake_run()
    run.trace = types.SimpleNamespace(devices=4)
    # step 0: mean of 120..150 = 135 ns; step 1: (140 + 3 * 100) / 4 = 110 ns
    assert session.load_reader("dp_collective_ms")(run) == pytest.approx((135 + 110) / 2 / 1e6)
    # one chip of the four: 120 and 140 ns
    run.trace = types.SimpleNamespace(devices=1)
    assert session.load_reader("dp_collective_ms")(run) == pytest.approx((120 + 140) / 2 / 1e6)


def test_dp_collective_ms_none_without_collectives(hand_built):
    run = fake_run()
    run.trace = types.SimpleNamespace(devices=1)
    assert session.load_reader("dp_collective_ms")(run) is None


def test_paged_attn_roofline_counts_every_chips_kernel_time():
    from bench import flops, trace_reduce
    from bench.tests.tiny import load_config
    from bench.peaks import PEAKS

    planes = four_chip_planes()
    summary = trace_reduce.reduce_planes(planes, 4)
    assert summary.kernel_seconds("paged_attention") == pytest.approx(50e-9)  # averaged over the chips
    conf = load_config("minicpm-2b")
    lengths = [100, 300, 17, 40, 1]  # the decode rows of all four chips
    step = harness.StepSpan(0.0, 1.0, decode=lengths, traced=True)
    w = harness.Window(0.0, 10.0, [step], {}, [], 0, 0.0)
    run = types.SimpleNamespace(window=w, trace=summary, conf=conf, server=conf["server"], peaks=PEAKS["TPU v5 lite"])
    fl, by = flops.paged_kernel_cost(conf, lengths, 16)
    least = max(fl / run.peaks.flops, by / run.peaks.hbm_bytes_s)
    assert session.load_reader("paged_attn_roofline")(run) == pytest.approx(100 * least / (4 * 50e-9))
    # the same rows on one chip
    run.trace = trace_reduce.reduce_planes(planes, 1)
    assert session.load_reader("paged_attn_roofline")(run) == pytest.approx(100 * least / 50e-9)
