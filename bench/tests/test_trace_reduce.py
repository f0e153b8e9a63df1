"""bench/trace_reduce.py on planes built by hand, and on a short trace
recorded on a TPU v5e chip (two decode steps of minicpm-2b)."""

import dataclasses
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: int
    duration_ns: int


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def planes():
    host = Plane(
        "/host:CPU",
        [
            Line("python", [Ev("bench.step", 1000, 1000), Ev("sample", 1500, 300), Ev("bench.step", 2500, 500)]),
        ],
    )
    dev = Plane(
        "/device:TPU:0",
        [
            Line("XLA Ops", [Ev("fusion", 900, 300), Ev("paged_attention", 1200, 50), Ev("fusion", 2000, 800), Ev("late", 3200, 100)]),
            Line("XLA Modules", [Ev("jit_step", 900, 2400)]),
        ],
    )
    return [host, dev]


def test_window_busy_and_ops():
    s = tr.reduce_planes(planes(), 1)
    assert s.window_s == pytest.approx(2000e-9)  # first step start to last step end
    # busy in [1000, 3000]: [1000, 1250] and [2000, 2800]
    assert s.busy_s == pytest.approx(1050e-9)
    assert s.ops == pytest.approx({"fusion": 1000e-9, "paged_attention": 50e-9})
    assert tr.op_name("%copy.56 = bf16[40,860] copy(x)") == "copy.56"
    assert s.kernel_seconds("paged_attention") == pytest.approx(50e-9)
    # idle [1250, 2000] mid 1625 inside "sample"; [2800, 3000] mid 2900 in a step
    assert s.idle == pytest.approx({"sample": 750e-9, "bench.step": 200e-9})
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion", pytest.approx(1000e-9)]
    assert len(b["idle_gaps"]) == 2


def test_self_times_subtract_nested_ops():
    evs = [("while", 0, 100), ("body_a", 10, 30), ("body_b", 40, 90), ("inner", 50, 60), ("after", 100, 120)]
    assert tr.self_times(evs) == pytest.approx({"while": 30e-9, "body_a": 20e-9, "body_b": 40e-9, "inner": 10e-9, "after": 20e-9})


def test_union():
    assert tr.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]


def test_no_step_span_raises():
    with pytest.raises(ValueError):
        tr.reduce_planes([Plane("/host:CPU", [Line("python", [])])], 1)


def test_recorded_chip_trace():
    path = os.path.join(DATA, "v5e_minicpm_decode.xplane.pb")
    from jax.profiler import ProfileData

    s = tr.reduce_planes(ProfileData.from_file(path).planes, 1)
    # two bench.step spans on the host; the device planes' XLA Ops
    assert s.window_s == pytest.approx(0.03259105)
    assert s.busy_s == pytest.approx(0.027057629)
    assert 0 < s.busy_s <= s.window_s
    assert s.kernel_seconds("paged_attention") == pytest.approx(0.003586889)
    assert len(s.breakdown()["device_ops"]) == tr.TOP
    assert sum(s.idle.values()) == pytest.approx(s.window_s - s.busy_s)
