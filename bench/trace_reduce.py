"""A profiler trace (``.xplane.pb``) -> the run's device numbers.

* The traced window is the span from the first to the last
  ``bench.step`` host span (the harness starts and stops the trace on
  step boundaries and marks each step).
* Busy time is the union of the intervals of the device's op events
  (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane) inside the
  window; ``busy_s`` averages it over the chips used.
* ``device_ops`` sums op self time (less the ops nested inside, as a
  ``while`` holds its body) by HLO instruction name on the first chip.
* ``idle_gaps`` attributes each gap between busy intervals on the first
  chip to the innermost host event (Python function or ``TraceMe``)
  running at its middle, summed by name.
"""

from __future__ import annotations

import bisect
import re
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

STEP_SPAN = "bench.step"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
TOP = 10


@dataclasses.dataclass
class Line:
    name: str
    events: List[Tuple[str, int, int]]  # (name, start ns, end ns)


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    ops: Dict[str, float]  # op name -> seconds, first chip
    idle: Dict[str, float]  # host activity -> idle seconds, first chip
    kernel_ops: Dict[str, float]  # op name -> seconds, averaged over chips

    def kernel_seconds(self, fragment: str) -> float:
        """Device seconds of the ops whose name holds ``fragment``,
        averaged over the chips."""
        return sum(v for k, v in self.kernel_ops.items() if fragment in k)

    def breakdown(self) -> Dict:
        top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.idle)}


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(a: int, b: int, lo: int, hi: int):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)


def op_name(name: str) -> str:
    """``%paged_attention.9 = bf16[32,1,2304]... custom-call(...)`` ->
    ``paged_attention.9`` (the HLO instruction's name)."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(events: List[Tuple[str, int, int]]) -> Dict[str, float]:
    """Seconds by name, each event less the events nested inside it on
    the same line (a ``while`` op holds its body's ops)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []  # [name, end, child ns]

    def close(item):
        name, a_end, kids, a = item
        out[name] += (a_end - a - kids) / 1e9
        if stack:
            stack[-1][2] += a_end - a

    for n, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        stack.append([n, b, 0, a])
    while stack:
        close(stack.pop())
    return dict(out)


def latest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def reduce_planes(planes, n_devices: int) -> Summary:
    """``planes``: objects with ``name`` and ``lines`` (each with
    ``name`` and ``events`` of ``name``, ``start_ns``, ``duration_ns``)
    as ``jax.profiler.ProfileData`` gives them."""
    planes = [Plane(p.name, [Line(ln.name, list(_events(ln))) for ln in p.lines]) for p in planes]
    host = [p for p in planes if p.name == HOST_PLANE]
    steps = [(a, b) for p in host for ln in p.lines for n, a, b in ln.events if n == STEP_SPAN]
    if not steps:
        raise ValueError(f"no {STEP_SPAN!r} host span in the trace")
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    devs = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    devs = sorted(devs, key=lambda p: int(re.sub(r"\D", "", p.name[len(DEVICE_PREFIX) :]) or 0))[:n_devices]
    if not devs:
        raise ValueError("no TPU device plane in the trace")
    busy_total = 0.0
    kernel: Dict[str, float] = defaultdict(float)
    first_ops: Dict[str, float] = {}
    first_busy: List[Tuple[int, int]] = []
    for i, p in enumerate(devs):
        evs = []
        for ln in p.lines:
            if ln.name == OPS_LINE:
                evs += [(op_name(n),) + c for n, a, b in ln.events if (c := clip(a, b, lo, hi))]
        ops = self_times(evs)
        for n, v in ops.items():
            kernel[n] += v / len(devs)
        u = union([(a, b) for _, a, b in evs])
        busy_total += sum(b - a for a, b in u) / 1e9
        if i == 0:
            first_ops, first_busy = ops, u
    idle = attribute_gaps(first_busy, lo, hi, host)
    return Summary((hi - lo) / 1e9, busy_total / len(devs), len(devs), first_ops, idle, dict(kernel))


def attribute_gaps(busy: List[Tuple[int, int]], lo: int, hi: int, host_planes) -> Dict[str, float]:
    """Idle seconds between ``busy`` intervals in [lo, hi], by the
    innermost host event that covers each gap's middle."""
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    evs = sorted((a, b, n) for p in host_planes for ln in p.lines for n, a, b in ln.events if b > a)
    starts = [a for a, _, _ in evs]
    out: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        mid = (a + b) // 2
        best = None
        # events nest per thread: the latest-starting event that still
        # covers the middle is the innermost one
        for j in range(bisect.bisect_right(starts, mid) - 1, max(-1, bisect.bisect_right(starts, mid) - 5001), -1):
            if evs[j][1] >= mid:
                best = evs[j]
                break
        out[best[2] if best else "(no host event)"] += (b - a) / 1e9
    return dict(out)


def reduce_dir(trace_dir: str, n_devices: int) -> Summary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(latest_xplane(trace_dir)).planes, n_devices)
