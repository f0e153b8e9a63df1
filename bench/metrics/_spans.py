"""Helpers for the readers of the server's own spans (not a metric
itself).  ``ContinuousBatchingServer.step_once`` opens ``serve.*``
profiler annotations (``serve.step`` around each call, its layers nested
inside, their counters as event stats); a traced run's ``.xplane.pb``
holds them on the device ops' clock.  A program without them gives
``None`` here, and every reader then returns ``None``."""

from __future__ import annotations

import dataclasses
import functools
import os
import re
from typing import Dict, List, Optional, Tuple

from bench import trace_reduce as tr

SERVE = "serve."


@dataclasses.dataclass
class Spans:
    """The traced window ``[lo, hi]`` (first to last ``bench.step``, ns),
    the ``serve.*`` host events inside it as (name, start, end, stats)
    by start, the first chip's busy intervals inside it, and each chip's
    device ops inside it as (HLO op name, start, end)."""

    lo: int
    hi: int
    events: List[Tuple[str, int, int, Dict]]
    busy: List[Tuple[int, int]]
    chip_ops: List[List[Tuple[str, int, int]]]

    def named(self, name: str):
        return [e for e in self.events if e[0] == name]

    def inside(self, name: str, a: int, b: int):
        return [e for e in self.events if e[0] == name and a <= e[1] and e[2] <= b]

    def busy_ns(self, a: int, b: int) -> int:
        return sum(max(0, min(b, y) - max(a, x)) for x, y in self.busy)

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds of the window by the innermost ``serve.*`` span
        at each gap's middle (``harness`` where none is open)."""
        lines = [tr.Line("serve", [(n, a, b) for n, a, b, _ in self.events])]
        idle = tr.attribute_gaps(self.busy, self.lo, self.hi, [tr.Plane("serve", lines)])
        if "(no host event)" in idle:
            idle["harness"] = idle.pop("(no host event)")
        return idle


def from_planes(planes) -> Optional[Spans]:
    """``planes`` as ``jax.profiler.ProfileData`` gives them (events with
    ``name``, ``start_ns``, ``duration_ns`` and ``stats``; ``planes``
    may be read once only)."""
    planes = list(planes)
    host = [p for p in planes if p.name == tr.HOST_PLANE]
    evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns), e) for p in host for ln in p.lines for e in ln.events]
    steps = [(a, b) for n, a, b, _ in evs if n == tr.STEP_SPAN]
    devs = sorted((p for p in planes if p.name.startswith(tr.DEVICE_PREFIX)), key=lambda p: int(re.sub(r"\D", "", p.name[len(tr.DEVICE_PREFIX) :]) or 0))
    if not steps or not devs:
        return None
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    serve = [(n, a, b, dict(getattr(e, "stats", ()))) for n, a, b, e in evs if n.startswith(SERVE) and lo <= a and b <= hi]
    if not serve:
        return None
    chip_ops = []
    for p in devs:
        evs = ((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for ln in p.lines if ln.name == tr.OPS_LINE for e in ln.events)
        chip_ops.append([(tr.op_name(n),) + c for n, a, b in evs if (c := tr.clip(a, b, lo, hi))])
    busy = tr.union([(a, b) for _, a, b in chip_ops[0]])
    return Spans(lo, hi, sorted(serve, key=lambda e: (e[1], -e[2])), busy, chip_ops)


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> Optional[Spans]:
    from jax.profiler import ProfileData

    return from_planes(ProfileData.from_file(path).planes)


def trace_dir() -> str:
    from bench.run import TRACE_DIR

    return TRACE_DIR


def spans(run) -> Optional[Spans]:
    """The run's spans, from the newest ``.xplane.pb`` under the
    benchmark's trace directory (parsed once per process); None in an
    untraced run or where the trace holds no ``serve.*`` event."""
    if run.trace is None:
        return None
    try:
        path = tr.latest_xplane(trace_dir())
    except FileNotFoundError:
        return None
    return _load(path, os.path.getmtime(path))
