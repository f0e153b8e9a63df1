"""Helpers shared by the metric readers (not a metric itself)."""

from __future__ import annotations

import numpy as np


def ttft_ms(run):
    """Time to first token of every request due in the window, in ms.
    A request that never got one counts with the time from its due time
    to the end of the run, a lower bound that misses any limit."""
    w = run.window
    end = w.t1 + w.drain_s
    out = [((s.tokens[0] if s.tokens else end) - s.due) * 1e3 for s in w.reqs.values() if s.due <= w.t1]
    return np.asarray(out)


def percentile(values, q):
    values = np.asarray(values, dtype=np.float64)
    if values.size == 0:
        return None
    return float(np.percentile(values, q))


def step_ms(run, kind):
    """Host times of the window's steps of ``kind``, in ms (steps under
    the profiler are left out: tracing slows the host)."""
    return [(s.t1 - s.t0) * 1e3 for s in run.window.steps if s.kind == kind and not s.traced and run.window.in_window(s.t0)]
