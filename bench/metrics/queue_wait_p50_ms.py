"""Scheduler: median wait from due time to admission into a slot, over
the requests due in the window that were admitted (harness spans: the
admission happens at the start of the ``step_once`` that admits)."""

from bench.metrics._common import percentile


def read(run):
    w = run.window
    return percentile([(s.admit - s.due) * 1e3 for s in w.reqs.values() if s.due <= w.t1 and s.admit is not None], 50)
