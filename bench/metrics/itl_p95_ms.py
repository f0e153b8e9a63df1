"""95th percentile gap between consecutive output tokens, over every
pair of one request whose later token came inside the window (host
clock, read after each ``step_once``)."""

import numpy as np

from bench.metrics._common import percentile


def read(run):
    w = run.window
    gaps = []
    for s in w.reqs.values():
        t = np.asarray(s.tokens)
        if t.size > 1:
            later = t[1:]
            gaps.extend(((later - t[:-1]) * 1e3)[(later >= w.t0) & (later <= w.t1)])
    return percentile(gaps, 95)
