"""Prefill path: median time from a request's first admission to its
first token, in ms, over the program's requests first admitted inside
the window that hold a first token (``Request.admit_t`` and
``first_token_t``, both on the host's monotonic clock)."""

from bench.metrics._common import percentile


def read(run):
    w = run.window
    return percentile([(r.first_token_t - r.admit_t) * 1e3 for r in w.requests if getattr(r, "admit_t", None) is not None and w.in_window(r.admit_t) and r.first_token_t is not None], 50)
