"""Median time from a request's due time to its first token, over all
requests due in the window (host clock)."""

from bench.metrics._common import percentile, ttft_ms


def read(run):
    return percentile(ttft_ms(run), 50)
