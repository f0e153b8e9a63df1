"""Scheduler: mean share of the decode batch's rows that carry a
request, over the traced ``serve.decode`` spans (their ``rows`` and
``batch`` counters), in %."""

from bench.metrics._spans import spans


def read(run):
    sp = spans(run)
    occ = [s["rows"] / s["batch"] for _, _, _, s in sp.named("serve.decode") if s.get("batch")] if sp is not None else []
    return 100.0 * sum(occ) / len(occ) if occ else None
