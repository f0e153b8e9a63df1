"""Prefix cache: prompt tokens served from cached blocks over prompt
tokens, of the requests admitted in the window (the scheduler's
per-request ``cached_prefix_tokens`` counter, read at admission)."""


def read(run):
    w = run.window
    adm = [s for s in w.reqs.values() if s.admit is not None and w.in_window(s.admit)]
    total = sum(s.prompt_len for s in adm)
    if not total:
        return None
    return sum(s.cached for s in adm) / total
