"""Output tokens that reached their requests inside the window, over
the window's length (host clock)."""


def read(run):
    w = run.window
    n = sum(1 for s in w.reqs.values() for t in s.tokens if w.t0 <= t <= w.t1)
    return n / w.seconds
