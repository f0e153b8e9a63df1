"""Traffic generators: one traffic file of parameters -> a fixed list
of requests with due times.

Two kinds, named by the traffic file's ``kind``:

* ``open``     -- independent requests at a Poisson rate (open loop:
  each is submitted once due and timed from its due time).
* ``backlog``  -- a fixed number of requests, all due at window start.

What the run's seed changes and what it keeps: the *shape* of the
traffic (every length and every due time, in order) is drawn from the
traffic file's ``base_seed``, so every seed serves the same work;
``--seed`` draws every token id.  (An order permuted by the seed was
tried first: which long requests came early then moved tokens/s by 8%
between seeds against about 2% between two runs of one seed.)  Lengths
are lognormal by median and sigma, clipped to [min, max].  The Poisson
arithmetic follows ``repro.serving.fleet.trace`` and
``benchmarks/serve_bench.make_trace``, with arrivals in seconds rather
than scheduler waves.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class TrafficRequest:
    rid: int
    due_s: float            # offset from window start
    prompt: np.ndarray      # [S] int32
    max_new: int


def lognormal_lengths(rng: np.random.Generator, n: int, spec: Dict) -> np.ndarray:
    """``n`` lengths, lognormal around ``spec['median']`` with
    ``spec['sigma']``, clipped to ``[spec['min'], spec['max']]``."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def poisson_gaps(rng: np.random.Generator, n: int, rate: float, seconds: float) -> np.ndarray:
    """``n`` exponential gaps at ``rate`` per second, rescaled so that
    the ``n`` arrivals fill ``seconds`` exactly (the same offered load
    on every seed)."""
    gaps = rng.exponential(1.0 / rate, size=n)
    return gaps * (seconds / gaps.sum())


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(n)).astype(np.int32)


def _open(spec: Dict, seed: int, seconds: float, vocab: int) -> List[TrafficRequest]:
    base = np.random.default_rng(spec["base_seed"])
    n = max(1, int(round(spec["rate_rps"] * seconds)))
    prompts = lognormal_lengths(base, n, spec["prompt"])
    outs = lognormal_lengths(base, n, spec["output"])
    gaps = poisson_gaps(base, n, spec["rate_rps"], seconds)
    rng = np.random.default_rng(seed)
    due = np.cumsum(gaps) - gaps[0]
    return [TrafficRequest(i, float(due[i]), _tokens(rng, prompts[i], vocab), int(outs[i])) for i in range(n)]


def _backlog(spec: Dict, seed: int, seconds: float, vocab: int) -> List[TrafficRequest]:
    base = np.random.default_rng(spec["base_seed"])
    n = int(spec["requests"])
    prompts = lognormal_lengths(base, n, spec["prompt"])
    outs = lognormal_lengths(base, n, spec["output"])
    rng = np.random.default_rng(seed)
    return [TrafficRequest(i, 0.0, _tokens(rng, prompts[i], vocab), int(outs[i])) for i in range(n)]


KINDS = {"open": _open, "backlog": _backlog}


def is_backlog(spec: Dict) -> bool:
    """A backlog is served until the window closes and judged by its
    tokens/s; every other kind is open loop, and its requests due in the
    window wait for their first token after the close."""
    return spec["kind"] == "backlog"


def generate(spec: Dict, seed: int, seconds: float, vocab: int) -> List[TrafficRequest]:
    """The requests of one run, sorted by due time, rids 0..n-1."""
    if spec["kind"] not in KINDS:
        raise ValueError(f"unknown traffic kind {spec['kind']!r}; known: {sorted(KINDS)}")
    return KINDS[spec["kind"]](spec, seed, seconds, vocab)
