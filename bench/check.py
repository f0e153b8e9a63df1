"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample
of the finished requests (drawn from the seed, always with the request
that served the most tokens) is run through the plain reference of the
configuration's family (``bench/families/<family>.py``) over prompt +
served tokens.  At every served position the reference's logits say how
far the served token lies below the reference's best token.  The number
compared is the widest such gap over all the sampled tokens; served
tokens are greedy, so a sound program only departs from the reference's
choice where two logits lie closer than its bf16 rounding.

Over several chips the window's load may sit on the first chip's rows
alone (the pool, shared by all rows, holds few requests), where a fault
in the exchange between chips would not show.  So the same server then
also serves one short request in every row (``harness.fill_rows``), and
the comparison adds one of them from each chip's rows
(``one_per_chip``); ``correct`` asks for every chip's rows compared.

``control=True`` reads the same gap for the token that the fp8 control
(the family's ``logits(..., control=True)``) puts first at each
position of the same prompts and tokens.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench import families

S_BUCKET = 1024  # sequence lengths are padded to a multiple of this


def sample_finished(finished: Sequence, seed: int, min_tokens: int, max_requests: int) -> List:
    """The finished request with the most served tokens, then others in
    an order drawn from ``seed`` until ``min_tokens`` served tokens or
    ``max_requests`` requests are reached."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: (-len(r.out), r.rid))
    chosen = [by_len[0]]
    rest = by_len[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    tokens = len(chosen[0].out)
    for i in order:
        if tokens >= min_tokens or len(chosen) >= max_requests:
            break
        chosen.append(rest[i])
        tokens += len(rest[i].out)
    return chosen


def one_per_chip(rows: Sequence, batch: int, chips: int, seed: int) -> List:
    """From (rows, request) pairs (``harness.fill_rows``), one request
    that sat in each chip's rows (a ``data`` mesh gives chip c the rows
    ``c * batch / chips`` up to the next chip's), drawn from ``seed``:
    [(chip, request)], a chip whose rows held none left out."""
    per = batch // chips
    rng = np.random.default_rng(seed)
    out = []
    for c in range(chips):
        held = sorted((req for rs, req in rows if any(i // per == c for i in rs)), key=lambda req: req.rid)
        if held:
            out.append((c, held[int(rng.integers(len(held)))]))
    return out


def verdict(widest_gap: float, tokens: int, limits: Dict, chips=None):
    """(correct, the compared numbers beside their limits): the widest
    gap at most its limit, over at least ``min_tokens`` served tokens
    (a gap that is not a number is not correct); over several chips
    (``chips`` = (chips whose rows made compared tokens, chips of the
    cell)) with every chip's rows compared."""
    checks = {
        "widest_logit_gap": {"value": widest_gap, "limit": limits["widest_logit_gap"]},
        "tokens_compared": {"value": tokens, "limit": limits["min_tokens"]},
    }
    ok = widest_gap <= limits["widest_logit_gap"] and tokens >= limits["min_tokens"]
    if chips is not None:
        checks["chips_compared"] = {"value": chips[0], "limit": chips[1]}
        ok = ok and chips[0] >= chips[1]
    return bool(ok), checks


def served_gaps(weights, conf: Dict, prompt: np.ndarray, out: Sequence[int], k: int, control: bool = False) -> Dict:
    """Reference gaps of one request's served tokens.  ``k`` (static)
    bounds the served tokens; returns the program's gaps and, with
    ``control``, the control's."""
    import jax.numpy as jnp

    fam = families.of(conf)
    fx = fam.factors(conf)
    p, n = len(prompt), len(out)
    if n > k:
        raise ValueError(f"{n} served tokens exceed the reference window {k}")
    seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)]).astype(np.int32)
    s = -(-(p - 1 + k) // S_BUCKET) * S_BUCKET
    toks = np.zeros((s,), np.int32)
    toks[: len(seq)] = seq
    ref = fam.logits(weights, conf, fx, jnp.asarray(toks), p - 1, k)
    best = jnp.max(ref, axis=-1)
    served = jnp.asarray(np.pad(np.asarray(out, np.int32), (0, k - n)))
    gap = best - jnp.take_along_axis(ref, served[:, None], axis=1)[:, 0]
    result = {"gap": np.asarray(gap[:n]), "n": n}
    if control:
        ctl = fam.logits(weights, conf, fx, jnp.asarray(toks), p - 1, k, control=True)
        pick = jnp.argmax(ctl, axis=-1)
        cgap = best - jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]
        result["control_gap"] = np.asarray(cgap[:n])
    return result


def compare(weights, conf: Dict, requests: Sequence, k: int, control: bool = False) -> Dict:
    """Widest gap over the served tokens of ``requests``."""
    gaps, cgaps, n = [], [], 0
    for req in requests:
        r = served_gaps(weights, conf, req.prompt, req.out, k, control)
        gaps.append(r["gap"])
        n += r["n"]
        if control:
            cgaps.append(r["control_gap"])
    out = {
        "requests": len(requests),
        "tokens": n,
        "widest_gap": float(np.max(np.concatenate(gaps))) if gaps else float("nan"),
        "off_best": int(sum(int(np.sum(g > 0)) for g in gaps)),
    }
    if control:
        c = np.concatenate(cgaps)
        out["control_widest_gap"] = float(np.max(c))
        out["control_off_best"] = int(np.sum(c > 0))
    return out
