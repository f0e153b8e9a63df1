"""Operations and bytes computed from shapes, for the model's tokens and
for the paged decode kernel.  Nothing here is read from the program.

What one token costs, what attention costs per position attended, and
what the paged kernel moves are the family's own counts
(``bench/families/<family>.py``); this module adds them up over a
server step's prefill chunks and decode rows.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from bench import families


def prefill_chunk_flops(conf: Dict, tokens: int, ctx: int) -> int:
    """A chunk of ``tokens`` prompt tokens after ``ctx`` cached ones
    (causal: token i attends ctx + i + 1 positions)."""
    fam = families.of(conf)
    attended = tokens * ctx + tokens * (tokens + 1) // 2
    return tokens * fam.flops_per_token(conf) + fam.attention_flops(conf, attended)


def decode_flops(conf: Dict, lengths: Iterable[int]) -> int:
    """One decode token per row; ``lengths`` are the positions each row
    attends (its context including the new token)."""
    fam = families.of(conf)
    lengths = list(lengths)
    return len(lengths) * fam.flops_per_token(conf) + fam.attention_flops(conf, sum(lengths))


def paged_kernel_cost(conf: Dict, lengths: Iterable[int], block_size: int) -> Tuple[int, int]:
    """(FLOPs, HBM bytes) of one decode step of the paged kernel over
    rows attending ``lengths`` positions, all layers."""
    return families.of(conf).paged_kernel_cost(conf, lengths, block_size)


def step_flops(conf: Dict, prefill: Iterable[Tuple[int, int]], decode: Iterable[int]) -> int:
    """Model FLOPs of one server step: its prefill chunks (tokens, ctx)
    and its decode rows (attended lengths)."""
    return sum(prefill_chunk_flops(conf, n, c) for n, c in prefill) + decode_flops(conf, decode)
