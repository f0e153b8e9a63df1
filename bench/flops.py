"""Operations and bytes computed from shapes, for the model's tokens and
for the paged decode kernel.  Nothing here is read from the program.

A token's model FLOPs are 2 per weight it multiplies (every layer's
projections and MLP, and the output head), plus attention: 4 * heads *
head_dim per position attended in each layer (scores and the weighted
sum of values).  Embedding lookups, norms and rotary terms are left out.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from bench.model import head_dim


def layer_matmul_params(conf: Dict) -> int:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    h, kv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], head_dim(conf)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def dense_flops_per_token(conf: Dict) -> int:
    """2 x (every layer's matmul weights + the head)."""
    return 2 * (conf["num_hidden_layers"] * layer_matmul_params(conf) + conf["hidden_size"] * conf["vocab_size"])


def attention_flops(conf: Dict, positions: int) -> int:
    """Scores and value sums over ``positions`` attended, all layers."""
    return 4 * conf["num_attention_heads"] * head_dim(conf) * positions * conf["num_hidden_layers"]


def prefill_chunk_flops(conf: Dict, tokens: int, ctx: int) -> int:
    """A chunk of ``tokens`` prompt tokens after ``ctx`` cached ones
    (causal: token i attends ctx + i + 1 positions)."""
    attended = tokens * ctx + tokens * (tokens + 1) // 2
    return tokens * dense_flops_per_token(conf) + attention_flops(conf, attended)


def decode_flops(conf: Dict, lengths: Iterable[int]) -> int:
    """One decode token per row; ``lengths`` are the positions each row
    attends (its context including the new token)."""
    lengths = list(lengths)
    return len(lengths) * dense_flops_per_token(conf) + attention_flops(conf, sum(lengths))


def paged_kernel_cost(conf: Dict, lengths: Iterable[int], block_size: int, dtype_bytes: int = 2) -> Tuple[int, int]:
    """(FLOPs, HBM bytes) the paged decode kernel needs for one decode
    step over all layers: per row of attended length ``c``, 4 * H * D *
    c FLOPs, the K and V of its ceil(c / block_size) blocks, and its
    query and output rows."""
    h, kv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], head_dim(conf)
    layers = conf["num_hidden_layers"]
    flops = nbytes = 0
    for c in lengths:
        blocks = -(-c // block_size)
        flops += 4 * h * hd * c
        nbytes += 2 * blocks * block_size * kv * hd * dtype_bytes + 2 * h * hd * dtype_bytes
    return flops * layers, nbytes * layers


def step_flops(conf: Dict, prefill: Iterable[Tuple[int, int]], decode: Iterable[int]) -> int:
    """Model FLOPs of one server step: its prefill chunks (tokens, ctx)
    and its decode rows (attended lengths)."""
    return sum(prefill_chunk_flops(conf, n, c) for n, c in prefill) + decode_flops(conf, decode)
