"""Public jit'd entry points for the Pallas kernels.

``default_interpret`` is the one place that decides the Pallas mode:
interpret off-TPU, so the kernels execute (and are tested) on CPU, and
native on a TPU backend.  The raw kernel modules take ``interpret`` as
a required argument, so no call can fall into the interpreter on a TPU
by default.
"""

from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import multi_add as _ma
from repro.kernels import paged_attention as _pa
from repro.kernels import selective_scan as _ss
from repro.kernels.ref import (flash_attention_ref, multi_add_ref,
                               paged_attention_ref, selective_scan_ref)


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def multi_add(stacked, *, block_n: int = _ma.DEFAULT_BLOCK_N,
              interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _ma.multi_add(stacked, block_n=block_n, interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    block_q: int = _fa.DEFAULT_BLOCK_Q,
                    block_k: int = _fa.DEFAULT_BLOCK_K,
                    interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    interpret: bool | None = None):
    """Block-indexed decode attention over a paged KV cache."""
    if interpret is None:
        interpret = default_interpret()
    return _pa.paged_attention(q, k_pages, v_pages, block_tables, lengths,
                               interpret=interpret)


def selective_scan(dt, x, b, c, a, h0, *,
                   block_d: int = _ss.DEFAULT_BLOCK_D,
                   chunk: int = _ss.DEFAULT_CHUNK,
                   interpret: bool | None = None,
                   trainable: bool = False):
    """Fused Mamba-1 scan.  ``trainable=True`` uses the custom-VJP
    variant whose backward kernel recomputes within chunks from saved
    chunk-boundary states (flash-style)."""
    if interpret is None:
        interpret = default_interpret()
    if trainable:
        return _ss.selective_scan_trainable(dt, x, b, c, a, h0, block_d,
                                            chunk, interpret)
    return _ss.selective_scan(dt, x, b, c, a, h0, block_d=block_d,
                              chunk=chunk, interpret=interpret)


__all__ = ["default_interpret", "multi_add", "flash_attention",
           "paged_attention", "selective_scan", "multi_add_ref",
           "flash_attention_ref", "paged_attention_ref",
           "selective_scan_ref"]
