"""Pallas TPU kernel: block-indexed decode attention over a paged KV
cache (vLLM-style PagedAttention, retargeted to the repo's serving
subsystem).

The physical cache is a pool of fixed-size blocks ``[N, bs, Hkv * D]``
(heads merged into the minor dim, so a block is one dense ``(bs,
Hkv * D)`` tile in HBM); each request owns a *block table* of pool
indices.  The kernel never materializes the gathered per-request
context: the block table and the per-request lengths are
**scalar-prefetched** so the BlockSpec index map can DMA exactly the
blocks a request references, one block (all KV heads) per grid step,
with an online-softmax accumulator carried in VMEM scratch.

Blocking: grid = (B, M) with M = blocks-per-request sequential so the
running max/denominator/accumulator scratch carries across a request's
blocks.  Every block's last two dims are either whole array dims or
``(bs, Hkv * D)``, which is what the TPU tiling rule asks of a block.
Queries are presented as ``[B, group, Hkv * D]`` (row ``g`` holds query
head ``hk * group + g`` in lanes ``hk * D:(hk + 1) * D``), so each query
row lines up lane-for-lane with the K/V block.  Per-head dot products
are lane-segment sums, done on the MXU as a matmul with the 0/1
head-membership matrix ``E [Hkv * D, Hkv]``; ``E^T`` spreads per-head
probabilities back over their D lanes.

Blocks past a request's length are skipped entirely via ``pl.when``
(short requests in a long-max-len batch cost only their own blocks).

The pure-jnp oracle is ``repro.kernels.ref.paged_attention_ref``; the
serving decode path (`repro.models.paged`) uses the gathered-jnp
fallback off-TPU and this kernel on TPU (``ops.paged_attention``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_HIGHEST = jax.lax.Precision.HIGHEST


def _head_membership(hd: int, hkv: int, d: int, transpose: bool):
    """0/1 f32 matrix with E[r, c] = 1 iff lane r belongs to head c."""
    shape = (hkv, hd) if transpose else (hd, hkv)
    lane_dim, head_dim = (1, 0) if transpose else (0, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, lane_dim)
    head = jax.lax.broadcasted_iota(jnp.int32, shape, head_dim)
    inside = (lane >= head * d) & (lane < head * d + d)
    return inside.astype(jnp.float32)


def _paged_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, block_size: int,
                  blocks_per_seq: int, head_dim: int):
    b = pl.program_id(0)
    bi = pl.program_id(1)
    group, hd = q_ref.shape
    hkv = hd // head_dim

    @pl.when(bi == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    length = lengths_ref[b]

    @pl.when(bi * block_size < length)
    def _update():
        e = _head_membership(hd, hkv, head_dim, transpose=False)
        et = _head_membership(hd, hkv, head_dim, transpose=True)
        k = k_ref[...].astype(jnp.float32)              # (bs, Hkv*D)
        v = v_ref[...].astype(jnp.float32)
        k_ids = bi * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_size, hkv), 0)
        mask = k_ids < length                           # (bs, Hkv)
        for g in range(group):
            q = q_ref[g:g + 1, :].astype(jnp.float32) * scale
            s = jnp.dot(k * q, e, precision=_HIGHEST,
                        preferred_element_type=jnp.float32)   # (bs, Hkv)
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_scr[g:g + 1, :]                  # (1, Hkv)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(jnp.where(mask, s - safe_m, _NEG_INF))
            corr = jnp.where(jnp.isfinite(m_prev),
                             jnp.exp(m_prev - safe_m), 0.0)
            l_scr[g:g + 1, :] = (l_scr[g:g + 1, :] * corr
                                 + jnp.sum(p, axis=0, keepdims=True))
            m_scr[g:g + 1, :] = m_new
            p_lanes = jnp.dot(p, et, precision=_HIGHEST,
                              preferred_element_type=jnp.float32)
            corr_lanes = jnp.dot(corr, et, precision=_HIGHEST,
                                 preferred_element_type=jnp.float32)
            acc_scr[g:g + 1, :] = (acc_scr[g:g + 1, :] * corr_lanes
                                   + jnp.sum(p_lanes * v, axis=0,
                                             keepdims=True))

    @pl.when(bi == blocks_per_seq - 1)
    def _finalize():
        et = _head_membership(hd, hkv, head_dim, transpose=True)
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        l_lanes = jnp.dot(l, et, precision=_HIGHEST,
                          preferred_element_type=jnp.float32)
        o_ref[...] = (acc_scr[...] / l_lanes).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    interpret: bool) -> jax.Array:
    """[B, H, D] x pool [N, bs, Hkv * D] block-indexed decode attention.

    ``block_tables``: [B, M] int32 pool indices in logical order;
    ``lengths``: [B] int32 valid tokens per request.  Returns [B, H, D].
    """
    b, h, d = q.shape
    n, bs, hd = k_pages.shape
    hkv = hd // d
    assert hkv * d == hd and h % hkv == 0, (q.shape, k_pages.shape)
    group = h // hkv
    m = block_tables.shape[1]
    scale = 1.0 / (d ** 0.5)
    # [B, H, D] -> [B, group, Hkv * D]: query head hk * group + g sits in
    # row g, lanes of KV head hk
    q_rows = q.reshape(b, hkv, group, d).transpose(0, 2, 1, 3).reshape(
        b, group, hd)

    kernel = functools.partial(_paged_kernel, scale=scale, block_size=bs,
                               blocks_per_seq=m, head_dim=d)
    row_spec = pl.BlockSpec((None, group, hd),
                            lambda bb, ii, tables, lens: (bb, 0, 0))
    page_spec = pl.BlockSpec((None, bs, hd),
                             lambda bb, ii, tables, lens:
                             (tables[bb, ii], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, m),
        in_specs=[row_spec, page_spec, page_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((group, hkv), jnp.float32),   # running max
            pltpu.VMEM((group, hkv), jnp.float32),   # running denom
            pltpu.VMEM((group, hd), jnp.float32),    # accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q_rows, k_pages, v_pages)
    return out.reshape(b, group, hkv, d).transpose(0, 2, 1, 3).reshape(
        b, h, d)


__all__ = ["paged_attention"]
