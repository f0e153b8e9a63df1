"""Pallas TPU kernel: block-indexed decode attention over a paged KV
cache (vLLM-style PagedAttention, retargeted to the repo's serving
subsystem).

The physical cache is a pool of fixed-size blocks ``[N, bs, Hkv * D]``
(heads merged into the minor dim, so a block is one dense ``(bs,
Hkv * D)`` tile in HBM); each request owns a *block table* of pool
indices.  The kernel never materializes the gathered per-request
context: the block tables and the lengths are **scalar-prefetched**
into SMEM and the pool stays in HBM (``memory_space=ANY``).

Loop: the grid runs over rows.  Each row walks only its own
``ceil(length / tile)`` tiles in a ``fori_loop``; a tile is P pages of
the row's block table (``tile_pages``: P from the page width, the dtype
and a VMEM budget), fetched page by page with async copies into one of
two VMEM slots.  The next tile's copies (the next row's first tile,
after a row's last) start before the current tile's compute, so the
fetch of one tile hides behind the arithmetic of another.  Pages past
a row's length are never fetched, and a row of length L costs
``ceil(L / tile)`` loop steps whatever the table width.  The running
max, denominator and accumulator carry across a row's tiles in VMEM
(online softmax).

Contraction, once per tile over all its ``P * bs`` tokens: the queries
are laid out once per row as a block-diagonal ``[H, Hkv * D]`` matrix
(query head ``h`` in the lanes of its KV head), so the scores of every
head are one ``(H, Hkv * D) x (P * bs, Hkv * D)^T`` matmul and
``p @ V`` is one ``(H, P * bs) x (P * bs, Hkv * D)`` matmul whose
block-diagonal lanes hold each head's output.  ``_dot_f32`` keeps both
exact to f32: bf16 x bf16 products are exact, and the f32 ``p`` is cut
into three bf16 pieces rather than rounded.

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
#: VMEM for the K and V tiles, two of each in flight
_TILE_BUFFER_BYTES = 6 * 2**20
_MAX_TILE_TOKENS = 512
#: query heads are padded to whole bf16 sublane tiles
_ROW_ALIGN = 16


def tile_pages(block_size: int, width: int, dtype, blocks_per_seq: int
               ) -> int:
    """Pages per tile: as many as the K/V double buffers' VMEM budget
    and ``_MAX_TILE_TOKENS`` allow, a multiple of 8 from 8 pages up,
    never more than the table holds."""
    per_page = 4 * block_size * width * jnp.dtype(dtype).itemsize
    pages = max(1, min(_TILE_BUFFER_BYTES // per_page,
                       _MAX_TILE_TOKENS // block_size))
    if pages >= 8:
        pages -= pages % 8
    return min(pages, blocks_per_seq)


def _dot_f32(a, b, contract_b: int):
    """``a @ b`` (``contract_b`` 0) or ``a @ b.T`` (1), accumulated in
    f32 and losing nothing to the MXU's bf16 passes.  bf16 x bf16 is one
    pass, its products exact in f32.  An f32 ``a`` against a bf16 ``b``
    is cut into three bf16 pieces that sum to it (8 significant bits
    each), stacked as rows of one pass.  Anything else runs at
    HIGHEST."""
    dims = (((1,), (contract_b,)), ((), ()))
    if b.dtype != jnp.bfloat16:
        return jax.lax.dot_general(a.astype(jnp.float32),
                                   b.astype(jnp.float32), dims,
                                   precision=_HIGHEST,
                                   preferred_element_type=jnp.float32)
    if a.dtype == jnp.bfloat16:
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32)
    pieces, rest = [], a
    for _ in range(3):
        piece = rest.astype(jnp.bfloat16)
        pieces.append(piece)
        rest = rest - piece.astype(jnp.float32)
    out = jax.lax.dot_general(jnp.concatenate(pieces, axis=0), b, dims,
                              preferred_element_type=jnp.float32)
    n = a.shape[0]
    return out[:n] + out[n:2 * n] + out[2 * n:]


def _index_div(x, k: int):
    """``x // k`` for int32 ``x`` in [0, 2**20), through f32: ``x + 0.5``
    keeps the quotient at least ``0.5 / k`` from a whole number, so the
    truncation is exact."""
    return ((x.astype(jnp.float32) + 0.5) * (1.0 / k)).astype(jnp.int32)


def _paged_kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, k_sem, v_sem, slot_ref, qbd_scr, m_scr,
                  l_scr, acc_scr, *, scale: float, blocks_per_seq: int):
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    h, d = q_ref.shape
    _, pages, bs, width = k_buf.shape
    tile = pages * bs
    hp = qbd_scr.shape[0]
    group = h // (width // d)

    def length_of(row):
        return jnp.minimum(lengths_ref[row], blocks_per_seq * bs)

    def tile_copies(row, i, slot, kv, op):
        """Start (or wait for) the copies of tile ``i`` of ``row`` into
        ``slot``: one per page that holds a token below the length."""
        first = i * pages
        live = jnp.clip(pl.cdiv(length_of(row), bs) - first, 0, pages)

        def page(j, carry):
            blk = tables_ref[row * blocks_per_seq + first + j]
            for src, buf, sem in kv:
                copy = pltpu.make_async_copy(src.at[blk], buf.at[slot, j],
                                             sem.at[slot])
                copy.start() if op == "start" else copy.wait()
            return carry

        jax.lax.fori_loop(0, live, page, 0)

    k_parts, v_parts = (k_hbm, k_buf, k_sem), (v_hbm, v_buf, v_sem)

    @pl.when(b == 0)
    def _first_row():
        # a page past a row's length is never fetched, so its slot holds
        # whatever the VMEM held: make that finite once, for 0 * v
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        slot_ref[0] = 0
        tile_copies(0, 0, 0, (k_parts, v_parts), "start")

    length = length_of(b)
    n_tiles = pl.cdiv(length, tile)

    @pl.when((n_tiles == 0) & (b + 1 < rows))
    def _pass_on():
        tile_copies(b + 1, 0, slot_ref[0], (k_parts, v_parts), "start")

    # block-diagonal queries: row r holds query head r (zero rows pad to
    # hp) in the lanes of its KV head
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    lane_head = _index_div(lane, d)
    row = jax.lax.broadcasted_iota(jnp.int32, (hp, 1), 0)
    row_head = jnp.where(row < h, _index_div(row, group), -1)
    own = lane_head == row_head                       # (hp, Hkv*D)
    d_idx = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0)
    spread = (lane - d * lane_head == d_idx).astype(
        k_buf.dtype)                                  # (D, Hkv*D) 0/1
    q = q_ref[...]
    if hp > h:
        q = jnp.concatenate([q, jnp.zeros((hp - h, d), q.dtype)], axis=0)
    qbd_scr[...] = jnp.where(own, _dot_f32(q, spread, 0),
                             0.0).astype(qbd_scr.dtype)
    m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
    l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
    acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    def body(i, carry):
        slot = slot_ref[0]
        nxt = 1 - slot

        @pl.when(i + 1 < n_tiles)
        def _next_tile():
            tile_copies(b, i + 1, nxt, (k_parts, v_parts), "start")

        @pl.when((i + 1 == n_tiles) & (b + 1 < rows))
        def _next_row():
            tile_copies(b + 1, 0, nxt, (k_parts, v_parts), "start")

        tile_copies(b, i, slot, (k_parts,), "wait")
        k = k_buf[slot].reshape(tile, width)
        s = _dot_f32(qbd_scr[...], k, 1) * scale          # (hp, tile)
        pos = i * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        tile_copies(b, i, slot, (v_parts,), "wait")
        v = v_buf[slot].reshape(tile, width)
        acc_scr[...] = acc_scr[...] * corr + _dot_f32(p, v, 0)
        slot_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, n_tiles, body, 0)

    l = l_scr[...]
    out = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
    # each head's output sits in its own KV head's lanes: gather them
    out = _dot_f32(jnp.where(own, out, 0.0), spread, 1)   # (hp, D)
    o_ref[...] = out[:h].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    interpret: bool) -> jax.Array:
    """[B, H, D] x pool [N, bs, Hkv * D] block-indexed decode attention.

    ``block_tables``: [B, M] int32 pool indices in logical order;
    ``lengths``: [B] int32 valid tokens per request.  Returns [B, H, D].
    """
    b, h, d = q.shape
    n, bs, width = k_pages.shape
    hkv = width // d
    assert hkv * d == width and h % hkv == 0, (q.shape, k_pages.shape)
    m = block_tables.shape[1]
    pages = tile_pages(bs, width, k_pages.dtype, m)
    hp = -(-h // _ROW_ALIGN) * _ROW_ALIGN

    kernel = functools.partial(_paged_kernel, scale=1.0 / (d ** 0.5),
                               blocks_per_seq=m)
    row_spec = pl.BlockSpec((None, h, d), lambda bb, tables, lens:
                            (bb, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[row_spec, pool_spec, pool_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, pages, bs, width), k_pages.dtype),  # K tiles
            pltpu.VMEM((2, pages, bs, width), v_pages.dtype),  # V tiles
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),             # slot of this tile
            pltpu.VMEM((hp, width), k_pages.dtype),  # block-diag queries
            pltpu.VMEM((hp, 1), jnp.float32),        # running max
            pltpu.VMEM((hp, 1), jnp.float32),        # running denom
            pltpu.VMEM((hp, width), jnp.float32),    # accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        # rows run in order: a row's last tile fetches the next row's
        # first, and the first row clears the V slots
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32),
      lengths.astype(jnp.int32), q, k_pages, v_pages)


__all__ = ["paged_attention", "tile_pages"]
