"""Paged-KV-cache model entry points (serving's continuous-batching
counterpart to the dense ``prefill``/``decode_step`` cache in
transformer.py).

The physical cache is a pool of fixed-size blocks shared by every
request in the batch::

    pages = {"k": [L, N, bs, Hkv * Hd], "v": [L, N, bs, Hkv * Hd]}

(heads merged into the minor dim: a block is one dense ``(bs, Hkv *
Hd)`` tile in HBM, which is what the decode kernel DMAs per step).

Per-request state lives host-side in the serving scheduler and is passed
in per call: ``block_tables`` [B, M] int32 (pool indices in logical
order) and ``ctx_lens`` [B] int32 (tokens already cached).  Block 0 is
reserved as a scratch sink: writes from padded chunk tails and inactive
batch rows are redirected there, so idle decode slots never clobber live
cache state (this is what lets the scheduler admit/retire every step
instead of padding waves with garbage rows).

One forward handles both phases:

* **chunked prefill** -- ``forward_paged`` with T > 1 processes a chunk
  of the prompt (long prompts stream in without stalling decode);
* **decode** -- T = 1; off-TPU attention runs a gathered pure-jnp path,
  on TPU the Pallas block-indexed kernel
  (``repro.kernels.paged_attention``) reads only the blocks each request
  references.

Supported families: dense and moe decoders (llava-style vision via
``soft_emb`` on the first chunk).  SSM/hybrid/encdec keep the dense
cache path -- their decode state is O(1) or windowed already.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import (_ambient_mesh, apply_rope, rms_norm,
                                 shard_hint)
from repro.models.transformer import _ffn_sublayer, _unembed

Params = Dict[str, Any]

PAGED_FAMILIES = ("dense", "moe")


def supports_paged(cfg: ArchConfig) -> bool:
    return cfg.family in PAGED_FAMILIES


def init_pages(cfg: ArchConfig, num_blocks: int,
               block_size: int) -> Dict[str, jax.Array]:
    """Zeroed physical block pool (block 0 is the scratch sink)."""
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"paged KV cache supports families {PAGED_FAMILIES}, "
            f"not {cfg.family!r} (constant-state families keep the dense "
            f"cache)")
    shape = (cfg.num_layers, num_blocks, block_size,
             cfg.num_kv_heads * cfg.resolved_head_dim)
    dtype = cfg.activation_dtype
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def copy_blocks(pages: Dict[str, jax.Array], src: jax.Array,
                dst: jax.Array) -> Dict[str, jax.Array]:
    """Copy whole physical blocks ``src[i] -> dst[i]`` in every layer
    of the pool (copy-on-write for shared prefix blocks: the scheduler
    re-points a request's table at a private copy before the request
    writes into a block other tables still read).

    ``src``/``dst``: [n] int32 pool indices; destinations must be
    distinct (they are freshly allocated), sources may repeat.
    """
    return {name: arr.at[:, dst].set(arr[:, src])
            for name, arr in pages.items()}


def _write_pages(pages_l: jax.Array, new: jax.Array,
                 block_tables: jax.Array, ctx_lens: jax.Array,
                 valid: jax.Array) -> jax.Array:
    """Scatter [B, T, Hkv, Hd] new KV into one layer's [N, bs, Hkv * Hd]
    pool.

    Position ctx+t of row b lands in slot (ctx+t) % bs of block
    block_tables[b, (ctx+t) // bs]; invalid positions (padded tails,
    inactive rows) are redirected into scratch block 0.
    """
    n, bs = pages_l.shape[:2]
    b, t = new.shape[:2]
    m = block_tables.shape[1]
    pos = ctx_lens[:, None] + jnp.arange(t)[None, :]          # [B, T]
    blk = jnp.take_along_axis(block_tables,
                              jnp.minimum(pos // bs, m - 1), axis=1)
    flat = blk * bs + pos % bs
    flat = jnp.where(valid, flat, pos % bs)                   # scratch
    out = pages_l.reshape(n * bs, pages_l.shape[2])
    out = out.at[flat.reshape(-1)].set(
        new.reshape(b * t, -1).astype(pages_l.dtype))
    return out.reshape(pages_l.shape)


def _gathered_attention(q: jax.Array, kp: jax.Array, vp: jax.Array,
                        block_tables: jax.Array, ctx_lens: jax.Array
                        ) -> jax.Array:
    """Pure-jnp paged attention for T >= 1 (prefill chunks, CPU decode).

    q: [B, T, H, Hd] at absolute positions ctx..ctx+T-1; kp/vp:
    [N, bs, Hkv * Hd] pool *after* the chunk's writes.  Causal over the
    gathered logical context.
    """
    n, bs, _ = kp.shape
    b, t, h, hd = q.shape
    hkv = kp.shape[2] // hd
    m = block_tables.shape[1]
    group = h // hkv
    idx = (block_tables[:, :, None] * bs
           + jnp.arange(bs)[None, None, :]).reshape(b, m * bs)
    # gather the referenced rows, then split heads on the gathered
    # context only (splitting the pool's minor dim would relayout it)
    k = kp.reshape(n * bs, -1)[idx].reshape(b, m * bs, hkv, hd)
    v = vp.reshape(n * bs, -1)[idx].reshape(b, m * bs, hkv, hd)
    kt = jnp.repeat(jnp.moveaxis(k, 1, 2), group, axis=1)     # [B, H, S, Hd]
    vt = jnp.repeat(jnp.moveaxis(v, 1, 2), group, axis=1)
    qt = jnp.moveaxis(q, 1, 2)                                # [B, H, T, Hd]
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                        preferred_element_type=jnp.float32) / (hd ** 0.5)
    q_pos = ctx_lens[:, None] + jnp.arange(t)[None, :]        # [B, T]
    k_pos = jnp.arange(m * bs)
    mask = k_pos[None, None, None, :] <= q_pos[:, None, :, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt.astype(jnp.float32))
    return jnp.moveaxis(out.astype(q.dtype), 1, 2)            # [B, T, H, Hd]


def _kernel_attention(q: jax.Array, kp: jax.Array, vp: jax.Array,
                      block_tables: jax.Array, lengths: jax.Array
                      ) -> jax.Array:
    """The Pallas decode kernel.  A Mosaic kernel cannot be partitioned
    by GSPMD, so under a mesh with batch axes it runs per batch shard
    (rows split, pool replicated)."""
    from repro.kernels.ops import paged_attention
    mesh = _ambient_mesh()
    dp = tuple(a for a in ("pod", "data")
               if mesh is not None and a in mesh.axis_names)
    if not dp:
        return paged_attention(q, kp, vp, block_tables, lengths)
    rows = P(dp if len(dp) > 1 else dp[0])
    return jax.shard_map(paged_attention, mesh=mesh,
                         in_specs=(rows, P(), P(), rows, rows),
                         out_specs=rows, check_vma=False)(
        q, kp, vp, block_tables, lengths)


def _paged_decoder_block(cfg: ArchConfig, x, lp, kp, vp, block_tables,
                         ctx_lens, valid, use_kernel: bool):
    b, t, _ = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    hn = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = shard_hint((hn @ lp["wq"]).reshape(b, t, h, hd),
                   "dp", None, "model", None)
    k = shard_hint((hn @ lp["wk"]).reshape(b, t, kvh, hd),
                   "dp", None, "model", None)
    v = shard_hint((hn @ lp["wv"]).reshape(b, t, kvh, hd),
                   "dp", None, "model", None)
    pos = ctx_lens[:, None] + jnp.arange(t)[None, :]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    kp = _write_pages(kp, k, block_tables, ctx_lens, valid)
    vp = _write_pages(vp, v, block_tables, ctx_lens, valid)
    if t == 1 and use_kernel:
        out = _kernel_attention(q[:, 0], kp, vp, block_tables,
                                ctx_lens + 1)[:, None]
    else:
        out = _gathered_attention(q, kp, vp, block_tables, ctx_lens)
    x = x + shard_hint(out.reshape(b, t, h * hd) @ lp["wo"],
                       "dp", None, None)
    f, _ = _ffn_sublayer(cfg, rms_norm(x, lp["ln2"], cfg.norm_eps), lp)
    return x + f, kp, vp


def forward_paged(params: Params, cfg: ArchConfig,
                  pages: Dict[str, jax.Array], batch: Dict[str, jax.Array],
                  block_tables: jax.Array, ctx_lens: jax.Array,
                  new_lens: Optional[jax.Array] = None, *,
                  use_kernel: bool = False
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Run T new tokens per row against the paged cache.

    ``batch``: {"tokens": [B, T], optional "soft_emb": [B, n_soft, Dm]
    (vision, first chunk only)}.  ``new_lens`` [B]: valid *token*
    positions this chunk (<= T; default all); soft positions are always
    valid when present.  Returns (logits [B, T, V] over token positions,
    updated pages).  Rows read/write positions ctx..ctx+n_soft+T-1;
    invalid tail positions write to the scratch block and their logits
    are garbage the caller must ignore.
    """
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"forward_paged: unsupported family {cfg.family!r}")
    tokens = batch["tokens"]
    b, t = tokens.shape
    if new_lens is None:
        new_lens = jnp.full((b,), t, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    soft = batch.get("soft_emb")
    n_soft = 0
    if soft is not None:
        n_soft = soft.shape[1]
        x = jnp.concatenate([soft.astype(x.dtype), x], axis=1)
    x = shard_hint(x, "dp", None, None)
    t_eff = t + n_soft
    valid = (jnp.arange(t_eff)[None, :]
             < (new_lens + n_soft)[:, None])                  # [B, T_eff]

    def layer(h, xs):
        lp, kp, vp = xs
        h, kp, vp = _paged_decoder_block(cfg, h, lp, kp, vp, block_tables,
                                         ctx_lens, valid, use_kernel)
        return h, (kp, vp)

    x2, (nk, nv) = jax.lax.scan(
        layer, x, (params["layers"], pages["k"], pages["v"]))
    if n_soft:
        x2 = x2[:, n_soft:]
    return _unembed(params, cfg, x2), {"k": nk, "v": nv}


def decode_step_paged(params: Params, cfg: ArchConfig,
                      pages: Dict[str, jax.Array], batch: Dict[str, jax.Array],
                      block_tables: jax.Array, ctx_lens: jax.Array, *,
                      use_kernel: bool = False
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token paged decode: batch {"tokens": [B, 1]} -> (logits
    [B, 1, V], pages).  ``use_kernel`` routes attention through the
    Pallas block-indexed kernel (native on TPU, interpret elsewhere)."""
    return forward_paged(params, cfg, pages, batch, block_tables, ctx_lens,
                         use_kernel=use_kernel)


__all__ = ["PAGED_FAMILIES", "supports_paged", "init_pages",
           "copy_blocks", "forward_paged", "decode_step_paged"]
