"""Pallas TPU kernel: flash attention with GQA, causal and sliding-window
masks (online softmax; Rabe & Staats / Dao et al., re-tiled for the MXU).

Blocking: grid = (batch*heads, S/bq, S/bk) with the KV dimension sequential
("arbitrary") so the running max/denominator/accumulator scratch carries
across KV steps.  Per-step VMEM working set is

    q tile (bq, d) + k tile (bk, d) + v tile (bk, d) + acc (bq, d) f32

with bq = bk = 128 hardware-aligned MXU tiles by default (d is the model's
head_dim, 64..128).  Causal/window-irrelevant KV blocks are skipped
entirely via ``pl.when`` (halves the FLOPs for causal prefill).

The dry-run model path uses the pure-JAX chunked oracle; this kernel is
the TPU execution path, validated in interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int | None,
                  block_q: int, block_k: int, kv_steps: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    q_start = qi * block_q
    kv_start = ki * block_k
    needed = jnp.bool_(True)
    if causal:
        needed &= kv_start <= q_start + block_q - 1
    if window is not None:
        needed &= kv_start + block_k - 1 > q_start - window

    @pl.when(needed)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_ids = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.bool_(True)
        if causal:
            mask &= k_ids <= q_ids
        if window is not None:
            mask &= k_ids > q_ids - window
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[...][:, :1]                        # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # rows with no valid key yet keep m = -inf; guard the exp
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(mask, s - safe_m, _NEG_INF))
        corr = jnp.where(jnp.isfinite(m_prev),
                         jnp.exp(m_prev - safe_m), 0.0)   # (bq, 1)
        l_new = l_scr[...][:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(p, v)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool) -> jax.Array:
    """[B, H, S, D] x [B, Hkv, S, D] -> [B, H, S, D] attention."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)
    kv_steps = s // block_k

    def kv_index(bh, qi, ki):
        return ((bh // h) * hkv + (bh % h) // group, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_steps=kv_steps)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, s // block_q, kv_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),     # accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)


__all__ = ["flash_attention", "DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K"]
