"""Mamba-1 (selective SSM) blocks, TPU-adapted.

The CUDA reference implements the selective scan as a fused kernel over
sequential timesteps.  On TPU we recast the recurrence as a chunked
associative linear scan (h_t = a_t h_{t-1} + b_t), which maps onto the
VPU/MXU and keeps the materialized state-expansion tensor bounded by the
chunk length (see models/scan_utils.py).  Decode carries a constant-size
(conv window, SSM state) pair -- this is why falcon-mamba runs the
long_500k cell.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import shard_hint
from repro.models.scan_utils import chunked_linear_scan


class SSMState(NamedTuple):
    conv: jax.Array   # [B, d_conv - 1, d_inner]  (shift register)
    h: jax.Array      # [B, d_inner, N]


def _causal_conv(x: jax.Array, w: jax.Array, prefix: jax.Array | None = None):
    """Depthwise causal conv along seq.  x: [B, S, C]; w: [K, C]."""
    k = w.shape[0]
    if prefix is None:
        prefix = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prefix, x], axis=1)
    out = jnp.zeros_like(x)
    for j in range(k):
        out = out + xp[:, j:j + x.shape[1]] * w[j]
    new_prefix = xp[:, x.shape[1]:]
    return out, new_prefix


def mamba_block(x: jax.Array, p: dict, *, ssm_state: int, chunk: int = 128,
                state: SSMState | None = None, single_step: bool = False,
                use_kernel: bool = False) -> Tuple[jax.Array, SSMState]:
    """One Mamba-1 mixing block.

    x: [B, S, D].  Params ``p``:
      in_proj [D, 2*di], conv_w [K, di], x_proj [di, R+2N], dt_w [R, di],
      dt_b [di], a_log [di, N], d_skip [di], out_proj [di, D].
    Returns (y [B, S, D], new_state).
    """
    b, s, d = x.shape
    di = p["a_log"].shape[0]
    n = ssm_state
    r = p["dt_w"].shape[0]

    xz = shard_hint(x @ p["in_proj"], "dp", None, "model")  # [B, S, 2di]
    xs, z = jnp.split(xz, 2, axis=-1)
    xs = shard_hint(xs, "dp", None, "model")
    z = shard_hint(z, "dp", None, "model")

    conv_prefix = state.conv if state is not None else None
    xs, new_conv = _causal_conv(xs, p["conv_w"], conv_prefix)
    xs = jax.nn.silu(xs)

    dbc = xs @ p["x_proj"]                      # [B, S, R+2N]
    dt, b_ssm, c_ssm = jnp.split(dbc, [r, r + n], axis=-1)
    delta = jax.nn.softplus(dt @ p["dt_w"] + p["dt_b"])     # [B, S, di]
    a = -jnp.exp(p["a_log"].astype(jnp.float32))            # [di, N]

    # discretize: a_bar = exp(delta * A); b_bar = delta * B * x
    delta32 = delta.astype(jnp.float32)
    a_bar = jnp.exp(delta32[..., None] * a)                  # [B, S, di, N]
    b_bar = (delta32[..., None]
             * b_ssm.astype(jnp.float32)[..., None, :]
             * xs.astype(jnp.float32)[..., None])            # [B, S, di, N]
    # keep the state-expansion tensors batch x TP sharded; without the
    # hint GSPMD replicates them across the model axis (16x traffic)
    a_bar = shard_hint(a_bar, "dp", None, "model", None)
    b_bar = shard_hint(b_bar, "dp", None, "model", None)

    h0 = (state.h if state is not None
          else jnp.zeros((b, di, n), jnp.float32))
    if single_step:
        assert s == 1
        h_new = a_bar[:, 0] * h0 + b_bar[:, 0]               # [B, di, N]
        h_all = h_new[:, None]
        y = jnp.einsum("bsdn,bsn->bsd", h_all,
                       c_ssm.astype(jnp.float32))            # [B, S, di]
    elif use_kernel:
        # fused Pallas scan: the state expansion never touches HBM
        # (repro/kernels/selective_scan.py; EXPERIMENTS.md §Perf Cell C)
        import math as _math
        from repro.kernels.ops import default_interpret
        from repro.kernels.selective_scan import selective_scan_trainable
        bd = _math.gcd(di, 256)
        ck = _math.gcd(s, 128)
        y, h_new = selective_scan_trainable(
            delta32, xs.astype(jnp.float32), b_ssm.astype(jnp.float32),
            c_ssm.astype(jnp.float32), a, h0, bd, ck, default_interpret())
    else:
        h_all, h_new = chunked_linear_scan(a_bar, b_bar, h0, chunk=chunk)
        h_all = shard_hint(h_all, "dp", None, "model", None)
        y = jnp.einsum("bsdn,bsn->bsd", h_all,
                       c_ssm.astype(jnp.float32))            # [B, S, di]
    y = y + xs.astype(jnp.float32) * p["d_skip"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = shard_hint(y @ p["out_proj"], "dp", None, None)
    return out, SSMState(conv=new_conv, h=h_new)


def init_mamba_params(key, d_model: int, d_inner: int, ssm_state: int,
                      dt_rank: int, d_conv: int, dtype) -> dict:
    ks = jax.random.split(key, 6)
    scale = d_model ** -0.5
    return {
        "in_proj": (jax.random.normal(ks[0], (d_model, 2 * d_inner)) * scale
                    ).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (d_conv, d_inner)) * 0.1
                   ).astype(dtype),
        "x_proj": (jax.random.normal(ks[2], (d_inner, dt_rank + 2 * ssm_state))
                   * d_inner ** -0.5).astype(dtype),
        "dt_w": (jax.random.normal(ks[3], (dt_rank, d_inner))
                 * dt_rank ** -0.5).astype(dtype),
        "dt_b": jnp.full((d_inner,), -4.6, dtype),   # softplus^-1(0.01)
        "a_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, ssm_state + 1, dtype=jnp.float32),
            (d_inner, ssm_state))),
        "d_skip": jnp.ones((d_inner,), jnp.float32),
        "out_proj": (jax.random.normal(ks[4], (d_inner, d_model))
                     * d_inner ** -0.5).astype(dtype),
    }


__all__ = ["mamba_block", "init_mamba_params", "SSMState"]
