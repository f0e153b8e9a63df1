"""Pallas TPU kernel: fused Mamba-1 selective scan.

The dry-run shows the pure-JAX chunked scan materializing the state
expansion (a_bar/b_bar broadcasts, [B, S, d_inner, N] f32) in HBM --
~2.8 TB of traffic per layer per device on falcon-mamba train_4k, 175x
the useful activation bytes (EXPERIMENTS.md §Perf).  The CUDA reference
fuses the whole recurrence in one kernel; this is the TPU-native
equivalent: the state h lives in a VMEM scratch tile and the recurrence

    h_t = exp(dt_t * A) * h_t-1 + (dt_t * B_t) x_t
    y_t = <h_t, C_t> + D * x_t

streams over sequence chunks with only the layer inputs/outputs touching
HBM.  Blocking: grid = (B, d_inner / block_d, S / chunk) with the
sequence dimension sequential; per-step VMEM = dt/x tiles (chunk,
block_d) + B/C tiles (chunk, N) + h scratch (block_d, N).

``h0`` enters via HBM and the final state is written back, so decode
and prefill reuse the same kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_D = 256
DEFAULT_CHUNK = 128


def _scan_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref,
                 y_ref, hout_ref, h_scr, *, chunk: int, s_steps: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)            # (block_d, N)

    def step(t, carry):
        h = carry                                  # (block_d, N)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)      # (block_d,)
        x_t = x_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)        # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)
        a_bar = jnp.exp(dt_t[:, None] * a)              # (block_d, N)
        b_bar = (dt_t * x_t)[:, None] * b_t[None, :]
        h = a_bar * h + b_bar
        y_t = jnp.sum(h * c_t[None, :], axis=1)         # (block_d,)
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(si == s_steps - 1)
    def _final():
        hout_ref[0] = h.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "chunk",
                                             "interpret"))
def selective_scan(dt: jax.Array, x: jax.Array, b: jax.Array,
                   c: jax.Array, a: jax.Array, h0: jax.Array, *,
                   block_d: int = DEFAULT_BLOCK_D,
                   chunk: int = DEFAULT_CHUNK,
                   interpret: bool):
    """dt/x: [B, S, D]; b/c: [B, S, N]; a: [D, N]; h0: [B, D, N].
    Returns (y [B, S, D] fp32-accurate in x.dtype, h_final [B, D, N])."""
    bsz, s, d = x.shape
    n = a.shape[1]
    block_d = min(block_d, d)
    chunk = min(chunk, s)
    assert d % block_d == 0 and s % chunk == 0, (d, block_d, s, chunk)
    s_steps = s // chunk
    grid = (bsz, d // block_d, s_steps)

    kernel = functools.partial(_scan_kernel, chunk=chunk, s_steps=s_steps)
    y, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, chunk, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((block_d, n), lambda i, j, k: (j, 0)),
            pl.BlockSpec((1, block_d, n), lambda i, j, k: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, block_d, n), lambda i, j, k: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), x.dtype),
            jax.ShapeDtypeStruct((bsz, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        interpret=interpret,
    )(dt, x, b, c, a, h0)
    return y, h_final


# ---------------------------------------------------------------------- #
# backward kernel (flash-style): the forward saves only chunk-boundary
# states; the backward recomputes h within each chunk (forward sub-pass
# in VMEM) and then runs the reverse adjoint recurrence
#
#     dh_t = dy_t c_t^T + a_{t+1} * dh_{t+1}
#     ddt_t = sum_n [ (dh_t*h_{t-1}*a_t) A + dh_t b_t x_t ]
#     dx_t  = sum_n dh_t dt_t b_t ;  db_t = sum_d dh_t dt_t x_t
#     dc_t  = sum_d h_t dy_t     ;  dA   = sum_t (dh_t*h_{t-1}*a_t) dt_t
#
# Per-D-block partials of db/dc (reduced over D) are emitted into a
# [B, n_dblocks, S, N] buffer and summed outside the kernel.
# ---------------------------------------------------------------------- #
def _scan_fwd_ckpt_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref,
                          y_ref, hout_ref, hck_ref, h_scr, *,
                          chunk: int, s_steps: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    hck_ref[0, 0] = h_scr[...]          # state at the chunk START
    a = a_ref[...].astype(jnp.float32)

    def step(t, h):
        dt_t = dt_ref[0, t, :].astype(jnp.float32)
        x_t = x_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        c_t = c_ref[0, t, :].astype(jnp.float32)
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        y_ref[0, t, :] = jnp.sum(h * c_t[None, :], axis=1).astype(
            y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(si == s_steps - 1)
    def _final():
        hout_ref[0] = h.astype(hout_ref.dtype)


def _scan_bwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, hck_ref, dy_ref,
                     dhf_ref, ddt_ref, dx_ref, db_ref, dc_ref, da_ref,
                     dh0_ref, dh_scr, h_hist, *, chunk: int, s_steps: int):
    si = pl.program_id(2)            # reversed: si=0 is the LAST chunk

    @pl.when(si == 0)
    def _init():
        dh_scr[...] = dhf_ref[0].astype(jnp.float32)
        da_ref[0] = jnp.zeros_like(da_ref[0])

    a = a_ref[...].astype(jnp.float32)

    # forward recompute within the chunk, storing h history in VMEM
    def fwd(t, h):
        h_hist[t] = h                # h_{t-1} (state BEFORE step t)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)
        x_t = x_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        return (jnp.exp(dt_t[:, None] * a) * h
                + (dt_t * x_t)[:, None] * b_t[None, :])

    h_start = hck_ref[0, 0]
    _ = jax.lax.fori_loop(0, chunk, fwd, h_start)

    def bwd(i, carry):
        dh, da_acc = carry
        t = chunk - 1 - i
        dt_t = dt_ref[0, t, :].astype(jnp.float32)
        x_t = x_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        c_t = c_ref[0, t, :].astype(jnp.float32)
        dy_t = dy_ref[0, t, :].astype(jnp.float32)
        h_prev = h_hist[t]
        a_t = jnp.exp(dt_t[:, None] * a)
        h_t = a_t * h_prev + (dt_t * x_t)[:, None] * b_t[None, :]
        # dh_t := contribution from y_t + carried adjoint
        dh_t = dy_t[:, None] * c_t[None, :] + dh
        dc_ref[0, 0, t, :] = jnp.sum(h_t * dy_t[:, None], axis=0).astype(
            dc_ref.dtype)
        g_a = dh_t * h_prev * a_t            # d/d(log a) * a
        ddt_ref[0, t, :] = (jnp.sum(g_a * a, axis=1)
                            + jnp.sum(dh_t * b_t[None, :], axis=1) * x_t
                            ).astype(ddt_ref.dtype)
        dx_ref[0, t, :] = (jnp.sum(dh_t * b_t[None, :], axis=1) * dt_t
                           ).astype(dx_ref.dtype)
        db_ref[0, 0, t, :] = jnp.sum(dh_t * (dt_t * x_t)[:, None],
                                     axis=0).astype(db_ref.dtype)
        da_acc = da_acc + g_a * dt_t[:, None]
        dh = a_t * dh_t                      # adjoint to h_{t-1}
        return dh, da_acc

    dh, da_acc = jax.lax.fori_loop(
        0, chunk, bwd, (dh_scr[...], da_ref[0].astype(jnp.float32)))
    dh_scr[...] = dh
    da_ref[0] = da_acc.astype(da_ref.dtype)

    @pl.when(si == s_steps - 1)
    def _final():
        dh0_ref[0] = dh.astype(dh0_ref.dtype)


def _fwd_with_ckpt(dt, x, b, c, a, h0, block_d, chunk, interpret):
    bsz, s, d = x.shape
    n = a.shape[1]
    s_steps = s // chunk
    grid = (bsz, d // block_d, s_steps)
    kernel = functools.partial(_scan_fwd_ckpt_kernel, chunk=chunk,
                               s_steps=s_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, chunk, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((block_d, n), lambda i, j, k: (j, 0)),
            pl.BlockSpec((1, block_d, n), lambda i, j, k: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, block_d, n), lambda i, j, k: (i, j, 0)),
            pl.BlockSpec((1, 1, block_d, n), lambda i, j, k: (i, k, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), x.dtype),
            jax.ShapeDtypeStruct((bsz, d, n), jnp.float32),
            jax.ShapeDtypeStruct((bsz, s_steps, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        interpret=interpret,
    )(dt, x, b, c, a, h0)


def _bwd_call(dt, x, b, c, a, hck, dy, dh_final, block_d, chunk,
              interpret):
    bsz, s, d = x.shape
    n = a.shape[1]
    s_steps = s // chunk
    nb = d // block_d
    grid = (bsz, nb, s_steps)
    kernel = functools.partial(_scan_bwd_kernel, chunk=chunk,
                               s_steps=s_steps)
    rev = lambda k, ss=s_steps: ss - 1 - k
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d),
                         lambda i, j, k: (i, rev(k), j)),
            pl.BlockSpec((1, chunk, block_d),
                         lambda i, j, k: (i, rev(k), j)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, rev(k), 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, rev(k), 0)),
            pl.BlockSpec((block_d, n), lambda i, j, k: (j, 0)),
            pl.BlockSpec((1, 1, block_d, n),
                         lambda i, j, k: (i, rev(k), j, 0)),
            pl.BlockSpec((1, chunk, block_d),
                         lambda i, j, k: (i, rev(k), j)),
            pl.BlockSpec((1, block_d, n), lambda i, j, k: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d),
                         lambda i, j, k: (i, rev(k), j)),
            pl.BlockSpec((1, chunk, block_d),
                         lambda i, j, k: (i, rev(k), j)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda i, j, k: (i, j, rev(k), 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda i, j, k: (i, j, rev(k), 0)),
            pl.BlockSpec((1, block_d, n), lambda i, j, k: (i, j, 0)),
            pl.BlockSpec((1, block_d, n), lambda i, j, k: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),   # ddt
            jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),   # dx
            jax.ShapeDtypeStruct((bsz, nb, s, n), jnp.float32),  # db part
            jax.ShapeDtypeStruct((bsz, nb, s, n), jnp.float32),  # dc part
            jax.ShapeDtypeStruct((bsz, d, n), jnp.float32),   # dA (per b)
            jax.ShapeDtypeStruct((bsz, d, n), jnp.float32),   # dh0
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32),
                        pltpu.VMEM((chunk, block_d, n), jnp.float32)],
        interpret=interpret,
    )(dt, x, b, c, a, hck, dy, dh_final)
    ddt, dx, db_p, dc_p, da_b, dh0 = outs
    return (ddt, dx, db_p.sum(axis=1), dc_p.sum(axis=1),
            da_b.sum(axis=0), dh0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def selective_scan_trainable(dt, x, b, c, a, h0, block_d, chunk, interpret):
    """Differentiable fused scan: forward saves only chunk-boundary
    states; backward recomputes within chunks (flash-style)."""
    y, h_final, _ = _fwd_with_ckpt(dt, x, b, c, a, h0, block_d, chunk,
                                   interpret)
    return y, h_final


def _ss_fwd(dt, x, b, c, a, h0, block_d, chunk, interpret):
    y, h_final, hck = _fwd_with_ckpt(dt, x, b, c, a, h0, block_d, chunk,
                                     interpret)
    return (y, h_final), (dt, x, b, c, a, hck)


def _ss_bwd(block_d, chunk, interpret, res, grads):
    dt, x, b, c, a, hck = res
    dy, dh_final = grads
    ddt, dx, db, dc, da, dh0 = _bwd_call(
        dt, x, b, c, a, hck, dy.astype(jnp.float32),
        dh_final.astype(jnp.float32), block_d, chunk, interpret)
    return (ddt.astype(dt.dtype), dx.astype(x.dtype), db.astype(b.dtype),
            dc.astype(c.dtype), da.astype(a.dtype), dh0)


selective_scan_trainable.defvjp(_ss_fwd, _ss_bwd)


__all__ = ["selective_scan", "selective_scan_trainable",
           "DEFAULT_BLOCK_D", "DEFAULT_CHUNK"]
