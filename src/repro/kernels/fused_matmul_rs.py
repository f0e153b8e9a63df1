"""Pallas fused matmul + ring reduce-scatter.

The tensor-parallel down-projection (and the FSDP boundary GEMM) ends
in a reduce-scatter over the contraction axis: every device holds
``x [M, K_loc]`` and ``w [K_loc, N]`` (K sharded), the full product is
``sum_d x_d @ w_d``, and device ``i`` only needs row block ``i`` of it.
Running the GEMM to completion and then reduce-scattering serializes
MXU time behind wire time.  This kernel interleaves them: the output's
``P`` row blocks are computed one ring step at a time, each block's
partial accumulated into a buffer that rotates downstream between
steps -- so the last GEMM tiles overlap the first wire bytes, the
wafer-scale playbook applied to the TPU ring.

Schedule (device ``d``, ring step ``t = 0..P-1``)::

    acc      <- gemm(x[rows of block (d+1) % P], w)          # t = 0
    for t in 1..P-1:
        acc  <- ppermute(acc, d -> d-1)                      # wire
        acc +<- gemm(x[rows of block (d+t+1) % P], w)        # MXU

The ppermute and the step-``t`` GEMM are data-independent, so the
compiler overlaps them; after ``P-1`` rotations device ``d`` holds
``sum_d' partial_d'[d]`` -- exactly ``lax.psum_scatter(x @ w, axis,
tiled=True)``.

The per-block GEMM is a Pallas tiled matmul (fp32 accumulation;
native on TPU, interpreted elsewhere, as ``ops.default_interpret``
decides).  The oracle lives in ``kernels/ref.py``
(``fused_matmul_rs_ref``); ``matmul_then_rs`` is the unfused gathered
fallback used off-TPU and for shapes the ring cannot tile (M not
divisible by P).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.collectives import shardmap_impl as impl

#: MXU output tiles: multiples of the 128x128 systolic array; trimmed
#: down automatically for the small shapes tests use.
DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 256


def _matmul_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _matmul_impl(x: jax.Array, w: jax.Array, block_m: int, block_n: int,
                 interpret: bool) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    if m % block_m or n % block_n:
        pm = (-m) % block_m
        pn = (-n) % block_n
        out = _matmul_impl(jnp.pad(x, ((0, pm), (0, 0))),
                           jnp.pad(w, ((0, 0), (0, pn))),
                           block_m, block_n, interpret)
        return out[:m, :n]
    grid = (m // block_m, n // block_n)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, block_n), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, w)


# pallas_call has no autodiff rule; the fused path sits inside the
# differentiated train step (TP down-projection), so give the tiled
# GEMM the standard matmul VJP (dense jnp.dot backward -- the backward
# GEMMs get their own fused treatment only if routed through here too).
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _mm(x, w, block_m, block_n, interpret):
    return _matmul_impl(x, w, block_m, block_n, interpret)


def _mm_fwd(x, w, block_m, block_n, interpret):
    return _matmul_impl(x, w, block_m, block_n, interpret), (x, w)


def _mm_bwd(block_m, block_n, interpret, res, g):
    x, w = res
    gf = g.astype(jnp.float32)
    dx = jnp.dot(gf, w.astype(jnp.float32).T,
                 preferred_element_type=jnp.float32).astype(x.dtype)
    dw = jnp.dot(x.astype(jnp.float32).T, gf,
                 preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dw


_mm.defvjp(_mm_fwd, _mm_bwd)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "interpret"))
def matmul_tiled(x: jax.Array, w: jax.Array, *,
                 block_m: int = DEFAULT_BLOCK_M,
                 block_n: int = DEFAULT_BLOCK_N,
                 interpret: bool) -> jax.Array:
    """``[M, K] @ [K, N] -> [M, N]`` Pallas tiled matmul, fp32
    accumulation.  The grid tiles M x N; each grid step holds a
    ``(block_m, K)`` x ``(K, block_n)`` operand pair in VMEM."""
    return _mm(x, w, block_m, block_n, interpret)


def fused_matmul_rs(x: jax.Array, w: jax.Array, axis, *,
                    block_m: int = DEFAULT_BLOCK_M,
                    block_n: int = DEFAULT_BLOCK_N,
                    interpret: bool | None = None) -> jax.Array:
    """Fused ``reduce_scatter(x @ w)`` over ``axis`` (a mesh axis name
    or a row-major-folded tuple), run inside shard_map.

    ``x``: local ``[M, K_loc]``; ``w``: local ``[K_loc, N]``; returns
    ``[M/P, N]`` with device ``i`` holding row block ``i`` of the
    summed product (``lax.psum_scatter(..., tiled=True)`` semantics).
    M must be divisible by the folded axis size.  ``interpret=None``
    takes the backend's mode (``ops.default_interpret``)."""
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    p = impl._axis_size(axis)
    if p == 1:
        return matmul_tiled(x, w, block_m=block_m, block_n=block_n,
                            interpret=interpret)
    m = x.shape[0]
    assert m % p == 0, (m, p)
    mb = m // p
    idx = impl._axis_index(axis)
    down = [(i, (i - 1) % p) for i in range(p)]

    def block_gemm(t: int) -> jax.Array:
        start = ((idx + t + 1) % p) * mb
        xb = lax.dynamic_slice_in_dim(x, start, mb, axis=0)
        return matmul_tiled(xb, w, block_m=block_m, block_n=block_n,
                            interpret=interpret)

    acc = block_gemm(0)
    for t in range(1, p):
        acc = lax.ppermute(acc, axis, down)
        acc = acc + block_gemm(t)
    return acc


def matmul_then_rs(x: jax.Array, w: jax.Array, axis) -> jax.Array:
    """Unfused gathered fallback: full local GEMM (fp32 accumulation),
    then the native reduce-scatter.  Bit-for-bit the semantics of
    :func:`fused_matmul_rs`, with MXU and wire time serialized."""
    y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                preferred_element_type=jnp.float32).astype(x.dtype)
    if impl._axis_size(axis) == 1:
        return y
    return lax.psum_scatter(y, axis, scatter_dimension=0, tiled=True)


__all__ = ["fused_matmul_rs", "matmul_then_rs", "matmul_tiled",
           "DEFAULT_BLOCK_M", "DEFAULT_BLOCK_N"]
