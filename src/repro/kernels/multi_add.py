"""Pallas TPU kernel: K-way fused accumulate.

The WSE reduce's compute hot spot is the elementwise add pipeline (one add
per cycle per PE).  On TPU the analogous hot spot in a reduction endpoint
is accumulating K partial vectors: a chain of K-1 binary adds reads
2(K-1)*N and writes (K-1)*N elements of HBM, while a fused K-way add reads
K*N and writes N -- a ~3x traffic cut for K=8.  This kernel performs the
fused accumulate with explicit VMEM tiling.

Layout: ``stacked`` [K, N] -> out [N].  The grid tiles N; each grid step
holds a (K, block_n) tile in VMEM and reduces over K in registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# (K, 512 lanes) tiles: K is small (2..32); 512 f32 lanes = 2 KB rows,
# keeping the tile well under VMEM while filling the 8x128 VPU layout.
DEFAULT_BLOCK_N = 512


def _multi_add_kernel(x_ref, o_ref):
    o_ref[...] = jnp.sum(x_ref[...].astype(jnp.float32), axis=0).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def multi_add(stacked: jax.Array, *, block_n: int = DEFAULT_BLOCK_N,
              interpret: bool) -> jax.Array:
    """Sum K stacked partials: [K, N] -> [N] with fp32 accumulation."""
    k, n = stacked.shape
    block_n = min(block_n, n)
    if n % block_n != 0:
        pad = block_n - n % block_n
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
        out = multi_add(stacked, block_n=block_n, interpret=interpret)
        return out[:n]
    grid = (n // block_n,)
    return pl.pallas_call(
        _multi_add_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((k, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), stacked.dtype),
        interpret=interpret,
    )(stacked)


__all__ = ["multi_add", "DEFAULT_BLOCK_N"]
