"""Bucketed gradient AllReduce with compute/comm overlap + compression.

Distributed-optimization layer for pure-DP training (params replicated on
the data/pod axes):

* **Bucketing**: gradients are flattened and packed into fixed-byte
  buckets; each bucket is AllReduced independently, so the paper's
  selector picks the right algorithm *per bucket size* -- small buckets
  ride low-depth trees, big ones ride ring/chain (exactly the Fig. 8
  heatmap in action).
* **Overlap**: buckets are reduced in reverse-layer order, letting XLA's
  latency-hiding scheduler overlap each bucket's ppermute chain with the
  remaining backward compute (on TPU the collectives are async).
* **Compression**: optional bf16 reduction with fp32 error feedback
  (residual carried between steps), halving the collective term.
* **Topology-aware multi-axis plans**: on the multi-pod mesh each
  bucket flows through ``engine.allreduce_multi``, so the planner
  jointly scores the paper's 2D patterns (xy/snake over the folded
  grid), the hierarchical RS -> AR -> AG composition (cross-pod phase
  on 1/P of the bytes), the flat folded ring, and the legacy
  per-axis sequential loop -- and runs the winner.  On heterogeneous
  fabrics the winning plan is often a ``*_pipelined`` variant: the
  engine then splits the bucket into ``plan.n_chunks`` slices and
  wavefronts the phases so one chunk's slow inter-pod phase overlaps
  the next chunk's fast inner phase (chunk count chosen by the
  planner's closed form; tiny buckets fall back to serial phases).
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.collectives.api import get_engine
from repro.collectives.engine import CollectiveEngine

DEFAULT_BUCKET_BYTES = 32 * 1024 * 1024


def _flatten_to_buckets(tree, bucket_bytes: int):
    leaves, treedef = jax.tree.flatten(tree)
    sizes = [l.size for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])
    n_per = max(1, bucket_bytes // 4)
    buckets = []
    i = 0
    while i < flat.size:
        buckets.append(flat[i:i + n_per])
        i += n_per
    return buckets, (treedef, sizes, [l.shape for l in leaves],
                     [l.dtype for l in leaves])


def _unflatten(buckets: List[jax.Array], meta) -> Any:
    treedef, sizes, shapes, dtypes = meta
    flat = jnp.concatenate(buckets)
    leaves = []
    off = 0
    for size, shape, dtype in zip(sizes, shapes, dtypes):
        leaves.append(flat[off:off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree.unflatten(treedef, leaves)


def flatten_tree(tree) -> Tuple[jax.Array, Any]:
    """Flatten a pytree to one fp32 vector + the meta ``unflatten_tree``
    needs to restore shapes/dtypes (the FSDP flat-shard layout)."""
    buckets, meta = _flatten_to_buckets(tree, bucket_bytes=1 << 62)
    return buckets[0], meta


def unflatten_tree(flat: jax.Array, meta) -> Any:
    return _unflatten([flat], meta)


def bucketed_allreduce(grads, mesh: Mesh, axes: Tuple[str, ...] = ("data",),
                       algorithm: str = "auto",
                       bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                       compress: bool = False,
                       error_feedback: Optional[Any] = None,
                       mean: bool = True,
                       engine: Optional[CollectiveEngine] = None):
    """AllReduce a gradient pytree over DP axes.

    Multi-axis (('pod','data')) buckets run the planner's joint
    topology plan (``engine.allreduce_multi``): hierarchical
    RS -> AR -> AG, the paper's 2D xy/snake patterns, the flat folded
    ring, or the sequential per-axis loop -- whichever Eq. (1) prices
    cheapest for the bucket size, per bucket.  Returns
    (reduced_grads, new_error_feedback).

    All collective traffic flows through the CollectiveEngine, so the
    per-bucket `auto` selection is cached across steps (one model sweep
    per bucket size, not one per trace).
    """
    if engine is None:
        engine = get_engine()
    if error_feedback is not None:
        grads = jax.tree.map(lambda g, e: g.astype(jnp.float32) + e,
                             grads, error_feedback)
    buckets, meta = _flatten_to_buckets(grads, bucket_bytes)

    def reduce_bucket(b):
        v = b
        if compress:
            v = v.astype(jnp.bfloat16)
        v = engine.allreduce_multi(v, axes, algorithm=algorithm)
        return v.astype(jnp.float32)

    spec = P()
    fn = shard_map(lambda *bs: tuple(reduce_bucket(b) for b in bs),
                   mesh=mesh, in_specs=spec, out_specs=spec,
                   check_vma=False)
    # reverse order: last layers' buckets first (they finish backward
    # earliest -> overlap with remaining backward compute)
    reduced = list(fn(*buckets[::-1]))[::-1]

    n = 1
    for ax in axes:
        n *= mesh.shape[ax]
    if mean:
        reduced = [b / n for b in reduced]

    new_ef = None
    if compress:
        # error feedback: residual between fp32 sum and bf16-compressed sum
        exact = [b * (n if mean else 1) for b in buckets]
        new_ef_flat = [e - r * (n if mean else 1)
                       for e, r in zip(exact, reduced)]
        new_ef = _unflatten(new_ef_flat, meta)
    out = _unflatten(reduced, meta)
    return out, new_ef


def bucket_algorithm_plan(grads, mesh: Mesh,
                          axes: Tuple[str, ...] = ("data",),
                          bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                          engine: Optional[CollectiveEngine] = None
                          ) -> List[Tuple[int, str]]:
    """What the planner would run per bucket (introspection/reporting).

    Takes the same axis tuple ``bucketed_allreduce`` executes with.  A
    single axis reports the 1D selector's algorithm; a multi-axis
    topology reports the joint plan shape, e.g.
    ``hierarchical(rs:ring->ar:ring->ag:doubling)``.
    """
    if engine is None:
        engine = get_engine()
    if isinstance(axes, str):       # tolerate the old single-axis form
        axes = (axes,)
    axes = tuple(axes)
    sizes = tuple(mesh.shape[a] for a in axes)
    leaves = jax.tree.leaves(grads)
    total = sum(l.size * 4 for l in leaves)
    plan = []
    off = 0
    while off < total:
        b = min(bucket_bytes, total - off)
        if len(axes) == 1:
            plan.append((b, engine.select("allreduce", b,
                                          sizes[0]).algorithm))
        else:
            plan.append((b, engine.plan_multi("allreduce", axes, sizes,
                                              b).describe()))
        off += b
    return plan


__all__ = ["bucketed_allreduce", "bucket_algorithm_plan",
           "flatten_tree", "unflatten_tree", "DEFAULT_BUCKET_BYTES"]
