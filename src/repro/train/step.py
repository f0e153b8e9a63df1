"""Step builders: train (fwd+bwd+AdamW), prefill, decode.

* loss: next-token cross entropy in fp32 (+ MoE load-balance aux);
* remat: per-layer (scan-level) activation checkpointing, policy set in
  the model;
* microbatching: gradient accumulation via lax.scan over microbatch
  slices (keeps the same global batch while bounding live activations);
* gradient sync: under jit+GSPMD the partitioner inserts the reductions
  implied by the shardings (reduce-scatter under FSDP).  The explicit
  paper-collective DP path runs when a ``GradSyncConfig`` is passed:
  gradients then flow through the CollectiveEngine's cached model-driven
  dispatch (repro.collectives) instead of GSPMD's defaults.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ArchConfig
from repro.models import transformer as tf
from repro.optim.adamw import AdamWConfig, AdamWState, apply_updates
from repro.train.state import TrainState

AUX_WEIGHT = 0.01


@dataclasses.dataclass
class GradSyncConfig:
    """Explicit DP gradient synchronization through the engine.

    ``mode="allreduce"`` (default): params replicated over ``axes``;
    after backward, gradients are bucketed and AllReduced with the
    planner's per-bucket-size cached joint topology plan
    (repro.collectives.overlap.bucketed_allreduce).

    ``mode="fsdp"``: the ZeRO-style pair instead -- gradients are
    reduce-scattered over ``axes`` (each device keeps its 1/P flat
    shard), the AdamW update runs on the shard against flat sharded
    optimizer state, and the updated params are allgathered -- with
    both halves routed through the engine's topology-aware plans
    instead of GSPMD's sharding-implied defaults.  ``master_weights``
    is supported: the fp32 master lives as one flat sharded vector
    updated in place.  ``compress`` is an allreduce-mode knob and is
    ignored here; ``algorithm`` picks the plan shape for all three
    phases ("auto" = planner argmin).

    ``fused=True`` (fsdp mode) routes the grad reduce-scatter through
    the engine's ``fused_matmul_reduce_scatter`` executor.  The grad
    sync site has no local GEMM to fuse (``w=None``), so this is the
    documented degenerate: the chunk-overlapped reduce-scatter -- the
    same opt-in flag the tensor-parallel projections use where a real
    GEMM does feed the ring (``models.layers.set_fused_tp``)."""

    mesh: Mesh
    axes: Tuple[str, ...] = ("data",)
    algorithm: str = "auto"
    bucket_bytes: int = 4 * 1024 * 1024
    compress: bool = False
    mode: str = "allreduce"        # "allreduce" | "fsdp"
    fused: bool = False

    def __post_init__(self):
        if self.mode not in ("allreduce", "fsdp"):
            raise ValueError(f"unknown grad-sync mode {self.mode!r}; "
                             f"expected 'allreduce' or 'fsdp'")


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """logits [B, S, V] fp32; labels [B, S] int32.

    The gold logit is extracted with a fused mask-reduce rather than
    take_along_axis: a gather over the vocab-sharded axis would make
    GSPMD all-gather the full logits; the mask-reduce keeps everything
    local + one tiny [B, S] all-reduce."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    vocab_ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                         logits.ndim - 1)
    mask = vocab_ids == labels[..., None].astype(jnp.int32)
    gold = jnp.sum(jnp.where(mask, logits, 0.0), axis=-1)
    return jnp.mean(lse - gold)


def loss_fn(params, cfg: ArchConfig, batch, remat: bool = True,
            unroll: bool = False):
    model_inputs = {k: v for k, v in batch.items() if k != "labels"}
    logits, aux = tf.forward_train(params, cfg, model_inputs, remat=remat,
                                   unroll=unroll)
    ce = cross_entropy(logits, batch["labels"])
    loss = ce + AUX_WEIGHT * aux
    return loss, {"ce": ce, "aux": aux}


def fsdp_sync_apply(opt_cfg: AdamWConfig, params, grads, opt,
                    gs: GradSyncConfig):
    """FSDP-style sync + update: reduce-scatter grads, AdamW on the
    flat shard, allgather updated params -- every byte through the
    CollectiveEngine's topology-aware plans.

    Numerically equivalent to ``apply_updates`` on fully-synced grads
    (same global clip, bias correction, and matrix-only weight decay;
    fp32 accumulation throughout), but the optimizer state lives as
    flat 1/P shards: ``opt.mu``/``opt.nu`` become single flat vectors,
    padded to a multiple of the folded DP size and sharded over
    ``gs.axes``.  With ``master_weights`` enabled the fp32 master copy
    lives the same way -- one flat sharded vector updated in place,
    with only the model-dtype params allgathered -- so bf16 training
    keeps full-precision state at 1/P memory.  A tree-shaped state
    (step 0, or a restored allreduce-mode checkpoint) is flattened in
    place.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.collectives.api import get_engine
    from repro.collectives.overlap import flatten_tree, unflatten_tree
    from repro.optim.adamw import lr_at

    axes = tuple(gs.axes)
    if not axes:
        # no DP axes (single-device run): nothing to scatter/gather
        return apply_updates(opt_cfg, params, grads, opt)
    engine = get_engine()
    sizes = tuple(gs.mesh.shape[a] for a in axes)
    n_world = 1
    for s in sizes:
        n_world *= s
    use_master = opt.master is not None

    flat_g, _ = flatten_tree(grads)
    flat_p, meta = flatten_tree(params)
    decay = jnp.concatenate(
        [jnp.full((l.size,), 1.0 if l.ndim >= 2 else 0.0, jnp.float32)
         for l in jax.tree.leaves(params)])
    n = flat_p.size
    pad = (-n) % n_world
    if pad:
        z = jnp.zeros((pad,), jnp.float32)
        flat_g, flat_p, decay = (jnp.concatenate([a, z])
                                 for a in (flat_g, flat_p, decay))

    def _as_flat(tree):
        """Flatten a (possibly already-flat) state tree to [n + pad]."""
        leaves = jax.tree.leaves(tree)
        if (len(leaves) == 1 and leaves[0].ndim == 1
                and leaves[0].size == n + pad):
            return leaves[0]
        flat, _ = flatten_tree(tree)
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        return flat

    mu, nu = _as_flat(opt.mu), _as_flat(opt.nu)
    # the fp32 working copy the update runs against: the persistent
    # master when enabled, else a per-step recast of the params
    w32 = _as_flat(opt.master) if use_master else flat_p
    # allgather in the model dtype when the params share one: the full
    # fp32 master never needs to cross the wire (the gathered values
    # are cast to the leaf dtypes at unflatten anyway)
    param_dtypes = {l.dtype for l in jax.tree.leaves(params)}
    gather_dtype = (param_dtypes.pop() if len(param_dtypes) == 1
                    else jnp.float32)

    count = opt.count + 1
    lr = lr_at(opt_cfg, count)
    b1c = 1 - opt_cfg.b1 ** count.astype(jnp.float32)
    b2c = 1 - opt_cfg.b2 ** count.astype(jnp.float32)

    def shard_fn(g, p32, dm, m, v):
        if gs.fused:
            g_s = engine.fused_matmul_reduce_scatter(
                g, None, axes, algorithm=gs.algorithm)
        else:
            g_s = engine.reduce_scatter_multi(g, axes,
                                              algorithm=gs.algorithm)
        g_s = g_s / float(n_world)      # mean over the DP world
        sq = engine.allreduce_multi(jnp.sum(jnp.square(g_s)).reshape(1),
                                    axes, algorithm=gs.algorithm)
        gnorm = jnp.sqrt(sq[0])
        gg = g_s * jnp.minimum(1.0, opt_cfg.grad_clip / (gnorm + 1e-9))
        m2 = opt_cfg.b1 * m + (1 - opt_cfg.b1) * gg
        v2 = opt_cfg.b2 * v + (1 - opt_cfg.b2) * jnp.square(gg)
        step = (m2 / b1c) / (jnp.sqrt(v2 / b2c) + opt_cfg.eps)
        step = step + opt_cfg.weight_decay * dm * p32
        w2 = p32 - lr * step
        w_full = engine.allgather_multi(w2.astype(gather_dtype), axes,
                                        algorithm=gs.algorithm)
        return w_full, m2, v2, w2, gnorm.reshape(1)

    spec = P(axes if len(axes) > 1 else axes[0])
    fn = shard_map(shard_fn, mesh=gs.mesh,
                   in_specs=(P(), spec, spec, spec, spec),
                   out_specs=(P(), spec, spec, spec, P()),
                   check_vma=False)
    w_full, mu2, nu2, w2, gnorm = fn(flat_g, w32, decay, mu, nu)
    params2 = unflatten_tree(w_full[:n], meta)
    opt2 = AdamWState(mu=mu2, nu=nu2, count=count,
                      master=w2 if use_master else None)
    return params2, opt2, {"grad_norm": gnorm[0], "lr": lr}


def _split_microbatches(batch, n: int):
    def split(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        return x.reshape((n, b // n) + x.shape[1:])
    return jax.tree.map(split, batch)


def make_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig,
                    microbatches: int = 1, remat: bool = True,
                    unroll: bool = False,
                    grad_sync: Optional[GradSyncConfig] = None
                    ) -> Callable[[TrainState, Dict[str, jax.Array]],
                                  Tuple[TrainState, Dict[str, jax.Array]]]:
    grad_fn = jax.value_and_grad(
        functools.partial(loss_fn, cfg=cfg, remat=remat, unroll=unroll),
        has_aux=True)

    def train_step(state: TrainState, batch):
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(state.params, batch=batch)
        else:
            mb = _split_microbatches(batch, microbatches)

            def acc_step(carry, mb_i):
                g_acc, l_acc = carry
                (l, _), g = grad_fn(state.params, batch=mb_i)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            if unroll:
                # measurement mode: Python loop so HloCostAnalysis sees
                # every microbatch (a scan body is counted once)
                carry = (g0, 0.0)
                for i in range(microbatches):
                    carry, _ = acc_step(
                        carry, jax.tree.map(lambda a: a[i], mb))
                grads, loss = carry
            else:
                (grads, loss), _ = jax.lax.scan(acc_step, (g0, 0.0), mb)
            inv = 1.0 / microbatches
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss = loss * inv
            metrics = {}
        if grad_sync is not None and grad_sync.mode == "fsdp":
            # ZeRO-style pair: reduce-scatter grads, update the flat
            # shard, allgather params -- all through the engine
            params, opt, opt_metrics = fsdp_sync_apply(
                opt_cfg, state.params, grads, state.opt, grad_sync)
            out = {"loss": loss, **metrics, **opt_metrics}
            return TrainState(params=params, opt=opt), out
        if grad_sync is not None:
            # explicit pure-DP sync: every gradient byte goes through the
            # CollectiveEngine's cached dispatch (import here to keep the
            # collectives layer optional for GSPMD-only users)
            from repro.collectives.overlap import bucketed_allreduce
            grads, _ = bucketed_allreduce(
                grads, grad_sync.mesh, axes=grad_sync.axes,
                algorithm=grad_sync.algorithm,
                bucket_bytes=grad_sync.bucket_bytes,
                compress=grad_sync.compress)
        params, opt, opt_metrics = apply_updates(
            opt_cfg, state.params, grads, state.opt)
        out = {"loss": loss, **metrics, **opt_metrics}
        return TrainState(params=params, opt=opt), out

    return train_step


def make_prefill_step(cfg: ArchConfig, unroll: bool = False):
    def prefill_step(params, batch):
        return tf.prefill(params, cfg, batch, unroll=unroll)
    return prefill_step


def make_decode_step(cfg: ArchConfig, unroll: bool = False):
    def decode_step(params, cache, batch):
        return tf.decode_step(params, cfg, cache, batch, unroll=unroll)
    return decode_step


__all__ = ["cross_entropy", "loss_fn", "make_train_step",
           "make_prefill_step", "make_decode_step", "GradSyncConfig",
           "fsdp_sync_apply", "AUX_WEIGHT"]
