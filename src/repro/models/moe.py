"""Capacity-based top-k Mixture-of-Experts FFN (expert-parallel friendly).

Group-local sort-based dispatch: tokens are organized as [G, gs, D] with G
(the batch/sequence groups) sharded over the data axis and experts sharded
over the model axis.  The argsort runs along the *unsharded* gs*k axis, so
dispatch needs no cross-device sort; the scatter into the [G, E, Cap, D]
expert buffers is where GSPMD inserts the all-to-all -- the EP pattern.

FLOPs are proportional to tokens * top_k * capacity_factor (no dense
all-experts waste), which keeps the roofline's MODEL_FLOPS/HLO_FLOPs
ratio honest for the MoE architectures.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import shard_hint


def moe_capacity(tokens_per_group: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    cap = int(tokens_per_group * top_k * capacity_factor / num_experts)
    return max(cap, top_k)


def moe_ffn(x: jax.Array, router_w: jax.Array, w_gate: jax.Array,
            w_up: jax.Array, w_down: jax.Array, *, top_k: int,
            capacity_factor: float = 1.25) -> Tuple[jax.Array, jax.Array]:
    """x: [G, gs, D]; router_w: [D, E]; w_gate/up: [E, D, F]; w_down:
    [E, F, D].  Returns (out [G, gs, D], aux_loss scalar)."""
    g, gs, d = x.shape
    e = router_w.shape[1]
    cap = moe_capacity(gs, e, top_k, capacity_factor)

    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))  # [G,gs,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)                 # [G, gs, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)     # renormalize

    # load-balancing aux loss (Switch-style)
    me = jnp.mean(probs, axis=(0, 1))                          # [E]
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=2),
        axis=(0, 1))
    aux_loss = e * jnp.sum(me * ce) / top_k

    # ---- group-local sort-based dispatch ----
    flat_e = top_e.reshape(g, gs * top_k)                      # [G, gsk]
    flat_w = top_p.reshape(g, gs * top_k).astype(x.dtype)
    sort_idx = jnp.argsort(flat_e, axis=1, stable=True)        # local sort
    sorted_e = jnp.take_along_axis(flat_e, sort_idx, axis=1)
    # position of each entry within its expert
    counts = jnp.sum(jax.nn.one_hot(flat_e, e, dtype=jnp.int32), axis=1)
    starts = jnp.cumsum(counts, axis=1) - counts               # [G, E]
    starts_sorted = jnp.take_along_axis(starts, sorted_e, axis=1)
    pos = jnp.arange(gs * top_k)[None, :] - starts_sorted      # [G, gsk]
    keep = pos < cap

    token_of = sort_idx // top_k                               # [G, gsk]
    g_idx = jnp.arange(g)[:, None]
    x_sel = jnp.take_along_axis(
        x, token_of[..., None], axis=1)                        # [G, gsk, D]
    x_sel = jnp.where(keep[..., None], x_sel, 0)

    buf = jnp.zeros((g, e, cap, d), dtype=x.dtype)
    buf = buf.at[g_idx, sorted_e, pos].set(x_sel, mode="drop")
    # EP: expert dim over the model axis (the scatter above is where the
    # all-to-all happens); groups stay on the DP axes
    buf = shard_hint(buf, "dp", "model", None, None)

    # ---- expert compute (E sharded over the model axis) ----
    h = shard_hint(jnp.einsum("gecd,edf->gecf", buf, w_gate),
                   "dp", "model", None, None)
    u = shard_hint(jnp.einsum("gecd,edf->gecf", buf, w_up),
                   "dp", "model", None, None)
    hidden = jax.nn.silu(h) * u
    y = jnp.einsum("gecf,efd->gecd", hidden, w_down)           # [G, E, Cap, D]
    y = shard_hint(y, "dp", "model", None, None)

    # ---- combine ----
    w_sorted = jnp.take_along_axis(flat_w, sort_idx, axis=1)
    y_tok = y[g_idx, sorted_e, pos]                            # [G, gsk, D]
    y_tok = jnp.where(keep[..., None], y_tok, 0) * w_sorted[..., None]
    out = jnp.zeros_like(x)
    out = out.at[g_idx, token_of].add(y_tok)
    return shard_hint(out, "dp", None, None), aux_loss


# ---------------------------------------------------------------------- #
# shard_map expert-parallel path
#
# The jnp/GSPMD path above lets the partitioner handle the dispatch
# scatter -- which it resolves as full-buffer cross-device gathers
# (~600 GB/layer/device on arctic-480b train_4k; see EXPERIMENTS.md
# §Perf).  This path makes the EP structure explicit instead:
#
#  * tokens are batch-sharded over the DP axes and replicated over
#    'model'; every model rank runs the (cheap) router + local sort for
#    its data shard -> the dispatch buffer slice [G_l, E_local, Cap, D]
#    for its OWN experts requires NO communication;
#  * expert weights are E-sharded over 'model' (+ FSDP over 'data'),
#    all-gathered over 'data' just-in-time;
#  * the combine is a scatter-add of each rank's expert outputs followed
#    by one [G_l, gs, D] psum over 'model' -- the classic EP exchange.
# ---------------------------------------------------------------------- #
def _moe_local(x, router_w, w_gate, w_up, w_down, *, top_k: int,
               capacity_factor: float, model_axis: str, fsdp_axis,
               dp_axes) -> Tuple[jax.Array, jax.Array]:
    """Per-device body (inside shard_map).

    x: [G_l, gs, D] (local data shard, replicated over model)
    router_w: [D, E] (replicated)
    w_gate/w_up: [E_local, D_fsdp, F]; w_down: [E_local, F, D_fsdp].
    """
    g, gs, d = x.shape
    e_total = router_w.shape[1]
    if fsdp_axis is not None:
        w_gate = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)
        w_up = jax.lax.all_gather(w_up, fsdp_axis, axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down, fsdp_axis, axis=2, tiled=True)
    e_local = w_gate.shape[0]
    rank = jax.lax.axis_index(model_axis)
    cap = moe_capacity(gs, e_total, top_k, capacity_factor)

    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, e_total,
                                         dtype=jnp.float32), axis=2),
                  axis=(0, 1))
    aux = e_total * jnp.sum(me * ce) / top_k
    for ax in dp_axes:
        aux = jax.lax.pmean(aux, ax)

    flat_e = top_e.reshape(g, gs * top_k)
    flat_w = top_p.reshape(g, gs * top_k).astype(x.dtype)
    sort_idx = jnp.argsort(flat_e, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, sort_idx, axis=1)
    counts = jnp.sum(jax.nn.one_hot(flat_e, e_total, dtype=jnp.int32),
                     axis=1)
    starts = jnp.cumsum(counts, axis=1) - counts
    starts_sorted = jnp.take_along_axis(starts, sorted_e, axis=1)
    pos = jnp.arange(gs * top_k)[None, :] - starts_sorted
    keep = pos < cap
    token_of = sort_idx // top_k
    g_idx = jnp.arange(g)[:, None]

    # local-expert coordinates: expert eid lives on rank eid // e_local
    local_e = sorted_e - rank * e_local
    mine = (local_e >= 0) & (local_e < e_local) & keep
    x_sel = jnp.take_along_axis(x, token_of[..., None], axis=1)
    x_sel = jnp.where(mine[..., None], x_sel, 0)
    buf = jnp.zeros((g, e_local, cap, d), dtype=x.dtype)
    buf = buf.at[g_idx, jnp.clip(local_e, 0, e_local - 1),
                 jnp.where(mine, pos, cap)].set(x_sel, mode="drop")

    h = jnp.einsum("gecd,edf->gecf", buf, w_gate)
    u = jnp.einsum("gecd,edf->gecf", buf, w_up)
    y = jnp.einsum("gecf,efd->gecd", jax.nn.silu(h) * u, w_down)

    w_sorted = jnp.take_along_axis(flat_w, sort_idx, axis=1)
    y_tok = y[g_idx, jnp.clip(local_e, 0, e_local - 1),
              jnp.where(mine, pos, 0)]
    y_tok = jnp.where(mine[..., None], y_tok, 0) * w_sorted[..., None]
    out = jnp.zeros_like(x)
    out = out.at[g_idx, token_of].add(y_tok)
    # combine: each rank contributed its experts' tokens
    out = jax.lax.psum(out, model_axis)
    return out, aux


def moe_ffn_sharded(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                    capacity_factor: float = 1.25):
    """Expert-parallel MoE via shard_map when a mesh is ambient; falls
    back to the GSPMD path otherwise (unit tests, single device)."""
    from repro.models.layers import _ambient_mesh
    mesh = _ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_ffn(x, router_w, w_gate, w_up, w_down, top_k=top_k,
                       capacity_factor=capacity_factor)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    import functools

    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    fsdp_axis = "data" if "data" in names else None
    e_total = router_w.shape[1]
    model_size = dict(mesh.shape)["model"]
    if (e_total % model_size != 0
            or (dp and x.shape[0] % mesh.shape[dp[0]] != 0)):
        return moe_ffn(x, router_w, w_gate, w_up, w_down, top_k=top_k,
                       capacity_factor=capacity_factor)

    body = functools.partial(
        _moe_local, top_k=top_k, capacity_factor=capacity_factor,
        model_axis="model", fsdp_axis=fsdp_axis, dp_axes=dp)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec, None, None),            # x
                  P(),                               # router (replicated)
                  P("model", fsdp_axis, None),       # w_gate
                  P("model", fsdp_axis, None),       # w_up
                  P("model", None, fsdp_axis)),      # w_down
        out_specs=(P(dp_spec, None, None), P()),
        check_vma=False)
    return fn(x, router_w, w_gate, w_up, w_down)


__all__ = ["moe_ffn", "moe_ffn_sharded", "moe_capacity"]
