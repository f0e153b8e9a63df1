"""Expert-parallel MoE dispatch through the CollectiveEngine's AllToAll.

The existing shard_map EP path (``moe.moe_ffn_sharded``) keeps tokens
*replicated* over the expert axis: every expert rank runs the router for
the whole batch shard and only the combine communicates (one psum).
That sidesteps the EP exchange entirely -- fine for correctness, but it
is not the traffic pattern a production expert-parallel MoE runs, and it
leaves the all-to-all outside the model-driven collective stack.

This module is the real thing: tokens are sharded over the EP axes,
each device routes only its own tokens, and dispatch/combine are
explicit **all-to-all** exchanges routed through
``CollectiveEngine.all_to_all_multi`` -- so the planner prices the
exchange per axis (`hierarchical` 2-phase intra-pod/inter-pod vs
`sequential` vs `flat` single-shot, plus chunk-pipelined variants that
overlap the inter-pod phase of one payload slice with the intra-pod
phase of the next), heterogeneous ``FabricTopology`` constants
included, and the decision lands in the persistent cache.

Layout (inside one shard_map over the mesh):

* tokens  ``x [G, gs, D]`` -- G sharded over ``dp_axes + ep_axes``;
* experts ``w_* [E, ...]`` -- E sharded over ``ep_axes`` (row-major
  folded rank r owns experts ``[r*E_l, (r+1)*E_l)``), optionally FSDP
  over a spare data axis, gathered just-in-time;
* dispatch: the group-local sort of ``moe.moe_ffn`` builds the
  ``[G_l, E, Cap, D]`` buffer, reordered destination-rank-major and
  exchanged (chunk r -> rank r); the reverse exchange brings expert
  outputs home for the weighted combine.

Per-token results are bit-comparable to ``moe.moe_ffn`` up to fp32
reassociation: routing, capacity, and the keep/pos bookkeeping are
identical -- only *where* each expert's FFN runs differs.

``algorithm`` selects the exchange backend: ``"lax"`` is the bare
``lax.all_to_all`` single-shot (the GSPMD-equivalent baseline), anything
else is handed to the engine (``"auto"``, a plan shape, or a 1D backend
name).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.moe import moe_capacity, moe_ffn_sharded


_warned_fallback = False


def _fallback(reason: str, x, router_w, w_gate, w_up, w_down, *,
              top_k: int, capacity_factor: float):
    """Route through the replicated-token path, loudly: a config that
    silently skips the EP exchange would make --moe-ep smokes vacuous."""
    global _warned_fallback
    if not _warned_fallback:
        _warned_fallback = True
        print(f"[moe_ep] WARNING: falling back to the replicated-token "
              f"shard_map path (no all-to-all dispatch): {reason}")
    return moe_ffn_sharded(x, router_w, w_gate, w_up, w_down,
                           top_k=top_k, capacity_factor=capacity_factor)


def _ep_axes_for(mesh) -> Tuple[str, ...]:
    """Mesh axes the expert dim shards over: the model axis when the
    mesh has a non-trivial one, else the folded DP axes (the
    ("pod", "data") expert mesh the planner's 2-phase decomposition
    targets).  Size-1 axes are skipped so a trivial model axis does
    not shadow a usable expert mesh."""
    names = mesh.axis_names
    sizes = dict(mesh.shape)
    if sizes.get("model", 0) > 1:
        return ("model",)
    return tuple(a for a in ("pod", "data") if sizes.get(a, 0) > 1)


def _moe_ep_local(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                  capacity_factor: float, ep_axes: Tuple[str, ...],
                  token_axes: Tuple[str, ...], fsdp_axis: Optional[str],
                  algorithm: str, engine):
    """Per-device body (inside shard_map).

    x: [G_l, gs, D] (local token groups); router_w: [D, E] replicated;
    w_gate/w_up: [E_l, D(_fsdp), F]; w_down: [E_l, F, D(_fsdp)].
    """
    g, gs, d = x.shape
    e_total = router_w.shape[1]
    if fsdp_axis is not None:
        w_gate = lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)
        w_up = lax.all_gather(w_up, fsdp_axis, axis=1, tiled=True)
        w_down = lax.all_gather(w_down, fsdp_axis, axis=2, tiled=True)
    e_local = w_gate.shape[0]
    n_ranks = e_total // e_local
    cap = moe_capacity(gs, e_total, top_k, capacity_factor)

    # ---- router + group-local sort dispatch (identical to moe_ffn) ----
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, e_total,
                                         dtype=jnp.float32), axis=2),
                  axis=(0, 1))
    aux = e_total * jnp.sum(me * ce) / top_k
    for ax in token_axes:
        aux = lax.pmean(aux, ax)

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

    x_sel = jnp.take_along_axis(x, token_of[..., None], axis=1)
    x_sel = jnp.where(keep[..., None], x_sel, 0)
    buf = jnp.zeros((g, e_total, cap, d), dtype=x.dtype)
    buf = buf.at[g_idx, sorted_e, pos].set(x_sel, mode="drop")

    # ---- dispatch all-to-all: chunk r carries rank r's experts ----
    def exchange(v):
        flat = v.reshape((n_ranks * g * e_local * cap, d))
        if algorithm == "lax":
            axis = ep_axes if len(ep_axes) > 1 else ep_axes[0]
            out = lax.all_to_all(flat, axis, split_axis=0, concat_axis=0,
                                 tiled=True)
        else:
            out = engine.all_to_all_multi(flat, ep_axes,
                                          algorithm=algorithm)
        return out.reshape((n_ranks, g, e_local, cap, d))

    send = buf.reshape(g, n_ranks, e_local, cap, d).transpose(
        1, 0, 2, 3, 4)
    recv = exchange(send)           # [src_rank, their G_l, my E_l, cap, D]

    # ---- expert compute on every rank's tokens for my experts ----
    tok = recv.transpose(2, 0, 1, 3, 4).reshape(
        e_local, n_ranks * g * cap, d)
    h = jnp.einsum("etd,edf->etf", tok, w_gate)
    u = jnp.einsum("etd,edf->etf", tok, w_up)
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(h) * u, w_down)

    # ---- combine all-to-all: results home to their token owners ----
    y = y.reshape(e_local, n_ranks, g, cap, d).transpose(1, 2, 0, 3, 4)
    back = exchange(y)              # [expert rank, my G_l, its E_l, cap, D]
    y_buf = back.transpose(1, 0, 2, 3, 4).reshape(g, e_total, cap, d)

    w_sorted = jnp.take_along_axis(flat_w, sort_idx, axis=1)
    y_tok = y_buf[g_idx, sorted_e, jnp.where(keep, pos, 0)]
    y_tok = jnp.where(keep[..., None], y_tok, 0) * w_sorted[..., None]
    out = jnp.zeros_like(x)
    out = out.at[g_idx, token_of].add(y_tok)
    return out, aux


def moe_ffn_ep(x, router_w, w_gate, w_up, w_down, *, top_k: int,
               capacity_factor: float = 1.25, algorithm: str = "auto",
               engine=None):
    """Engine-routed expert-parallel MoE when a mesh is ambient; falls
    back to ``moe_ffn_sharded`` (and transitively the GSPMD path) when
    there is no mesh or the shapes don't tile the EP world."""
    from repro.models.layers import _ambient_mesh
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _ambient_mesh()
    ep_axes = _ep_axes_for(mesh) if mesh is not None else ()
    if mesh is None or not ep_axes:
        return _fallback("no ambient mesh / no EP-capable axis", x,
                         router_w, w_gate, w_up, w_down, top_k=top_k,
                         capacity_factor=capacity_factor)
    names = mesh.axis_names
    sizes = dict(mesh.shape)
    dp_axes = tuple(a for a in ("pod", "data")
                    if a in names and a not in ep_axes)
    token_axes = dp_axes + ep_axes
    fsdp_axis = ("data" if "data" in names and "data" not in ep_axes
                 else None)
    e_total = router_w.shape[1]
    n_ranks = 1
    for a in ep_axes:
        n_ranks *= sizes[a]
    n_tok = n_ranks
    for a in dp_axes:
        n_tok *= sizes[a]
    if (n_ranks == 1 or e_total % n_ranks != 0
            or x.shape[0] % n_tok != 0):
        return _fallback(
            f"E={e_total} over {n_ranks} EP ranks ({ep_axes}) or "
            f"G={x.shape[0]} over {n_tok} token shards does not tile",
            x, router_w, w_gate, w_up, w_down, top_k=top_k,
            capacity_factor=capacity_factor)
    if engine is None and algorithm != "lax":
        from repro.collectives.api import get_engine
        engine = get_engine()

    ep_spec = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    tok_spec = token_axes if len(token_axes) > 1 else token_axes[0]
    body = functools.partial(
        _moe_ep_local, top_k=top_k, capacity_factor=capacity_factor,
        ep_axes=ep_axes, token_axes=token_axes, fsdp_axis=fsdp_axis,
        algorithm=algorithm, engine=engine)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(tok_spec, None, None),           # x (tokens)
                  P(),                               # router (replicated)
                  P(ep_spec, fsdp_axis, None),       # w_gate
                  P(ep_spec, fsdp_axis, None),       # w_up
                  P(ep_spec, None, fsdp_axis)),      # w_down
        out_specs=(P(tok_spec, None, None), P()),
        check_vma=False)
    return fn(x, router_w, w_gate, w_up, w_down)


__all__ = ["moe_ffn_ep"]
