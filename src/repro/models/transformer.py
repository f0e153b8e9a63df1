"""Unified model zoo: dense GQA decoders, MoE decoders (+Arctic dense
residual), Mamba SSM stacks, RG-LRU hybrids, and encoder-decoder
backbones -- all as functional JAX with stacked-layer parameters and
``lax.scan`` over layers (keeps HLO size and compile time bounded for the
35..64-layer full configs).

Three entry points per family, shared signature:

    forward_train(params, cfg, batch)              -> logits
    prefill(params, cfg, batch)                    -> (logits, cache)
    decode_step(params, cfg, cache, batch)         -> (logits, cache)

``batch`` dicts come from ``repro.launch.input_specs`` (ShapeDtypeStructs
in the dry-run, real arrays in tests/examples).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (apply_rope, attention, rms_norm,
                                 shard_hint, swiglu)

Params = Dict[str, Any]


# ====================================================================== #
# parameter initialization
# ====================================================================== #
def _norm(d, dtype):
    return jnp.zeros((d,), dtype)


def _dense(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _attn_layer(key, cfg: ArchConfig, dtype) -> Params:
    d, h, kv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                    cfg.resolved_head_dim)
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": _dense(ks[0], (d, h * hd), s, dtype),
        "wk": _dense(ks[1], (d, kv * hd), s, dtype),
        "wv": _dense(ks[2], (d, kv * hd), s, dtype),
        "wo": _dense(ks[3], (h * hd, d), (h * hd) ** -0.5, dtype),
    }


def _mlp_layer(key, d, f, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "wg": _dense(ks[0], (d, f), d ** -0.5, dtype),
        "wu": _dense(ks[1], (d, f), d ** -0.5, dtype),
        "wd": _dense(ks[2], (f, d), f ** -0.5, dtype),
    }


def _moe_layer(key, cfg: ArchConfig, dtype) -> Params:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense(ks[0], (d, e), d ** -0.5, jnp.float32),
        "eg": _dense(ks[1], (e, d, f), d ** -0.5, dtype),
        "eu": _dense(ks[2], (e, d, f), d ** -0.5, dtype),
        "ed": _dense(ks[3], (e, f, d), f ** -0.5, dtype),
    }
    if cfg.moe_dense_ff:
        p["dense_mlp"] = _mlp_layer(ks[4], d, cfg.moe_dense_ff, dtype)
    return p


def _decoder_layer(key, cfg: ArchConfig, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p = {"ln1": _norm(cfg.d_model, dtype), "ln2": _norm(cfg.d_model, dtype)}
    p.update(_attn_layer(ks[0], cfg, dtype))
    if cfg.family == "moe":
        p.update(_moe_layer(ks[1], cfg, dtype))
    else:
        p.update(_mlp_layer(ks[2], cfg.d_model, cfg.d_ff, dtype))
    return p


def _stack(layer_params):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params)


def _stacked(make_layer, keys):
    """``_stack([make_layer(k) for k in keys])`` built in one vmapped
    pass: the same values, without a per-layer copy of every weight
    alive next to the stacked result."""
    return jax.vmap(make_layer)(keys)


def init_params(key, cfg: ArchConfig) -> Params:
    dtype = cfg.activation_dtype
    d, v = cfg.d_model, cfg.vocab_size
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    params: Params = {
        "embed": _dense(k_embed, (v, d), 1.0, dtype),
        "ln_f": _norm(d, dtype),
        "head": _dense(k_head, (d, v), d ** -0.5, dtype),
    }
    lkeys = jax.random.split(k_layers, max(cfg.num_layers, 1) + 8)

    if cfg.family == "ssm":
        params["layers"] = _stacked(
            lambda k: {"ln": _norm(d, dtype),
                       **ssm_lib.init_mamba_params(k, d, cfg.d_inner,
                                                   cfg.ssm_state,
                                                   cfg.dt_rank,
                                                   cfg.ssm_conv, dtype)},
            lkeys[:cfg.num_layers])
        return params

    if cfg.family == "hybrid":
        pattern = cfg.block_pattern
        cyc = len(pattern)
        n_cycles, rem = divmod(cfg.num_layers, cyc)
        ki = iter(jax.random.split(lkeys[0], cfg.num_layers + 4))

        def make_block(kind, key):
            if kind == "local":
                return _decoder_layer(key, cfg, dtype)
            return {"ln": _norm(d, dtype),
                    **rglru_lib.init_rglru_params(
                        key, d, d, cfg.num_heads, cfg.ssm_conv, dtype)}

        cycles = {f"b{j}": [] for j in range(cyc)}
        for _ in range(n_cycles):
            for j, kind in enumerate(pattern):
                cycles[f"b{j}"].append(make_block(kind, next(ki)))
        params["cycles"] = {k: _stack(vs) for k, vs in cycles.items()}
        params["tail"] = [make_block(pattern[j], next(ki))
                          for j in range(rem)]
        return params

    if cfg.family == "encdec":
        enc = [
            {"ln1": _norm(d, dtype), "ln2": _norm(d, dtype),
             **_attn_layer(lkeys[i], cfg, dtype),
             **_mlp_layer(jax.random.fold_in(lkeys[i], 1), d, cfg.d_ff,
                          dtype)}
            for i in range(cfg.encoder_layers)]
        dec = []
        for i in range(cfg.num_layers):
            k0 = jax.random.fold_in(lkeys[i], 2)
            k1 = jax.random.fold_in(lkeys[i], 3)
            k2 = jax.random.fold_in(lkeys[i], 4)
            layer = {"ln1": _norm(d, dtype), "lnx": _norm(d, dtype),
                     "ln2": _norm(d, dtype)}
            layer.update(_attn_layer(k0, cfg, dtype))
            layer.update({f"x_{k}": v
                          for k, v in _attn_layer(k1, cfg, dtype).items()})
            layer.update(_mlp_layer(k2, d, cfg.d_ff, dtype))
            dec.append(layer)
        params["encoder"] = _stack(enc)
        params["enc_ln_f"] = _norm(d, dtype)
        params["layers"] = _stack(dec)
        return params

    # dense / moe / vlm decoder stacks
    params["layers"] = _stacked(lambda k: _decoder_layer(k, cfg, dtype),
                                lkeys[:cfg.num_layers])
    return params


def param_specs(cfg: ArchConfig):
    """Shapes/dtypes of every parameter without allocating anything."""
    return jax.eval_shape(
        functools.partial(init_params, cfg=cfg), jax.random.PRNGKey(0))


# ====================================================================== #
# blocks
# ====================================================================== #
def _attention_sublayer(cfg: ArchConfig, x, p, *, causal=True, window=None,
                        pos=0, cache_kv=None, prefix=""):
    """Returns (attn_out, new_kv or None)."""
    b, s, d = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    wq, wk, wv, wo = (p[prefix + "wq"], p[prefix + "wk"], p[prefix + "wv"],
                      p[prefix + "wo"])
    q = shard_hint((x @ wq).reshape(b, s, h, hd), "dp", None, "model", None)
    k = shard_hint((x @ wk).reshape(b, s, kv, hd), "dp", None, "model", None)
    v = shard_hint((x @ wv).reshape(b, s, kv, hd), "dp", None, "model", None)
    positions = pos + jnp.arange(s)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cache_kv is None:
        out = attention(q, k, v, causal=causal, window=window,
                        probs_bf16=cfg.attn_probs_bf16)
        new_kv = (k, v)
    else:
        ck, cv = cache_kv
        smax = ck.shape[1]
        write = jnp.minimum(pos, smax - s)
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, write, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, write, 0, 0))
        from repro.models.layers import flash_decode, use_flash_decode
        fd = use_flash_decode(b, s, smax, kv) if causal and not window \
            else None
        if fd is not None:
            mesh_, dp_spec = fd
            out = flash_decode(q, ck, cv, pos + s, mesh_, dp_spec)
        else:
            out = attention(q, ck, cv, causal=causal, window=window,
                            q_offset=pos, kv_len=pos + s,
                            probs_bf16=cfg.attn_probs_bf16)
        new_kv = (ck, cv)
    return shard_hint(out.reshape(b, s, h * hd) @ wo,
                      "dp", None, None), new_kv


def _cross_attention(cfg: ArchConfig, x, p, enc_k, enc_v):
    b, s, d = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["x_wq"]).reshape(b, s, h, hd)
    out = attention(q, enc_k, enc_v, causal=False)
    return out.reshape(b, s, h * hd) @ p["x_wo"]


def _ffn_sublayer(cfg: ArchConfig, x, p):
    """Returns (ffn_out, aux_loss)."""
    if cfg.family == "moe":
        if cfg.moe_ep:
            from repro.models.moe_ep import moe_ffn_ep
            moe_fn = functools.partial(moe_ffn_ep,
                                       algorithm=cfg.moe_ep_algorithm)
        else:
            moe_fn = (moe_lib.moe_ffn_sharded if cfg.moe_shardmap_ep
                      else moe_lib.moe_ffn)
        out, aux = moe_fn(
            x, p["router"], p["eg"], p["eu"], p["ed"],
            top_k=cfg.experts_per_token,
            capacity_factor=cfg.capacity_factor)
        if cfg.moe_dense_ff:
            dm = p["dense_mlp"]
            out = out + swiglu(x, dm["wg"], dm["wu"], dm["wd"])
        return out, aux
    return swiglu(x, p["wg"], p["wu"], p["wd"]), 0.0


def _decoder_block(cfg: ArchConfig, x, p, *, pos=0, cache_kv=None,
                   window=None):
    if getattr(cfg, "grad_barrier", False) and x.shape[1] > 1:
        from repro.models.layers import grad_barrier
        x = grad_barrier(x)
    if cfg.sp_residuals and x.shape[1] > 1:
        # sequence-parallel residual stream: the tensor saved by remat
        # (the scan carry) is sharded over 'model' along the sequence;
        # GSPMD turns the surrounding TP all-reduces into
        # reduce-scatter + all-gather pairs (same wire bytes)
        x = shard_hint(x, "dp", "model", None)
    a, new_kv = _attention_sublayer(
        cfg, rms_norm(x, p["ln1"], cfg.norm_eps), p,
        causal=True, window=window, pos=pos, cache_kv=cache_kv)
    x = x + a
    f, aux = _ffn_sublayer(cfg, rms_norm(x, p["ln2"], cfg.norm_eps), p)
    out = x + f
    if cfg.sp_residuals and x.shape[1] > 1:
        out = shard_hint(out, "dp", "model", None)
    return out, new_kv, aux


def _hybrid_block(cfg: ArchConfig, kind: str, x, p, *, pos=0,
                  cache=None, single_step=False):
    """kind: 'local' (windowed attention) or 'rglru'."""
    if kind == "local":
        y, new_kv = _decoder_block(cfg, x, p, pos=pos, cache_kv=cache,
                                   window=cfg.local_window)[:2]
        return y, new_kv
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    y, new_state = rglru_lib.rglru_block(
        h, p, state=cache, single_step=single_step)
    return x + y, new_state


# ====================================================================== #
# decoder-only families: train / prefill / decode
# ====================================================================== #
def _embed(params, cfg: ArchConfig, tokens, soft_emb=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if soft_emb is not None:
        x = jnp.concatenate([soft_emb.astype(x.dtype), x], axis=1)
    return shard_hint(x, "dp", None, None)


def _unembed(params, cfg: ArchConfig, x):
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    out_dtype = jnp.bfloat16 if cfg.logits_bf16 else jnp.float32
    logits = (x @ params["head"]).astype(out_dtype)
    return shard_hint(logits, "dp", None, "model")


def _remat_policy(cfg):
    name = getattr(cfg, "remat_policy", "full")
    if name == "dots":
        return jax.checkpoint_policies.dots_saveable
    if name == "dots_no_batch":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def _scan_layers(cfg, params, x, layer_fn, remat: bool = True,
                 unroll: bool = False):
    fn = layer_fn
    if remat:
        fn = jax.checkpoint(layer_fn, policy=_remat_policy(cfg))

    if unroll:
        # Python loop: larger HLO, exact per-op cost analysis (the scan
        # body would otherwise be counted once by HloCostAnalysis).
        aux = 0.0
        n = jax.tree.leaves(params["layers"])[0].shape[0]
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, aux_i = fn(x, lp)
            aux = aux + aux_i
        return x, aux

    def body(carry, lp):
        h, aux = carry
        h, aux_i = fn(h, lp)
        return (h, aux + aux_i), None

    (x, aux), _ = jax.lax.scan(body, (x, 0.0), params["layers"])
    return x, aux


def forward_train(params, cfg: ArchConfig, batch, remat: bool = True,
                  unroll: bool = False):
    """Returns (logits [B, S, V], aux_loss)."""
    tokens = batch["tokens"]
    soft = batch.get("soft_emb")
    if cfg.family == "encdec":
        return _encdec_forward_train(params, cfg, batch, remat, unroll)
    x = _embed(params, cfg, tokens, soft)

    if cfg.family == "ssm":
        def layer(h, lp):
            y, _ = ssm_lib.mamba_block(
                rms_norm(h, lp["ln"], cfg.norm_eps), lp,
                ssm_state=cfg.ssm_state)
            return h + y, 0.0
        x, aux = _scan_layers(cfg, params, x, layer, remat, unroll)
    elif cfg.family == "hybrid":
        x, aux = _hybrid_forward(params, cfg, x, remat, unroll)
    else:
        def layer(h, lp):
            h, _, aux = _decoder_block(cfg, h, lp)
            return h, aux
        x, aux = _scan_layers(cfg, params, x, layer, remat, unroll)

    if soft is not None:
        x = x[:, soft.shape[1]:]
    return _unembed(params, cfg, x), aux


def _hybrid_forward(params, cfg: ArchConfig, x, remat=True, unroll=False):
    pattern = cfg.block_pattern

    def cycle_fn(h, cyc_params):
        for j, kind in enumerate(pattern):
            h, _ = _hybrid_block(cfg, kind, h, cyc_params[f"b{j}"])
        return h, 0.0

    fn = jax.checkpoint(cycle_fn, policy=_remat_policy(cfg)) \
        if remat else cycle_fn

    if unroll:
        n = jax.tree.leaves(params["cycles"])[0].shape[0]
        aux = 0.0
        for c in range(n):
            cp = jax.tree.map(lambda a: a[c], params["cycles"])
            x, a = fn(x, cp)
            aux = aux + a
    else:
        def body(carry, cp):
            h, aux = carry
            h, a = fn(h, cp)
            return (h, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, 0.0), params["cycles"])
    for j, tp in enumerate(params["tail"]):
        x, _ = _hybrid_block(cfg, pattern[j], x, tp)
    return x, aux


# ---------------------------------------------------------------------- #
# caches
# ---------------------------------------------------------------------- #
def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               enc_len: int = 0) -> Dict[str, Any]:
    """Zeroed decode cache (ShapeDtypeStructs via jax.eval_shape in the
    dry-run).  Dense/MoE: per-layer KV; SSM: conv+state; hybrid: windowed
    KV for the attention blocks + RG-LRU states; encdec: self KV + cross
    KV over the encoder output."""
    dtype = cfg.activation_dtype
    d, kvh, hd = cfg.d_model, cfg.num_kv_heads, cfg.resolved_head_dim
    L = cfg.num_layers
    if cfg.family == "ssm":
        return {
            "conv": jnp.zeros((L, batch, cfg.ssm_conv - 1, cfg.d_inner),
                              dtype),
            "h": jnp.zeros((L, batch, cfg.d_inner, cfg.ssm_state),
                           jnp.float32),
            "pos": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "hybrid":
        pattern = cfg.block_pattern
        kinds = [pattern[i % len(pattern)] for i in range(L)]
        n_attn = sum(k == "local" for k in kinds)
        n_rec = L - n_attn
        w = min(cfg.local_window, max_len)
        return {
            "k": jnp.zeros((n_attn, batch, w, kvh, hd), dtype),
            "v": jnp.zeros((n_attn, batch, w, kvh, hd), dtype),
            "conv": jnp.zeros((n_rec, batch, cfg.ssm_conv - 1, d), dtype),
            "h": jnp.zeros((n_rec, batch, d), jnp.float32),
            "pos": jnp.zeros((), jnp.int32),
        }
    cache = {
        "k": jnp.zeros((L, batch, max_len, kvh, hd), dtype),
        "v": jnp.zeros((L, batch, max_len, kvh, hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.family == "encdec":
        cache["enc_k"] = jnp.zeros((L, batch, enc_len, kvh, hd), dtype)
        cache["enc_v"] = jnp.zeros((L, batch, enc_len, kvh, hd), dtype)
    return cache


def _scan_or_unroll(layer, x, stacked_xs, unroll: bool):
    """lax.scan with per-layer ys, or an equivalent Python loop."""
    if not unroll:
        return jax.lax.scan(layer, x, stacked_xs)
    n = jax.tree.leaves(stacked_xs)[0].shape[0]
    ys = []
    for i in range(n):
        xs_i = jax.tree.map(lambda a: a[i], stacked_xs)
        x, y = layer(x, xs_i)
        ys.append(y)
    stacked_ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    return x, stacked_ys


def prefill(params, cfg: ArchConfig, batch, unroll: bool = False):
    """Forward over a prompt, returning last-position logits + the cache."""
    if cfg.family == "encdec":
        return _encdec_prefill(params, cfg, batch, unroll)
    tokens = batch["tokens"]
    soft = batch.get("soft_emb")
    x = _embed(params, cfg, tokens, soft)
    b, s = x.shape[:2]

    if cfg.family == "ssm":
        def layer(h, lp):
            y, st = ssm_lib.mamba_block(
                rms_norm(h, lp["ln"], cfg.norm_eps), lp,
                ssm_state=cfg.ssm_state)
            return h + y, st
        x2, states = _scan_or_unroll(layer, x, params["layers"], unroll)
        cache = {"conv": states.conv, "h": states.h,
                 "pos": jnp.asarray(s, jnp.int32)}
        return _unembed(params, cfg, x2[:, -1:]), cache

    if cfg.family == "hybrid":
        return _hybrid_prefill(params, cfg, x)

    def layer(h, lp):
        h, kv, _ = _decoder_block(cfg, h, lp)
        return h, kv
    x2, kvs = _scan_or_unroll(layer, x, params["layers"], unroll)
    cache = {"k": kvs[0], "v": kvs[1], "pos": jnp.asarray(s, jnp.int32)}
    return _unembed(params, cfg, x2[:, -1:]), cache


def _hybrid_prefill(params, cfg: ArchConfig, x):
    pattern = cfg.block_pattern
    b, s = x.shape[:2]
    w = cfg.local_window
    ks, vs, convs, hs = [], [], [], []

    def run(kind, h, p):
        if kind == "local":
            h2, kv = _hybrid_block(cfg, kind, h, p)
            k, v = kv
            # keep only the trailing window
            if k.shape[1] > w:
                k, v = k[:, -w:], v[:, -w:]
            elif k.shape[1] < w:
                pad = w - k.shape[1]
                k = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
            ks.append(k), vs.append(v)
            return h2
        h2, st = _hybrid_block(cfg, kind, h, p)
        convs.append(st.conv), hs.append(st.h)
        return h2

    n_cycles = params["cycles"]["b0"]["out" if pattern[0] != "local"
                                      else "wo"].shape[0]
    for c in range(n_cycles):
        cp = jax.tree.map(lambda a: a[c], params["cycles"])
        for j, kind in enumerate(pattern):
            x = run(kind, x, cp[f"b{j}"])
    for j, tp in enumerate(params["tail"]):
        x = run(pattern[j], x, tp)

    cache = {
        "k": jnp.stack(ks) if ks else jnp.zeros(
            (0, b, w, cfg.num_kv_heads, cfg.resolved_head_dim),
            cfg.activation_dtype),
        "v": jnp.stack(vs) if vs else jnp.zeros(
            (0, b, w, cfg.num_kv_heads, cfg.resolved_head_dim),
            cfg.activation_dtype),
        "conv": jnp.stack(convs),
        "h": jnp.stack(hs),
        "pos": jnp.asarray(s, jnp.int32),
    }
    return _unembed(params, cfg, x[:, -1:]), cache


def decode_step(params, cfg: ArchConfig, cache, batch,
                unroll: bool = False):
    """One-token decode.  batch: {"tokens": [B, 1]}.  Returns
    (logits [B, 1, V], new cache)."""
    if cfg.family == "encdec":
        return _encdec_decode(params, cfg, cache, batch, unroll)
    tokens = batch["tokens"]
    pos = cache["pos"]
    x = _embed(params, cfg, tokens)

    if cfg.family == "ssm":
        def layer(h, xs):
            lp, conv, hstate = xs
            st = ssm_lib.SSMState(conv=conv, h=hstate)
            y, st2 = ssm_lib.mamba_block(
                rms_norm(h, lp["ln"], cfg.norm_eps), lp,
                ssm_state=cfg.ssm_state, state=st, single_step=True)
            return h + y, (st2.conv, st2.h)
        x2, (convs, hs) = _scan_or_unroll(
            layer, x, (params["layers"], cache["conv"], cache["h"]),
            unroll)
        new_cache = {"conv": convs, "h": hs, "pos": pos + 1}
        return _unembed(params, cfg, x2), new_cache

    if cfg.family == "hybrid":
        return _hybrid_decode(params, cfg, cache, x)

    def layer(h, xs):
        lp, ck, cv = xs
        h, (nk, nv), _ = _decoder_block(cfg, h, lp, pos=pos,
                                        cache_kv=(ck, cv))
        return h, (nk, nv)
    x2, (nks, nvs) = _scan_or_unroll(
        layer, x, (params["layers"], cache["k"], cache["v"]), unroll)
    new_cache = dict(cache, k=nks, v=nvs, pos=pos + 1)
    return _unembed(params, cfg, x2), new_cache


def _hybrid_decode(params, cfg: ArchConfig, cache, x):
    pattern = cfg.block_pattern
    pos = cache["pos"]
    w = cache["k"].shape[2]
    ai = 0
    ri = 0
    nks, nvs, nconvs, nhs = ([None] * cache["k"].shape[0],
                             [None] * cache["v"].shape[0],
                             [None] * cache["conv"].shape[0],
                             [None] * cache["h"].shape[0])

    def run(kind, h, p, ai, ri):
        if kind == "local":
            # ring-buffer local attention: write at pos % w, attend over
            # the window (RoPE applied at absolute positions pre-write).
            ck, cv = cache["k"][ai], cache["v"][ai]
            b = h.shape[0]
            hn = rms_norm(h, p["ln1"], cfg.norm_eps)
            hd = cfg.resolved_head_dim
            q = (hn @ p["wq"]).reshape(b, 1, cfg.num_heads, hd)
            k = (hn @ p["wk"]).reshape(b, 1, cfg.num_kv_heads, hd)
            v = (hn @ p["wv"]).reshape(b, 1, cfg.num_kv_heads, hd)
            q = apply_rope(q, pos[None], cfg.rope_theta)
            k = apply_rope(k, pos[None], cfg.rope_theta)
            slot = jnp.mod(pos, w)
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, slot, 0, 0))
            # valid entries: age < window (ring semantics, RoPE absolute)
            from repro.models.layers import _direct_attention, _repeat_kv
            group = cfg.num_heads // cfg.num_kv_heads
            kt = _repeat_kv(jnp.moveaxis(ck, 1, 2), group)
            vt = _repeat_kv(jnp.moveaxis(cv, 1, 2), group)
            qt = jnp.moveaxis(q, 1, 2)
            n_valid = jnp.minimum(pos + 1, w)
            out = _direct_attention(qt, kt, vt, causal=False, window=None,
                                    kv_len=n_valid)
            out = jnp.moveaxis(out, 1, 2).reshape(b, 1,
                                                  cfg.num_heads * hd)
            h = h + out @ p["wo"]
            f, _ = _ffn_sublayer(cfg, rms_norm(h, p["ln2"], cfg.norm_eps), p)
            nks[ai], nvs[ai] = ck, cv
            return h + f, ai + 1, ri
        st = rglru_lib.RGLRUState(conv=cache["conv"][ri], h=cache["h"][ri])
        h2, st2 = _hybrid_block(cfg, kind, h, p, cache=st, single_step=True)
        nconvs[ri], nhs[ri] = st2.conv, st2.h
        return h2, ai, ri + 1

    n_cycles = jax.tree.leaves(params["cycles"])[0].shape[0]
    for c in range(n_cycles):
        cp = jax.tree.map(lambda a: a[c], params["cycles"])
        for j, kind in enumerate(pattern):
            x, ai, ri = run(kind, x, cp[f"b{j}"], ai, ri)
    for j, tp in enumerate(params["tail"]):
        x, ai, ri = run(pattern[j], x, tp, ai, ri)

    new_cache = {
        "k": jnp.stack(nks) if nks else cache["k"],
        "v": jnp.stack(nvs) if nvs else cache["v"],
        "conv": jnp.stack(nconvs) if nconvs else cache["conv"],
        "h": jnp.stack(nhs) if nhs else cache["h"],
        "pos": pos + 1,
    }
    return _unembed(params, cfg, x), new_cache


# ====================================================================== #
# encoder-decoder (Whisper backbone)
# ====================================================================== #
def _encoder_forward(params, cfg: ArchConfig, frames, unroll: bool = False):
    """frames: [B, S_enc, D] precomputed frame embeddings (stub
    frontend)."""
    def layer(h, lp):
        a, _ = _attention_sublayer(
            cfg, rms_norm(h, lp["ln1"], cfg.norm_eps), lp, causal=False)
        h = h + a
        f = swiglu(rms_norm(h, lp["ln2"], cfg.norm_eps),
                   lp["wg"], lp["wu"], lp["wd"])
        return h + f, None
    fn = jax.checkpoint(layer,
                        policy=jax.checkpoint_policies.nothing_saveable)
    if unroll:
        x = frames
        n = jax.tree.leaves(params["encoder"])[0].shape[0]
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], params["encoder"])
            x, _ = fn(x, lp)
    else:
        x, _ = jax.lax.scan(lambda h, lp: fn(h, lp), frames,
                            params["encoder"])
    return rms_norm(x, params["enc_ln_f"], cfg.norm_eps)


def _cross_kv(cfg: ArchConfig, enc_out, lp):
    b, s, d = enc_out.shape
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    k = (enc_out @ lp["x_wk"]).reshape(b, s, kvh, hd)
    v = (enc_out @ lp["x_wv"]).reshape(b, s, kvh, hd)
    return k, v


def _dec_layer(cfg: ArchConfig, h, lp, enc_kv, *, pos=0, cache_kv=None):
    a, new_kv = _attention_sublayer(
        cfg, rms_norm(h, lp["ln1"], cfg.norm_eps), lp,
        causal=True, pos=pos, cache_kv=cache_kv)
    h = h + a
    c = _cross_attention(cfg, rms_norm(h, lp["lnx"], cfg.norm_eps), lp,
                         *enc_kv)
    h = h + c
    f = swiglu(rms_norm(h, lp["ln2"], cfg.norm_eps),
               lp["wg"], lp["wu"], lp["wd"])
    return h + f, new_kv


def _encdec_forward_train(params, cfg: ArchConfig, batch, remat=True,
                          unroll=False):
    enc_out = _encoder_forward(params, cfg, batch["frames"], unroll=unroll)
    x = _embed(params, cfg, batch["tokens"])

    def layer(h, lp):
        enc_kv = _cross_kv(cfg, enc_out, lp)
        h, _ = _dec_layer(cfg, h, lp, enc_kv)
        return h, 0.0
    x, aux = _scan_layers(cfg, {"layers": params["layers"]}, x, layer,
                          remat, unroll)
    return _unembed(params, cfg, x), aux


def _encdec_prefill(params, cfg: ArchConfig, batch, unroll: bool = False):
    """Encoder pass + cross-KV materialization + first decoder position."""
    enc_out = _encoder_forward(params, cfg, batch["frames"], unroll=unroll)
    tokens = batch["tokens"]           # [B, S_dec] decoder prompt
    x = _embed(params, cfg, tokens)
    s = tokens.shape[1]

    def layer(h, lp):
        enc_kv = _cross_kv(cfg, enc_out, lp)
        h, kv = _dec_layer(cfg, h, lp, enc_kv)
        return h, (kv, enc_kv)
    x2, (kvs, enc_kvs) = _scan_or_unroll(layer, x, params["layers"],
                                         unroll)
    cache = {"k": kvs[0], "v": kvs[1],
             "enc_k": enc_kvs[0], "enc_v": enc_kvs[1],
             "pos": jnp.asarray(s, jnp.int32)}
    return _unembed(params, cfg, x2[:, -1:]), cache


def _encdec_decode(params, cfg: ArchConfig, cache, batch,
                   unroll: bool = False):
    pos = cache["pos"]
    x = _embed(params, cfg, batch["tokens"])

    def layer(h, xs):
        lp, ck, cv, ek, ev = xs
        h, (nk, nv) = _dec_layer(cfg, h, lp, (ek, ev), pos=pos,
                                 cache_kv=(ck, cv))
        return h, (nk, nv)
    x2, (nks, nvs) = _scan_or_unroll(
        layer, x, (params["layers"], cache["k"], cache["v"],
                   cache["enc_k"], cache["enc_v"]), unroll)
    new_cache = dict(cache, k=nks, v=nvs, pos=pos + 1)
    return _unembed(params, cfg, x2), new_cache


__all__ = [
    "init_params", "param_specs", "forward_train", "prefill",
    "decode_step", "init_cache",
]
