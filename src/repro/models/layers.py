"""Shared model layers: RMSNorm, RoPE, GQA attention (direct + chunked
flash), SwiGLU MLP.  Pure functional JAX; parameters are plain pytrees.

Attention uses an online-softmax KV-chunked implementation (a pure-JAX
flash attention) whenever the sequence is long, so that the compiled HLO
never materializes an S x S logits tensor -- this is what makes the 32k
prefill and 4k train shapes compile within per-chip HBM.  On TPU the
Pallas kernel (repro.kernels.flash_attention) implements the same
computation; see DESIGN.md (hardware adaptation).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_CHUNK_THRESHOLD = 2048  # direct attention below this sequence length
_KV_CHUNK = 1024


# ---------------------------------------------------------------------- #
# activation sharding hints
#
# Without these, GSPMD is free to pick pathological strategies (e.g.
# partial-summing attention logits over a split head_dim, or reducing
# activations over the FSDP axis instead of gathering weights).  The
# hints use the mesh installed with ``jax.set_mesh`` (dry-run,
# launchers) and are no-ops otherwise (CPU unit tests).
# ---------------------------------------------------------------------- #
def _ambient_mesh():
    """The mesh set by ``jax.set_mesh`` when every axis is Auto (GSPMD
    partitions it), else None: no mesh is set, or the caller runs inside
    a shard_map body that already owns some axes."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or any(t != jax.sharding.AxisType.Auto
                         for t in mesh.axis_types):
        return None
    return mesh


def shard_hint(x: jax.Array, *dims: str | None) -> jax.Array:
    """with_sharding_constraint using placeholder axis roles.

    dims entries: "dp" (batch axes: pod+data), "model", or None.  Missing
    mesh axes degrade to None; no ambient mesh -> identity.
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    spec = []
    for d in dims:
        if d == "dp":
            spec.append(dp if len(dp) > 1 else (dp[0] if dp else None))
        elif d == "model":
            spec.append("model" if "model" in names else None)
        else:
            spec.append(None)
    from jax.sharding import PartitionSpec as _P
    return jax.lax.with_sharding_constraint(x, _P(*spec))


@jax.custom_vjp
def grad_barrier(x):
    """Identity whose cotangent passes an optimization barrier: stops
    XLA from sinking f32 converts across the TP all-reduce in backward
    (which would double the gradient all-reduce bytes)."""
    return x


def _gb_fwd(x):
    return x, None


def _gb_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


grad_barrier.defvjp(_gb_fwd, _gb_bwd)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S] (or [S])."""
    b, s, h, d = x.shape
    freqs = rope_frequencies(d, theta)                       # [D/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _repeat_kv(k: jax.Array, group: int) -> jax.Array:
    if group == 1:
        return k
    return jnp.repeat(k, group, axis=1)


def _direct_attention(q, k, v, causal: bool, window: Optional[int],
                      q_offset: int | jax.Array = 0,
                      kv_len: Optional[jax.Array] = None,
                      probs_bf16: bool = False) -> jax.Array:
    """q: [B, H, Sq, D]; k/v: [B, H, Skv, D] (already GQA-expanded)."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    scale = 1.0 / (d ** 0.5)
    # native-dtype matmul with fp32 accumulation (the MXU's mode): no
    # fp32 upcast of the (potentially huge) K operand
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    q_ids = q_offset + jnp.arange(sq)[:, None]
    k_ids = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= k_ids <= q_ids
    if window is not None:
        mask &= k_ids > q_ids - window
    mask = mask[None, None]
    if kv_len is not None:
        mask &= (k_ids < kv_len)[None, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    if probs_bf16:
        out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(jnp.bfloat16),
                         v.astype(jnp.bfloat16))
    else:
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


_INNER_UNROLL = False   # dry-run measurement mode: unroll inner scans so
                        # HloCostAnalysis sees real ops, not loop carries
                        # (TPU aliases loop carries in place; the CPU HLO
                        # would otherwise charge giant copy traffic)


def set_inner_unroll(value: bool) -> None:
    global _INNER_UNROLL
    _INNER_UNROLL = value


def inner_unroll_enabled() -> bool:
    return _INNER_UNROLL


def _chunked_attention(q, k, v, causal: bool, window: Optional[int],
                       chunk: int = _KV_CHUNK,
                       probs_bf16: bool = False) -> jax.Array:
    """Online-softmax attention scanning KV chunks; never materializes
    S x S logits.  q: [B, H, S, D]; k/v: [B, H, S, D] (GQA-expanded).

    Each chunk step is rematerialized (jax.checkpoint): the backward pass
    recomputes the chunk's logits instead of saving exp(logits) -- the
    flash-attention-backward memory behavior, matching what the Pallas
    kernel does natively on TPU."""
    b, h, s, d = q.shape
    scale = 1.0 / (d ** 0.5)
    chunk = min(chunk, s)
    if _INNER_UNROLL and s // chunk > 16:
        chunk = -(-s // 16)           # bound the measurement unroll
        while s % chunk != 0:
            chunk += 1
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    qf = q.astype(jnp.float32) * scale
    kc = k.reshape(b, h, n_chunks, chunk, d)
    vc = v.reshape(b, h, n_chunks, chunk, d)
    kc = jnp.moveaxis(kc, 2, 0)  # [n, B, H, chunk, D]
    vc = jnp.moveaxis(vc, 2, 0)
    q_ids = jnp.arange(s)

    @jax.checkpoint
    def step(carry, inputs):
        m, l, acc = carry
        ci, kb, vb = inputs
        k_ids = ci * chunk + jnp.arange(chunk)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kb.astype(jnp.float32))
        mask = jnp.ones((s, chunk), dtype=bool)
        if causal:
            mask &= k_ids[None, :] <= q_ids[:, None]
        if window is not None:
            mask &= k_ids[None, :] > q_ids[:, None] - window
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(mask[None, None], logits - safe_m[..., None],
                              -jnp.inf))
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        if probs_bf16:
            pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(jnp.bfloat16),
                            vb.astype(jnp.bfloat16)).astype(jnp.float32)
        else:
            pv = jnp.einsum("bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    acc0 = jnp.zeros((b, h, s, d), jnp.float32)
    xs = (jnp.arange(n_chunks), kc, vc)
    if _INNER_UNROLL:
        carry = (m0, l0, acc0)
        for i in range(n_chunks):
            carry, _ = step(carry, jax.tree.map(lambda a: a[i], xs))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), xs)
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------- #
# sequence-parallel flash decode (beyond-paper optimization)
#
# GQA archs whose kv-head count doesn't divide the model axis keep their
# KV cache *sequence*-sharded (sharding/rules._kv_cache_spec).  A naive
# decode then all-gathers the whole cache every token (~1 GB/layer on
# yi-34b).  Here each model rank computes flash partials (m, l, acc)
# over its local cache shard and the ranks combine with a log-sum-exp
# merge: one [B, H, D]-sized psum (~0.2 MB) instead of the gather.
# ---------------------------------------------------------------------- #
def flash_decode(q: jax.Array, ck: jax.Array, cv: jax.Array,
                 kv_len: jax.Array, mesh, dp_spec) -> jax.Array:
    """q: [B, 1, H, D] (replicated over model); ck/cv: [B, S, KV, D]
    sequence-sharded over 'model'.  Returns [B, 1, H, D]."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    b, _, h, d = q.shape
    kvh = ck.shape[2]
    group = h // kvh
    n_ranks = dict(mesh.shape)["model"]
    s_local = ck.shape[1] // n_ranks

    def body(q_l, k_l, v_l, kv_len_l):
        rank = jax.lax.axis_index("model")
        offset = rank * s_local
        kt = _repeat_kv(jnp.moveaxis(k_l, 1, 2), group)   # [B, H, S_l, D]
        vt = _repeat_kv(jnp.moveaxis(v_l, 1, 2), group)
        qt = jnp.moveaxis(q_l, 1, 2)                      # [B, H, 1, D]
        logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                            preferred_element_type=jnp.float32)
        logits = logits / (d ** 0.5)
        ids = offset + jnp.arange(s_local)
        mask = (ids < kv_len_l)[None, None, None, :]
        logits = jnp.where(mask, logits, -jnp.inf)
        m_l = jnp.max(logits, axis=-1)                    # [B, H, 1]
        m_g = jax.lax.pmax(m_l, "model")
        safe = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
        p = jnp.exp(jnp.where(mask, logits - safe[..., None], -jnp.inf))
        l_l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bhqk,bhkd->bhqd", p, vt.astype(jnp.float32))
        l_g = jax.lax.psum(l_l, "model")
        acc_g = jax.lax.psum(acc, "model")
        l_g = jnp.where(l_g == 0.0, 1.0, l_g)
        out = (acc_g / l_g[..., None]).astype(q_l.dtype)
        return jnp.moveaxis(out, 1, 2)                    # [B, 1, H, D]

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec, None, None, None),
                  P(dp_spec, "model", None, None),
                  P(dp_spec, "model", None, None),
                  P()),
        out_specs=P(dp_spec, None, None, None),
        check_vma=False)
    return fn(q, ck, cv, jnp.asarray(kv_len, jnp.int32))


def use_flash_decode(b: int, sq: int, skv: int, kvh: int):
    """(mesh, dp_spec) when the seq-parallel decode path applies."""
    mesh = _ambient_mesh()
    if mesh is None or sq != 1 or "model" not in mesh.axis_names:
        return None
    sizes = dict(mesh.shape)
    m = sizes["model"]
    if kvh % m == 0 or skv % m != 0:
        return None   # head-sharded caches take the regular path
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_n = 1
    for a in dp:
        dp_n *= sizes[a]
    if dp and b % dp_n != 0:
        dp = ()
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    return mesh, dp_spec


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              q_offset: int | jax.Array = 0,
              kv_len: Optional[jax.Array] = None,
              probs_bf16: bool = False) -> jax.Array:
    """GQA attention.  q: [B, Sq, H, D]; k/v: [B, Skv, Hkv, D].
    Returns [B, Sq, H, D]."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qt = jnp.moveaxis(q, 1, 2)                       # [B, H, Sq, D]
    kt = _repeat_kv(jnp.moveaxis(k, 1, 2), group)    # [B, H, Skv, D]
    vt = _repeat_kv(jnp.moveaxis(v, 1, 2), group)
    # batch over DP, heads over TP; head_dim/seq stay unsharded so the
    # QK^T contraction never partial-sums (no logits all-reduce)
    qt = shard_hint(qt, "dp", "model", None, None)
    kt = shard_hint(kt, "dp", "model", None, None)
    vt = shard_hint(vt, "dp", "model", None, None)
    skv = kt.shape[2]
    if sq == skv and sq > _CHUNK_THRESHOLD and kv_len is None:
        out = _chunked_attention(qt, kt, vt, causal, window,
                                 probs_bf16=probs_bf16)
    else:
        out = _direct_attention(qt, kt, vt, causal, window,
                                q_offset=q_offset, kv_len=kv_len,
                                probs_bf16=probs_bf16)
    return jnp.moveaxis(out, 1, 2)


# ---------------------------------------------------------------------- #
# fused tensor-parallel down-projection (opt-in)
#
# The TP down-projection contracts the model-sharded hidden dim, so its
# natural lowering is a partial-sum + psum.  With fusion enabled the
# psum is decomposed into reduce-scatter + allgather and the RS rides
# the engine's fused matmul+reduce-scatter executor
# (kernels/fused_matmul_rs.py): GEMM row blocks feed the ring as they
# complete instead of serializing MXU time behind wire time.
# ---------------------------------------------------------------------- #
_FUSED_TP = False


def set_fused_tp(value: bool) -> None:
    """Enable/disable the fused TP down-projection (launchers flip this
    under ``--fused``; a no-op unless the mesh has a model axis > 1 and
    the shapes tile the ring)."""
    global _FUSED_TP
    _FUSED_TP = bool(value)


def fused_tp_enabled() -> bool:
    return _FUSED_TP


def _fused_tp_applicable(mesh, h: jax.Array) -> bool:
    sizes = dict(mesh.shape)
    pm = sizes.get("model", 1)
    if pm <= 1:
        return False
    b, s, f = h.shape
    if f % pm:
        return False
    n_dp = 1
    for a in ("pod", "data"):
        n_dp *= sizes.get(a, 1)
    if b % n_dp:
        return False
    return ((b // n_dp) * s) % pm == 0


def _fused_down_proj(h: jax.Array, w_down: jax.Array, mesh) -> jax.Array:
    """``psum(h @ w_down)`` over 'model' as reduce-scatter + allgather,
    the RS fused with the GEMM ring."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.collectives.api import get_engine

    eng = get_engine()
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp_spec = (dp if len(dp) > 1 else dp[0]) if dp else None
    b, s, _ = h.shape
    d = w_down.shape[-1]

    def body(h_l, w_l):
        bl = h_l.shape[0]
        x2 = h_l.reshape(bl * s, h_l.shape[-1])
        y_s = eng.fused_matmul_reduce_scatter(x2, w_l, "model")
        y = eng.allgather_inside(y_s, "model")
        return y.reshape(bl, s, d)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(dp_spec, None, "model"), P("model", None)),
                   out_specs=P(dp_spec, None, None), check_vma=False)
    return fn(h, w_down)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    # hidden is TP-sharded; forces FSDP weight-gather over data instead of
    # partial-sum all-reducing [B,S,F] activations over the data axis
    g = shard_hint(x @ w_gate, "dp", None, "model")
    u = shard_hint(x @ w_up, "dp", None, "model")
    h = jax.nn.silu(g) * u
    if _FUSED_TP:
        mesh = _ambient_mesh()
        if mesh is not None and _fused_tp_applicable(mesh, h):
            return _fused_down_proj(h, w_down, mesh)
    return shard_hint(h @ w_down, "dp", None, None)


def gelu_mlp(x: jax.Array, w_up: jax.Array, b_up: jax.Array,
             w_down: jax.Array, b_down: jax.Array) -> jax.Array:
    h = jax.nn.gelu(x @ w_up + b_up, approximate=True)
    return h @ w_down + b_down


__all__ = ["rms_norm", "apply_rope", "attention", "swiglu", "gelu_mlp",
           "set_fused_tp", "fused_tp_enabled"]
