"""The plain reference: the published decoder's forward pass in
straightforward ``jax.numpy``, float32 at ``Precision.HIGHEST``, with no
kernel, cache or batching.  It imports nothing of the program.

It reads the weights the benchmark made (``bench/model.py``) and turns
them back into the model's own terms with the configuration's factors.
``control=True`` computes the same pass with every weight matrix
product in float8 (e4m3): activations rounded per row and weights per
output column, the lower precision a later change might be tempted to
serve, used only to show that the comparison fails it.

The sequence runs layer by layer (a scan that casts one layer's weights
to float32 at a time) and attention runs in blocks of query rows, so a
long sequence fits beside the weights.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.model import Factors, head_dim

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
VOCAB_CHUNKS = 8


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def fp8_round(x: jax.Array, axis: int) -> jax.Array:
    """float8 e4m3 with one scale per slice (the max over ``axis`` maps
    to e4m3's largest value, 448), back in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _qmm(a, w, control: bool):
    """``a @ w``; the control rounds ``a`` per row and ``w`` per output
    column to float8 e4m3 first (an fp8 x fp8 matmul with float32
    scales and accumulation)."""
    if control:
        return _mm(fp8_round(a, -1), fp8_round(w, -2))
    return _mm(a, w)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x [S, heads, D]; rotate-half rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def causal_attention(q, k, v):
    """q [S, H, D], k/v [S, Hkv, D] -> [S, H, D], causal, softmax in
    float32, in blocks of ``Q_BLOCK`` query rows."""
    s, h, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, hkv, g, d)

    def block(args):
        i, qi = args
        sc = jnp.einsum("qkgd,skd->kgqs", qi, k, precision=HIGHEST) / math.sqrt(d)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        mask = jnp.arange(s)[None, :] <= rows[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(s // Q_BLOCK), qb))
    return out.reshape(s, h, d)


def _layer(conf: Dict, fx: Factors, control: bool, x, lw):
    f32 = lambda a: a.astype(jnp.float32)
    mm = lambda a, w: _qmm(a, f32(w), control)
    eps = conf["rms_norm_eps"]
    h, kv, d = conf["num_attention_heads"], conf["num_key_value_heads"], head_dim(conf)
    s = x.shape[0]
    y = rms_norm(x, 1.0 + f32(lw["ln1"]), eps)
    q = rope(mm(y, lw["wq"]).reshape(s, h, d), conf["rope_theta"])
    k = rope(mm(y, lw["wk"]).reshape(s, kv, d), conf["rope_theta"])
    v = mm(y, lw["wv"]).reshape(s, kv, d)
    a = causal_attention(q, k, v).reshape(s, h * d)
    x = x + fx.depth * (mm(a, lw["wo"]) / fx.depth)
    y = rms_norm(x, 1.0 + f32(lw["ln2"]), eps)
    m = jax.nn.silu(mm(y, lw["wg"])) * mm(y, lw["wu"])
    return x + fx.depth * (mm(m, lw["wd"]) / fx.depth), None


@functools.partial(jax.jit, static_argnames=("conf_items", "fx", "control", "k"))
def _logits(weights, tokens, start, *, conf_items, fx, control, k):
    conf = dict(conf_items)
    emb = weights["embed"].astype(jnp.float32) * (fx.emb_program / fx.emb_model)
    x = emb[tokens] * fx.emb_model
    x, _ = jax.lax.scan(functools.partial(_layer, conf, fx, control), x, weights["layers"])
    x = jax.lax.dynamic_slice_in_dim(x, start, k, axis=0)
    x = rms_norm(x, 1.0 + weights["ln_f"].astype(jnp.float32), conf["rms_norm_eps"])
    head = weights["head"]
    v = head.shape[1]
    step = -(-v // VOCAB_CHUNKS)
    parts = []
    for c in range(0, v, step):  # the head in float32, a slice at a time
        w = head[:, c : c + step].astype(jnp.float32) * fx.logit_div
        parts.append(_qmm(x, w, control))
    return jnp.concatenate(parts, axis=1) / fx.logit_div


def _hashable(conf: Dict):
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size", "rms_norm_eps", "rope_theta", "head_dim")
    return tuple((key, conf[key]) for key in keys if key in conf)


def logits(weights, conf: Dict, fx: Factors, tokens, start: int, k: int, control: bool = False):
    """Float32 logits [k, V] at positions start..start+k-1 of the token
    sequence ``tokens`` (padded by the caller to a multiple of
    ``Q_BLOCK``; padding after a position does not change it)."""
    return _logits(weights, tokens, jnp.int32(start), conf_items=_hashable(conf), fx=fx, control=control, k=k)
