"""The default family: llama-style dense decoders (MiniCPM, Mistral).
A configuration file without a ``family`` key is served, checked and
counted by this module (see ``bench/families/__init__.py``).

**Weights.**  The configuration files hold the published
``config.json`` keys of the model.  The program's layer equations are a
llama-style decoder; the published models add constant factors
(MiniCPM's ``scale_emb``, ``scale_depth`` and ``dim_model_base``) and
may tie the output head to the embedding.  The benchmark makes the
weights in the published model's own terms and folds those constants
into the tensors it hands the program, the way a checkpoint converter
would:

* embedding: the program multiplies by ``bf16(sqrt(d_model))``, the
  model by ``scale_emb`` (1 where the config has none);
* norm gains: the program applies ``1 + w``;
* residual branches: MiniCPM scales each by ``scale_depth /
  sqrt(num_hidden_layers)``, folded into ``wo`` and ``wd``;
* head: MiniCPM divides the logits by ``hidden_size /
  dim_model_base`` and ties the head to the embedding.

The reference undoes the folding exactly (factors only), so it computes
the published equations from the very numbers the program serves.
(The ``notes`` of ``bench/configs/minicpm-2b.json`` name
``bench/model.py``, where this code lived before: it is this module.)

**The plain reference** (``logits``): the published decoder's forward
pass in straightforward ``jax.numpy``, float32 at
``Precision.HIGHEST``, with no kernel, cache or batching.  It imports
nothing of the program.  ``control=True`` computes the same pass with
every weight matrix product in float8 (e4m3): activations rounded per
row and weights per output column, the lower precision a later change
might be tempted to serve, used only to show that the comparison fails
it.  The sequence runs layer by layer (a scan that casts one layer's
weights to float32 at a time) and attention runs in blocks of query
rows, so a long sequence fits beside the weights.

**Counts**, computed from shapes, nothing read from the program.  A
token's model FLOPs are 2 per weight it multiplies (every layer's
projections and MLP, and the output head), plus attention: 4 * heads *
head_dim per position attended in each layer (scores and the weighted
sum of values).  Embedding lookups, norms and rotary terms are left out.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: a token enters the residual stream at this size, below what the
#: layers add: with a tied head a token that dominated its own residual
#: would be its own best successor, and greedy decoding would repeat it
#: whatever the context held (and a served token's margin would be too
#: wide for any lower precision to change it)
EMB_RMS = 0.02


@dataclasses.dataclass(frozen=True)
class Factors:
    """The published model's constant factors, and the program's."""
    emb_program: float    # what the program multiplies embeddings by
    emb_model: float      # scale_emb
    depth: float          # residual branch scale
    logit_div: float      # logits are divided by this
    tied: bool


def factors(conf: Dict) -> Factors:
    d = conf["hidden_size"]
    depth = 1.0
    if "scale_depth" in conf:
        depth = conf["scale_depth"] / math.sqrt(conf["num_hidden_layers"])
    logit_div = d / conf["dim_model_base"] if "dim_model_base" in conf else 1.0
    return Factors(
        emb_program=float(jnp.asarray(d**0.5, jnp.bfloat16)),
        emb_model=float(conf.get("scale_emb", 1.0)),
        depth=depth,
        logit_div=logit_div,
        tied=bool(conf.get("tie_word_embeddings", False)),
    )


def head_dim(conf: Dict) -> int:
    return conf.get("head_dim") or conf["hidden_size"] // conf["num_attention_heads"]


def arch_config(conf: Dict):
    """The program's ``ArchConfig`` for this configuration file: the
    registry entry ``conf['arch']`` with every size the file states."""
    from repro.configs import get_config

    base = get_config(conf["arch"])
    cfg = dataclasses.replace(
        base,
        num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=head_dim(conf),
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        dtype=conf["torch_dtype"],
    )
    if cfg.family != "dense":
        raise ValueError(f"{conf['name']}: the benchmark's weights cover dense decoders, not {cfg.family!r}")
    return cfg


def weight_shapes(conf: Dict) -> Dict:
    """Shapes of the program's parameter tree for this configuration."""
    d, f, v = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    h, kv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], head_dim(conf)
    n = conf["num_hidden_layers"]
    return {
        "embed": (v, d),
        "ln_f": (d,),
        "head": (d, v),
        "layers": {
            "ln1": (n, d),
            "ln2": (n, d),
            "wq": (n, d, h * hd),
            "wk": (n, d, kv * hd),
            "wv": (n, d, kv * hd),
            "wo": (n, h * hd, d),
            "wg": (n, d, f),
            "wu": (n, d, f),
            "wd": (n, f, d),
        },
    }


def make_weights(conf: Dict, seed: int, sharding=None):
    """The program's parameters, made on the device in one jitted call
    from ``seed``, in bf16, placed by ``sharding`` (the default device
    where None).  In the model's own terms every matrix is
    N(0, 1/fan_in), the embedding N(0, (EMB_RMS/scale_emb)^2) and each norm gain
    1 + N(0, 0.1^2); the factors of ``factors(conf)`` are then folded
    in (see the module docstring)."""
    fx = factors(conf)
    shapes = weight_shapes(conf)
    dtype = jnp.bfloat16

    def build(key):
        keys = iter(jax.random.split(key, 16))
        normal = lambda shape: jax.random.normal(next(keys), shape, jnp.float32)
        mat = lambda shape, scale=1.0: (normal(shape) * (scale / math.sqrt(shape[-2]))).astype(dtype)
        gain = lambda shape: (0.1 * normal(shape)).astype(dtype)  # program stores gain - 1
        emb = normal(shapes["embed"]) * (EMB_RMS / fx.emb_model)
        lay = shapes["layers"]
        if fx.tied:
            head = emb.T
        else:
            head = normal(shapes["head"]) / math.sqrt(conf["hidden_size"])
        return {
            "embed": (emb * (fx.emb_model / fx.emb_program)).astype(dtype),
            "ln_f": gain(shapes["ln_f"]),
            "head": (head / fx.logit_div).astype(dtype),
            "layers": {
                "ln1": gain(lay["ln1"]),
                "ln2": gain(lay["ln2"]),
                "wq": mat(lay["wq"]),
                "wk": mat(lay["wk"]),
                "wv": mat(lay["wv"]),
                "wo": mat(lay["wo"], fx.depth),
                "wg": mat(lay["wg"]),
                "wu": mat(lay["wu"]),
                "wd": mat(lay["wd"], fx.depth),
            },
        }

    key = jax.random.PRNGKey(np.uint32(seed % 2**32))
    built = jax.jit(build) if sharding is None else jax.jit(build, out_shardings=sharding)
    return built(key)


# ---------------------------------------------------------------------- #
# the plain reference

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


# ---------------------------------------------------------------------- #
# counts


def layer_matmul_params(conf: Dict) -> int:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    h, kv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], head_dim(conf)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def flops_per_token(conf: Dict) -> int:
    """2 x (every layer's matmul weights + the head)."""
    return 2 * (conf["num_hidden_layers"] * layer_matmul_params(conf) + conf["hidden_size"] * conf["vocab_size"])


def attention_flops(conf: Dict, positions: int) -> int:
    """Scores and value sums over ``positions`` attended, all layers."""
    return 4 * conf["num_attention_heads"] * head_dim(conf) * positions * conf["num_hidden_layers"]


def paged_kernel_cost(conf: Dict, lengths: Iterable[int], block_size: int, dtype_bytes: int = 2) -> Tuple[int, int]:
    """(FLOPs, HBM bytes) the paged decode kernel needs for one decode
    step over all layers: per row of attended length ``c``, 4 * H * D *
    c FLOPs, the K and V of its ceil(c / block_size) blocks, and its
    query and output rows."""
    h, kv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], head_dim(conf)
    layers = conf["num_hidden_layers"]
    flops = nbytes = 0
    for c in lengths:
        blocks = -(-c // block_size)
        flops += 4 * h * hd * c
        nbytes += 2 * blocks * block_size * kv * hd * dtype_bytes + 2 * h * hd * dtype_bytes
    return flops * layers, nbytes * layers
