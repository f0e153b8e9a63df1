"""A configuration file -> the program's ``ArchConfig``, and the
weights the benchmark makes for it.

The configuration files (``bench/configs/<name>.json``) hold the
published ``config.json`` keys of the model.  The program's layer
equations are a llama-style decoder; the published models add constant
factors (MiniCPM's ``scale_emb``, ``scale_depth`` and
``dim_model_base``) and may tie the output head to the embedding.  The
benchmark makes the weights in the published model's own terms and
folds those constants into the tensors it hands the program, the way a
checkpoint converter would:

* embedding: the program multiplies by ``bf16(sqrt(d_model))``, the
  model by ``scale_emb`` (1 where the config has none);
* norm gains: the program applies ``1 + w``;
* residual branches: MiniCPM scales each by ``scale_depth /
  sqrt(num_hidden_layers)``, folded into ``wo`` and ``wd``;
* head: MiniCPM divides the logits by ``hidden_size /
  dim_model_base`` and ties the head to the embedding.

``to_model_terms`` undoes the folding exactly (factors only), so the
reference in ``bench/reference.py`` computes the published equations
from the very numbers the program serves.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Dict

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

#: a token enters the residual stream at this size, below what the
#: layers add: with a tied head a token that dominated its own residual
#: would be its own best successor, and greedy decoding would repeat it
#: whatever the context held (and a served token's margin would be too
#: wide for any lower precision to change it)
EMB_RMS = 0.02


def load_config(name: str) -> Dict:
    """``bench/configs/<name>.json``."""
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Factors:
    """The published model's constant factors, and the program's."""
    emb_program: float    # what the program multiplies embeddings by
    emb_model: float      # scale_emb
    depth: float          # residual branch scale
    logit_div: float      # logits are divided by this
    tied: bool


def factors(conf: Dict) -> Factors:
    import jax.numpy as jnp
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


def make_weights(conf: Dict, seed: int):
    """The program's parameters, made on the device in one jitted call
    from ``seed``, in bf16.  In the model's own terms every matrix is
    N(0, 1/fan_in), the embedding N(0, (EMB_RMS/scale_emb)^2) and each norm gain
    1 + N(0, 0.1^2); the factors of ``factors(conf)`` are then folded
    in (see the module docstring)."""
    import jax
    import jax.numpy as jnp

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
    return jax.jit(build)(key)


def check_layout(params, cfg) -> None:
    """Raise unless ``params`` has the tree and shapes of the program's
    own ``init_params`` for ``cfg``."""
    import jax
    from repro.models.transformer import param_specs

    want = jax.tree.map(lambda s: (s.shape, s.dtype), param_specs(cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError(f"benchmark weights do not match the program's layout:\n{got}\n!=\n{want}")

