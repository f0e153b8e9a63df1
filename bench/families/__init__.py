"""Model code found by name.

Everything the benchmark knows of a model's equations lives in one
family module, ``bench/families/<family>.py``; a configuration file
names its family by the key ``family``, and a file without the key is
served by ``dense`` (llama-style decoders).  A new architecture is a
new family file and a configuration naming it, never an edit here or
in the harness.  A family module provides:

* ``arch_config(conf)``: the program's ``ArchConfig`` for the file;
* ``make_weights(conf, seed, sharding=None)``: the program's parameter
  tree, made on the device in one jitted call from the seed;
* ``factors(conf)``: the constants the reference needs to read those
  weights in the model's own terms (hashable);
* ``logits(weights, conf, fx, tokens, start, k, control=False)``: the
  plain reference, float32 at ``Precision.HIGHEST``, with its fp8
  control; ``tokens`` are padded to a multiple of 1024;
* the counts: ``flops_per_token(conf)``, ``attention_flops(conf,
  positions)`` (all layers, over the positions attended) and
  ``paged_kernel_cost(conf, lengths, block_size)`` (FLOPs and HBM
  bytes of one decode step of the paged kernel).
"""

from __future__ import annotations

import functools
import importlib.util
import os
import sys
from typing import Dict

FAMILIES_DIR = os.path.dirname(os.path.abspath(__file__))
DEFAULT = "dense"


@functools.lru_cache(maxsize=None)
def _load(path: str, name: str):
    modname = f"bench.families.{name}"
    known = sys.modules.get(modname)
    if known is not None and getattr(known, "__file__", None) == path:
        return known  # imported as a package module already
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault(modname, mod)
    spec.loader.exec_module(mod)
    return mod


def of(conf: Dict):
    """The family module of configuration ``conf`` (loaded once per
    process and directory)."""
    name = conf.get("family", DEFAULT)
    path = os.path.join(FAMILIES_DIR, f"{name}.py")
    if not os.path.exists(path):
        raise LookupError(f"{conf.get('name')}: no family module {path}")
    return _load(path, name)


def check_layout(params, cfg) -> None:
    """Raise unless ``params`` has the tree and shapes of the program's
    own ``init_params`` for ``cfg``."""
    import jax
    from repro.models.transformer import param_specs

    want = jax.tree.map(lambda s: (s.shape, s.dtype), param_specs(cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError(f"benchmark weights do not match the program's layout:\n{got}\n!=\n{want}")
