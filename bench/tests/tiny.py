"""A tiny cell for the CPU tests: minicpm-2b's file with small widths,
and a traffic file's shape at small lengths."""

import copy
import json
import os
import time

from bench import peaks as peaks_mod
from bench import session

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_config(name):
    """``bench/configs/<name>.json``."""
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


TINY_WIDTHS = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=2, vocab_size=256)
TINY_LIMITS = {"widest_logit_gap": 0.006, "min_tokens": 96, "max_requests": 6, "max_served": 64}


def tiny_conf(name="minicpm-2b", **widths):
    conf = copy.deepcopy(load_config(name))
    conf.update(TINY_WIDTHS, **widths)
    conf["server"].update(batch=4, max_len=512, kv_pool_blocks=160, prefill_chunk=32)
    return conf


def tiny_traffic(name):
    with open(os.path.join(BENCH_DIR, "traffic", f"{name}.json")) as f:
        s = json.load(f)
    s.update(rate_rps=3.0, requests=8)
    s["prompt"] = dict(s["prompt"], median=40, min=8, max=200)
    s["output"] = dict(s["output"], median=24, min=8, max=64)
    return s


def run_tiny(conf, spec, metrics=(), seed=5, seconds=3.0, limits=TINY_LIMITS, monkeypatch=None, chips=1):
    """``session.run_cell`` on the CPU over ``chips`` devices: the chip
    look is skipped and the peaks are the v5e's (no number here is a
    device number)."""
    import jax

    if monkeypatch is not None:
        monkeypatch.setattr(session, "peaks", lambda kind: peaks_mod.PEAKS["TPU v5 lite"])
    return session.run_cell(conf, spec, limits, [{"name": m, "unit": "x"} for m in metrics], seed, seconds, False, jax.devices()[:chips], time.monotonic(), "unused", "unused", lambda m: None)
