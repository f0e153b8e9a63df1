"""The fp8 control fails the comparison that a sound program passes,
at a tiny size on the CPU (the chip readings at the cells' own sizes
are in PERF.md).  The tiny model's limit is the tiny sound runs'
widest gap with room, as a cell's limit is set."""

import gc

import jax
import pytest

from bench import check, families, gen, harness
from bench.tests.tiny import TINY_LIMITS, tiny_conf, tiny_traffic


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_separates(seed):
    conf = tiny_conf(num_hidden_layers=4, hidden_size=128, intermediate_size=256, vocab_size=1024)
    fam = families.of(conf)
    cfg = fam.arch_config(conf)
    weights = fam.make_weights(conf, seed)
    server = harness.build_server(cfg, weights, conf["server"], jax.devices()[:1])
    harness.warm_up(server, cfg.vocab_size, seed)
    traffic = gen.generate(tiny_traffic("chat"), seed, 3.0, cfg.vocab_size)
    w = harness.run_window(server, traffic, 3.0)
    finished = [r for r in w.requests if r.done]
    del server
    gc.collect()
    sample = check.sample_finished(finished, seed, 192, 8)
    cmp = check.compare(weights, conf, sample, TINY_LIMITS["max_served"], control=True)
    limits = dict(TINY_LIMITS, min_tokens=192)
    assert check.verdict(cmp["widest_gap"], cmp["tokens"], limits)[0] is True
    assert check.verdict(cmp["control_widest_gap"], cmp["tokens"], limits)[0] is False
