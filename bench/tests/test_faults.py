"""The rest of a run, with the chip look skipped, on a tiny cell: a
sound program is correct, and the timed path broken underneath makes
``correct`` false, once for each fault a serving cell can have."""

import pytest

from bench import harness
from bench.tests.tiny import run_tiny, tiny_conf, tiny_traffic


def broken(monkeypatch, fault):
    build = harness.build_server

    def build_broken(cfg, params, server_conf):
        server = build(cfg, params, server_conf)
        if fault == "token_altered":
            greedy = server._greedy_fn
            vocab = cfg.vocab_size
            server._greedy_fn = lambda lg: (greedy(lg) + 1) % vocab
        elif fault == "state_unchanged":
            decode = server._decode_fn

            def decode_unchanged(params, pages, *args):
                logits, _ = decode(params, pages, *args)
                return logits, pages  # the KV writes of the step dropped

            server._decode_fn = decode_unchanged
        return server

    monkeypatch.setattr(harness, "build_server", build_broken)


@pytest.mark.parametrize("traffic", ["chat", "long-doc"])
def test_sound_program_is_correct(monkeypatch, traffic):
    res = run_tiny(tiny_conf(), tiny_traffic(traffic), monkeypatch=monkeypatch)
    assert res["correct"] is True
    assert res["checks"]["tokens_compared"]["value"] >= 32
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_fault_is_not_correct(monkeypatch, fault):
    broken(monkeypatch, fault)
    res = run_tiny(tiny_conf(), tiny_traffic("chat"), monkeypatch=monkeypatch)
    assert res["correct"] is False
    assert res["checks"]["widest_logit_gap"]["value"] > res["checks"]["widest_logit_gap"]["limit"]
