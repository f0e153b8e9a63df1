"""The rest of a run, with the chip look skipped, on a tiny cell: a
sound program is correct, and the timed path broken underneath makes
``correct`` false, once for each fault a serving cell can have."""

import pytest

from bench import faults, harness
from bench.tests.tiny import run_tiny, tiny_conf, tiny_traffic


def broken(monkeypatch, fault):
    build = harness.build_server

    def build_broken(cfg, params, server_conf, devices):
        server = build(cfg, params, server_conf, devices)
        faults.plant(server, fault)
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
