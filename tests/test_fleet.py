"""Multi-replica serving fleet: router policies, admission control,
load signals, and the fleet determinism contract.

Single-server scheduler/cache behavior lives in tests/test_serving.py;
this file covers the layer above -- N replicas in lockstep waves behind
a telemetry-driven router.
"""

import math

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.launch.roofline import V5E, device_peaks
from repro.models import init_params
from repro.serving import ContinuousBatchingServer, Request
from repro.serving.fleet import (AdmissionConfig, AdmissionController,
                                 CostRouter, FleetServer, LoadSignal,
                                 REJECT_QUEUE_FULL, REJECT_RATE_LIMITED,
                                 ROUTER_POLICIES, Replica, arrival_waves,
                                 export_fleet_stats, make_router)

TINY = ArchConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                  num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                  dtype="float32")


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(jax.random.PRNGKey(0), TINY)


def _fleet(params, n_replicas=2, **kw):
    kw.setdefault("batch_size", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    if kw.get("router") == "cost":
        # the CPU has no published peak: price at the v5e rate
        kw["router"] = CostRouter(TINY, peak_flops=V5E.flops)
    return FleetServer(TINY, params, n_replicas, **kw)


def _req(rid, prompt_len=8, max_new=4, rng_seed=None, **kw):
    rng = np.random.default_rng(rid if rng_seed is None else rng_seed)
    return Request(rid=rid,
                   prompt=rng.integers(0, TINY.vocab_size,
                                       prompt_len).astype(np.int32),
                   max_new_tokens=max_new, **kw)


def _signal(replica=0, queue_depth=0, queued=0, inflight=0, active=0):
    return LoadSignal(replica=replica, queue_depth=queue_depth,
                      active=active, running=active,
                      queued_prefill_tokens=queued,
                      inflight_prefill_tokens=inflight,
                      kv_blocks_live=0, kv_blocks_evictable=0,
                      kv_blocks_free=8, ttft_ewma_s=None,
                      queue_wait_p50_ms=None)


# ------------------------------ routers ------------------------------- #
def test_round_robin_cycles():
    r = make_router("round_robin")
    sigs = [_signal(i) for i in range(3)]
    got = [r.route(_req(i), [None] * 3, sigs) for i in range(7)]
    assert got == [0, 1, 2, 0, 1, 2, 0]


def test_least_queue_picks_least_committed_prefill():
    r = make_router("least_queue")
    sigs = [_signal(0, queued=40), _signal(1, queued=8, inflight=8),
            _signal(2, queued=24)]
    assert r.route(_req(0), [None] * 3, sigs) == 1
    # tie on pending prefill -> backlog, then lowest index
    sigs = [_signal(0, queued=8), _signal(1, queued=8)]
    assert r.route(_req(1), [None] * 2, sigs) == 0


def test_make_router_rejects_unknown_and_cost_needs_cfg():
    with pytest.raises(ValueError):
        make_router("wishful_thinking")
    with pytest.raises(ValueError):
        make_router("cost")
    assert make_router("cost", TINY,
                       peak_flops=V5E.flops).price_per_token_s > 0


def test_peak_rates_keyed_by_device_kind():
    """The router's rate comes from the published peak of the device
    serving; a device with no published peak is an error, not a
    default."""
    assert device_peaks("TPU v5 lite") is V5E
    with pytest.raises(LookupError):
        device_peaks("cpu")
    with pytest.raises(LookupError):
        make_router("cost", TINY)            # the CPU has no peak rate
    assert math.isclose(
        CostRouter(TINY, peak_flops=2.0).price_per_token_s,
        TINY.active_param_count())


def test_cost_router_prices_uncached_suffix(tiny_params):
    """The cost router must prefer the replica whose prefix cache
    already holds the prompt's blocks (smaller uncached suffix)."""
    fleet = _fleet(tiny_params, n_replicas=2, router="cost",
                   num_blocks=32, prefix_cache=True)
    warm, cold = fleet.replicas
    prompt = _req(0, prompt_len=12).prompt
    warm.submit(Request(rid=100, prompt=prompt.copy(), max_new_tokens=2))
    while warm.has_work():
        warm.step()
    assert warm.predicted_cached_tokens(prompt) > 0
    assert cold.predicted_cached_tokens(prompt) == 0

    router = fleet.router
    req = Request(rid=101, prompt=prompt.copy(), max_new_tokens=2)
    sigs = [r.load_signal() for r in fleet.replicas]
    assert router.route(req, fleet.replicas, sigs) == 0
    assert router.last_costs[0] < router.last_costs[1]
    # modeled cost is roofline-priced seconds of prefill compute
    expected = router.price_per_token_s * (
        len(prompt) - warm.predicted_cached_tokens(prompt))
    assert math.isclose(router.last_costs[0], expected)


def test_prefix_affinity_pins_before_first_insertion(tiny_params):
    """A burst of same-prefix requests must all land on one replica
    even though the first is still queued (nothing cached yet) -- the
    pin is recorded at routing time, not at cache-insertion time."""
    fleet = _fleet(tiny_params, n_replicas=2, router="prefix_affinity",
                   num_blocks=32, prefix_cache=True)
    shared = _req(0, prompt_len=8).prompt
    for rid in range(4):
        req = Request(rid=rid, prompt=shared.copy(), max_new_tokens=2)
        assert fleet.submit(req, tenant="t0") is None
    assert fleet.routed in ([4, 0], [0, 4])


def test_prefix_affinity_separates_tenants(tiny_params):
    """Distinct prefixes spread over replicas by least committed work
    instead of stacking on one."""
    fleet = _fleet(tiny_params, n_replicas=2, router="prefix_affinity",
                   num_blocks=32, prefix_cache=True)
    for rid in range(4):
        fleet.submit(_req(rid, prompt_len=8), tenant=f"t{rid}")
    assert sorted(fleet.routed) == [2, 2]


# ----------------------------- admission ------------------------------ #
def test_admission_queue_cap_rejects_with_retry_hint():
    ctl = AdmissionController(AdmissionConfig(queue_cap=2))
    assert ctl.admit(_req(0), "a", fleet_queue_depth=1, wave=0) is None
    rej = ctl.admit(_req(1), "a", fleet_queue_depth=2, wave=3)
    assert rej is not None and rej.reason == REJECT_QUEUE_FULL
    assert rej.retry_after_waves == 1 and rej.wave == 3
    deeper = ctl.admit(_req(2), "a", fleet_queue_depth=5, wave=4)
    assert deeper.retry_after_waves == 4, "hint scales with overflow"
    assert (ctl.admitted, ctl.rejected) == (1, 2)
    assert ctl.rejected_below_cap == 0


def test_admission_token_bucket_isolates_tenants():
    # burst = 2x rate of 20 tokens/wave; each request costs 8 + 4 = 12
    ctl = AdmissionController(AdmissionConfig(tenant_rate=20.0,
                                              tenant_burst=40.0))
    assert ctl.admit(_req(0), "hog", fleet_queue_depth=0, wave=0) is None
    assert ctl.admit(_req(1), "hog", fleet_queue_depth=0, wave=0) is None
    assert ctl.admit(_req(2), "hog", fleet_queue_depth=0, wave=0) is None
    rej = ctl.admit(_req(3), "hog", fleet_queue_depth=0, wave=0)
    assert rej is not None and rej.reason == REJECT_RATE_LIMITED
    assert rej.retry_after_waves >= 1
    # a different tenant is untouched by the hog's empty bucket
    assert ctl.admit(_req(4), "quiet", fleet_queue_depth=0, wave=0) is None
    # the hog's bucket refills with the wave clock
    later = rej.wave + rej.retry_after_waves
    assert ctl.admit(_req(5), "hog", fleet_queue_depth=0,
                     wave=later) is None


def test_admission_uncapped_admits_everything():
    ctl = AdmissionController(AdmissionConfig())
    for rid in range(32):
        assert ctl.admit(_req(rid), "t", fleet_queue_depth=rid,
                         wave=rid) is None
    assert ctl.rejected == 0


# ---------------------------- load signals ---------------------------- #
def test_load_signal_tracks_queue_and_inflight(tiny_params):
    srv = ContinuousBatchingServer(TINY, tiny_params, batch_size=1,
                                   max_len=32, block_size=4,
                                   prefill_chunk=4, num_blocks=32)
    rep = Replica(0, srv)
    sig = rep.load_signal()
    assert (sig.queue_depth, sig.active, sig.pending_prefill_tokens) == \
        (0, 0, 0)
    rep.submit(_req(0, prompt_len=8, max_new=4))
    rep.submit(_req(1, prompt_len=12, max_new=4))
    sig = rep.load_signal()     # batch of 1: second request queued
    assert sig.backlog == 2
    assert sig.pending_prefill_tokens == 20
    rep.step()                  # admit + first prefill chunk of req 0
    sig = rep.load_signal()
    assert sig.active == 1 and sig.queue_depth == 1
    assert sig.inflight_prefill_tokens == 4     # 8-token prompt, chunk 4
    assert sig.queued_prefill_tokens == 12
    assert sig.kv_blocks_live > 0
    while rep.has_work():
        rep.step()
    sig = rep.load_signal()
    assert sig.ttft_ewma_s is not None and sig.ttft_ewma_s > 0
    assert sig.queue_wait_p50_ms is not None


# ------------------------------- fleet -------------------------------- #
@pytest.mark.parametrize("policy", ROUTER_POLICIES)
def test_fleet_matches_single_server_bitwise(tiny_params, policy):
    """The determinism contract: greedy token streams are bitwise
    identical between --replicas 1 and --replicas 3 under every
    routing policy."""
    def serve(n):
        fleet = _fleet(tiny_params, n_replicas=n, router=policy,
                       num_blocks=32, prefix_cache=True)
        for rid in range(6):
            assert fleet.submit(_req(rid, max_new=4),
                                tenant=f"t{rid % 2}") is None
        return fleet.run()

    single, multi = serve(1), serve(3)
    assert single == multi
    assert all(len(v) == 4 for v in multi.values())


def test_fleet_run_trace_respects_arrival_waves(tiny_params):
    fleet = _fleet(tiny_params, n_replicas=2, num_blocks=32)
    arrivals = [(0, "a", _req(0, max_new=2)),
                (4, "b", _req(1, max_new=2))]
    results, rejections = fleet.run_trace(arrivals)
    assert rejections == []
    assert sorted(results) == [0, 1]
    snap = fleet.snapshot()
    assert snap.waves >= 5, "late arrival must not be served early"
    assert snap.admitted == 2 and snap.tokens_out == 4


def test_fleet_capped_trace_sheds_only_above_cap(tiny_params):
    """A same-wave burst over a tight cap sheds the overflow -- with
    retry-after hints and zero rejects below the cap."""
    fleet = _fleet(tiny_params, n_replicas=2, num_blocks=32,
                   admission=AdmissionConfig(queue_cap=2))
    arrivals = [(0, "t", _req(rid, max_new=2)) for rid in range(8)]
    results, rejections = fleet.run_trace(arrivals)
    snap = fleet.snapshot()
    assert snap.rejected == len(rejections) > 0
    assert snap.rejected_below_cap == 0
    assert all(r.reason == REJECT_QUEUE_FULL and r.retry_after_waves >= 1
               for r in rejections)
    served = {rid for rid in range(8)} - {r.rid for r in rejections}
    assert set(results) == served
    assert all(len(results[rid]) == 2 for rid in served)


def test_fleet_affinity_beats_round_robin_on_cached_fraction(tiny_params):
    """The tentpole headline at test scale: with K tenants sharing
    prompts, affinity pays each cold prefix once fleet-wide while
    round-robin pays it once per replica."""
    shared = [_req(t, prompt_len=8).prompt for t in range(2)]
    # tenants arrive in runs, not alternating: round-robin's rid parity
    # then splits every tenant across both replicas (each pays both
    # cold prefixes) while affinity pins each prefix to one replica
    tenant_of = [0, 0, 0, 0, 1, 1, 1, 1]

    def serve(policy):
        fleet = _fleet(tiny_params, n_replicas=2, router=policy,
                       num_blocks=32, prefix_cache=True)
        for rid in range(8):
            t = tenant_of[rid]
            req = Request(rid=rid, prompt=shared[t].copy(),
                          max_new_tokens=2)
            fleet.submit(req, tenant=f"t{t}")
        results = fleet.run()
        assert all(len(v) == 2 for v in results.values())
        return fleet.snapshot()

    rr, aff = serve("round_robin"), serve("prefix_affinity")
    assert aff.cached_token_fraction > rr.cached_token_fraction
    assert aff.prefill_tokens_computed < rr.prefill_tokens_computed


def test_fleet_export_and_arrival_modes(tiny_params):
    from repro.obs.registry import MetricsRegistry, validate_export
    fleet = _fleet(tiny_params, n_replicas=2, num_blocks=32,
                   admission=AdmissionConfig(queue_cap=1))
    for rid in range(4):
        fleet.submit(_req(rid, max_new=2), tenant='quo"ted\ntenant')
    fleet.run()
    reg = MetricsRegistry()
    export_fleet_stats(fleet, reg)
    blob = reg.export_json()
    assert validate_export(blob) == []
    gauges = blob["gauges"]
    assert "fleet_waves" in gauges
    assert any(k.startswith("fleet_rejected_by_tenant{") for k in gauges)
    assert any(k.startswith("fleet_routed{replica=") for k in gauges)
    # the tenant label with quotes/newline survives text exposition
    assert "fleet_rejected_by_tenant" in reg.export_prometheus()

    # arrival generator: fixed is wave-0, modes are seeded + monotone
    assert arrival_waves(5, "fixed") == [0] * 5
    for mode in ("poisson", "bursty"):
        a = arrival_waves(50, mode, rng=np.random.default_rng(7), rate=2.0)
        b = arrival_waves(50, mode, rng=np.random.default_rng(7), rate=2.0)
        assert a == b and len(a) == 50
        assert all(x <= y for x, y in zip(a, a[1:]))
    with pytest.raises(ValueError):
        arrival_waves(5, "poisson")     # rng required
    with pytest.raises(ValueError):
        arrival_waves(5, "fractal", rng=np.random.default_rng(0))
