"""Fleet benchmark: multi-replica routing + admission under a Zipf
multi-tenant, bursty (Markov-modulated Poisson) arrival trace.

Drives a 2-replica ``FleetServer`` over one wave-stamped trace
(``serve_bench.make_trace(arrival="bursty")``) once per router policy,
plus a capped admission run and a 1-replica determinism reference.
Emits ``BENCH_fleet.json`` with deterministic counters gated by
``bench_gate`` against ``baselines/fleet_small.json``:

* ``affinity_gain``     -- fleet ``cached_token_fraction`` under
  ``prefix_affinity`` minus under ``round_robin``; must stay strictly
  positive (affinity keeps a tenant's blocks on one replica instead of
  recomputing the prefix once per replica).
* ``prefill_imbalance`` -- max/mean per-replica
  ``prefill_tokens_computed`` under ``least_queue``; bounded.
* ``rejected`` / ``rejected_below_cap`` -- uncapped runs shed nothing;
  the capped run sheds only with zero queue headroom left.
* ``determinism_ok``    -- greedy streams bitwise identical between 1
  and 2 replicas under deterministic routing.

CPU-scale shapes; counters track the routing/admission logic, not
hardware throughput (wall time is informational).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.common import emit
from benchmarks.serve_bench import make_trace

#: bound gated on least_queue per-replica prefill-compute imbalance
IMBALANCE_BOUND = 1.5


def _fleet_serve(cfg, params, trace, *, n_replicas, router, batch,
                 max_len, block_size, prefill_chunk, seed, num_blocks,
                 queue_cap=None):
    from repro.launch.roofline import V5E
    from repro.serving import Request
    from repro.serving.fleet import AdmissionConfig, CostRouter, FleetServer

    if router == "cost":
        # counters only: routing is scale-free in the rate, so price at
        # the v5e peak whatever device runs this
        router = CostRouter(cfg, peak_flops=V5E.flops)

    fleet = FleetServer(
        cfg, params, n_replicas, batch, max_len, router=router,
        admission=AdmissionConfig(queue_cap=queue_cap), seed=seed,
        block_size=block_size, prefill_chunk=prefill_chunk,
        num_blocks=num_blocks, prefix_cache=True)
    arrivals = [(tr.arrival_wave, tr.tenant,
                 Request(rid=tr.rid, prompt=tr.prompt.copy(),
                         max_new_tokens=tr.max_new))
                for tr in trace]
    t0 = time.time()
    results, _rejections = fleet.run_trace(arrivals)
    wall = time.time() - t0
    snap = fleet.snapshot()
    counters = {
        "tokens_out": snap.tokens_out,
        "wall_s": wall,
        "waves": snap.waves,
        "decode_steps": sum(r.decode_steps for r in snap.replicas),
        "preemptions": sum(r.preemptions for r in snap.replicas),
        "prefill_tokens_computed": snap.prefill_tokens_computed,
        "cached_prefix_tokens": snap.cached_prefix_tokens,
        "cached_token_fraction": snap.cached_token_fraction,
        "prefix_evictions": sum(r.prefix_evictions for r in snap.replicas),
        "rejected": snap.rejected,
        "rejected_below_cap": snap.rejected_below_cap,
        "per_replica": {
            f"replica_{i}": {
                "routed": snap.routed[i],
                "prefill_tokens_computed":
                    snap.replicas[i].prefill_tokens_computed,
                "queue_depth_max": snap.queue_depth_max[i],
            } for i in range(n_replicas)},
    }
    return results, counters, fleet


def run(arch: str = "minicpm-2b", replicas: int = 2, batch: int = 4,
        requests: int = 24, n_prompts: int = 4, sys_len: int = 48,
        user_len: int = 12, new_tokens: int = 12, block_size: int = 16,
        prefill_chunk: int = 16, queue_cap: int = 6, seed: int = 0):
    import jax
    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config(arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    max_len = sys_len + user_len + new_tokens + block_size
    # per-replica pool sized like serve_bench: tight enough that the
    # evictable LRU works for a living, roomy enough to never deadlock
    blocks_per_seq = -(-max_len // block_size)
    num_blocks = int(2.5 * blocks_per_seq) + 1
    trace, shared_frac = make_trace(
        np.random.default_rng(seed), requests, cfg.vocab_size,
        n_prompts=n_prompts, sys_len=sys_len, user_len=user_len,
        new_tokens=new_tokens, arrival="bursty", arrival_rate=2.0,
        arrival_seed=seed + 1)
    kw = dict(batch=batch, max_len=max_len, block_size=block_size,
              prefill_chunk=prefill_chunk, seed=seed,
              num_blocks=num_blocks)

    policies = {}
    results_by_policy = {}
    for policy in ("round_robin", "least_queue", "cost",
                   "prefix_affinity"):
        res, counters, fleet = _fleet_serve(
            cfg, params, trace, n_replicas=replicas, router=policy, **kw)
        policies[policy] = counters
        results_by_policy[policy] = res
        if policy == "prefix_affinity":
            affinity_fleet = fleet

    # determinism: the same greedy trace on one replica must emit the
    # same streams the 2-replica fleet does under every policy
    res_single, single, _ = _fleet_serve(
        cfg, params, trace, n_replicas=1, router="round_robin", **kw)
    determinism_ok = int(all(res == res_single
                             for res in results_by_policy.values()))

    # admission: burst into a tight fleet queue cap
    _res_cap, capped, _ = _fleet_serve(
        cfg, params, trace, n_replicas=replicas, router="round_robin",
        queue_cap=queue_cap, **kw)

    rr = policies["round_robin"]
    lq = policies["least_queue"]
    per_prefill = [v["prefill_tokens_computed"]
                   for v in lq["per_replica"].values()]
    imbalance = (max(per_prefill) / (sum(per_prefill) / len(per_prefill))
                 if sum(per_prefill) else 1.0)
    gain = (policies["prefix_affinity"]["cached_token_fraction"]
            - rr["cached_token_fraction"])

    from repro.obs.registry import MetricsRegistry
    from repro.serving.fleet import export_fleet_stats
    reg = MetricsRegistry()
    export_fleet_stats(affinity_fleet, reg)
    return {
        "metrics": reg.export_json(),
        "arch": arch,
        "replicas": replicas,
        "requests": requests,
        "n_prompts": n_prompts,
        "queue_cap": queue_cap,
        "shared_token_fraction": shared_frac,
        "policies": policies,
        "capped": capped,
        "single": {k: single[k] for k in ("tokens_out",
                                          "prefill_tokens_computed",
                                          "cached_token_fraction")},
        "affinity_gain": round(gain, 6),
        "prefill_imbalance": round(imbalance, 6),
        "determinism_ok": determinism_ok,
    }


def check(res) -> None:
    """The fleet acceptance contract on the seeded bursty trace."""
    pol = res["policies"]
    # prefix affinity strictly beats replica-oblivious routing on
    # fleet-wide cached-token fraction
    assert res["affinity_gain"] > 0, (
        f"prefix_affinity fraction "
        f"{pol['prefix_affinity']['cached_token_fraction']:.3f} did not "
        f"beat round_robin {pol['round_robin']['cached_token_fraction']:.3f}")
    # least_queue keeps per-replica prefill compute balanced
    assert res["prefill_imbalance"] <= IMBALANCE_BOUND, (
        f"least_queue prefill imbalance {res['prefill_imbalance']:.3f} "
        f"exceeds {IMBALANCE_BOUND}")
    # zero rejects below the cap: uncapped runs shed nothing...
    for name, counters in pol.items():
        assert counters["rejected"] == 0, (name, counters["rejected"])
        assert counters["rejected_below_cap"] == 0
    # ...the capped run sheds, and only with zero queue headroom left
    assert res["capped"]["rejected"] > 0, "burst never hit the cap"
    assert res["capped"]["rejected_below_cap"] == 0, (
        f"{res['capped']['rejected_below_cap']} rejects below the cap")
    # greedy streams bitwise identical across fleet sizes
    assert res["determinism_ok"] == 1, (
        "fleet routing changed greedy token streams")
    # every admitted request generated tokens under every policy
    assert all(c["tokens_out"] > 0 for c in pol.values())


def main(out_path: str = "BENCH_fleet.json"):
    res = run()
    with open(out_path, "w") as f:
        json.dump(res, f, indent=2)
    emit("fleet/affinity_gain", 0.0, f"{res['affinity_gain']:.3f}")
    emit("fleet/cached_frac_affinity", 0.0,
         f"{res['policies']['prefix_affinity']['cached_token_fraction']:.2f}")
    emit("fleet/cached_frac_round_robin", 0.0,
         f"{res['policies']['round_robin']['cached_token_fraction']:.2f}")
    emit("fleet/prefill_imbalance", 0.0,
         f"{res['prefill_imbalance']:.2f}")
    emit("fleet/capped_rejected", 0.0, str(res["capped"]["rejected"]))
    emit("fleet/determinism_ok", 0.0, str(res["determinism_ok"]))
    print(f"# wrote {os.path.abspath(out_path)}")
    check(res)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_fleet.json")
    args = ap.parse_args()
    main(args.out)
