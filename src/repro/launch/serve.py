"""Serving CLI: a thin driver over ``repro.serving`` (paged KV cache +
true continuous batching).  By default it serves the architecture at
its published widths (minicpm-2b fits one 16 GB TPU v5e chip in bf16);
``--reduced`` swaps in the tiny same-family config for CPU runs.

Requests arrive with different prompts and lengths; the scheduler
admits and retires them *every decode step* -- a short request frees
its slot mid-flight and a queued request takes it over while longer
requests keep decoding.  With ``--dp`` the slot rows are striped over
all local devices and per-shard sampled tokens are assembled with the
CollectiveEngine's cached model-driven allgather, so serve traffic
exercises the same dispatch layer as gradient sync.

With ``--replicas N`` the driver stands up a multi-replica fleet
behind a telemetry-driven router (``--router``): requests pass
admission control (``--queue-cap`` / ``--tenant-rate``), are routed on
the replicas' load signals, and the replicas step in deterministic
lockstep waves.  ``--arrival bursty`` stamps Markov-modulated Poisson
arrival waves on the trace instead of submitting everything up front.

The legacy names (``BatchedServer``, ``Request``) are the serving
subsystem's classes re-exported; the old static wave-batcher is gone.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.models.frontend import vision_patches
from repro.obs import cli as obs_cli
from repro.serving import (ContinuousBatchingServer as BatchedServer,
                           Request, SamplingParams)
from repro.serving.telemetry import ttft_low_confidence


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU runs); default "
                         "is the published widths")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="content-addressed CoW sharing of prompt-prefix "
                         "KV blocks across requests (--no-prefix-cache "
                         "recomputes every prompt)")
    ap.add_argument("--shared-prompts", type=int, default=0, metavar="K",
                    help="draw each request's prompt prefix from K shared "
                         "system prompts (0 = fully distinct prompts); "
                         "exercises the prefix cache")
    ap.add_argument("--dp", action="store_true",
                    help="stripe the slot rows over all local devices "
                         "and route token sync through the "
                         "CollectiveEngine")
    from repro.serving.fleet import ARRIVAL_MODES, ROUTER_POLICIES
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="serve behind a fleet of N replicas in "
                         "lockstep waves (1 = single server)")
    ap.add_argument("--router", default="round_robin",
                    choices=ROUTER_POLICIES,
                    help="fleet routing policy (used when --replicas "
                         "> 1 or other fleet flags are set)")
    ap.add_argument("--queue-cap", type=int, default=None, metavar="Q",
                    help="fleet-wide queued-request cap; arrivals above "
                         "the cap are rejected with a retry-after hint")
    ap.add_argument("--tenant-rate", type=float, default=None,
                    metavar="TOK",
                    help="per-tenant token-bucket refill "
                         "(prompt+output tokens per wave)")
    ap.add_argument("--tenant-burst", type=float, default=None,
                    metavar="TOK",
                    help="per-tenant bucket capacity (default 8x rate)")
    ap.add_argument("--arrival", default="fixed", choices=ARRIVAL_MODES,
                    help="arrival process for the request trace "
                         "(bursty = Markov-modulated Poisson)")
    ap.add_argument("--arrival-rate", type=float, default=2.0,
                    help="mean arrivals per wave in the calm state")
    obs_cli.add_obs_args(ap)
    args = ap.parse_args(argv)
    obs_cli.begin(args.trace, args.obs_report, args.metrics_out)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    from repro.models import supports_paged
    if not supports_paged(cfg):
        ap.error(
            f"--arch {args.arch} (family {cfg.family!r}) is not servable "
            f"yet: the paged KV cache covers dense/moe decoder families "
            f"(constant-state families keep the dense training cache)")
    print(f"[serve] compile cache: {enable_compile_cache()}")
    params = build_params(cfg)
    mesh = None
    batch = args.batch
    if args.dp:
        ndev = len(jax.devices())
        mesh = make_mesh((ndev,), ("data",))
        if batch % ndev:
            batch += -batch % ndev
            print(f"[serve] rounding batch to {batch} "
                  f"(dp axis = {ndev} devices)")
    max_len = args.prompt_len + args.new_tokens + cfg.frontend_tokens + \
        args.block_size
    fleet_mode = (args.replicas > 1 or args.arrival != "fixed"
                  or args.queue_cap is not None
                  or args.tenant_rate is not None)
    rng = np.random.default_rng(0)
    shared = [rng.integers(0, cfg.vocab_size,
                           size=max(args.prompt_len - 4, 1)).astype(np.int32)
              for _ in range(args.shared_prompts)]

    def make_request(rid):
        soft = None
        if cfg.frontend == "vision":
            soft = vision_patches(jax.random.PRNGKey(rid), cfg, 1)
        tenant = "solo"
        if shared:
            # shared system prompt + short per-request suffix
            tenant = f"tenant-{rid % len(shared)}"
            prompt = np.concatenate(
                [shared[rid % len(shared)],
                 rng.integers(0, cfg.vocab_size, size=4).astype(np.int32)])
        else:
            prompt = rng.integers(0, cfg.vocab_size,
                                  size=args.prompt_len).astype(np.int32)
        return tenant, Request(
            rid=rid,
            prompt=prompt,
            max_new_tokens=args.new_tokens,
            sampling=SamplingParams(temperature=args.temperature),
            soft_emb=soft)

    if fleet_mode:
        results, snap = _run_fleet(args, cfg, params, batch, max_len, mesh,
                                   make_request)
    else:
        server = BatchedServer(cfg, params, batch, max_len=max_len,
                               mesh=mesh, block_size=args.block_size,
                               prefill_chunk=args.prefill_chunk,
                               top_k=args.top_k,
                               prefix_cache=args.prefix_cache)
        print(f"[serve] {describe_sizes(cfg, server)}")
        print(f"[serve] decode program: "
              f"{describe_memory(server.compile_decode())}")
        t0 = time.time()
        for rid in range(args.requests):
            server.submit(make_request(rid)[1])
        results = server.run()
        dt = time.time() - t0
        snap = server.snapshot()
        total = sum(len(v) for v in results.values())
        print(f"[serve] {len(results)} requests, {total} tokens in "
              f"{dt:.1f}s ({total / dt:.1f} tok/s)")
        lc = (f" (low confidence, n={snap.ttft_samples})"
              if ttft_low_confidence(snap) else "")
        print(f"[serve] ttft p50={snap.ttft_p50_ms:.0f}ms "
              f"p99={snap.ttft_p99_ms:.0f}ms{lc} | decode steps "
              f"{snap.decode_steps} | prefill chunks "
              f"{snap.prefill_chunks} | preemptions {snap.preemptions} | "
              f"peak kv occupancy {snap.kv_peak_occupancy:.2f}")
        print(f"[serve] prefix cache: "
              f"{'on' if args.prefix_cache else 'off'} | prefill tokens "
              f"computed {snap.prefill_tokens_computed} | cached "
              f"{snap.cached_prefix_tokens} "
              f"({snap.cached_token_fraction:.0%}) | evictions "
              f"{snap.prefix_evictions} | kv blocks live "
              f"{snap.kv_blocks_live} / evictable "
              f"{snap.kv_blocks_evictable}")
    for rid in sorted(results)[:3]:
        print(f"  req {rid}: {results[rid][:8]}...")
    tel_snap = snap if not fleet_mode else None
    if mesh is not None:
        with jax.set_mesh(mesh):
            obs_cli.finish(args.trace, args.obs_report, args.metrics_out,
                           mesh=mesh, telemetry_snapshot=tel_snap,
                           label="serve")
    else:
        obs_cli.finish(args.trace, args.obs_report, args.metrics_out,
                       telemetry_snapshot=tel_snap, label="serve")


def build_params(cfg, seed: int = 0):
    """Random weights made on the device in one jitted program (an eager
    per-layer build would hold a second copy of the layer weights)."""
    return jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


def _gib(nbytes: float) -> str:
    return f"{nbytes / 2**30:.2f} GiB"


def describe_sizes(cfg, server) -> str:
    """Parameter and KV-pool bytes of a built server."""
    nbytes = lambda tree: sum(x.nbytes for x in jax.tree.leaves(tree))
    return (f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
            f"heads {cfg.num_heads}/{cfg.num_kv_heads}, vocab "
            f"{cfg.vocab_size} | params {_gib(nbytes(server.params))} | "
            f"kv pool {server.allocator.num_blocks} blocks x "
            f"{server.block_size} = {_gib(nbytes(server.pages))}")


def describe_memory(compiled) -> str:
    """The compiler's per-device memory analysis of a compiled program."""
    ma = compiled.memory_analysis()
    if ma is None:
        return "memory analysis not reported by this backend"
    return (f"arguments {_gib(ma.argument_size_in_bytes)}, outputs "
            f"{_gib(ma.output_size_in_bytes)}, aliased "
            f"{_gib(ma.alias_size_in_bytes)}, temporaries "
            f"{_gib(ma.temp_size_in_bytes)}")


def _run_fleet(args, cfg, params, batch, max_len, mesh, make_request):
    """Fleet path: wave-stamped arrivals -> admission -> router ->
    lockstep replicas.  Returns (results, FleetSnapshot)."""
    from repro.serving.fleet import (AdmissionConfig, FleetServer,
                                     arrival_waves, export_fleet_stats)
    admission = AdmissionConfig(queue_cap=args.queue_cap,
                                tenant_rate=args.tenant_rate,
                                tenant_burst=args.tenant_burst)
    fleet = FleetServer(cfg, params, args.replicas, batch, max_len,
                        router=args.router, admission=admission,
                        mesh=mesh, block_size=args.block_size,
                        prefill_chunk=args.prefill_chunk,
                        top_k=args.top_k,
                        prefix_cache=args.prefix_cache)
    waves = arrival_waves(args.requests, args.arrival,
                          rng=np.random.default_rng(1),
                          rate=args.arrival_rate)
    arrivals = []
    for rid in range(args.requests):
        tenant, req = make_request(rid)
        arrivals.append((waves[rid], tenant, req))
    t0 = time.time()
    results, rejections = fleet.run_trace(arrivals)
    dt = time.time() - t0
    snap = fleet.snapshot()
    total = sum(len(v) for v in results.values())
    print(f"[fleet] {args.replicas} replicas | router {args.router} | "
          f"arrival {args.arrival} | {len(results)} requests, {total} "
          f"tokens in {dt:.1f}s ({total / dt:.1f} tok/s)")
    print(f"[fleet] waves {snap.waves} | routed {list(snap.routed)} | "
          f"admitted {snap.admitted} | rejected {snap.rejected} "
          f"({dict(snap.rejected_by_reason)}) | below-cap rejects "
          f"{snap.rejected_below_cap}")
    print(f"[fleet] fleet prefill computed "
          f"{snap.prefill_tokens_computed} | cached "
          f"{snap.cached_prefix_tokens} "
          f"({snap.cached_token_fraction:.0%}) | per-replica queue "
          f"depth max {list(snap.queue_depth_max)}")
    for i, rs in enumerate(snap.replicas):
        qw = (f"{rs.queue_wait_p50_ms:.0f}ms"
              if rs.queue_wait_p50_ms is not None else "n/a")
        print(f"[fleet]   replica {i}: decode steps {rs.decode_steps} | "
              f"prefill computed {rs.prefill_tokens_computed} | cached "
              f"{rs.cached_prefix_tokens} | queue wait p50 {qw}")
    if rejections:
        r = rejections[0]
        print(f"[fleet]   first rejection: rid {r.rid} ({r.reason}) "
              f"retry after {r.retry_after_waves} waves")
    export_fleet_stats(fleet)
    return results, snap


if __name__ == "__main__":
    main()
