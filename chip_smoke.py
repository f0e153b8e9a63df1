"""Chip smoke run: serve minicpm-2b at its published widths on a TPU.

    python chip_smoke.py              # one chip: the serving path
    python chip_smoke.py --chips 4    # four chips: the engine's
                                      # collectives and --dp serving only

The one-chip run builds the published minicpm-2b (40 layers, d_model
2304, vocab 122,753) with random weights from ``--seed``, serves 8
greedy requests (prompts of 128 tokens, six of them sharing a 96-token
prefix, 32 new tokens each) through ``ContinuousBatchingServer``, and
checks that:

* the decode program contains the Pallas paged-attention kernel;
* every request returns its tokens;
* the five prompts that arrive after the first one's prefill take the
  shared prefix from the prefix cache;
* one decode step's logits agree between the kernel path and the
  gathered path on the same live pages;
* the kernel matches ``paged_attention_ref`` at these widths (and at
  one GQA width).

The four-chip run checks the engine's collectives (``auto`` and every
forced ppermute-round backend) against the ``jax.lax`` collectives, and
that ``--dp`` serving over four chips emits the tokens of a one-device
server.  Everything runs in this one process.  It exits non-zero, and
prints no result line, when JAX finds no TPU.  Every duration and rate
it prints is a smoke reading of one run, not a benchmark.  The last
line of its output is one JSON object: ``{"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
#: the engine's decision cache and Auto-Gen tables: emptied at start, so
#: every decision is regenerated from code
DECISION_DIR = os.path.join(REPO, "var", "smoke_decisions")

ARCH = "minicpm-2b"
BATCH = 8
REQUESTS = 8
PROMPT_LEN = 128
SHARED_LEN = 96          # prefix shared by the first six prompts
SHARING = 6
NEW_TOKENS = 32
BLOCK = 16
DP_NEW_TOKENS = 16
#: kernel vs gathered decode logits: max |diff| over max |logit|.  Both
#: paths round attention outputs to bf16; 40 bf16 layers move a logit by
#: a few bf16 ulps (2^-8 = 0.39% each)
LOGIT_TOL = 2e-2
#: kernel vs f32 oracle: the kernel's bf16 output is within 2 bf16 ulps
KERNEL_TOL = 2e-2
#: engine collective vs jax.lax, f32 (summation order differs)
COLLECTIVE_TOL = 1e-4
COLLECTIVE_SIZES = (4 << 10, 1 << 20, 32 << 20)   # bytes per device
FORCED_BACKENDS = {
    "allreduce": ("chain", "tree", "two_phase", "star", "ring",
                  "autogen", "autogen_pipelined"),
    "reduce_scatter": ("ring", "autogen"),
    "allgather": ("ring", "doubling", "autogen"),
    "all_to_all": ("ring", "halving"),
}


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"ok: {what}")


# ---------------------------------------------------------------------- #
# one chip: the serving path
# ---------------------------------------------------------------------- #
def make_prompts(vocab: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, size=SHARED_LEN).astype(np.int32)
    prompts = []
    for i in range(REQUESTS):
        if i < SHARING:
            tail = rng.integers(0, vocab, size=PROMPT_LEN - SHARED_LEN)
            prompts.append(np.concatenate([shared, tail.astype(np.int32)]))
        else:
            prompts.append(
                rng.integers(0, vocab, size=PROMPT_LEN).astype(np.int32))
    return prompts


def build_server(cfg, params, new_tokens: int, mesh=None):
    from repro.serving import ContinuousBatchingServer
    return ContinuousBatchingServer(
        cfg, params, BATCH, max_len=PROMPT_LEN + new_tokens + BLOCK,
        mesh=mesh, block_size=BLOCK)


def submit_all(server, prompts, new_tokens: int) -> None:
    """Request 0 first; the rest once its prompt is prefilled, so the
    prompts that share its prefix find it in the prefix cache."""
    from repro.serving import Request
    submit = lambda rid: server.submit(Request(
        rid=rid, prompt=prompts[rid].copy(), max_new_tokens=new_tokens))
    submit(0)
    while not server.scheduler.running():
        server.step_once()
    for rid in range(1, len(prompts)):
        submit(rid)


def check_results(server, results, vocab: int, new_tokens: int) -> None:
    check(sorted(results) == list(range(REQUESTS))
          and all(len(results[r]) == new_tokens for r in results),
          f"all {REQUESTS} requests returned {new_tokens} tokens each")
    check(all(0 <= t < vocab for toks in results.values() for t in toks),
          "every token id lies in the vocabulary")
    cached = server.snapshot().cached_prefix_tokens
    check(cached == (SHARING - 1) * SHARED_LEN,
          f"the shared prefix was served from the prefix cache ({cached} "
          f"tokens)")


def compare_decode_paths(server, cfg) -> None:
    """One decode step on the server's live pages, through the kernel
    and through the gathered jnp path."""
    import jax
    import numpy as np
    from repro.models import decode_step_paged

    running, (tokens, bt, ctx) = server.decode_batch()
    check(bool(running), "a decode batch is live mid-run")
    logits = {}
    for use_kernel in (True, False):
        fn = jax.jit(lambda p, pg, t, b, c, uk=use_kernel: decode_step_paged(
            p, cfg, pg, {"tokens": t}, b, c, use_kernel=uk)[0])
        logits[use_kernel] = np.asarray(
            fn(server.params, server.pages, tokens, bt, ctx), np.float32)
    rows = [i for i, _ in running]
    kern, gath = logits[True][rows, 0], logits[False][rows, 0]
    err = float(np.max(np.abs(kern - gath)) / np.max(np.abs(gath)))
    agree = float(np.mean(kern.argmax(-1) == gath.argmax(-1)))
    log(f"decode logits, kernel vs gathered over {len(rows)} live rows: "
        f"max|diff|/max|logit| = {err:.3e} (tolerance {LOGIT_TOL}), "
        f"greedy tokens agree on {agree:.0%}")
    check(err <= LOGIT_TOL, "kernel and gathered decode logits agree")


def compare_kernel_to_reference(cfg, seed: int) -> None:
    """The native kernel against the f32 oracle on random pools, at the
    config's widths and at one GQA width."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.kernels.ref import paged_attention_ref

    widths = [(cfg.name, cfg.num_heads, cfg.num_kv_heads,
               cfg.resolved_head_dim), ("GQA", 32, 8, 128)]
    n, m = 65, 8
    rng = np.random.default_rng(seed)
    for name, h, hkv, d in widths:
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (BATCH, h, d)).astype(jnp.bfloat16)
        kp = jax.random.normal(ks[1], (n, BLOCK, hkv, d)).astype(
            jnp.bfloat16)
        vp = jax.random.normal(ks[2], (n, BLOCK, hkv, d)).astype(
            jnp.bfloat16)
        bt = jnp.asarray(rng.permutation(np.arange(1, n))[:BATCH * m]
                         .reshape(BATCH, m).astype(np.int32))
        lengths = rng.integers(1, m * BLOCK + 1, size=BATCH)
        lengths[0] = m * BLOCK
        lengths = jnp.asarray(lengths.astype(np.int32))
        got = ops.paged_attention(q, kp.reshape(n, BLOCK, -1),
                                  vp.reshape(n, BLOCK, -1), bt, lengths)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(paged_attention_ref)(q, kp, vp, bt, lengths)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        log(f"kernel vs paged_attention_ref, {name} (H {h}, Hkv {hkv}, "
            f"D {d}): max|diff| = {err:.3e} (tolerance {KERNEL_TOL})")
        check(err <= KERNEL_TOL,
              f"kernel matches the reference at {name} widths")


def one_chip(seed: int) -> None:
    import jax
    from repro.configs import get_config
    from repro.kernels.ops import default_interpret
    from repro.launch.serve import (build_params, describe_memory,
                                    describe_sizes)

    check(not default_interpret(), "Pallas kernels run natively")
    cfg = get_config(ARCH)
    log(f"config {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}, "
        f"{cfg.param_count() / 1e9:.2f} B params (random, seed {seed})")
    t0 = time.perf_counter()
    params = jax.block_until_ready(build_params(cfg, seed))
    log(f"parameters built on the device in "
        f"{time.perf_counter() - t0:.1f} s (compile included)")
    server = build_server(cfg, params, NEW_TOKENS)
    log(describe_sizes(cfg, server))
    t0 = time.perf_counter()
    compiled = server.compile_decode()
    log(f"decode program compiled in {time.perf_counter() - t0:.1f} s: "
        f"{describe_memory(compiled)}")
    check("tpu_custom_call" in compiled.as_text(),
          "the decode program contains the Pallas kernel (tpu_custom_call)")

    submit_all(server, make_prompts(cfg.vocab_size, seed), NEW_TOKENS)
    t0 = time.perf_counter()
    results = server.run(max_steps=NEW_TOKENS // 2)
    compare_decode_paths(server, cfg)
    results.update(server.run())
    wall = time.perf_counter() - t0
    check_results(server, results, cfg.vocab_size, NEW_TOKENS)
    snap = server.snapshot()
    total = sum(len(v) for v in results.values())
    log(f"served {len(results)} requests, {total} tokens in {wall:.1f} s "
        f"wall, compiles included ({total / wall:.1f} tok/s; a smoke "
        f"reading of one run, not a benchmark) | decode steps "
        f"{snap.decode_steps} | prefill chunks {snap.prefill_chunks} | "
        f"cached prefix tokens {snap.cached_prefix_tokens}")
    log(f"req 0 tokens: {results[0][:8]}...")
    del server
    compare_kernel_to_reference(cfg, seed)


# ---------------------------------------------------------------------- #
# four chips: engine collectives and --dp serving
# ---------------------------------------------------------------------- #
def _collective_body(op: str, eng):
    """Per-device body computing every variant of ``op`` and its
    ``jax.lax`` reference.  Each device's result is returned (concatenated
    over the axis), so a wrong value on any device shows."""
    from jax import lax

    ref = {"allreduce": lambda x: lax.psum(x, "data"),
           "reduce_scatter": lambda x: lax.psum_scatter(x, "data",
                                                        tiled=True),
           "allgather": lambda x: lax.all_gather(x, "data", tiled=True),
           "all_to_all": lambda x: lax.all_to_all(x, "data", 0, 0,
                                                  tiled=True)}[op]
    run = {"allreduce": eng.allreduce_inside,
           "reduce_scatter": eng.reduce_scatter_inside,
           "allgather": eng.allgather_inside,
           "all_to_all": eng.all_to_all_inside}[op]

    def body(x):
        outs = {"lax": ref(x)}
        for algo in ("auto",) + FORCED_BACKENDS[op]:
            outs[algo] = run(x, "data", algo)
        return outs

    return body


def check_collectives(mesh, seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.collectives.api import get_engine

    eng = get_engine()
    p = mesh.shape["data"]
    rows = NamedSharding(mesh, P("data"))
    for op in FORCED_BACKENDS:
        fn = jax.jit(jax.shard_map(_collective_body(op, eng), mesh=mesh,
                                   in_specs=P("data"), out_specs=P("data"),
                                   check_vma=False))
        for nbytes in COLLECTIVE_SIZES:
            n = nbytes // 4
            make = jax.jit(lambda k, n=n: jax.random.normal(k, (p * n,)),
                           out_shardings=rows)
            x = make(jax.random.PRNGKey(seed + nbytes))
            outs = {k: np.asarray(v) for k, v in fn(x).items()}
            ref = outs.pop("lax")
            errs = {a: float(np.max(np.abs(v - ref))) for a, v in
                    outs.items()}
            scale = max(1.0, float(np.max(np.abs(ref))))
            chosen = eng.select(op, nbytes * (p if op == "allgather"
                                              else 1), p).algorithm
            log(f"{op} {nbytes} B/device: auto picks {chosen}; max|diff| "
                f"vs lax " + ", ".join(f"{a} {e:.1e}"
                                       for a, e in errs.items()))
            check(all(e <= COLLECTIVE_TOL * scale for e in errs.values()),
                  f"{op} at {nbytes} B/device matches jax.lax for auto "
                  f"and {len(errs) - 1} forced backends")


def dp_serving(mesh, seed: int) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.launch.serve import build_params, describe_memory

    cfg = get_config(ARCH)
    prompts = make_prompts(cfg.vocab_size, seed)
    params = build_params(cfg, seed)
    single = build_server(cfg, params, DP_NEW_TOKENS)
    submit_all(single, prompts, DP_NEW_TOKENS)
    t0 = time.perf_counter()
    want = single.run()
    log(f"one-device server: {sum(map(len, want.values()))} tokens in "
        f"{time.perf_counter() - t0:.1f} s wall (smoke reading)")
    del single
    params = jax.device_put(params, NamedSharding(mesh, P()))
    server = build_server(cfg, params, DP_NEW_TOKENS, mesh=mesh)
    del params
    compiled = server.compile_decode()
    log(f"dp decode program: {describe_memory(compiled)}")
    check("tpu_custom_call" in compiled.as_text(),
          "the dp decode program contains the Pallas kernel")
    n_dev = mesh.size
    whole = [x for x in jax.tree.leaves((server.params, server.pages))
             if len(x.sharding.device_set) != n_dev]
    check(not whole, f"weights and KV pool are placed on all {n_dev} "
                     f"devices (none whole on one device)")
    _, args = server.decode_batch()
    check(all(a.sharding.spec == P("data") for a in args),
          "decode rows are split over the dp axis")
    submit_all(server, prompts, DP_NEW_TOKENS)
    t0 = time.perf_counter()
    got = server.run()
    log(f"dp server over {n_dev} chips: {sum(map(len, got.values()))} "
        f"tokens in {time.perf_counter() - t0:.1f} s wall (smoke reading)")
    check_results(server, got, cfg.vocab_size, DP_NEW_TOKENS)
    check(got == want, "dp greedy tokens equal the one-device server's")


def four_chips(devices, seed: int) -> None:
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("data",), devices=devices[:4])
    check_collectives(mesh, seed)
    dp_serving(mesh, seed)


# ---------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve on one chip; 4: collectives and dp "
                         "serving over four chips, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # decisions and Auto-Gen tables are regenerated from code: start the
    # cache empty at a fixed path and restore no calibrated topology
    shutil.rmtree(DECISION_DIR, ignore_errors=True)
    os.environ["REPRO_CACHE_DIR"] = DECISION_DIR
    os.environ["REPRO_RESTORE_TOPOLOGY"] = "0"
    sys.path.insert(0, os.path.join(REPO, "src"))

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        log(f"no TPU found: JAX's devices are {platform!r}; this script "
            f"runs only on a TPU")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} TPU devices, found "
            f"{len(devices)}")
        return 1
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        log(f"the repository's src/ is missing next to this script: {e}")
        return 2
    log(f"device {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{enable_compile_cache()}; decision cache {DECISION_DIR} "
        f"(emptied)")
    try:
        if args.chips == 4:
            four_chips(devices, args.seed)
        else:
            one_chip(args.seed)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    stats = devices[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"peak_bytes_in_use on device 0: "
            f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
