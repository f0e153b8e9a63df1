"""Serving subsystem: block allocator, continuous-batching scheduler
(mid-decode retirement, out-of-blocks preemption), sampling, telemetry.

Model-level paged-cache numerics live in tests/test_paged_attention.py.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig
from repro.models import init_params
from repro.serving import (BlockAllocator, BlockTable,
                           ContinuousBatchingServer, Request,
                           SamplingParams, sample_tokens)
from repro.serving.blocks import RESERVED_BLOCKS

TINY = ArchConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                  num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                  dtype="float32")


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(jax.random.PRNGKey(0), TINY)


def _server(params, **kw):
    kw.setdefault("batch_size", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return ContinuousBatchingServer(TINY, params, **kw)


def _req(rid, prompt_len=8, max_new=4, rng_seed=None, **kw):
    rng = np.random.default_rng(rid if rng_seed is None else rng_seed)
    return Request(rid=rid,
                   prompt=rng.integers(0, TINY.vocab_size,
                                       prompt_len).astype(np.int32),
                   max_new_tokens=max_new, **kw)


# ------------------------------ allocator ----------------------------- #
def test_allocator_alloc_free_reuse():
    a = BlockAllocator(num_blocks=8, block_size=4)
    assert a.capacity == 8 - RESERVED_BLOCKS
    got = a.alloc(3)
    assert len(got) == 3 and len(set(got)) == 3
    assert 0 not in got, "scratch block must never be handed out"
    assert (a.num_used, a.num_free) == (3, 4)
    a.free(got[:2])
    assert (a.num_used, a.num_free) == (1, 6)
    again = a.alloc(6)
    assert again is not None and 0 not in again
    assert a.num_free == 0


def test_allocator_all_or_nothing_and_double_free():
    a = BlockAllocator(num_blocks=5, block_size=4)
    assert a.alloc(5) is None, "over-ask must not partially allocate"
    assert a.num_used == 0
    got = a.alloc(4)
    assert a.alloc(1) is None
    a.free(got)
    with pytest.raises(ValueError):
        a.free(got[:1])
    with pytest.raises(ValueError):
        a.free([0])        # the reserved scratch block was never allocated


def test_allocator_fragmentation_accounting():
    a = BlockAllocator(num_blocks=16, block_size=8)
    assert a.blocks_for(1) == 1 and a.blocks_for(8) == 1
    assert a.blocks_for(9) == 2
    # 3 requests at 5, 8, 17 tokens -> waste 3 + 0 + 7 slots
    assert a.internal_fragmentation([5, 8, 17]) == 10


def test_block_table_grow_release():
    a = BlockAllocator(num_blocks=6, block_size=4)
    t = BlockTable(a)
    assert t.ensure_capacity(9)       # 3 blocks
    assert t.num_slots == 12 and a.num_used == 3
    assert t.ensure_capacity(12)      # no growth needed
    assert a.num_used == 3
    t2 = BlockTable(a)
    assert t2.ensure_capacity(9) is False, "pool exhausted is all-or-nothing"
    assert a.num_used == 3
    t.release()
    assert a.num_used == 0 and t.blocks == []


# ------------------------------ sampling ------------------------------ #
def test_sampling_greedy_matches_argmax():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (4, 32))
    toks = sample_tokens(logits, jnp.arange(4), jnp.zeros(4), key)
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.argmax(np.asarray(logits), -1))


def test_sampling_top_k_support_and_determinism():
    key = jax.random.PRNGKey(1)
    logits = jax.random.normal(key, (6, 64))
    temps = jnp.full((6,), 0.7)
    a = sample_tokens(logits, jnp.arange(6), temps, key, top_ks=4)
    b = sample_tokens(logits, jnp.arange(6), temps, key, top_ks=4)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    top4 = np.argsort(np.asarray(logits), -1)[:, -4:]
    for i, t in enumerate(np.asarray(a)):
        assert t in top4[i], "sampled token outside the top-k set"
    # different per-row ids give (generically) different draws
    c = sample_tokens(logits, jnp.arange(6) + 100, temps, key, top_ks=4)
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_sampling_per_row_top_k():
    """top_k is honored per row: k=1 forces the argmax even at high
    temperature, k=0 leaves the full vocabulary open."""
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (8, 64)) * 4.0
    temps = jnp.full((8,), 5.0)
    ks = jnp.asarray([1, 0, 1, 0, 1, 0, 1, 0], jnp.int32)
    toks = np.asarray(sample_tokens(logits, jnp.arange(8), temps, key, ks))
    argmax = np.argmax(np.asarray(logits), -1)
    np.testing.assert_array_equal(toks[::2], argmax[::2])


def test_sampling_mixed_greedy_and_stochastic_rows():
    key = jax.random.PRNGKey(2)
    logits = jax.random.normal(key, (4, 32))
    temps = jnp.asarray([0.0, 1.0, 0.0, 1.0])
    toks = np.asarray(sample_tokens(logits, jnp.arange(4), temps, key))
    argmax = np.argmax(np.asarray(logits), -1)
    assert toks[0] == argmax[0] and toks[2] == argmax[2]


# ------------------------- continuous batching ------------------------ #
def test_mid_decode_retirement_and_slot_reuse(tiny_params):
    """A short request retires early and a *queued* request is admitted
    into its slot before the long request finishes -- in one run()."""
    srv = _server(tiny_params, batch_size=2, max_len=96, block_size=4,
                  num_blocks=64)
    short = _req(0, max_new=4)
    long = _req(1, max_new=64)
    queued = _req(2, max_new=4)
    for r in (short, long, queued):
        srv.submit(r)
    results = srv.run()
    assert len(results[0]) == 4
    assert len(results[1]) == 64
    assert len(results[2]) == 4
    # the queued request started while the long one was still decoding
    assert queued.admit_step is not None and long.finish_step is not None
    assert queued.admit_step < long.finish_step
    assert queued.finish_step < long.finish_step
    assert srv.snapshot().preemptions == 0


def test_retirement_frees_blocks_for_admission(tiny_params):
    """Pool sized so the queued request can only be admitted after the
    short one releases its blocks (retire -> admit in the same step)."""
    srv = _server(tiny_params, batch_size=2, max_len=16, block_size=4,
                  num_blocks=9)        # 8 allocatable
    a, b, c = _req(0, max_new=4), _req(1, max_new=8), _req(2, max_new=4)
    for r in (a, b, c):
        srv.submit(r)
    results = srv.run()
    assert {len(results[i]) for i in (0, 2)} == {4} and len(results[1]) == 8
    assert c.admit_step >= a.finish_step


def test_out_of_blocks_preemption_recovers(tiny_params):
    """Decode growth exhausts the pool: the latest-admitted request is
    preempted, re-queued, and still completes with identical tokens."""
    def serve(num_blocks):
        srv = _server(tiny_params, batch_size=2, max_len=16, block_size=4,
                      num_blocks=num_blocks, prefill_chunk=8)
        for rid in range(3):
            srv.submit(_req(rid, prompt_len=8, max_new=8))
        return srv.run(), srv.snapshot()

    tight, snap_tight = serve(6)
    roomy, snap_roomy = serve(13)
    assert snap_tight.preemptions >= 1
    assert snap_roomy.preemptions == 0
    assert all(len(tight[r]) == 8 for r in range(3))
    # recompute-style preemption must not change the sampled streams
    assert tight == roomy


def test_preemption_never_replays_finished_requests(tiny_params):
    """A request that finishes at prefill (max_new=1) sits done-but-
    unretired for one step; pool-exhausted growth must not pick it as a
    preemption victim (a replay would over-generate)."""
    srv = _server(tiny_params, batch_size=2, max_len=16, block_size=4,
                  num_blocks=4, prefill_chunk=8)
    srv.submit(_req(0, prompt_len=8, max_new=4))
    srv.submit(_req(1, prompt_len=4, max_new=1))
    results = srv.run()
    assert len(results[0]) == 4
    assert len(results[1]) == 1, "finished request was replayed"
    snap = srv.snapshot()
    assert snap.finished == snap.submitted == 2


def test_large_request_ids_do_not_overflow(tiny_params):
    """Sample ids wrap modulo 2^31; rid 2048+ must serve fine."""
    srv = _server(tiny_params, batch_size=2, max_len=32, num_blocks=17)
    for rid in (2047, 5000, 123456):
        srv.submit(_req(rid, max_new=4, rng_seed=rid % 7,
                        sampling=SamplingParams(temperature=0.5)))
    results = srv.run()
    assert all(len(results[r]) == 4 for r in (2047, 5000, 123456))


def test_chunked_prefill_interleaves_with_decode(tiny_params):
    """A long prompt streams in chunks while a running request keeps
    decoding (no decode stall)."""
    srv = _server(tiny_params, batch_size=2, max_len=96, block_size=8,
                  num_blocks=32, prefill_chunk=8, prefill_per_step=1)
    srv.submit(_req(0, prompt_len=8, max_new=24))
    srv.submit(_req(1, prompt_len=48, max_new=4))   # 6 chunks
    results = srv.run()
    assert len(results[0]) == 24 and len(results[1]) == 4
    snap = srv.snapshot()
    # 1 + 6 prompt chunks, and every iteration that streamed a chunk of
    # the long prompt also ran a decode step (no decode stall)
    assert snap.prefill_chunks >= 7
    assert snap.decode_steps == snap.steps


def test_request_never_fits_raises(tiny_params):
    srv = _server(tiny_params, batch_size=2, max_len=16, block_size=4)
    with pytest.raises(ValueError):
        srv.submit(_req(0, prompt_len=30, max_new=8))   # > max_len


def test_degenerate_requests_rejected(tiny_params):
    srv = _server(tiny_params)
    with pytest.raises(ValueError):
        srv.submit(Request(rid=0, prompt=np.empty(0, np.int32),
                           max_new_tokens=4))
    with pytest.raises(ValueError):
        srv.submit(_req(1, max_new=0))


def test_partial_results_on_step_budget(tiny_params):
    srv = _server(tiny_params, batch_size=2, max_len=32, num_blocks=17)
    srv.submit(_req(0, max_new=16))
    results = srv.run(max_steps=3)
    assert 1 <= len(results[0]) < 16


def test_fcfs_admission_preserves_submit_order():
    """FCFS: free slots fill from the queue head in submission order."""
    from repro.serving.scheduler import PREFILLING, QUEUED, Scheduler
    a = BlockAllocator(num_blocks=32, block_size=4)
    sched = Scheduler(batch_size=2, allocator=a, max_blocks_per_seq=4,
                      prefill_chunk=8)
    reqs = [_req(rid) for rid in range(3)]
    for r in reqs:
        sched.submit(r, now=float(r.rid))
    admitted = sched.admit(step=0)
    assert [r.rid for r in admitted] == [0, 1]
    assert admitted[0]._admit_seq < admitted[1]._admit_seq
    assert reqs[0].state == PREFILLING and reqs[2].state == QUEUED
    assert [r.rid for r in sched.queue] == [2]


def test_fcfs_head_of_line_blocks_smaller_requests():
    """Admission stops at the first request that does not fit: a small
    request behind a big head must not leapfrog it (the head would
    starve), and the head goes first once blocks free up."""
    from repro.serving.scheduler import Scheduler
    a = BlockAllocator(num_blocks=8, block_size=4)
    hog = a.alloc(6)                    # leave 1 free block
    sched = Scheduler(batch_size=2, allocator=a, max_blocks_per_seq=4,
                      prefill_chunk=8)
    big = _req(0, prompt_len=8, max_new=4)      # needs 2 blocks
    small = _req(1, prompt_len=4, max_new=4)    # would fit in the 1 free
    sched.submit(big, now=0.0)
    sched.submit(small, now=0.0)
    assert sched.admit(step=0) == []
    assert [r.rid for r in sched.queue] == [0, 1], \
        "small request leapfrogged the head of the queue"
    a.free(hog)
    admitted = sched.admit(step=1)
    assert [r.rid for r in admitted] == [0, 1], "head must admit first"


def test_queue_wait_telemetry_and_depth_history(tiny_params):
    """Scheduling delay (submit -> admit) and the deepest per-step
    queue are recorded: batch of 1 makes the waits strictly staircase
    and the depths deterministic."""
    srv = _server(tiny_params, batch_size=1, max_len=32, num_blocks=17)
    for rid in range(3):
        srv.submit(_req(rid, max_new=2))
    srv.run()
    snap = srv.snapshot()
    assert snap.queue_wait_samples == 3
    assert snap.queue_wait_p50_ms is not None
    assert snap.queue_wait_p50_ms >= 0.0
    # queue depth: starts at 2 waiting (one admitted), drains to 0
    assert snap.queue_depth_max == 2
    assert snap.queue_depth == 0

    from repro.obs.registry import MetricsRegistry
    from repro.serving.telemetry import export_to_registry
    reg = MetricsRegistry()
    export_to_registry(snap, reg, prefix="serve")
    gauges = reg.snapshot()["gauges"]
    assert gauges["serve_queue_wait_p50_ms"] == snap.queue_wait_p50_ms
    assert gauges["serve_queue_wait_samples"] == 3
    assert gauges["serve_queue_depth_max"] == 2


def test_telemetry_snapshot_sane(tiny_params):
    srv = _server(tiny_params, batch_size=2, max_len=32, num_blocks=17)
    for rid in range(3):
        srv.submit(_req(rid, max_new=4))
    srv.run()
    snap = srv.snapshot()
    assert snap.submitted == 3 and snap.finished == 3
    assert snap.tokens_out == 12
    assert snap.queue_depth == 0 and snap.active == 0
    assert snap.kv_blocks_used == 0 and snap.kv_occupancy == 0.0
    assert snap.kv_peak_occupancy > 0.0
    assert snap.ttft_p50_ms is not None and snap.ttft_p99_ms is not None
    assert snap.ttft_p50_ms <= snap.ttft_p99_ms
    assert snap.tok_per_s > 0


def test_sampled_serving_stays_in_vocab(tiny_params):
    srv = _server(tiny_params, batch_size=2, max_len=32, num_blocks=17,
                  top_k=8)
    for rid in range(3):
        srv.submit(_req(rid, max_new=6,
                        sampling=SamplingParams(temperature=0.9, top_k=8)))
    results = srv.run()
    for toks in results.values():
        assert len(toks) == 6
        assert all(0 <= t < TINY.vocab_size for t in toks)


def test_moe_family_serves(tiny_params):
    del tiny_params
    from repro.configs import get_config
    cfg = get_config("olmoe-1b-7b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    srv = ContinuousBatchingServer(cfg, params, batch_size=2, max_len=32,
                                   block_size=8, prefill_chunk=8)
    rng = np.random.default_rng(0)
    for rid in range(2):
        srv.submit(Request(rid=rid,
                           prompt=rng.integers(0, cfg.vocab_size,
                                               8).astype(np.int32),
                           max_new_tokens=4))
    results = srv.run()
    assert all(len(v) == 4 for v in results.values())


def test_unsupported_family_raises():
    from repro.configs import get_config
    cfg = get_config("falcon-mamba-7b").reduced()
    with pytest.raises(NotImplementedError):
        ContinuousBatchingServer(cfg, None, batch_size=2, max_len=32)
