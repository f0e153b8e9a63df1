"""The serving engine: paged KV cache + continuous batching over the
CollectiveEngine.

``ContinuousBatchingServer`` glues the host-side ``Scheduler`` to the
jitted paged model entry points (``repro.models.paged``):

* one jitted **prefill-chunk** program ([1, chunk] tokens, so every
  prompt length reuses the same executable),
* one jitted **decode** program over the full slot array ([B, 1]),
  idle slots masked to the scratch block,
* one jitted **sample(+gather)** program.

The programs are named functions, so a profile's ``XLA Modules`` line
reads ``jit_serve_prefill``, ``jit_serve_decode``, ``jit_serve_greedy``,
``jit_serve_sample``, ``jit_serve_sample_notopk`` and
``jit_serve_copy_blocks`` (``*_gather`` under DP).  ``step_once``
opens ``serve.*`` spans (``repro.obs.trace``, category ``serve``) at
its layer boundaries; they reach a profile on the device's clock, and
the in-memory tracer when it is enabled.

Data-parallel serving (``mesh=``) stripes the slot rows over the DP
axis.  Every host-side scheduling decision needs the *global* token
vector, so per-shard sampled tokens are assembled with the
CollectiveEngine's cached model-driven allgather -- the serve path
generates real per-step collective traffic through the same dispatch
layer as gradient sync (no bare ``jax.lax`` collectives anywhere in
this package).  Sampling keys travel with the rows (per
(request, position) ids), so DP and single-device serving emit
identical tokens.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.collectives.api import get_engine
from repro.models.paged import (copy_blocks, decode_step_paged,
                                forward_paged, init_pages, supports_paged)
from repro.obs.trace import CAT_SERVE, get_tracer
from repro.serving.blocks import BlockAllocator
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampling import sample_tokens
from repro.serving.scheduler import (PrefillChunk, Request, Scheduler,
                                     RUNNING)
from repro.serving.telemetry import Telemetry, TelemetrySnapshot

#: sample-id stride per request; bounds max_new_tokens per request.
#: ids wrap modulo 2^31 (int32 PRNG fold-in data), so key reuse across
#: requests is possible every 2^31/stride rids -- a statistical, not a
#: correctness, concern (determinism only needs ids to be a pure
#: function of (rid, position))
_SAMPLE_STRIDE = 1 << 20
_SAMPLE_MOD = 1 << 31


def _span(name: str, **args):
    """A serving span on the process tracer (profiler annotation always,
    in-memory record when tracing is enabled)."""
    return get_tracer().span(name, CAT_SERVE, **args)


# The jitted programs are functions named ``serve_*`` (module-level,
# or nested where they close over the model): a program's name is its
# module's name in a profile (``jit_serve_greedy``); a lambda would
# read ``jit__lambda`` and a bare ``functools.partial``
# ``jit__unknown``.
def serve_copy_blocks(pages, src, dst):
    return copy_blocks(pages, src, dst)


def serve_greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class StepOutcome:
    """What one scheduler iteration did (the unit a fleet drives in
    lockstep waves)."""
    finished: Dict[int, List[int]]      # rid -> tokens retired this step
    decoded: bool                       # a decode batch launched
    progressed: bool                    # False = nothing active (drained)


class ContinuousBatchingServer:
    """Paged-cache continuous-batching server over the functional
    model API (the legacy ``BatchedServer`` constructor signature)."""

    def __init__(self, cfg, params, batch_size: int, max_len: int,
                 seed: int = 0, mesh: Optional[Mesh] = None,
                 dp_axis: str = "data", engine=None, *,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefill_chunk: int = 32, prefill_per_step: int = 1,
                 top_k: int = 0, use_kernel: Optional[bool] = None,
                 prefix_cache: bool = True):
        if not supports_paged(cfg):
            raise NotImplementedError(
                f"serving supports dense/moe decoder families, not "
                f"{cfg.family!r}")
        self.cfg = cfg
        self.params = params
        self.batch = batch_size
        self.block_size = block_size
        self.prefill_chunk = prefill_chunk
        self.max_blocks_per_seq = -(-max_len // block_size)
        if num_blocks is None:
            num_blocks = batch_size * self.max_blocks_per_seq + 1
        self.allocator = BlockAllocator(num_blocks, block_size)
        self.prefix_cache = (PrefixCache(self.allocator) if prefix_cache
                             else None)
        self.scheduler = Scheduler(batch_size, self.allocator,
                                   self.max_blocks_per_seq, prefill_chunk,
                                   prefill_per_step,
                                   prefix_cache=self.prefix_cache)
        self.telemetry = Telemetry()
        self.top_k = top_k          # default for requests with top_k=0
        self.key = jax.random.PRNGKey(seed)
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        self._step = 0

        self.mesh = mesh
        self.dp_axis = dp_axis
        self._engine = engine
        self.pages = init_pages(cfg, num_blocks, block_size)
        if mesh is not None:
            if batch_size % mesh.shape[dp_axis] != 0:
                raise ValueError(
                    f"batch {batch_size} not divisible by dp axis "
                    f"{mesh.shape[dp_axis]}")
            self._engine = engine or get_engine()
            self._row_sharding = NamedSharding(mesh, P(dp_axis))
            # replicate the weights and the block pool across the DP
            # shards up front so every program runs on the mesh from the
            # first call (and no call re-sends them)
            replicated = NamedSharding(mesh, P())
            self.params = jax.device_put(self.params, replicated)
            self.pages = jax.device_put(self.pages, replicated)

        key = self.key

        def serve_prefill(params, pages, tokens, bt, ctx, new_len,
                          soft=None):
            batch = {"tokens": tokens}
            if soft is not None:
                batch["soft_emb"] = soft
            return forward_paged(params, cfg, pages, batch, bt, ctx,
                                 new_len, use_kernel=False)

        def serve_decode(params, pages, tokens, bt, ctx):
            return decode_step_paged(params, cfg, pages, {"tokens": tokens},
                                     bt, ctx, use_kernel=use_kernel)

        def serve_sample(logits, sids, temps, topks):
            return sample_tokens(logits, sids, temps, key, topks)

        # batches with no top-k row skip the cutoff sort (trace-time 0)
        def serve_sample_notopk(logits, sids, temps):
            return sample_tokens(logits, sids, temps, key, 0)

        # the page pool is dead after each call (run() reassigns it), so
        # donate it where the backend supports donation -- decode then
        # updates the cache in place instead of copying the whole pool
        donate = (1,) if jax.default_backend() != "cpu" else ()
        self._prefill_fn = jax.jit(serve_prefill, donate_argnums=donate)
        # copy-on-write pool copies (whole blocks src -> dst); donated
        # like the other pool-threading programs
        self._copy_fn = jax.jit(serve_copy_blocks,
                                donate_argnums=(0,) if donate else ())
        self._decode_fn = jax.jit(serve_decode, donate_argnums=donate)
        self._sample_fn = jax.jit(serve_sample)
        self._sample_notopk_fn = jax.jit(serve_sample_notopk)
        # all-greedy batches (the common case) skip the sampling math
        self._greedy_fn = jax.jit(serve_greedy)
        self._sample_gather_fn = None
        self._sample_notopk_gather_fn = None
        self._greedy_gather_fn = None
        self.assembly_decision = None
        self.assembly_regime = None
        if mesh is not None:
            eng = self._engine
            # Decode token assembly gathers one int32 per sequence over
            # the DP axis -- a few hundred bytes, firmly below the
            # latency/bandwidth crossover.  Precompute the planner's
            # decision once so operators can see which side of the
            # crossover the serving hot path landed on; the engine
            # stamps the same choice on every span as ``regime=``.
            n_dp = mesh.shape[dp_axis]
            dec = eng.select("allgather", batch_size * 4, n_dp,
                             fabric=eng.topology.for_axis(dp_axis))
            self.assembly_decision = dec
            self.assembly_regime = ("latency" if dec.algorithm == "oneshot"
                                    else "bandwidth")

            def _gathered(fn, in_specs):
                # per-shard tokens assembled by the engine's cached
                # model-driven allgather, as ``<program>_gather``
                def local(lg, *rest):
                    return eng.allgather_inside(fn(lg, *rest), dp_axis)
                local.__name__ = f"{fn.__name__}_gather"
                return jax.jit(shard_map(local, mesh=mesh,
                                         in_specs=in_specs, out_specs=P(),
                                         check_vma=False))

            row_specs = (P(dp_axis),) * 4
            self._sample_gather_fn = _gathered(serve_sample, row_specs)
            self._sample_notopk_gather_fn = _gathered(serve_sample_notopk,
                                                      row_specs[:3])
            self._greedy_gather_fn = _gathered(serve_greedy, P(dp_axis))

    def compile_decode(self):
        """Ahead-of-time compile of the decode program at this server's
        shapes (the program every decode step runs), for
        ``memory_analysis()`` and ``as_text()``."""
        sharding = self._row_sharding if self.mesh is not None else None
        rows = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                   sharding=sharding)
        b, m = self.batch, self.max_blocks_per_seq
        with self._on_mesh():
            return self._decode_fn.lower(self.params, self.pages,
                                         rows(b, 1), rows(b, m),
                                         rows(b)).compile()

    def _on_mesh(self):
        """Trace and run the decode program with the DP mesh set, so the
        model's sharding hints and the per-shard decode kernel see it."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    # ------------------------------------------------------------------ #
    def submit(self, req: Request) -> None:
        self.scheduler.submit(req, now=self.telemetry.now())
        self.telemetry.record_submit()

    def snapshot(self) -> TelemetrySnapshot:
        return self.telemetry.snapshot(
            queue_depth=len(self.scheduler.queue),
            active=len(self.scheduler.active()),
            allocator=self.allocator,
            block_usage=self.scheduler.block_usage())

    # ------------------------------------------------------------------ #
    def _sample_rows(self, logits: jax.Array, reqs: List[Request],
                     rows: List[int], gathered: bool) -> jax.Array:
        """logits [B, V] -> tokens [B], dispatched and not waited on;
        per-(request, position) keys make the result independent of
        slot placement and DP."""
        b = logits.shape[0]
        sids = np.zeros((b,), np.int32)
        temps = np.zeros((b,), np.float32)
        topks = np.zeros((b,), np.int32)
        for row, req in zip(rows, reqs):
            sids[row] = (req.rid * _SAMPLE_STRIDE
                         + len(req.out)) % _SAMPLE_MOD
            temps[row] = req.sampling.temperature
            topks[row] = req.sampling.top_k or self.top_k
        if not np.any(temps > 0):       # all-greedy hot path: argmax only
            fn = self._greedy_gather_fn if gathered else self._greedy_fn
            return fn(logits)
        if not np.any(topks > 0):       # no cutoff sort needed
            fn = (self._sample_notopk_gather_fn if gathered
                  else self._sample_notopk_fn)
            return fn(logits, jnp.asarray(sids), jnp.asarray(temps))
        fn = self._sample_gather_fn if gathered else self._sample_fn
        return fn(logits, jnp.asarray(sids), jnp.asarray(temps),
                  jnp.asarray(topks))

    def _next_tokens(self, logits: jax.Array, reqs: List[Request],
                     rows: List[int], gathered: bool) -> np.ndarray:
        """Sample the rows' next tokens and wait for them on the host:
        the step's one wait on the device."""
        with _span("serve.sample", rows=len(rows)):
            toks = self._sample_rows(logits, reqs, rows, gathered)
        with _span("serve.wait"):
            return np.asarray(toks)

    def _append_token(self, req: Request, token: int) -> None:
        req.out.append(int(token))
        self.telemetry.record_tokens(1)
        if req.first_token_t is None:
            req.first_token_t = self.telemetry.now()
            self.telemetry.record_first_token(req.arrival_t)
        if len(req.out) >= req.max_new_tokens:
            req.done = True
            req.finish_t = self.telemetry.now()
            req.finish_step = self._step
            self.telemetry.record_finish()

    # ------------------------------------------------------------------ #
    def _run_prefill_chunk(self, chunk: PrefillChunk) -> None:
        req, start, n = chunk.req, chunk.start, chunk.length
        replay = req.replay_tokens
        with _span("serve.prefill", rid=req.rid, tokens=n, ctx=req.ctx_len):
            tokens = np.zeros((1, self.prefill_chunk), np.int32)
            tokens[0, :n] = replay[start:start + n]
            bt = np.zeros((1, self.max_blocks_per_seq), np.int32)
            bt[0, :len(req.table.blocks)] = req.table.blocks
            ctx = np.asarray([req.ctx_len], np.int32)
            new_len = np.asarray([n], np.int32)
            if start == 0 and req.soft_emb is not None:
                logits, self.pages = self._prefill_fn(
                    self.params, self.pages, tokens, bt, ctx, new_len,
                    req.soft_emb)
                req.ctx_len += req.n_soft
            else:
                logits, self.pages = self._prefill_fn(
                    self.params, self.pages, tokens, bt, ctx, new_len)
            req.prefilled += n
            req.ctx_len += n
            self.telemetry.record_prefill_tokens(n)
            self.scheduler.note_prefilled(req)
            # prompt fully cached: the chunk's last valid position
            # yields this request's next token (its first, unless it
            # was preempted mid-decode and replayed)
            last = logits[:, n - 1] if req.prefilled == len(replay) else None
        if last is not None:
            req.state = RUNNING
            tok = self._next_tokens(last, [req], [0], gathered=False)
            with _span("serve.append", tokens=1):
                self._append_token(req, int(tok[0]))

    def decode_batch(self):
        """The next decode step's inputs: (running [(row, request)],
        [tokens [B, 1], block tables [B, M], context lengths [B]]),
        idle rows zero (their writes land in the scratch block)."""
        running = self.scheduler.running()
        return running, self._decode_args(running)

    def _decode_args(self, running) -> List[jax.Array]:
        tokens = np.zeros((self.batch, 1), np.int32)
        bt = np.zeros((self.batch, self.max_blocks_per_seq), np.int32)
        ctx = np.zeros((self.batch,), np.int32)
        for i, req in running:
            tokens[i, 0] = req.out[-1]
            bt[i, :len(req.table.blocks)] = req.table.blocks
            ctx[i] = req.ctx_len
        args = [jnp.asarray(tokens), jnp.asarray(bt), jnp.asarray(ctx)]
        if self.mesh is not None:
            args = [jax.device_put(a, self._row_sharding) for a in args]
        return args

    def _run_decode(self) -> None:
        running = self.scheduler.running()
        with _span("serve.decode.batch", rows=len(running)):
            args = self._decode_args(running)
        rows = [i for i, _ in running]
        reqs = [r for _, r in running]
        # the pages the decode kernel fetches per layer: each row's
        # context with the token this step appends
        kv_pages = sum(-(-(r.ctx_len + 1) // self.block_size) for r in reqs)
        with _span("serve.decode", rows=len(rows), batch=self.batch,
                   kv_pages=kv_pages):
            with self._on_mesh():
                logits, self.pages = self._decode_fn(self.params,
                                                     self.pages, *args)
            last = logits[:, 0]
        toks = self._next_tokens(last, reqs, rows,
                                 gathered=self.mesh is not None)
        with _span("serve.append", tokens=len(running)):
            for i, req in running:
                req.ctx_len += 1
                self._append_token(req, int(toks[i]))

    # ------------------------------------------------------------------ #
    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def step_once(self) -> StepOutcome:
        """One scheduler iteration: retire, admit, apply CoW copies,
        prefill chunks, decode (at most one batch).  The unit
        ``run()`` loops over and a fleet drives in lockstep waves; a
        stalled scheduler (queued work that can never be admitted)
        raises."""
        sched = self.scheduler
        with _span("serve.step", step=self._step):
            results: Dict[int, List[int]] = {}
            with _span("serve.schedule", queued=len(sched.queue)):
                for req in sched.retire_finished():
                    results[req.rid] = req.out
                now = self.telemetry.now()
                for req in sched.admit(self._step, now):
                    self.telemetry.record_queue_wait(now - req.arrival_t)
                    if req.cached_prefix_tokens:
                        self.telemetry.record_cached_prefix(
                            req.cached_prefix_tokens)
                cows = sched.drain_cow_copies()
                if not sched.active():
                    if sched.queue:
                        raise RuntimeError(
                            "serving stalled: queued request cannot be "
                            "admitted (KV block pool too small?)")
                    return StepOutcome(results, decoded=False,
                                       progressed=False)
                plan = sched.prefill_plan()
            if cows:
                # private replacements for shared blocks about to be
                # written; must land before this step's prefill chunks
                with _span("serve.cow", blocks=len(cows)):
                    src = jnp.asarray([s for s, _ in cows], jnp.int32)
                    dst = jnp.asarray([d for _, d in cows], jnp.int32)
                    self.pages = self._copy_fn(self.pages, src, dst)
            for chunk in plan:
                self._run_prefill_chunk(chunk)
            decoded = False
            if sched.any_running():
                with _span("serve.schedule", queued=len(sched.queue)):
                    for _ in sched.grow_for_decode():
                        self.telemetry.record_preemption()
                if sched.any_running():
                    self._run_decode()
                    decoded = True
            self.telemetry.record_step(decoded=decoded,
                                       prefill_chunks=len(plan),
                                       kv_occupancy=self.allocator.occupancy,
                                       queue_depth=len(sched.queue))
            self._step += 1
            if not plan and not decoded and not any(
                    r.done for r in sched.slots if r):
                raise RuntimeError("scheduler made no progress")
            return StepOutcome(results, decoded=decoded, progressed=True)

    def run(self, max_steps: Optional[int] = None) -> Dict[int, List[int]]:
        """Serve until queue + slots drain, or ``max_steps`` decode
        iterations when given (default: drain -- total decode work is
        bounded by the submitted max_new_tokens, and a stalled scheduler
        raises).  Returns {rid: generated tokens} (partial outputs
        included when a step budget ends first)."""
        results: Dict[int, List[int]] = {}
        decode_steps = 0
        if max_steps is None:
            max_steps = float("inf")
        while self.scheduler.has_work():
            out = self.step_once()
            results.update(out.finished)
            if not out.progressed:
                break       # drained
            decode_steps += int(out.decoded)
            if decode_steps >= max_steps:
                break
        for req in self.scheduler.retire_finished():
            results[req.rid] = req.out
        # step budget exhausted: report partial generations
        for _, req in self.scheduler.active():
            results.setdefault(req.rid, req.out)
        for req in self.scheduler.queue:
            results.setdefault(req.rid, req.out)
        return results


__all__ = ["ContinuousBatchingServer", "Request", "StepOutcome"]
