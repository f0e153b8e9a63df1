"""One run of one cell: set-up, the measured window, the drain, and the
records the metric readers take their numbers from.

The harness drives ``ContinuousBatchingServer.step_once`` (the server
that ``launch/serve.py`` builds) from an open-loop schedule: a request
is submitted once its due time has come, and its time to first token
counts from that due time.  Its own spans, kept in memory, are

* ``StepSpan``: one ``step_once`` call, with what it did (prefill
  chunks with their context, decode rows with their context), read
  from the requests before and after the call;
* ``ReqSpan``: one request's due, submit, admit and token times.

With a trace, a profiler trace covers the whole steps of the window's
last seconds, from a step boundary to the first one after the close,
and each step is also written into the trace as a ``bench.step`` host
span on the profiler's clock.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from bench.gen import TrafficRequest


@dataclasses.dataclass
class StepSpan:
    t0: float
    t1: float
    prefill: List = dataclasses.field(default_factory=list)  # (tokens, ctx before)
    decode: List = dataclasses.field(default_factory=list)  # ctx after the token
    traced: bool = False

    @property
    def kind(self) -> str:
        if self.prefill and self.decode:
            return "mixed"
        if self.decode:
            return "decode"
        if self.prefill:
            return "prefill"
        return "idle"


@dataclasses.dataclass
class ReqSpan:
    rid: int
    due: float
    prompt_len: int
    max_new: int
    submit: Optional[float] = None
    admit: Optional[float] = None
    cached: int = 0
    tokens: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None


@dataclasses.dataclass
class Window:
    """Everything the window recorded (host monotonic seconds)."""

    t0: float
    seconds: float
    steps: List[StepSpan]
    reqs: Dict[int, ReqSpan]
    lateness: List[float]
    compiles: int
    drain_s: float
    requests: List = dataclasses.field(default_factory=list)  # the program's Request objects

    @property
    def t1(self) -> float:
        return self.t0 + self.seconds

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1


class CompileCounter:
    """Counts traces and backend compiles reported by JAX's monitoring
    events while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration", "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event in self.EVENTS:
            self.count += 1


#: how long a run keeps stepping after the window for the requests the
#: counts and the correctness sample wait for
DRAIN_S = 60.0


def data_mesh(devices):
    """A ``data`` mesh over the run's chips, as ``launch/serve.py --dp``
    builds it; None on one chip."""
    if len(devices) < 2:
        return None
    from repro.launch.mesh import make_mesh

    return make_mesh((len(devices),), ("data",), devices=devices)


def weight_sharding(devices):
    """Where the weights are made: every chip of a data mesh holds the
    whole model, as the server places it (None: the default device)."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = data_mesh(devices)
    return None if mesh is None else NamedSharding(mesh, PartitionSpec())


def build_server(cfg, params, server_conf: Dict, devices):
    """The server over ``devices``.  The configuration's ``batch`` and
    ``kv_pool_blocks`` are one chip's share: over n chips the server
    runs n x ``batch`` rows striped over a ``data`` mesh, and, as the
    program replicates its block pool, every chip holds the one pool of
    ``kv_pool_blocks`` that all rows share."""
    from repro.serving import ContinuousBatchingServer

    return ContinuousBatchingServer(
        cfg,
        params,
        server_conf["batch"] * len(devices),
        max_len=server_conf["max_len"],
        mesh=data_mesh(devices),
        block_size=server_conf["block_size"],
        num_blocks=server_conf["kv_pool_blocks"],
        prefill_chunk=server_conf["prefill_chunk"],
    )


def warm_up(server, vocab: int, seed: int) -> None:
    """Compile every program the window runs, through the server itself:
    a prefill chunk (full and partial), the first token's greedy pick,
    the decode step with the kernel and the batch's greedy pick."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    chunk, bs = server.prefill_chunk, server.block_size
    a = rng.integers(0, vocab, size=chunk + 1).astype(np.int32)
    b = rng.integers(0, vocab, size=2 * bs).astype(np.int32)
    server.submit(Request(rid=-1, prompt=a, max_new_tokens=3))
    server.submit(Request(rid=-2, prompt=b, max_new_tokens=2))
    while server.has_work():
        server.step_once()


#: the rids of ``fill_rows``'s requests (the traffic's are 0..n-1)
FILL_RID = 1 << 30
#: how long ``fill_rows`` steps at most
FILL_S = 120.0


def fill_rows(server, prompt_len: int, new_tokens: int, vocab: int, seed: int, limit_s: float = FILL_S) -> List:
    """After the window, with nothing more due: one request for every
    row of the server (``prompt_len`` token ids drawn from ``seed``,
    ``new_tokens`` greedy outputs), all submitted at once, stepped
    until each is done or ``limit_s`` has passed.  Over a ``data`` mesh
    the window's load need not reach every chip's rows; these requests
    do, through the same server and its compiled programs.  Returns
    (the rows a request sat in, the request) for each finished one."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    reqs = [Request(rid=FILL_RID + i, prompt=rng.integers(0, vocab, size=prompt_len).astype(np.int32), max_new_tokens=new_tokens) for i in range(server.batch)]
    for req in reqs:
        server.submit(req)
    rows: Dict[int, set] = {req.rid: set() for req in reqs}
    end = time.monotonic() + limit_s
    while not all(req.done for req in reqs) and time.monotonic() < end:
        server.step_once()
        for i, req in enumerate(server.scheduler.slots):
            if req is not None and req.rid in rows:
                rows[req.rid].add(i)
    return [(sorted(rows[req.rid]), req) for req in reqs if req.done]


def longest_steps(window: Window, n: int = 3) -> str:
    """The ``n`` longest steps of the window, with their kind, index and
    start, beside the median step of each kind: a host stall shows as
    one step far above its kind's median."""
    steps = [(i, s) for i, s in enumerate(window.steps) if window.in_window(s.t0)]
    med = {}
    for k in ("decode", "mixed", "prefill"):
        d = [s.t1 - s.t0 for _, s in steps if s.kind == k]
        if d:
            med[k] = float(np.median(d)) * 1e3
    top = sorted(steps, key=lambda x: x[1].t0 - x[1].t1)[:n]
    parts = [f"{(s.t1 - s.t0) * 1e3:.1f} ms ({s.kind} #{i} at {s.t0 - window.t0:.2f} s)" for i, s in top]
    return "longest steps " + ", ".join(parts) + "; medians " + ", ".join(f"{k} {v:.1f} ms" for k, v in med.items())


def _snapshot(live) -> Dict:
    return {r.rid: (r.prefilled, len(r.out), r.state) for r in live}


def run_window(
    server,
    traffic: List[TrafficRequest],
    seconds: float,
    *,
    drain_s: float = DRAIN_S,
    drain_first_tokens: bool = True,
    drain_finished: tuple = (0, 0),
    trace_dir: Optional[str] = None,
    trace_s: float = 4.0,
    counter: Optional[CompileCounter] = None,
    start_delay: float = 0.05,
) -> Window:
    """Serve ``traffic`` open-loop for ``seconds``, then keep stepping
    (submitting nothing new) for at most ``drain_s`` more seconds: while
    a request due in the window has no first token
    (``drain_first_tokens``), or while the ``drain_finished[1]`` longest
    finished requests hold fewer than ``drain_finished[0]`` served tokens
    (the correctness check compares such a sample of finished
    requests)."""
    import jax
    from repro.serving import Request

    pending = sorted(traffic, key=lambda r: r.due_s)
    reqs: Dict[int, ReqSpan] = {}
    objs: Dict[int, Request] = {}
    steps: List[StepSpan] = []
    lateness: List[float] = []
    live: List = []
    tracing = False
    trace_start = trace_stop = None
    gc.collect()
    gc.disable()
    if counter is not None:
        counter.active = True
    t0 = time.monotonic() + start_delay
    if trace_dir is not None:
        # the last trace_s seconds of the window: stopping the profiler
        # (which writes the trace) then stalls the host after the close
        trace_start = t0 + max(0.0, seconds - trace_s)
        trace_stop = t0 + seconds
    nxt = 0
    try:
        while True:
            now = time.monotonic()
            closed = now >= t0 + seconds
            while not closed and nxt < len(pending) and t0 + pending[nxt].due_s <= now:
                tr = pending[nxt]
                nxt += 1
                req = Request(rid=tr.rid, prompt=tr.prompt, max_new_tokens=tr.max_new)
                due = t0 + tr.due_s
                server.submit(req)
                sub = time.monotonic()
                lateness.append(sub - due)
                reqs[tr.rid] = ReqSpan(tr.rid, due, len(tr.prompt), tr.max_new, submit=sub)
                objs[tr.rid] = req
                live.append(req)
            if closed:
                waiting = drain_first_tokens and any(s.due <= t0 + seconds and not s.tokens for s in reqs.values())
                done = sorted((len(r.out) for r in objs.values() if r.done), reverse=True)
                short = sum(done[: drain_finished[1]]) < drain_finished[0]
                if not (waiting or short) or now >= t0 + seconds + drain_s:
                    break
            if tracing and now >= trace_stop:
                jax.profiler.stop_trace()
                tracing = False
                trace_stop = float("inf")
            if trace_start is not None and not tracing and now >= trace_start and trace_stop != float("inf"):
                jax.profiler.start_trace(trace_dir)
                tracing = True
            if not server.has_work():
                if nxt >= len(pending) or closed:
                    if closed:
                        break
                    time.sleep(0.001)
                    continue
                wait = t0 + pending[nxt].due_s - time.monotonic()
                if wait > 0:
                    time.sleep(min(wait, 0.05))
                continue
            before = _snapshot(live)
            s0 = time.monotonic()
            if tracing:
                with jax.profiler.TraceAnnotation("bench.step"):
                    server.step_once()
            else:
                server.step_once()
            s1 = time.monotonic()
            span = StepSpan(s0, s1, traced=tracing)
            still = []
            for req in live:
                pf, nout, state = before.get(req.rid, (0, 0, "queued"))
                rs = reqs[req.rid]
                if state == "queued" and req.state != "queued":
                    rs.admit = s0 if rs.admit is None else rs.admit
                    rs.cached = req.cached_prefix_tokens
                    pf = req.cached_prefix_tokens
                if req.prefilled > pf:
                    span.prefill.append((req.prefilled - pf, pf))
                grown = len(req.out) - nout
                if grown > 0:
                    rs.tokens.extend([s1] * grown)
                    first = state != "running" and req.state == "running"
                    if grown - int(first) > 0:
                        span.decode.append(req.ctx_len)
                if req.done:
                    rs.done = s1
                else:
                    still.append(req)
            live = still
            steps.append(span)
    finally:
        if tracing:
            jax.profiler.stop_trace()
        if counter is not None:
            counter.active = False
        gc.enable()
    end = time.monotonic()
    w = Window(t0, seconds, steps, reqs, lateness, counter.count if counter else 0, max(0.0, end - t0 - seconds))
    w.requests = list(objs.values())
    return w
