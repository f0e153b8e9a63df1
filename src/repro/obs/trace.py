"""Span tracing for engine collectives and the serving loop: structured
spans, Chrome-trace export, opt-in blocking measurement, and a profiler
annotation for every span.

Every engine collective call emits one *span* carrying the op, the
axes/sizes it ran over, payload bytes, the chosen plan
(``plan.describe()``, ``n_chunks``), whether the decision came from the
cache, and the model's predicted time (Eq.-1 cycles) -- the per-call
evidence behind the paper's "<4% model error" claim.  Phases executed
by the engine's wavefront runner nest as child spans and are
additionally wrapped in ``jax.named_scope`` so an XLA profile lines up
with the model's phase decomposition.

Two measurement regimes, because engine calls run in two worlds:

* **traced** -- the call happened under ``jax.jit`` tracing (the
  train/serve hot paths).  The span records host-side planning time
  and ``measured_s=None``; nothing blocks, the compiled program is
  untouched.
* **eager** -- the call executed op-by-op on concrete arrays.  With
  the tracer's ``measure=True`` (opt-in -- ``block_until_ready`` never
  taxes the hot path by default) the span blocks on the result and
  ``measured_s`` is real wall time.

Every span has two sinks.  The in-memory record (the :class:`Span`,
exported as Chrome JSON) is opt-in: :func:`enable_tracing`.  The
profiler sink is always there: ``Tracer.span`` also opens a
``jax.profiler.TraceAnnotation`` of the same name with the span's args
as event stats, so while a profiler session is on (``jax.profiler.
start_trace``) the span lands in the ``.xplane.pb`` on the same clock as
the device's ops; with no session it costs about a microsecond.  The
serving loop (``ContinuousBatchingServer.step_once``) opens
:data:`CAT_SERVE` spans; they never block.

``measured_s`` for traced spans can be backfilled afterwards with
:func:`repro.obs.replay.measure_spans`, which re-executes each unique
collective signature eagerly on the mesh and times it.

Export is Chrome-trace JSON (``chrome://tracing`` / Perfetto: complete
``"X"`` events with span/parent ids in ``args``), loadable back into
:class:`Span` objects via :func:`load_chrome_trace` for offline
analysis (``benchmarks/obs_report.py``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import jax
from jax.profiler import TraceAnnotation

#: span categories (the ``cat`` field of the Chrome events)
CAT_COLLECTIVE = "collective"
CAT_PHASE = "phase"
CAT_SERVE = "serve"

#: schema tag written into the trace metadata; bump when span args
#: change incompatibly.
TRACE_SCHEMA = "repro-trace-v1"

#: args every CAT_COLLECTIVE span must carry (the contract
#: ``obs_report.py --check`` enforces).
REQUIRED_COLLECTIVE_ARGS = ("op", "axes", "bytes", "plan", "cache",
                            "predicted", "measured_s", "mode")


@dataclasses.dataclass
class Span:
    """One traced operation.  ``t0``/``dur`` are host seconds relative
    to the tracer epoch; ``args`` is the structured payload."""

    span_id: int
    parent_id: Optional[int]
    name: str
    cat: str
    t0: float
    dur: float = 0.0
    tid: int = 0
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def set(self, **kw: Any) -> None:
        self.args.update(kw)

    @property
    def predicted(self) -> Optional[float]:
        return self.args.get("predicted")

    @property
    def measured_s(self) -> Optional[float]:
        return self.args.get("measured_s")


class _NullSpan:
    """No-op span handed out while tracing is disabled: the hot path
    pays one attribute check and nothing else."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None

    def set(self, **kw: Any) -> None:
        return None

    def finish_result(self, result: Any, block: Optional[bool] = None
                      ) -> None:
        return None


NULL_SPAN = _NullSpan()


class Annotation(TraceAnnotation):
    """A span's profiler sink: a ``TraceMe`` carrying the span's name
    and args.  Alone, it is what ``Tracer.span`` hands out while the
    in-memory tracer is off, so it takes the no-op span's calls."""

    __slots__ = ()

    def set(self, **kw: Any) -> None:
        return None

    def finish_result(self, result: Any, block: Optional[bool] = None
                      ) -> None:
        return None


def _is_traced(x: Any) -> bool:
    return isinstance(x, jax.core.Tracer)


class _SpanContext:
    """Context manager binding a live :class:`Span` to the tracer's
    thread-local stack (and its profiler annotation)."""

    __slots__ = ("_tracer", "span", "_finished", "_annotation")

    def __init__(self, tracer: "Tracer", span: Span,
                 annotation: Annotation):
        self._tracer = tracer
        self.span = span
        self._finished = False
        self._annotation = annotation

    def __enter__(self) -> "_SpanContext":
        self._tracer._push(self.span)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc: Any) -> None:
        self._annotation.__exit__(*exc)
        if not self._finished:
            self.span.dur = self._tracer._now() - self.span.t0
        self._tracer._pop(self.span)

    def set(self, **kw: Any) -> None:
        self.span.set(**kw)

    def finish_result(self, result: Any, block: Optional[bool] = None
                      ) -> None:
        """Stamp mode and (optionally) measured wall time from the
        op's result.  ``block=None`` blocks iff the tracer is in
        measurement mode; traced results never block."""
        traced = _is_traced(result)
        self.span.set(mode="traced" if traced else "eager")
        should_block = self._tracer.measure if block is None else block
        if should_block and not traced:
            jax.block_until_ready(result)
            self.span.dur = self._tracer._now() - self.span.t0
            self.span.set(measured_s=self.span.dur)
            self._finished = True
        elif "measured_s" not in self.span.args:
            self.span.set(measured_s=None)


class Tracer:
    """Collects spans; disabled by default (every ``span()`` call
    returns a bare profiler :class:`Annotation`)."""

    def __init__(self, enabled: bool = False, measure: bool = False,
                 max_spans: int = 200_000,
                 clock=time.perf_counter):
        self.enabled = enabled
        self.measure = measure
        self.max_spans = max_spans
        self.dropped = 0
        self._clock = clock
        self._epoch = clock()
        self._lock = threading.Lock()
        self._local = threading.local()
        self._spans: List[Span] = []
        self._next_id = 0

    # ------------------------------------------------------------------ #
    def _now(self) -> float:
        return self._clock() - self._epoch

    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = []
            self._local.stack = st
        return st

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()

    # ------------------------------------------------------------------ #
    def span(self, name: str, cat: str = CAT_COLLECTIVE, **args: Any):
        """Open a span (context manager).  It is always annotated for
        the profiler; it is recorded in memory only while tracing is
        enabled (else the bare :class:`Annotation` is returned)."""
        if not self.enabled:
            return Annotation(name, **args)
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
                return Annotation(name, **args)
            sid = self._next_id
            self._next_id += 1
            st = self._stack()
            parent = st[-1].span_id if st else None
            sp = Span(span_id=sid, parent_id=parent, name=name, cat=cat,
                      t0=self._now(), tid=threading.get_ident() & 0xFFFF,
                      args=dict(args))
            self._spans.append(sp)
        return _SpanContext(self, sp, Annotation(name, **args))

    def current_span(self):
        """The innermost live span on this thread (the one a nested
        resolution step should annotate), or the no-op when tracing is
        off / no span is open."""
        if not self.enabled:
            return NULL_SPAN
        st = self._stack()
        return st[-1] if st else NULL_SPAN

    @property
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._next_id = 0
            self.dropped = 0

    # ------------------------------------------------------------------ #
    def to_chrome_events(self) -> List[Dict[str, Any]]:
        events = []
        for sp in self.spans:
            args = dict(sp.args)
            args["span_id"] = sp.span_id
            args["parent_id"] = sp.parent_id
            events.append({
                "name": sp.name, "cat": sp.cat, "ph": "X",
                "ts": sp.t0 * 1e6, "dur": max(sp.dur, 0.0) * 1e6,
                "pid": os.getpid(), "tid": sp.tid, "args": args,
            })
        return events

    def export_chrome(self, path: str) -> int:
        """Write Chrome-trace JSON; returns the number of spans."""
        events = self.to_chrome_events()
        payload = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {"schema": TRACE_SCHEMA, "dropped": self.dropped},
        }
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        return len(events)


def spans_from_events(events: List[Dict[str, Any]]) -> List[Span]:
    """Rebuild :class:`Span` objects from Chrome events (inverse of
    :meth:`Tracer.to_chrome_events`), ordered by start time then id."""
    spans = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = dict(ev.get("args", {}))
        sid = args.pop("span_id", len(spans))
        parent = args.pop("parent_id", None)
        spans.append(Span(
            span_id=int(sid),
            parent_id=None if parent is None else int(parent),
            name=str(ev.get("name", "")), cat=str(ev.get("cat", "")),
            t0=float(ev.get("ts", 0.0)) / 1e6,
            dur=float(ev.get("dur", 0.0)) / 1e6,
            tid=int(ev.get("tid", 0)), args=args))
    spans.sort(key=lambda s: (s.t0, s.span_id))
    return spans


def load_chrome_trace(path: str) -> List[Span]:
    with open(path) as f:
        payload = json.load(f)
    events = (payload["traceEvents"] if isinstance(payload, dict)
              else payload)
    return spans_from_events(events)


def collective_spans(spans: List[Span]) -> Iterator[Span]:
    for sp in spans:
        if sp.cat == CAT_COLLECTIVE:
            yield sp


def validate_spans(spans: List[Span]) -> List[str]:
    """The ``obs_report.py --check`` contract: every collective span
    carries the predicted-cost fields.  Returns problems (empty =
    conformant)."""
    problems = []
    n_coll = 0
    for sp in collective_spans(spans):
        n_coll += 1
        missing = [k for k in REQUIRED_COLLECTIVE_ARGS if k not in sp.args]
        if missing:
            problems.append(f"span {sp.span_id} ({sp.name}): missing "
                            f"args {missing}")
            continue
        if sp.args.get("predicted") is None and \
                not sp.args.get("algorithm_forced"):
            problems.append(f"span {sp.span_id} ({sp.name}): predicted "
                            f"cost is null on a model-selected span")
    if n_coll == 0:
        problems.append("no collective spans in trace")
    return problems


# ---------------------------------------------------------------------- #
# process-wide tracer
# ---------------------------------------------------------------------- #
_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process tracer (tests); returns the previous one."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer
    return prev


def enable_tracing(measure: bool = False, max_spans: int = 200_000
                   ) -> Tracer:
    """Turn on span collection process-wide.  ``measure=True``
    additionally blocks on eager collective results to record wall
    time (never affects jit-traced calls)."""
    tracer = get_tracer()
    tracer.enabled = True
    tracer.measure = measure
    tracer.max_spans = max_spans
    return tracer


def disable_tracing() -> None:
    get_tracer().enabled = False


__all__ = ["Span", "Tracer", "NULL_SPAN", "Annotation", "TRACE_SCHEMA",
           "CAT_COLLECTIVE", "CAT_PHASE", "CAT_SERVE",
           "REQUIRED_COLLECTIVE_ARGS",
           "get_tracer", "set_tracer", "enable_tracing", "disable_tracing",
           "load_chrome_trace", "spans_from_events", "collective_spans",
           "validate_spans"]
