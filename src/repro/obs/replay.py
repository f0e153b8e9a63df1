"""Measured replay: backfill wall time into jit-traced spans.

The train/serve hot paths call the engine inside ``jax.jit``, so their
spans are recorded at trace time with ``measured_s=None`` -- there is
no per-collective wall time inside a fused compiled step, and the
tracer refuses to tax the hot path to get one.  This module recovers
the measurement offline: for every unique collective *signature*
``(op, axes, bytes, algorithm)`` seen in a trace, it builds the same
engine call as a standalone jitted ``shard_map`` program on the live
mesh, times it (compile excluded, best of ``repeats``), and writes the
result back into every span carrying that signature
(``measured_s`` + ``measured_via="replay"``).

The engine's decision/plan caches are warm from the traced run, so the
replay executes exactly the plan the span recorded -- the measurement
really is of the plan whose predicted cost the span carries.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.obs import trace as obs_trace

Signature = Tuple[str, Tuple[str, ...], int, str]

#: ops the replay knows how to reconstruct a payload for
_REPLAYABLE = ("allreduce", "reduce_scatter", "allgather", "all_to_all")


def span_signature(span) -> Optional[Signature]:
    """The replayable identity of a collective span (None when the
    span is not a replayable engine collective)."""
    args = span.args
    op = args.get("op")
    axes = args.get("axes")
    nbytes = args.get("bytes")
    if op not in _REPLAYABLE or not axes or not nbytes:
        return None
    algo = args.get("algorithm") or "auto"
    if algo == "identity":
        return None
    return (str(op), tuple(str(a) for a in axes), int(nbytes), str(algo))


def _fold_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    return p


def _build_call(engine, mesh: Mesh, sig: Signature):
    """(jitted zero-arg callable, payload description) for one
    signature, or None when the mesh cannot host it."""
    op, axes, nbytes, algo = sig
    if any(a not in mesh.shape for a in axes):
        return None
    p = _fold_size(mesh, axes)
    spec = P(axes if len(axes) > 1 else axes[0])
    multi = len(axes) > 1

    if op == "allreduce":
        n = max(1, nbytes // 4)
        x = jnp.zeros((n,), jnp.float32)
        if multi:
            fn = lambda v: engine.allreduce_multi(v, axes, algo)
        else:
            fn = lambda v: engine.allreduce_inside(v, axes[0], algo)
        wrapped = shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                            check_vma=False)
    elif op == "reduce_scatter":
        m = max(1, nbytes // (4 * p))
        x = jnp.zeros((p * m,), jnp.float32)
        if multi:
            fn = lambda v: engine.reduce_scatter_multi(v, axes, algo)
        else:
            fn = lambda v: engine.reduce_scatter_inside(v, axes[0], algo)
        wrapped = shard_map(fn, mesh=mesh, in_specs=P(), out_specs=spec,
                            check_vma=False)
    elif op == "allgather":
        # span nbytes is the *global* gathered size (the model's B)
        n = max(1, nbytes // 4)
        n += (-n) % p
        x = jnp.zeros((n,), jnp.float32)
        if multi:
            fn = lambda v: engine.allgather_multi(v, axes, algo)
        else:
            fn = lambda v: engine.allgather_inside(v, axes[0], algo)
        wrapped = shard_map(fn, mesh=mesh, in_specs=spec, out_specs=P(),
                            check_vma=False)
    elif op == "all_to_all":
        # span nbytes is the per-device shard: [p * m] rows locally
        m = max(1, nbytes // (4 * p))
        x = jnp.zeros((p * (p * m),), jnp.float32)
        if multi:
            fn = lambda v: engine.all_to_all_multi(v, axes, algo)
        else:
            fn = lambda v: engine.all_to_all_inside(v, axes[0], algo)
        wrapped = shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                            check_vma=False)
    else:
        return None
    jitted = jax.jit(wrapped)
    return lambda: jitted(x)


def measure_signature(engine, mesh: Mesh, sig: Signature,
                      repeats: int = 3) -> Optional[float]:
    """Wall seconds for one collective signature on the mesh (best of
    ``repeats``, compile excluded), or None when not replayable."""
    call = _build_call(engine, mesh, sig)
    if call is None:
        return None
    tracer = obs_trace.get_tracer()
    was_enabled = tracer.enabled
    tracer.enabled = False      # replay must not re-enter the trace
    try:
        jax.block_until_ready(call())      # compile + cache warm
        best = float("inf")
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            jax.block_until_ready(call())
            best = min(best, time.perf_counter() - t0)
        return best
    finally:
        tracer.enabled = was_enabled


def measure_spans(spans: List[Any], mesh: Mesh, engine=None,
                  repeats: int = 3,
                  only_missing: bool = True) -> Dict[Signature, float]:
    """Backfill ``measured_s`` into every replayable span.

    Spans that already carry a measurement keep it unless
    ``only_missing=False``.  Returns ``{signature: seconds}`` for the
    signatures actually measured."""
    if engine is None:
        from repro.collectives.api import get_engine
        engine = get_engine()
    sigs: Dict[Signature, List[Any]] = {}
    for sp in spans:
        if getattr(sp, "cat", None) != obs_trace.CAT_COLLECTIVE:
            continue
        if only_missing and sp.args.get("measured_s") is not None:
            continue
        sig = span_signature(sp)
        if sig is not None:
            sigs.setdefault(sig, []).append(sp)
    measured: Dict[Signature, float] = {}
    for sig, members in sigs.items():
        secs = measure_signature(engine, mesh, sig, repeats=repeats)
        if secs is None:
            continue
        measured[sig] = secs
        for sp in members:
            sp.set(measured_s=secs, measured_via="replay")
    return measured


__all__ = ["measure_spans", "measure_signature", "span_signature"]
