"""What one run of a cell does, from set-up to the result line (see
``bench/run.py`` for the command)."""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import os
import shutil
import time
from typing import Callable, Dict, Optional

import numpy as np

from bench import check, families, gen, harness
from bench.peaks import Peaks, peaks

METRICS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "metrics")


@dataclasses.dataclass
class Run:
    """What a metric reader gets: the window's spans and counters, the
    configuration, the reduced trace (``--trace 1`` only) and the chip's
    peaks."""

    conf: Dict
    server: Dict
    window: harness.Window
    setup_s: float
    peaks: Peaks
    trace: Optional[object] = None


def load_reader(name: str) -> Callable:
    path = os.path.join(METRICS_DIR, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def counts(window: harness.Window, backlog: bool):
    """(attempted, failed).  Open loop: every request due in the window
    is attempted, and fails if it never got a first token.  Backlog:
    the requests admitted before the window closed are attempted; one
    still in prefill when the run ends is unfinished, not failed (the
    server raises on a request it cannot serve)."""
    spans = list(window.reqs.values())
    if backlog:
        return sum(1 for s in spans if s.admit is not None and s.admit <= window.t1), 0
    spans = [s for s in spans if s.due <= window.t1]
    return len(spans), sum(1 for s in spans if not s.tokens)


def serve(conf, spec, limits, seed, seconds, log, devices, trace_dir=None, marks=None):
    """Set-up and window of one run on ``devices``: weights from the
    seed, the server, its warm-up, the traffic, then ``seconds`` of
    open-loop serving and the drain.  Returns (weights, window,
    server); ``marks`` (a list) gets (phase, host time) as each set-up
    phase ends."""
    import jax

    mark = (lambda name: marks.append((name, time.monotonic()))) if marks is not None else (lambda name: None)
    fam = families.of(conf)
    cfg = fam.arch_config(conf)
    weights = fam.make_weights(conf, seed, harness.weight_sharding(devices))
    jax.block_until_ready(weights)
    mark("weights")
    families.check_layout(weights, cfg)
    server = harness.build_server(cfg, weights, conf["server"], devices)
    jax.block_until_ready(server.pages)
    mark("server")
    pool = sum(x.nbytes for x in jax.tree.leaves(server.pages))
    wbytes = sum(x.nbytes for x in jax.tree.leaves(weights))
    log(f"{conf['name']}: weights {wbytes / 2**30:.3f} GiB, KV pool {server.allocator.num_blocks} blocks x {server.block_size} = {pool / 2**30:.3f} GiB, {server.batch} rows over {len(devices)} chip(s)")
    harness.warm_up(server, cfg.vocab_size, seed ^ 0x5EED)
    mark("warm-up")
    traffic = gen.generate(spec, seed, seconds, cfg.vocab_size)
    too_long = [r.rid for r in traffic if len(r.prompt) + r.max_new > conf["server"]["max_len"]]
    if too_long:
        raise ValueError(f"requests {too_long[:5]} exceed max_len {conf['server']['max_len']}")
    if trace_dir is not None:
        # the profiler's first start takes seconds: pay it in set-up
        jax.profiler.start_trace(trace_dir)
        jax.profiler.stop_trace()
        shutil.rmtree(trace_dir, ignore_errors=True)
    mark("traffic")
    counter = harness.CompileCounter()
    window = harness.run_window(
        server,
        traffic,
        seconds,
        drain_first_tokens=not gen.is_backlog(spec),
        drain_finished=(limits["min_tokens"], limits["max_requests"]),
        trace_dir=trace_dir,
        counter=counter,
    )
    late = np.asarray(window.lateness) * 1e3 if window.lateness else np.zeros(1)
    log(f"generator lateness over {len(window.lateness)} submits: p50 {np.median(late):.3f} ms, max {late.max():.3f} ms")
    log(f"window {seconds} s, drain {window.drain_s:.3f} s, {len(window.steps)} steps, {window.compiles} compiles or traces inside the window")
    log(harness.longest_steps(window))
    return weights, window, server


#: salt of the seed that draws ``harness.fill_rows``'s prompts
FILL_SALT = 0xF111


def to_compare(server, window, spec, seed, limits, chips: int, log):
    """The requests the check compares, as (the window's sample
    (``check.sample_finished``), the fill's, the chips whose rows made
    the fill's tokens): over several chips the fill's are one request of
    ``harness.fill_rows`` from each chip's rows, at the mix's shortest
    prompt and output; on one chip there are none, and no count (None)."""
    finished = [r for r in window.requests if r.done]
    sample = check.sample_finished(finished, seed, limits["min_tokens"], limits["max_requests"])
    if chips < 2:
        return sample, [], None
    t = time.monotonic()
    rows = harness.fill_rows(server, spec["prompt"]["min"], spec["output"]["min"], server.cfg.vocab_size, seed ^ FILL_SALT)
    picked = check.one_per_chip(rows, server.batch, chips, seed)
    log(f"rows filled: {len(rows)} of {server.batch} requests finished in {time.monotonic() - t:.1f} s; compared from chips {[c for c, _ in picked]}, rows {[rs for c, r in picked for rs, q in rows if q is r]}")
    return sample, list({id(r): r for _, r in picked}.values()), len(picked)


def run_cell(conf, spec, limits, metrics, seed, seconds, trace, devices, setup_t0, cache_dir, trace_dir, log) -> Dict:
    """One run; ``setup_t0`` is when JAX held the chip, where ``setup_s``
    starts."""
    dev = devices[0]
    pk = peaks(dev.device_kind)
    log(f"compile cache {cache_dir}")
    marks = []
    weights, window, server = serve(conf, spec, limits, seed, seconds, log, devices, trace_dir if trace else None, marks)
    marks.append(("window", window.t0))
    log("set-up phases, s from the chip held: " + ", ".join(f"{name} {t - setup_t0:.3f}" for name, t in marks))
    setup_s = window.t0 - setup_t0
    sample, fill, chips = to_compare(server, window, spec, seed, limits, len(devices), log)
    sample += fill
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    log(f"peak_bytes_in_use {peak} ({peak / 2**30:.3f} GiB) of bytes_limit {stats[0].get('bytes_limit')}")
    del server
    gc.collect()

    t = time.monotonic()
    cmp = check.compare(weights, conf, sample, limits["max_served"])
    log(f"reference over {cmp['requests']} requests ({sum(len(r.prompt) + len(r.out) for r in sample)} tokens), {cmp['tokens']} served tokens compared in {time.monotonic() - t:.1f} s; {cmp['off_best']} tokens off the reference's best")
    correct, checks = check.verdict(cmp["widest_gap"], cmp["tokens"], limits, None if chips is None else (chips, len(devices)))

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices), "memory_peak_bytes": peak}
    summary = None
    if trace:
        from bench import trace_reduce

        summary = trace_reduce.reduce_dir(trace_dir, len(devices))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    run = Run(conf, conf["server"], window, setup_s, pk, summary)
    values = {}
    for m in metrics:
        v = load_reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted, failed = counts(window, gen.is_backlog(spec))
    result = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": values, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result
