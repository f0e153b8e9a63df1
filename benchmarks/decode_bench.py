"""Decode-size collective sweep: the latency/bandwidth regime A/B.

Decode steps move hundreds of bytes per collective (one int32 token per
sequence, a logit row, a router decision), not the megabytes training
buckets ship -- at those sizes per-phase launch overhead dominates wire
time and the planner must switch from the bandwidth-optimal cascades to
the single-shot latency algorithms.  This bench sweeps the decode
payload range (256 B .. 256 KiB) over the 8-device (pod=2 x data=4)
debug mesh and records, per op:

* **model sweep** (deterministic, gated): the planner's chosen plan
  shape per size, ``latency_selected`` (1 when the one-phase latency
  plan is the argmin), its ``predicted_cycles``, and the modeled
  crossover size where the selection flips to a bandwidth shape.
* **calibration demo** (deterministic, gated): synthetic decode-step
  replays built from the engine's own uncalibrated prices plus an
  injected per-round launch overhead (``T_LAUNCH_TRUE`` cycles,
  converted to seconds) -- the ground truth the model does not know.
  ``engine.calibrate_launch`` must recover the overhead from the
  samples, and the model-error monitor's small-B decile bins must go
  from drifted (>4% -- launch overhead unmodeled) to clean (<4%) once
  the fitted ``t_launch`` enters the predictions.  ``drifted_bins``
  after calibration gates at 0.
* **replay** (wall clock, informational): measured seconds for the
  ``auto`` plan per (op, size) on host devices, via the obs replay
  harness.  Printed for context, never gated -- CI timing noise.

Emits ``BENCH_decode.json``.  The replay runs in a subprocess so the
XLA_FLAGS device-count override never leaks into the parent.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import emit

SIZES = (256, 1024, 4096, 16384, 65536, 262144)
OPS = ("allgather", "allreduce", "all_to_all")
AXES = ("pod", "data")
MESH = (2, 4)

#: injected per-round launch overhead for the calibration demo, in
#: model cycles -- roughly a v5e kernel-launch latency against the
#: WSE-2 time base, and large enough to dominate sub-4KiB payloads
T_LAUNCH_TRUE = 240.0
#: synthetic seconds-per-cycle for the replay samples
S_PER_CYCLE = 2.5e-9
#: "small B" = payloads under 10 KiB (bytes-decile <= 3), the decode
#: regime the latency plans exist for
SMALL_B_MAX_DECILE = 3

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
from repro.collectives.engine import CollectiveEngine
from repro.launch.mesh import make_mesh
from repro.obs.replay import measure_signature

mesh = make_mesh(%(mesh)s, %(axes)s)
eng = CollectiveEngine(persist=False)
out = {}
for op in %(ops)s:
    per = {}
    for nbytes in %(sizes)s:
        secs = measure_signature(eng, mesh, (op, %(axes)s, nbytes,
                                             "auto"), repeats=3)
        per[str(nbytes)] = {"wall_s": secs}
    out[op] = per
print("JSON" + json.dumps(out))
"""


def _model_sweep():
    """Planner-side view per (op, size): chosen shape, latency bit,
    argmin price, crossover.  No devices needed; prices come from the
    declared fabric constants, so every counter is deterministic."""
    from repro.collectives.engine import CollectiveEngine

    eng = CollectiveEngine(persist=False)
    out = {}
    for op in OPS:
        per = {}
        for nbytes in SIZES:
            plan = eng.plan_multi(op, AXES, MESH, nbytes)
            pred = min(plan.predictions.values())
            per[str(nbytes)] = {
                "plan": plan.describe(),
                "shape": plan.shape,
                "latency_selected": int(plan.shape == "latency"),
                "predicted_cycles": round(float(pred), 3),
                "lower_bound": plan.lower_bound,
                "predictions": {k: round(float(v), 3)
                                for k, v in plan.predictions.items()},
            }
        crossover = next((b for b in SIZES
                          if not per[str(b)]["latency_selected"]), None)
        out[op] = {"sizes": per, "crossover_bytes": crossover}
    return out


def _calibration_demo():
    """Recover an injected launch overhead from synthetic replays and
    show the small-B model-error bins going drifted -> clean.

    Ground truth: ``seconds = S_PER_CYCLE * (base + T_LAUNCH_TRUE *
    launches)`` where ``base`` is the engine's own uncalibrated price
    -- the exact generative model ``calibrate_launch`` fits, so the
    recovery must be exact and the post-calibration bins exactly
    clean; what the gate protects is the machinery (launch counting,
    the lstsq fit, cache invalidation, prediction refresh), not a
    hardware measurement."""
    from repro.collectives.engine import CollectiveEngine
    from repro.core import patterns as pat
    from repro.obs.model_error import ModelErrorMonitor

    p = 1
    for s in MESH:
        p *= s
    cal_algos = {"allreduce": ("ring", "oneshot"),
                 "allgather": ("ring", "doubling", "oneshot")}

    eng = CollectiveEngine(persist=False)
    fab = eng.topology.for_axis(None)
    samples = []
    for nbytes in SIZES:
        for op, algos in cal_algos.items():
            for algo in algos:
                base = eng.select(op, nbytes, p,
                                  fabric=fab).predictions[algo]
                launches = pat.launch_count(op, algo, p)
                secs = S_PER_CYCLE * (base + T_LAUNCH_TRUE * launches)
                samples.append((op, p, nbytes, algo, secs))

    def score(monitor):
        for op, _, nbytes, algo, secs in samples:
            pred = eng.select(op, nbytes, p,
                              fabric=eng.topology.for_axis(None)
                              ).predictions[algo]
            monitor.observe(op, str(p), nbytes, pred, secs)
        return monitor

    before = score(ModelErrorMonitor(min_samples=2,
                                     seconds_per_cycle=S_PER_CYCLE))
    fitted = eng.calibrate_launch(samples)
    after = score(ModelErrorMonitor(min_samples=2,
                                    seconds_per_cycle=S_PER_CYCLE))

    def small_b(mon):
        return [b.as_dict() for key, b in sorted(mon.bins.items())
                if key[2] <= SMALL_B_MAX_DECILE]

    return {
        "t_launch_true": T_LAUNCH_TRUE,
        "t_launch_fitted": fitted,
        "smallb_bins_before": small_b(before),
        "smallb_bins_after": small_b(after),
        "smallb_drifted_before": sum(b["drifted"]
                                     for b in small_b(before)),
        "drifted_bins": int(len(after.drifted_bins())),
    }


def _replay():
    """Measured wall seconds per (op, size) for the auto plan, on 8
    host devices in a subprocess.  Informational only."""
    child = _CHILD % {"mesh": repr(MESH), "axes": repr(AXES),
                      "ops": repr(OPS), "sizes": repr(SIZES)}
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    env["REPRO_RESTORE_TOPOLOGY"] = "0"
    # a replay on virtual CPU devices: the child never reaches for an
    # accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=1500)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("JSON")][-1]
    return json.loads(line[4:])


def run(verbose: bool = True, replay: bool = True):
    results = {"mesh": dict(zip(AXES, MESH))}
    results["model"] = _model_sweep()
    results["calibration"] = _calibration_demo()
    if replay:
        results["replay"] = _replay()
    if verbose:
        for op in OPS:
            sizes = results["model"][op]["sizes"]
            for nbytes in SIZES:
                r = sizes[str(nbytes)]
                wall = ""
                if replay:
                    w = results["replay"][op][str(nbytes)]["wall_s"]
                    wall = f" wall={w * 1e3:.2f}ms"
                emit(f"decode/{op}/{nbytes}", 0.0,
                     f"{r['shape']} pred={r['predicted_cycles']:g}"
                     f"{wall}")
            emit(f"decode/{op}/crossover", 0.0,
                 str(results["model"][op]["crossover_bytes"]))
        cal = results["calibration"]
        emit("decode/calibration", 0.0,
             f"t_launch {cal['t_launch_fitted']:g} "
             f"(true {cal['t_launch_true']:g}), small-B bins "
             f"{cal['smallb_drifted_before']} drifted -> "
             f"{cal['drifted_bins']}")
    return results


def check(results):
    """The acceptance ordering on the deterministic sections."""
    model = results["model"]
    for op in OPS:
        sizes = model[op]["sizes"]
        crossover = model[op]["crossover_bytes"]
        # the smallest decode payloads are always in the latency regime
        assert sizes[str(SIZES[0])]["latency_selected"] == 1, (
            op, sizes[str(SIZES[0])])
        # nothing undercuts the overlap-aware lower bound
        for nbytes_s, r in sizes.items():
            assert all(t >= r["lower_bound"] - 1e-6
                       for t in r["predictions"].values()), (op, nbytes_s)
        # selection is monotone: latency below the crossover,
        # bandwidth shapes at and above it
        for nbytes in SIZES:
            want = crossover is None or nbytes < crossover
            assert bool(sizes[str(nbytes)]["latency_selected"]) == want, (
                op, nbytes, crossover)
    # the bandwidth regime still exists: the gather-heavy ops leave
    # the latency plan within the swept range
    assert model["allgather"]["crossover_bytes"] is not None
    assert model["allreduce"]["crossover_bytes"] is not None

    cal = results["calibration"]
    fitted, true = cal["t_launch_fitted"], cal["t_launch_true"]
    assert abs(fitted - true) <= 0.01 * true, (fitted, true)
    # pre-calibration the unmodeled launch overhead shows up exactly
    # where the latency regime lives: the small-B bins drift ...
    assert cal["smallb_drifted_before"] >= 1, cal["smallb_bins_before"]
    # ... and the fitted t_launch clears every bin
    assert cal["drifted_bins"] == 0, cal["smallb_bins_after"]


def main(out_path: str = "BENCH_decode.json", replay: bool = True):
    results = run(replay=replay)
    check(results)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    emit("decode/json", 0.0, out_path)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_decode.json")
    ap.add_argument("--no-replay", action="store_true",
                    help="skip the wall-clock subprocess (model + "
                         "calibration sections only)")
    args = ap.parse_args()
    main(out_path=args.out, replay=not args.no_replay)
