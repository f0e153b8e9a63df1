"""Readings for the correctness limit of a cell, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed, one process-local run of the cell at its own load (a
window of ``--seconds``), then, at every served position of the same
sampled requests, the widest gap of the program's token and of the
fp8 control's token below the float32 reference's best, and the
verdict (``check.verdict``, as a run decides ``correct``) that each
gets against the limit in ``bench/limits/<cell>.json``.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import run as bench_run  # bench/run.py: paths, cache, chip look


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    bench = bench_run.load_json("BENCHMARK.json")
    cell = bench_run.find(bench["workloads"], args.workload, "workload")
    conf = bench_run.load_json("bench", "configs", f"{cell['config']}.json")
    spec = bench_run.load_json("bench", "traffic", f"{cell['traffic']}.json")
    limits = bench_run.load_json("bench", "limits", f"{cell['name']}.json")
    bench_run.require_chips(cell["chips"])
    bench_run.enable_cache()
    from bench import check, model, session

    for seed in [int(s) for s in args.seeds.split(",")]:
        weights, window, server = session.serve(conf, spec, limits, seed, args.seconds, bench_run.log)
        finished = [r for r in window.requests if r.done]
        del server
        gc.collect()
        sample = check.sample_finished(finished, seed, limits["min_tokens"], limits["max_requests"])
        cmp = check.compare(weights, conf, model.factors(conf), sample, limits["max_served"], control=True)
        # the verdict a run would give, with the program and with the
        # control in its place
        cmp["program_correct"] = check.verdict(cmp["widest_gap"], cmp["tokens"], limits)[0]
        cmp["control_correct"] = check.verdict(cmp["control_widest_gap"], cmp["tokens"], limits)[0]
        cmp.update(cell=cell["name"], seed=seed, finished=len(finished))
        print(json.dumps(cmp), flush=True)
        del weights
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
