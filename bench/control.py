"""Readings for the correctness limit of a cell, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20 [--fault exchange_left_out,half_batch]

For each seed, one process-local run of the cell at its own load (a
window of ``--seconds``), then, at every served position of the same
requests that a run compares (``session.to_compare``), the widest gap
of the program's token and of the fp8 control's token below the
float32 reference's best, and the verdict (``check.verdict``, as a run
decides ``correct``) that each gets against the limit in
``bench/limits/<cell>.json``.  With ``--fault`` (``bench/faults.py``;
several, comma-separated, taken in turn over the seeds) the same
server is then broken and fills its rows once more
(``harness.fill_rows``); the window's sample with one of those requests
from each chip's rows gets the verdict as ``fault_correct``.  The
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
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    bench = bench_run.load_json("BENCHMARK.json")
    cell = bench_run.find(bench["workloads"], args.workload, "workload")
    conf = bench_run.load_json("bench", "configs", f"{cell['config']}.json")
    spec = bench_run.load_json("bench", "traffic", f"{cell['traffic']}.json")
    limits = bench_run.load_json("bench", "limits", f"{cell['name']}.json")
    devices = bench_run.require_chips(cell["chips"])
    bench_run.enable_cache()
    from bench import check, faults, harness, session

    n = len(devices)
    planted = args.fault.split(",") if args.fault else []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        fault = planted[i % len(planted)] if planted else None
        weights, window, server = session.serve(conf, spec, limits, seed, args.seconds, bench_run.log, devices)
        sample, fill, chips = session.to_compare(server, window, spec, seed, limits, n, bench_run.log)
        broken = None
        if fault:
            faults.plant(server, fault)
            rows = harness.fill_rows(server, spec["prompt"]["min"], spec["output"]["min"], server.cfg.vocab_size, seed ^ session.FILL_SALT ^ 1)
            picked = check.one_per_chip(rows, server.batch, n, seed)
            broken = (sample + list({id(r): r for _, r in picked}.values()), len(picked))
        finished = sum(1 for r in window.requests if r.done)
        del server
        gc.collect()
        at = None if chips is None else (chips, n)
        cmp = check.compare(weights, conf, sample + fill, limits["max_served"], control=True)
        # the verdict a run would give, with the program and with the
        # control in its place
        cmp["program_correct"] = check.verdict(cmp["widest_gap"], cmp["tokens"], limits, at)[0]
        cmp["control_correct"] = check.verdict(cmp["control_widest_gap"], cmp["tokens"], limits, at)[0]
        cmp.update(cell=cell["name"], seed=seed, finished=finished, chips_compared=chips)
        if broken is not None:
            bad = check.compare(weights, conf, broken[0], limits["max_served"])
            ok, checks = check.verdict(bad["widest_gap"], bad["tokens"], limits, (broken[1], n) if n > 1 else None)
            cmp.update(fault=fault, fault_widest_gap=bad["widest_gap"], fault_off_best=bad["off_best"], fault_correct=ok, fault_checks=checks)
        print(json.dumps(cmp), flush=True)
        del weights
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
