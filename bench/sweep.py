"""Knee sweep of an open-loop cell, on the chip, in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 30 --rates 1,2,3

For each rate (requests per second) one window is served from the same set-up, and one line
reports the time to first token, tokens/s and the requests still
waiting when the window closed.  The highest rate without a growing
queue is the knee; a cell's traffic file takes about 0.8 of it.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as bench_run  # bench/run.py: paths, cache, chip look


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = bench_run.load_json("BENCHMARK.json")
    cell = bench_run.find(bench["workloads"], args.workload, "workload")
    conf = bench_run.load_json("bench", "configs", f"{cell['config']}.json")
    spec = bench_run.load_json("bench", "traffic", f"{cell['traffic']}.json")
    devices = bench_run.require_chips(cell["chips"])
    bench_run.enable_cache()
    from bench import families, gen, harness
    from bench.metrics import _common

    fam = families.of(conf)
    cfg = fam.arch_config(conf)
    weights = fam.make_weights(conf, args.seed, harness.weight_sharding(devices))
    server = harness.build_server(cfg, weights, conf["server"], devices)
    harness.warm_up(server, cfg.vocab_size, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = gen.generate(dict(spec, rate_rps=rate), args.seed, args.seconds, cfg.vocab_size)
        w = harness.run_window(server, traffic, args.seconds, drain_s=0.0)
        waiting = sum(1 for s in w.reqs.values() if s.admit is None or s.admit > w.t1)
        run = type("R", (), {"window": w})
        ttft = _common.ttft_ms(run)
        out = sum(1 for s in w.reqs.values() for t in s.tokens if w.in_window(t)) / w.seconds
        print(json.dumps({"rate": rate, "requests": len(traffic), "ttft_p50_ms": float(np.percentile(ttft, 50)), "ttft_p90_ms": float(np.percentile(ttft, 90)), "out_tok_s": out, "waiting_at_close": waiting, "steps": len(w.steps)}), flush=True)
        while server.has_work():  # drain before the next rate
            server.step_once()
    return 0


if __name__ == "__main__":
    sys.exit(main())
