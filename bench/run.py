"""The benchmark: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell; the cell names a configuration file
(``bench/configs/<config>.json``) and a traffic file
(``bench/traffic/<traffic>.json``); the configuration names the family
module that holds its equations (``bench/families/<family>.py``); each
metric the cell reports is read by ``bench/metrics/<metric>.py``; the
limits of the correctness check are in ``bench/limits/<cell>.json``.  A
new cell, configuration, family, mix or metric is a new file and a new
entry, never an edit here.  A cell on several chips serves over a
``data`` mesh of them.

The run exits non-zero, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for.  It prints how late the generator
ran, the KV pool and the peak device memory on earlier lines, the
compared numbers with their limits as the last lines of standard error,
and one JSON result line as the last line of standard output.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

#: JAX's persistent compilation cache, at a fixed path in the checkout
#: unless the environment names one
CACHE_DIR = os.path.join(ROOT, "bench", ".cache", "jax")
TRACE_DIR = os.path.join(ROOT, "bench", ".cache", "trace")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench, cell, trace: bool):
    """The metric entries this cell reports: end-to-end ones without a
    ``workloads`` key or listing the cell; with a trace, the per-layer
    ones listing the cell, or without the key where the cell reports
    the end-to-end metric they move."""
    e2e = [m for m in bench["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"] if cell["name"] in m.get("workloads", []) or ("workloads" not in m and m["moves"] in names)]


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        log(f"needs {n} TPU chip(s); JAX finds {len(devs)} {devs[0].platform} device(s)")
        sys.exit(3)
    return devs[:n]


def enable_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = load_json("BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    conf = load_json("bench", "configs", f"{cell['config']}.json")
    spec = load_json("bench", "traffic", f"{cell['traffic']}.json")
    limits = load_json("bench", "limits", f"{cell['name']}.json")
    metrics = cell_metrics(bench, cell, bool(args.trace))
    devices = require_chips(cell["chips"])
    chip_t0 = time.monotonic()
    log(f"chip held {chip_t0 - PROCESS_T0:.3f} s after process start")
    from bench import session

    result = session.run_cell(conf, spec, limits, metrics, args.seed, args.seconds, bool(args.trace), devices, chip_t0, enable_cache(), TRACE_DIR, log)
    checks = result.pop("checks")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
