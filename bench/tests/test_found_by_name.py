"""A new cell, mix or per-layer metric is files and entries, found by
name: a metric reader and a traffic file added in a copy of the
benchmark's directories are used without an edit to any existing file."""

import json
import os
import shutil

from bench import session
from bench.tests.tiny import BENCH_DIR, run_tiny, tiny_conf, tiny_traffic

import importlib.util

spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH_DIR, "run.py"))
bench_run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench_run)

NEW_READER = '''
def read(run):
    """Steps the window ran (a metric a later change might add)."""
    return float(len(run.window.steps))
'''


def test_added_metric_and_traffic_are_found(tmp_path, monkeypatch):
    metrics = tmp_path / "metrics"
    shutil.copytree(os.path.join(BENCH_DIR, "metrics"), metrics)
    (metrics / "window_steps.py").write_text(NEW_READER)
    monkeypatch.setattr(session, "METRICS_DIR", str(metrics))
    traffic = tiny_traffic("chat")
    traffic["rate_rps"] = 5.0
    (tmp_path / "chat-fast.json").write_text(json.dumps(traffic))
    loaded = json.loads((tmp_path / "chat-fast.json").read_text())
    res = run_tiny(tiny_conf(), loaded, metrics=["window_steps", "out_tok_s"], monkeypatch=monkeypatch)
    assert res["metrics"]["window_steps"]["value"] > 0
    assert res["metrics"]["out_tok_s"]["value"] > 0


def test_cell_metrics_by_entry():
    bench = {
        "end_to_end": [{"name": "out_tok_s", "unit": "tokens/s"}, {"name": "ttft_p50_ms", "unit": "ms", "workloads": ["a"]}],
        "per_layer": [
            {"name": "window_steps", "unit": "steps", "moves": "out_tok_s", "workloads": ["b"]},
            {"name": "decode_step_ms", "unit": "ms", "moves": "out_tok_s"},
        ],
    }
    cell = {"name": "b"}
    assert [m["name"] for m in bench_run.cell_metrics(bench, cell, False)] == ["out_tok_s"]
    assert [m["name"] for m in bench_run.cell_metrics(bench, cell, True)] == ["window_steps", "decode_step_ms"]
