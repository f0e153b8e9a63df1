"""Dry-run plumbing on an 8-virtual-device debug mesh (subprocess; the
512-device production sweep is exercised by repro.launch.dryrun itself
and its artifacts are validated in test_dryrun_artifacts.py)."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.multidev, pytest.mark.slow]

_SCRIPT = r"""
import os, json, dataclasses
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, SHAPES
from repro.launch import specs as sp
from repro.launch.mesh import make_debug_mesh
from repro.launch.roofline import parse_collective_bytes, collective_total
from repro.sharding import rules
from repro.train.state import abstract_train_state, train_state_shardings
from repro.train.step import make_train_step, make_prefill_step, make_decode_step
from repro.optim.adamw import AdamWConfig
from repro.models import transformer as tf

results = {}
mesh = make_debug_mesh(multi_pod=True)   # (2,2,2): pod axis proof
policy = rules.for_mesh(mesh)

for name in ("yi-34b", "olmoe-1b-7b", "falcon-mamba-7b",
             "recurrentgemma-9b", "whisper-medium"):
    cfg = get_config(name).reduced()
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=256,
                                global_batch=4)
    state_specs = abstract_train_state(cfg)
    state_sh = train_state_shardings(state_specs, mesh, policy)
    bs = sp.train_input_specs(cfg, shape)
    bsh = {k: NamedSharding(mesh, s)
           for k, s in rules.batch_sharding_specs(policy, bs).items()}
    step = make_train_step(cfg, AdamWConfig())
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=(state_sh, bsh),
                           donate_argnums=(0,)).lower(state_specs, bs).compile()
    ca = compiled.cost_analysis()
    # jax used to return [dict]; newer versions return the dict itself
    cost = dict(ca[0] if isinstance(ca, (list, tuple)) else ca)
    coll = parse_collective_bytes(compiled.as_text())
    results[f"{name}/train"] = {
        "flops_positive": float(cost.get("flops", 0)) > 0,
        "has_collectives": collective_total(coll) > 0,
        "mem_ok": compiled.memory_analysis() is not None,
    }
print("JSON" + json.dumps(results))
"""


def test_dryrun_debug_mesh_multipod():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON")][-1]
    results = json.loads(line[4:])
    for cell, checks in results.items():
        for k, ok in checks.items():
            assert ok, (cell, k)
