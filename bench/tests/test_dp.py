"""A cell over four chips: the rest of a run on 4 virtual CPU devices,
in a child process (the device count is fixed when JAX starts).  The
server runs under a ``data`` mesh with four times the configuration's
rows, the weights are made where the server keeps them (no second
copy), the run is correct with every chip's rows compared, and with a
fault planted (``bench/faults.py``) it is not: with the window's
requests spread over the chips, and with them all on the first chip's
rows, as the pool shared by all rows leaves them on the chip."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHILD = r"""
import json, sys
import jax
from bench import faults, families, harness
from bench.tests.tiny import run_tiny, tiny_conf, tiny_traffic

fault, per_chip = sys.argv[1], int(sys.argv[2])
devices = jax.devices()[:4]
assert len(devices) == 4, devices
conf = tiny_conf()
# one row a chip: concurrent requests span the chips; eight: the
# traffic's 8 requests all sit in the first chip's rows
conf["server"]["batch"] = per_chip
fam = families.of(conf)
weights = fam.make_weights(conf, 5, harness.weight_sharding(devices))
server = harness.build_server(fam.arch_config(conf), weights, conf["server"], devices)
ptrs = lambda tree: [s.data.unsafe_buffer_pointer() for x in jax.tree.leaves(tree) for s in x.addressable_shards]
facts = {
    "mesh": dict(server.mesh.shape),
    "rows": server.batch,
    "weights_shared": ptrs(server.params) == ptrs(weights),
    "weights_on": len(jax.tree.leaves(weights)[0].sharding.device_set),
}
del server, weights

class Patch:
    def setattr(self, obj, name, value):
        setattr(obj, name, value)

build = harness.build_server
window_rows = set()

def build_broken(cfg, params, server_conf, devs):
    server = build(cfg, params, server_conf, devs)
    sched = server.scheduler
    admit = sched.admit

    def admit_seen(step, now=None):
        got = admit(step, now)
        window_rows.update(i for i, r in enumerate(sched.slots) if r is not None and r.rid < harness.FILL_RID)
        return got

    sched.admit = admit_seen
    if fault != "none":
        faults.plant(server, fault)
    return server

harness.build_server = build_broken
res = run_tiny(conf, tiny_traffic("chat"), metrics=["out_tok_s", "itl_p95_ms"], monkeypatch=Patch(), chips=4)
facts["window_rows_max"] = max(window_rows)
print(json.dumps({"facts": facts, "result": res}))
"""


def run_child(fault, per_chip=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4", PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", CHILD, fault, str(per_chip)], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def assert_verdict(res, fault):
    assert res["device"]["count"] == 4
    assert res["metrics"]["out_tok_s"]["value"] > 0
    if fault == "none":
        assert res["correct"] is True
        assert res["checks"]["tokens_compared"]["value"] >= 32
        assert res["checks"]["chips_compared"] == {"value": 4, "limit": 4}
    else:
        assert res["correct"] is False
        assert res["checks"]["widest_logit_gap"]["value"] > res["checks"]["widest_logit_gap"]["limit"]


@pytest.mark.parametrize("fault", ["none", "exchange_left_out", "state_unchanged"])
def test_four_chip_cell(fault):
    out = run_child(fault)
    assert out["facts"] == {"mesh": {"data": 4}, "rows": 4, "weights_shared": True, "weights_on": 4, "window_rows_max": out["facts"]["window_rows_max"]}
    assert_verdict(out["result"], fault)


@pytest.mark.parametrize("fault", ["none", "exchange_left_out", "half_batch"])
def test_four_chip_cell_load_on_first_chip(fault):
    out = run_child(fault, per_chip=8)
    assert out["facts"]["rows"] == 32
    assert out["facts"]["window_rows_max"] < 8  # the window never left the first chip's rows
    assert_verdict(out["result"], fault)
