"""Model code found by name: the default family reproduces, bit for bit,
what the benchmark computed before its equations moved into
``bench/families/dense.py`` (values recorded from those functions at
tiny widths on the CPU), and a family added as a new file, with a
configuration naming it, is used with no existing file edited."""

import hashlib
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, flops
from bench.tests.tiny import BENCH_DIR, load_config, run_tiny, tiny_conf, tiny_traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**31 + 7

#: sha256 over (tree path, bytes) of every leaf of make_weights(tiny, SEED)
WEIGHTS = {
    "minicpm-2b": "cb13a8fd2143082eafca246836e83251312c3e761aa690e3039acac604483590",
    "mistral-nemo-12b-d10": "d48666476ef67dc525dc1290f4d389b0162ee2997a7fed7b8585a25163142e2b",
}

#: every count, at tiny and at the files' own widths
COUNTS = {
    "minicpm-2b": {
        "tiny": (196608, 512000, 24140800, 754688, (171008, 190464), 25138176),
        "real": (5449388544, 368640000, 548164454400, 16466867712, (123125760, 137134080), 575421719040),
    },
    "mistral-nemo-12b-d10": {
        "tiny": (655360, 4096000, 101376000, 3284992, (1368064, 1523712), 104767488),
        "real": (6794772480, 163840000, 680910848000, 20437073920, (54722560, 15728640), 714889297920),
    },
}


def counts(conf):
    fam = families.of(conf)
    return (
        fam.flops_per_token(conf),
        fam.attention_flops(conf, 1000),
        flops.prefill_chunk_flops(conf, 100, 37),
        flops.decode_flops(conf, [5, 17, 300]),
        tuple(flops.paged_kernel_cost(conf, [1, 16, 17, 300], 16)),
        flops.step_flops(conf, [(100, 37), (3, 0)], [5, 17]),
    )


@pytest.mark.parametrize("name", sorted(WEIGHTS))
def test_default_family_weights_unchanged(name):
    conf = tiny_conf(name)
    assert "family" not in load_config(name)
    assert families.of(conf) is families.of(load_config(name))
    w = families.of(conf).make_weights(conf, SEED)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS[name]


@pytest.mark.parametrize("name", sorted(WEIGHTS))
def test_default_family_reference_and_control_unchanged(name):
    conf = tiny_conf(name)
    fam = families.of(conf)
    w = fam.make_weights(conf, SEED)
    toks = np.zeros(512, np.int32)
    toks[:40] = np.random.default_rng(11).integers(0, conf["vocab_size"], 40)
    want = np.load(os.path.join(DATA, "dense_reference_logits.npz"))
    for kind in ("reference", "control"):
        got = np.asarray(fam.logits(w, conf, fam.factors(conf), jnp.asarray(toks), 29, 8, control=kind == "control"))
        np.testing.assert_array_equal(got, want[f"{name}.{kind}"], err_msg=kind)


@pytest.mark.parametrize("name", sorted(COUNTS))
@pytest.mark.parametrize("size", ["tiny", "real"])
def test_default_family_counts_unchanged(name, size):
    conf = tiny_conf(name) if size == "tiny" else load_config(name)
    assert counts(conf) == COUNTS[name][size]


TOY = '''
"""A toy family: the dense equations with every count doubled."""
from bench.families.dense import *  # noqa: F401,F403
from bench.families import dense


def flops_per_token(conf):
    return 2 * dense.flops_per_token(conf)
'''


def test_added_family_is_found_by_name(tmp_path, monkeypatch):
    copy = tmp_path / "families"
    shutil.copytree(os.path.join(BENCH_DIR, "families"), copy, ignore=shutil.ignore_patterns("__pycache__"))
    (copy / "toy.py").write_text(TOY)
    monkeypatch.setattr(families, "FAMILIES_DIR", str(copy))
    conf = tiny_conf(family="toy")
    fam = families.of(conf)
    assert fam.__file__ == str(copy / "toy.py")
    dense = families.of(tiny_conf())
    assert fam.flops_per_token(conf) == 2 * dense.flops_per_token(conf)
    assert flops.decode_flops(conf, [5]) == 2 * dense.flops_per_token(conf) + dense.attention_flops(conf, 5)
    res = run_tiny(conf, tiny_traffic("chat"), monkeypatch=monkeypatch)
    assert res["correct"] is True


def test_unknown_family_raises():
    with pytest.raises(LookupError):
        families.of(tiny_conf(family="no-such-family"))
