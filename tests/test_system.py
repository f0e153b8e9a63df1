"""End-to-end behaviour tests: train loop learns, checkpoint-resume is
bit-stable, serving loop decodes."""

import tempfile

import numpy as np
import jax
import pytest

from repro.configs.base import ArchConfig

pytestmark = pytest.mark.slow


def tiny_cfg() -> ArchConfig:
    return ArchConfig(name="tiny-dense", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
                      vocab_size=128, dtype="float32")


def test_train_loss_decreases_and_resumes():
    import repro.launch.train as T
    cfg = tiny_cfg()
    orig = T.get_config
    T.get_config = lambda name: cfg if name == cfg.name else orig(name)
    try:
        with tempfile.TemporaryDirectory() as ckpt:
            losses = T.run(cfg.name, steps=30, batch_size=4, seq_len=64,
                           reduced=False, ckpt_dir=ckpt, ckpt_every=10,
                           lr=3e-3, log_every=100)
            assert losses[-1] < losses[0], (losses[0], losses[-1])
            # resume continues from the last committed step
            more = T.run(cfg.name, steps=35, batch_size=4, seq_len=64,
                         reduced=False, ckpt_dir=ckpt, ckpt_every=100,
                         lr=3e-3, log_every=100)
            assert len(more) == 5  # only the new steps ran
    finally:
        T.get_config = orig


def test_serving_loop_decodes():
    from repro.launch.serve import BatchedServer, Request
    from repro.models import init_params
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = BatchedServer(cfg, params, batch_size=2, max_len=64)
    rng = np.random.default_rng(0)
    for rid in range(4):
        server.submit(Request(rid=rid,
                              prompt=rng.integers(0, 128, 8,
                                                  dtype=np.int32),
                              max_new_tokens=4))
    results = server.run()
    assert sorted(results) == [0, 1, 2, 3]
    assert all(len(v) == 4 for v in results.values())


def test_serve_cli_reduced_reports_sizes_and_memory(monkeypatch, tmp_path,
                                                   capsys):
    from repro.launch import serve
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    serve.main(["--reduced", "--requests", "2", "--batch", "2",
                "--prompt-len", "8", "--new-tokens", "4"])
    out = capsys.readouterr().out
    assert f"compile cache: {tmp_path}" in out
    assert "2 layers, d_model 64" in out and "kv pool" in out
    assert "decode program: arguments" in out
    assert "2 requests, 8 tokens" in out


def test_compile_cache_takes_the_environment_dir(monkeypatch, tmp_path):
    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads it


def test_compile_cache_defaults_to_var_in_the_checkout(monkeypatch):
    import os
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch.compile_cache import (DEFAULT_DIR, ENV_VAR,
                                            enable_compile_cache)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_DIR == os.path.join(repo, "var", "jax_cache")
    monkeypatch.delenv(ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("arch", ["minicpm-2b", "falcon-mamba-7b"])
def test_device_param_build_matches_eager_init(arch):
    """The jitted build (one program, stacked layers made by vmap) gives
    the eager build's weights (within one ulp: a fused ``log`` may round
    differently), and the vmapped stack equals the per-layer list
    stacked."""
    from repro.configs import get_config
    from repro.launch.serve import build_params
    from repro.models import init_params
    from repro.models import transformer as T
    cfg = get_config(arch).reduced()
    eager = init_params(jax.random.PRNGKey(0), cfg)
    built = build_params(cfg, seed=0)
    assert jax.tree.structure(eager) == jax.tree.structure(built)
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(built)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=0,
                                   rtol=float(jax.numpy.finfo(a.dtype).eps))
    if cfg.family != "ssm":
        keys = jax.random.split(jax.random.PRNGKey(1), cfg.num_layers)
        make = lambda k: T._decoder_layer(k, cfg, cfg.activation_dtype)
        stacked = T._stacked(make, keys)
        listed = T._stack([make(k) for k in keys])
        for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(listed)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_microbatched_step_matches_full_batch():
    from repro.models import init_params
    from repro.optim.adamw import AdamWConfig
    from repro.train.state import init_train_state
    from repro.train.step import make_train_step
    cfg = tiny_cfg()
    key = jax.random.PRNGKey(3)
    params = init_params(key, cfg)
    batch = {
        "tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.fold_in(key, 1), (4, 32),
                                     0, cfg.vocab_size),
    }
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    s1, m1 = jax.jit(make_train_step(cfg, opt, microbatches=1))(
        init_train_state(params), batch)
    s2, m2 = jax.jit(make_train_step(cfg, opt, microbatches=2))(
        init_train_state(params), batch)
    a = jax.tree.leaves(s1.params)[2]
    b = jax.tree.leaves(s2.params)[2]
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-4,
                               atol=2e-5)


def test_elastic_restart_end_to_end(tmp_path=None):
    """Node-failure drill: train -> fail a host -> plan the shrunken mesh
    -> restore the committed checkpoint -> continue training on the
    smaller data axis with bit-identical parameters."""
    import tempfile
    from repro.checkpoint import CheckpointManager
    from repro.data.pipeline import DataConfig, SyntheticLMDataset
    from repro.models import init_params
    from repro.optim.adamw import AdamWConfig
    from repro.runtime import plan_elastic_remesh
    from repro.train.state import init_train_state
    from repro.train.step import make_train_step

    cfg = tiny_cfg()
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=100)
    step_fn = jax.jit(make_train_step(cfg, opt))
    state = init_train_state(init_params(jax.random.PRNGKey(0), cfg))
    data16 = SyntheticLMDataset(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=16))

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        for step in range(6):
            b = data16.batch(step)
            state, m = step_fn(state, {k: jax.numpy.asarray(v)
                                       for k, v in b.items()})
        mgr.save(6, state)

        # host h3 dies: plan the shrunken mesh
        plan = plan_elastic_remesh(
            mesh_shape=(16, 16), axis_names=("data", "model"),
            hosts_per_slice=1, failed_hosts={"h3"},
            all_hosts=[f"h{i}" for i in range(16)], restore_step=6)
        assert plan.new_mesh == (8, 16)       # data axis 16 -> 8
        assert plan.restore_step == 6

        # restart: restore + continue with the smaller data degree
        step_r, state_r, _ = mgr.restore(state)
        assert step_r == 6
        a = jax.tree.leaves(state.params)[1]
        b_ = jax.tree.leaves(state_r.params)[1]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
        data8 = SyntheticLMDataset(
            DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                       global_batch=8))
        losses = []
        for step in range(6, 12):
            b = data8.batch(step)
            state_r, m = step_fn(state_r, {k: jax.numpy.asarray(v)
                                           for k, v in b.items()})
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
