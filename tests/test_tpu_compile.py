"""Main-path programs compiled for a described TPU v5e (no chip needed).

The TPU compiler installed with JAX compiles for a chip that is
described, not attached.  These tests compile the serving decode kernel
and the fused-matmul GEMM natively (``interpret=False``) at real widths,
plus the whole full-width minicpm-2b decode step, so a tiling or
fast-memory rule the chip would refuse fails here.  Nothing runs.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and every test worker imports this
file.
"""

import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-chip compile can be written to the persistent cache
    but never read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("h,hkv,d,b,m,n", [
    pytest.param(36, 36, 64, 8, 64, 513, id="36-36-64"),  # MHA, group 1
    pytest.param(32, 8, 128, 8, 64, 513, id="32-8-128"),  # GQA, group 4
    # the benchmark cells' decode batches: minicpm-2b chat (B 32, max_len
    # 4096, 860 pool blocks) and mistral-nemo-12b-d10 long-doc (B 16,
    # max_len 9216, 5000 blocks)
    (36, 36, 64, 32, 256, 860),
    (32, 8, 128, 16, 576, 5000),
])
def test_paged_kernel_compiles_for_v5e(one_chip, h, hkv, d, b, m, n):
    from repro.kernels.paged_attention import paged_attention
    bs = 16
    compiled = jax.jit(functools.partial(
        paged_attention, interpret=False)).lower(
        _shape(one_chip, (b, h, d)),
        _shape(one_chip, (n, bs, hkv * d)),
        _shape(one_chip, (n, bs, hkv * d)),
        _shape(one_chip, (b, m), jnp.int32),
        _shape(one_chip, (b,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,k,n", [
    (256, 1440, 2304),      # minicpm-2b down-projection, K over 4 chips
    (1024, 5760, 2304),     # the whole down-projection on one chip
])
def test_matmul_tiled_compiles_for_v5e(one_chip, m, k, n):
    from repro.kernels.fused_matmul_rs import matmul_tiled
    compiled = jax.jit(functools.partial(
        matmul_tiled, interpret=False)).lower(
        _shape(one_chip, (m, k)), _shape(one_chip, (k, n))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_decode_step_fits_one_v5e(one_chip, monkeypatch):
    """The serving decode program of minicpm-2b at published widths
    (batch 8, 11 blocks of 16 per sequence) carries the native kernel
    and fits the chip's 16 GB."""
    from repro.kernels import ops
    from repro.models import decode_step_paged, init_pages, param_specs

    # this sandbox's backend is the CPU, which would pick interpret mode
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    cfg = get_config("minicpm-2b")
    b, m, n, bs = 8, 11, 89, 16
    on_chip = lambda tree: jax.tree.map(
        lambda s: _shape(one_chip, s.shape, s.dtype), tree)
    params = on_chip(param_specs(cfg))
    pages = on_chip(jax.eval_shape(lambda: init_pages(cfg, n, bs)))
    step = jax.jit(lambda p, pg, t, bt, c: decode_step_paged(
        p, cfg, pg, {"tokens": t}, bt, c, use_kernel=True),
        donate_argnums=(1,))
    compiled = step.lower(params, pages,
                          _shape(one_chip, (b, 1), jnp.int32),
                          _shape(one_chip, (b, m), jnp.int32),
                          _shape(one_chip, (b,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert used < 15 * 2**30, used
