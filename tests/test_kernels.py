"""Pallas kernels vs ref.py oracles: shape/dtype sweeps in interpret
mode (CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ops import flash_attention, multi_add
from repro.kernels.ref import flash_attention_ref, multi_add_ref


@pytest.mark.parametrize("k", [2, 3, 8, 17])
@pytest.mark.parametrize("n", [128, 512, 1000, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_multi_add_sweep(k, n, dtype):
    x = jax.random.normal(jax.random.PRNGKey(k * n), (k, n)).astype(dtype)
    got = multi_add(x)
    want = multi_add_ref(x)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,h,hkv,s,d", [
    (1, 2, 2, 128, 32),
    (2, 4, 2, 256, 64),
    (1, 8, 1, 256, 64),     # MQA
    (2, 4, 4, 128, 128),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, h, hkv, s, d, causal):
    keys = jax.random.split(jax.random.PRNGKey(b * s + h), 3)
    q = jax.random.normal(keys[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, hkv, s, d), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_window(window):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    b, h, s, d = 1, 4, 256, 64
    q = jax.random.normal(keys[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, h, s, d), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64)
    want = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    b, h, s, d = 1, 2, 128, 64
    q = jax.random.normal(keys[0], (b, h, s, d)).astype(jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, h, s, d)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, h, s, d)).astype(jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=64, block_k=64)
    want = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_jax_chunked_attention_matches_kernel_oracle():
    """The pure-JAX chunked path used by the dry-run model == kernel
    oracle."""
    from repro.models.layers import attention
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    b, s, h, hkv, d = 2, 4096, 4, 2, 32   # s > chunk threshold
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, hkv, d), jnp.float32)
    got = attention(q, k, v, causal=True)           # [B, S, H, D]
    want = flash_attention_ref(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                               jnp.moveaxis(v, 1, 2), causal=True)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(got, 1, 2)),
                               np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("b,s,d,n,block_d,chunk", [
    (1, 32, 16, 8, 16, 16),
    (2, 64, 32, 8, 16, 32),
    (2, 128, 64, 16, 32, 64),
    (1, 64, 48, 16, 16, 16),
])
def test_selective_scan_sweep(b, s, d, n, block_d, chunk):
    from repro.kernels.ops import selective_scan
    from repro.kernels.ref import selective_scan_ref
    ks = jax.random.split(jax.random.PRNGKey(b * s + d), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, d)))
    x = jax.random.normal(ks[1], (b, s, d))
    bb = jax.random.normal(ks[2], (b, s, n))
    c = jax.random.normal(ks[3], (b, s, n))
    a = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.5)
    h0 = jax.random.normal(ks[5], (b, d, n))
    y_k, h_k = selective_scan(dt, x, bb, c, a, h0, block_d=block_d,
                              chunk=chunk)
    y_r, h_r = selective_scan_ref(dt, x, bb, c, a, h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               rtol=1e-4, atol=1e-4)


def test_selective_scan_bf16_inputs():
    from repro.kernels.ops import selective_scan
    from repro.kernels.ref import selective_scan_ref
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    b, s, d, n = 1, 32, 16, 8
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, d))).astype(
        jnp.bfloat16)
    x = jax.random.normal(ks[1], (b, s, d)).astype(jnp.bfloat16)
    bb = jax.random.normal(ks[2], (b, s, n)).astype(jnp.bfloat16)
    c = jax.random.normal(ks[3], (b, s, n)).astype(jnp.bfloat16)
    a = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.5)
    h0 = jnp.zeros((b, d, n), jnp.float32)
    y_k, h_k = selective_scan(dt, x, bb, c, a, h0, block_d=16, chunk=16)
    y_r, h_r = selective_scan_ref(dt, x, bb, c, a, h0)
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("b,s,d,n,block_d,chunk", [
    (1, 32, 16, 8, 16, 16),
    (2, 64, 32, 8, 16, 32),
])
def test_selective_scan_backward_kernel(b, s, d, n, block_d, chunk):
    """Backward (flash-style recompute) kernel vs jax.grad of the
    oracle, for every input cotangent."""
    from repro.kernels.selective_scan import selective_scan_trainable
    from repro.kernels.ref import selective_scan_ref
    ks = jax.random.split(jax.random.PRNGKey(7 * b + s), 7)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, s, d)))
    x = jax.random.normal(ks[1], (b, s, d))
    bb = jax.random.normal(ks[2], (b, s, n))
    c = jax.random.normal(ks[3], (b, s, n))
    a = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.5)
    h0 = jax.random.normal(ks[5], (b, d, n))
    w = jax.random.normal(ks[6], (b, s, d))

    def lk(*args):
        y, hf = selective_scan_trainable(*args, block_d, chunk, True)
        return jnp.sum(y * w) + 0.5 * jnp.sum(hf)

    def lr(*args):
        y, hf = selective_scan_ref(*args)
        return jnp.sum(y * w) + 0.5 * jnp.sum(hf)

    gk = jax.grad(lk, argnums=tuple(range(6)))(dt, x, bb, c, a, h0)
    gr = jax.grad(lr, argnums=tuple(range(6)))(dt, x, bb, c, a, h0)
    for k_, r_ in zip(gk, gr):
        denom = float(jnp.max(jnp.abs(r_))) + 1e-9
        assert float(jnp.max(jnp.abs(k_ - r_))) / denom < 1e-4


def test_mamba_block_kernel_path_matches_jnp():
    """The fused-kernel mamba block (fwd + grad) == the chunked jnp
    path."""
    from repro.models.ssm import init_mamba_params, mamba_block
    key = jax.random.PRNGKey(0)
    d_model, di, n, r = 32, 64, 8, 4
    p = init_mamba_params(key, d_model, di, n, r, 4, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, d_model))
    y1, st1 = mamba_block(x, p, ssm_state=n, use_kernel=False)
    y2, st2 = mamba_block(x, p, ssm_state=n, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)
    g1 = jax.grad(lambda x: mamba_block(x, p, ssm_state=n)[0].sum())(x)
    g2 = jax.grad(lambda x: mamba_block(x, p, ssm_state=n,
                                        use_kernel=True)[0].sum())(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-3, atol=1e-5)
