"""Paged KV cache numerics: the Pallas block-indexed decode kernel vs
its jnp oracle, and the paged model path (chunked prefill + decode) vs
the dense-cache ``decode_step`` logits on two model families
(decoder-only + vision), fp32 tolerance.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention import paged_attention, tile_pages
from repro.kernels.ref import paged_attention_ref
from repro.models import (decode_step, decode_step_paged, forward_paged,
                          forward_train, init_pages, init_params, prefill)


def _kernel(q, kp, vp, bt, lengths):
    """The kernel reads the pool with heads merged: [N, bs, Hkv * D]."""
    n, bs = kp.shape[:2]
    return paged_attention(q, kp.reshape(n, bs, -1), vp.reshape(n, bs, -1),
                           bt, lengths, interpret=True)


# ------------------------- kernel vs oracle --------------------------- #
def _tables_and_lengths(case, rng, b, bs, n, m, tile):
    """Block tables [B, M] and lengths [B] for one named case; ``tile``
    is the kernel's tokens per tile at these widths."""
    ids = rng.permutation(np.arange(1, n))
    bt = np.resize(ids[:b * m], (b, m)).astype(np.int32)
    if case in ("random", "bf16_pool"):
        lengths = rng.integers(1, m * bs + 1, size=(b,))
        if case == "bf16_pool":
            lengths[0] = m * bs
    elif case == "idle_beside_long":      # an idle row reads scratch block 0
        bt[0] = 0
        lengths = [1, m * bs, m * bs - 3 * tile // 2]
    elif case == "tile_edges":
        lengths = [tile, tile + 1, 2 * tile + 1]
    elif case == "m_not_multiple":        # M is not a whole number of tiles
        assert m % (tile // bs)
        lengths = [m * bs, m * bs - bs - 5, m * bs - tile]
    elif case == "several_tiles":
        lengths = [m * bs - 7]
    elif case == "shared_pages":          # a shared prefix, a page twice
        bt[1, :m // 2] = bt[0, :m // 2]
        bt[2, 3] = bt[0, 5]
        lengths = [m * bs, m * bs - 9, m * bs // 2 + 1]
    else:
        raise ValueError(case)
    return jnp.asarray(bt), jnp.asarray(np.asarray(lengths, np.int32))


_OLD = lambda *args: pytest.param(*args, "random", id="-".join(map(str, args)))


@pytest.mark.parametrize("b,h,hkv,d,bs,n,m,case", [
    _OLD(2, 4, 2, 16, 8, 10, 3),
    _OLD(3, 8, 1, 32, 16, 12, 2),        # MQA
    _OLD(1, 4, 4, 64, 8, 6, 4),          # MHA (group = 1)
    _OLD(4, 8, 2, 128, 16, 24, 5),
    (3, 4, 2, 64, 16, 160, 70, "idle_beside_long"),
    (3, 8, 2, 64, 16, 160, 70, "tile_edges"),
    (3, 8, 8, 32, 16, 160, 70, "m_not_multiple"),
    (1, 4, 1, 128, 8, 240, 200, "several_tiles"),
    (3, 8, 4, 32, 16, 100, 40, "shared_pages"),
    (4, 8, 2, 128, 16, 160, 70, "bf16_pool"),
])
def test_paged_kernel_matches_ref(b, h, hkv, d, bs, n, m, case):
    """f32 pools at f32 tolerance; a bf16 pool against the f32 oracle on
    the same (bf16) values, within the output's bf16 rounding."""
    rng = np.random.default_rng(b * 31 + n)
    dtype = jnp.bfloat16 if case == "bf16_pool" else jnp.float32
    q = jnp.asarray(rng.normal(size=(b, h, d))).astype(dtype)
    kp = jnp.asarray(rng.normal(size=(n, bs, hkv, d))).astype(dtype)
    vp = jnp.asarray(rng.normal(size=(n, bs, hkv, d))).astype(dtype)
    tile = tile_pages(bs, hkv * d, dtype, m) * bs
    bt, lengths = _tables_and_lengths(case, rng, b, bs, n, m, tile)
    got = _kernel(q, kp, vp, bt, lengths)
    f32 = lambda x: x.astype(jnp.float32)
    want = paged_attention_ref(f32(q), f32(kp), f32(vp), bt, lengths)
    tol = 1e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)


def test_paged_kernel_bf16():
    rng = np.random.default_rng(7)
    b, h, hkv, d, bs, n, m = 2, 4, 2, 32, 8, 8, 2
    q = jnp.asarray(rng.normal(size=(b, h, d))).astype(jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(n, bs, hkv, d))).astype(jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(n, bs, hkv, d))).astype(jnp.bfloat16)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lengths = jnp.asarray([9, 16], jnp.int32)
    got = _kernel(q, kp, vp, bt, lengths)
    want = paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_paged_kernel_short_rows_ignore_stale_blocks():
    """Slots past a row's length must not leak into the output even
    when the pool holds other requests' live data there."""
    rng = np.random.default_rng(3)
    b, h, hkv, d, bs, n, m = 2, 4, 2, 16, 8, 6, 2
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n, bs, hkv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n, bs, hkv, d)), jnp.float32)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lengths = jnp.asarray([3, 11], jnp.int32)
    base = _kernel(q, kp, vp, bt, lengths)
    # clobber everything outside the valid prefixes
    kp2 = kp.at[2].set(99.0).at[4, 3:].set(-99.0).at[5].set(99.0)
    vp2 = vp.at[2].set(99.0).at[4, 3:].set(-99.0).at[5].set(99.0)
    again = _kernel(q, kp2, vp2, bt, lengths)
    np.testing.assert_allclose(np.asarray(base), np.asarray(again),
                               rtol=1e-6, atol=1e-6)


# ------------------ paged model path vs dense cache ------------------- #
def _paged_vs_dense(arch, chunk):
    """Chunked paged prefill + decode vs dense prefill/decode + full
    forward ground truth; returns max abs logit errors."""
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    B, S = 1, 12
    toks = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    n_soft = 0
    if cfg.frontend == "vision":
        from repro.models.frontend import vision_patches
        batch["soft_emb"] = vision_patches(key, cfg, B)
        n_soft = batch["soft_emb"].shape[1]

    lg_dense, cache = prefill(params, cfg, batch)
    nxt = jnp.argmax(lg_dense[:, -1], -1).astype(jnp.int32)
    # headroom so the dense decode does not overwrite the last prompt KV
    pad = [(0, 0), (0, 0), (0, 8), (0, 0), (0, 0)]
    cache = dict(cache, k=jnp.pad(cache["k"], pad),
                 v=jnp.pad(cache["v"], pad))
    lg_dense2, _ = decode_step(params, cfg, cache, {"tokens": nxt[:, None]})
    full = dict(batch, tokens=jnp.concatenate([toks, nxt[:, None]], 1))
    lg_full, _ = forward_train(params, cfg, full)

    bs = 8
    pages = init_pages(cfg, 10, bs)
    bt = jnp.asarray([[1, 2, 3, 4, 5, 6]], jnp.int32)
    ctx = jnp.zeros((B,), jnp.int32)
    off, first, lg_paged = 0, True, None
    while off < S:
        n = min(chunk, S - off)
        tb = jnp.zeros((B, chunk), jnp.int32).at[:, :n].set(
            toks[:, off:off + n])
        cb = {"tokens": tb}
        if first and n_soft:
            cb["soft_emb"] = batch["soft_emb"]
        lg_paged, pages = forward_paged(
            params, cfg, pages, cb, bt, ctx, jnp.full((B,), n, jnp.int32))
        ctx = ctx + n + (n_soft if first else 0)
        off += n
        first = False
    errs = {"prefill": float(jnp.max(jnp.abs(
        lg_paged[:, (S % chunk or chunk) - 1] - lg_dense[:, -1])))}
    for uk in (False, True):
        lg_p2, _ = decode_step_paged(params, cfg, pages,
                                     {"tokens": nxt[:, None]}, bt, ctx,
                                     use_kernel=uk)
        name = "kernel" if uk else "jnp"
        errs[f"decode_{name}_vs_dense"] = float(jnp.max(jnp.abs(
            lg_p2[:, 0] - lg_dense2[:, 0])))
        errs[f"decode_{name}_vs_full"] = float(jnp.max(jnp.abs(
            lg_p2[:, 0] - lg_full[:, -1])))
    return errs


@pytest.mark.parametrize("arch", ["minicpm-2b", "llava-next-34b"])
@pytest.mark.parametrize("chunk", [8, 12])
def test_paged_matches_dense_cache(arch, chunk):
    """Decoder-only + vision families: paged chunked prefill and both
    decode paths (gathered jnp and the Pallas kernel) reproduce the
    dense-cache logits at fp32 tolerance."""
    errs = _paged_vs_dense(arch, chunk)
    for name, err in errs.items():
        assert err < 2e-4, (name, err, errs)


def test_paged_rejects_constant_state_families():
    cfg = get_config("falcon-mamba-7b").reduced()
    with pytest.raises(NotImplementedError):
        init_pages(cfg, 8, 16)


def test_scratch_block_isolates_invalid_writes():
    """Padded tail positions must land in scratch block 0, leaving
    allocated blocks untouched."""
    cfg = dataclasses.replace(get_config("minicpm-2b").reduced(),
                              dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    pages = init_pages(cfg, 6, 8)
    bt = jnp.asarray([[1, 2]], jnp.int32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                              cfg.vocab_size)
    # only 5 of 8 positions valid
    _, pages2 = forward_paged(params, cfg, pages, {"tokens": toks}, bt,
                              jnp.zeros((1,), jnp.int32),
                              jnp.asarray([5], jnp.int32))
    k = np.asarray(pages2["k"])
    assert np.any(k[:, 1, :5] != 0), "valid positions must be written"
    assert np.all(k[:, 1, 5:] == 0), "padded tail leaked into block 1"
    assert np.all(k[:, 2] == 0), "padded tail leaked into block 2"
    assert np.any(k[:, 0] != 0), "scratch block should absorb the tail"
    assert np.all(k[:, 3:] == 0), "unallocated blocks must stay clean"
