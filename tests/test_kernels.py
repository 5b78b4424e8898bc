"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles in ref.py.

Kernels execute in interpret mode on CPU (the kernel body runs in Python);
on a real TPU the same pallas_call compiles to Mosaic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# --- TOPSIS kernel ------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 4, 100, 128, 1000, 4096])
@pytest.mark.parametrize("c", [2, 5, 8])
def test_topsis_kernel_sweep(n, c):
    key = jax.random.PRNGKey(n * 31 + c)
    mat = jax.random.uniform(key, (n, c), jnp.float32, 0.05, 10.0)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (c,), jnp.float32,
                           0.1, 1.0)
    benefit = jax.random.bernoulli(jax.random.fold_in(key, 2), shape=(c,))
    got = ops.topsis_closeness(mat, w, benefit)
    want = ref.topsis_closeness_ref(mat, w, benefit)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False),
                                           ("gpu", None)])
def test_interpret_mode_follows_platform(monkeypatch, platform, want):
    """cpu interprets, tpu compiles, any other platform raises naming
    itself — no silent interpreter fallback; an explicit flag wins."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert ops.resolve_interpret(False) is False
    assert ops.resolve_interpret(True) is True
    if want is None:
        with pytest.raises(RuntimeError, match=repr(platform)):
            ops.resolve_interpret()
        with pytest.raises(RuntimeError, match=repr(platform)):
            ops.topsis_closeness(jnp.ones((4, 5)), jnp.ones(5),
                                 jnp.ones(5, bool))
    else:
        assert ops.resolve_interpret() is want


@pytest.mark.parametrize("block_n", [128, 256, 2048])
def test_topsis_kernel_block_shapes(block_n):
    key = jax.random.PRNGKey(0)
    mat = jax.random.uniform(key, (700, 5), jnp.float32, 0.05, 10.0)
    w = jnp.ones((5,)) / 5
    benefit = jnp.array([0, 0, 1, 1, 1], bool)
    got = ops.topsis_closeness(mat, w, benefit, block_n=block_n)
    want = ref.topsis_closeness_ref(mat, w, benefit)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_topsis_kernel_matches_core_engine():
    """Kernel == repro.core.topsis.closeness (the paper-semantics engine)."""
    from repro.core.topsis import closeness
    key = jax.random.PRNGKey(3)
    mat = jax.random.uniform(key, (64, 5), jnp.float32, 0.1, 5.0)
    w = jnp.asarray([.2, .35, .15, .15, .15])
    benefit = jnp.array([0, 0, 1, 1, 1], bool)
    got = ops.topsis_closeness(mat, w, benefit)
    want = closeness(mat, w, benefit).closeness
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# --- RMSNorm kernel -------------------------------------------------------------
@pytest.mark.parametrize("shape", [(4, 64), (2, 7, 96), (1, 128), (3, 300),
                                   (256, 1024), (5, 2, 3, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel_sweep(shape, dtype):
    key = jax.random.PRNGKey(hash(shape) % (2 ** 31))
    x = jax.random.normal(key, shape, dtype)
    g = jax.random.normal(jax.random.fold_in(key, 1), (shape[-1],),
                          jnp.float32)
    got = ops.rmsnorm(x, g)
    want = ref.rmsnorm_ref(x, g)
    assert got.dtype == x.dtype and got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("block_rows", [8, 64, 512])
def test_rmsnorm_block_shapes(block_rows):
    x = jax.random.normal(jax.random.PRNGKey(0), (100, 384), jnp.float32)
    g = jnp.ones((384,))
    got = ops.rmsnorm(x, g, block_rows=block_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.rmsnorm_ref(x, g)),
                               atol=2e-5, rtol=2e-5)


# --- Flash attention kernel ------------------------------------------------------
@pytest.mark.parametrize("s,h,hkv,d", [
    (64, 4, 4, 32),          # MHA
    (128, 8, 2, 64),         # GQA 4:1
    (256, 4, 1, 64),         # MQA
    (96, 2, 2, 80),          # ragged seq + odd head dim
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(s, h, hkv, d, causal, dtype):
    key = jax.random.PRNGKey(s + h * 7 + d)
    ks = jax.random.split(key, 3)
    q = (jax.random.normal(ks[0], (2, h, s, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (2, hkv, s, d)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (2, hkv, s, d)) * 0.5).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=causal)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_sliding_window(window):
    """Mixtral-style SWA against the masked reference."""
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64)) * 0.5
    k = jax.random.normal(ks[1], (1, 2, 128, 64)) * 0.5
    v = jax.random.normal(ks[2], (1, 2, 128, 64)) * 0.5
    got = ops.flash_attention(q, k, v, causal=True, window=window)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_shapes(bq, bk):
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64)) * 0.5
    k = jax.random.normal(ks[1], (1, 2, 256, 64)) * 0.5
    v = jax.random.normal(ks[2], (1, 2, 256, 64)) * 0.5
    got = ops.flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_matches_model_sdpa():
    """Kernel semantics == the model's _sdpa (what runs in the dry-run HLO)."""
    from repro.models.layers import _sdpa
    key = jax.random.PRNGKey(13)
    ks = jax.random.split(key, 3)
    B, S, H, D = 2, 64, 4, 32
    q = jax.random.normal(ks[0], (B, S, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, D)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, D)) * 0.5
    want = _sdpa(q, k, v, causal=True, window=None)          # (B, S, H, D)
    got = ops.flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want.transpose(0, 2, 1, 3)),
                               atol=2e-5, rtol=2e-5)
