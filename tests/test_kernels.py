"""Per-kernel validation: interpret=True Pallas vs pure-jnp oracle,
with hypothesis sweeps over shapes and dtypes (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as hst

from repro.kernels import ops, ref


@pytest.fixture(autouse=True, scope="module")
def _highest_matmul_precision():
    """fp32-exact oracles for this file only: a config set at import would
    leak into every test file a worker collects."""
    with jax.default_matmul_precision("highest"):
        yield


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5
    )


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #
@settings(max_examples=12, deadline=None)
@given(
    hst.sampled_from([(1, 4, 128, 32), (2, 6, 256, 64), (1, 8, 64, 16)]),
    hst.sampled_from([1, 2]),       # GQA group size
    hst.booleans(),                  # causal
    hst.sampled_from([None, 32]),    # window
    hst.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_flash_attention_matches_ref(dims, g, causal, window, dtype):
    B, H, S, D = dims
    if H % g:
        g = 1
    KV = H // g
    if window is not None and not causal:
        window = None  # windowed-bidir unused by any arch
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(k1, (B, S, H, D)).astype(dtype)
    k = jax.random.normal(k2, (B, S, KV, D)).astype(dtype)
    v = jax.random.normal(k3, (B, S, KV, D)).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              interpret=True)
    # ref uses kernel layout
    expect = ref.flash_attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal, window=window,
    )
    np.testing.assert_allclose(
        np.asarray(jnp.swapaxes(out, 1, 2), np.float32),
        np.asarray(expect, np.float32), **_tol(dtype)
    )


def test_flash_attention_nondivisible_seq_padding():
    B, H, S, D = 1, 2, 100, 32  # S not a multiple of the block
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    expect = ref.flash_attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(jnp.swapaxes(out, 1, 2), np.float32),
        np.asarray(expect, np.float32), rtol=2e-5, atol=2e-5,
    )


# --------------------------------------------------------------------------- #
# flash decode
# --------------------------------------------------------------------------- #
@settings(max_examples=10, deadline=None)
@given(
    hst.sampled_from([(1, 4, 64, 32), (2, 8, 128, 16)]),
    hst.sampled_from([1, 2]),
    hst.sampled_from([None, 48]),
    hst.integers(5, 60),
)
def test_flash_decode_matches_ref(dims, g, window, pos):
    B, H, W, D = dims
    if H % g:
        g = 1
    KV = H // g
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k_cache = jax.random.normal(ks[1], (B, W, KV, D))
    v_cache = jax.random.normal(ks[2], (B, W, KV, D))
    # linear cache filled up to pos
    cache_pos = jnp.broadcast_to(jnp.arange(W), (B, W))
    cache_pos = jnp.where(cache_pos <= pos, cache_pos, -1).astype(jnp.int32)
    q_pos = jnp.full((B,), pos, jnp.int32)
    out = ops.flash_decode(q, k_cache, v_cache, cache_pos, q_pos,
                           window=window, interpret=True)
    expect = ref.flash_decode_ref(
        q, jnp.swapaxes(k_cache, 1, 2), jnp.swapaxes(v_cache, 1, 2),
        cache_pos, q_pos, window=window,
    )
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# SSD scan
# --------------------------------------------------------------------------- #
@settings(max_examples=8, deadline=None)
@given(
    hst.sampled_from([(1, 64, 2, 16, 8), (2, 128, 4, 32, 16)]),
    hst.sampled_from([16, 32]),
)
def test_ssd_scan_matches_recurrence(dims, chunk):
    B, S, H, P, N = dims
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    y, h = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    y_ref, h_ref = ref.ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(h_ref, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_ssd_model_chunked_matches_kernel():
    """The model's pure-JAX chunked SSD and the Pallas kernel agree."""
    from repro.models.mamba2 import ssd_chunked

    B, S, H, P, N = 2, 64, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    y1, h1 = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    y2, h2 = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------- #
# RG-LRU scan
# --------------------------------------------------------------------------- #
@settings(max_examples=8, deadline=None)
@given(
    hst.sampled_from([(1, 64, 128), (2, 128, 256), (1, 32, 128)]),
    hst.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_rglru_matches_recurrence(dims, dtype):
    B, S, W = dims
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))).astype(dtype)
    b = (jax.random.normal(ks[1], (B, S, W)) * 0.1).astype(dtype)
    h0 = jax.random.normal(ks[2], (B, W)).astype(jnp.float32)
    y, hN = ops.rglru(a, b, h0, interpret=True)
    y_ref, h_ref = ref.rglru_ref(a, b, h0)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(hN), np.asarray(h_ref), **tol)


# --------------------------------------------------------------------------- #
# grouped matmul
# --------------------------------------------------------------------------- #
@settings(max_examples=8, deadline=None)
@given(
    hst.sampled_from([(2, 64, 32, 48), (4, 100, 64, 96), (1, 128, 128, 128)]),
    hst.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_moe_gmm_matches_einsum(dims, dtype):
    E, C, D, F = dims
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    x = (jax.random.normal(ks[0], (E, C, D)) * 0.5).astype(dtype)
    w = (jax.random.normal(ks[1], (E, D, F)) * 0.5).astype(dtype)
    out = ops.moe_gmm(x, w, interpret=True)
    expect = ref.moe_gmm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))
