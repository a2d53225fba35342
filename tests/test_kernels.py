"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles.

Required by the brief: for each kernel, sweep shapes & dtypes and
assert_allclose against the pure-jnp oracle (interpret=True on CPU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels import lowrank as lr

SHAPES = [(128, 128), (256, 512), (512, 256), (384, 640), (1024, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]
RANKS = [4, 16, 64]


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rank", [4, 64])
def test_p_kernel_sweep(shape, dtype, rank):
    m, n = shape
    g, e = _rand(shape, dtype, 0), _rand(shape, dtype, 1)
    q = _rand((n, rank), jnp.float32, 2)
    got = lr.ef_lowrank_p(g, e, q, interpret=True)
    want = ref.ef_lowrank_p(g, e, q)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_q_kernel_sweep(shape, dtype):
    m, n = shape
    rank = 16
    g, e = _rand(shape, dtype, 3), _rand(shape, dtype, 4)
    p_hat = _rand((m, rank), jnp.float32, 5)
    got = lr.ef_lowrank_q(g, e, p_hat, interpret=True)
    want = ref.ef_lowrank_q(g, e, p_hat)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_decompress_kernel_sweep(shape, dtype):
    m, n = shape
    rank = 8
    g, e = _rand(shape, dtype, 6), _rand(shape, dtype, 7)
    p_hat = _rand((m, rank), jnp.float32, 8)
    q = _rand((n, rank), jnp.float32, 9)
    gh, ne = lr.decompress_residual(p_hat, q, g, e, interpret=True)
    ghr, ner = ref.decompress_residual(p_hat, q, g, e)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(gh, np.float32),
                               np.asarray(ghr, np.float32), rtol=tol, atol=tol * 10)
    np.testing.assert_allclose(np.asarray(ne, np.float32),
                               np.asarray(ner, np.float32), rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("m", [64, 256, 1024])
@pytest.mark.parametrize("r", RANKS)
def test_gram_schmidt_panel_sweep(m, r):
    p = _rand((m, r), jnp.float32, 10)
    got = lr.gram_schmidt_panel(p, interpret=True)
    # orthonormal + same span as the oracle
    eye = np.asarray(got.T @ got)
    np.testing.assert_allclose(eye, np.eye(r), atol=2e-4)
    want = ref.gram_schmidt(p)
    overlap = np.abs(np.asarray(got.T @ want))
    np.testing.assert_allclose(overlap, np.eye(r), atol=2e-3)


@pytest.mark.parametrize("cond", [1e2, 1e4, 1e6])
def test_gram_schmidt_panel_ill_conditioned(cond):
    """PowerSGD's warm-started P = M Q can be far from orthogonal; one
    classical Gram-Schmidt pass then leaves it far from orthonormal."""
    m, r = 1024, 32
    rng = np.random.default_rng(40)
    u = np.linalg.qr(rng.standard_normal((m, r)))[0]
    v = np.linalg.qr(rng.standard_normal((r, r)))[0]
    p = jnp.asarray((u * np.logspace(0, -np.log10(cond), r)) @ v, jnp.float32)
    got = np.asarray(lr.gram_schmidt_panel(p, interpret=True))
    np.testing.assert_allclose(got.T @ got, np.eye(r), atol=1e-2)


@pytest.mark.parametrize("n", [1000, 4096, 100_000])
@pytest.mark.parametrize("bins", [64, 256])
def test_entropy_hist_kernel_sweep(n, bins):
    x = _rand((n,), jnp.float32, 11) * 0.37
    got = float(ops.sampled_entropy_hist(x, num_bins=bins))
    want = float(ref.sampled_entropy_hist(x, num_bins=bins))
    assert got == pytest.approx(want, abs=1e-5)


@given(mexp=st.integers(1, 3), nexp=st.integers(1, 3),
       rank=st.sampled_from([4, 8, 32]))
@settings(max_examples=10, deadline=None)
def test_p_kernel_property(mexp, nexp, rank):
    m, n = 128 * mexp, 128 * nexp
    g, e = _rand((m, n), jnp.float32, 12), _rand((m, n), jnp.float32, 13)
    q = _rand((n, rank), jnp.float32, 14)
    got = lr.ef_lowrank_p(g, e, q, interpret=True)
    want = ref.ef_lowrank_p(g, e, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_ops_fallback_untileable():
    """Non-128-multiple shapes silently use the oracle — same numbers."""
    g, e = _rand((100, 300), jnp.float32, 15), _rand((100, 300), jnp.float32, 16)
    q = _rand((300, 8), jnp.float32, 17)
    np.testing.assert_allclose(np.asarray(ops.lowrank_p(g, e, q)),
                               np.asarray(ref.ef_lowrank_p(g, e, q)),
                               rtol=1e-5)


def test_hist_kernel_padding_correct():
    """Non-multiple-of-block sizes: the pad sentinel must not leak counts."""
    x = _rand((3001,), jnp.float32, 18)
    got = float(ops.sampled_entropy_hist(x))
    want = float(ref.sampled_entropy_hist(x))
    # f32 accumulation order differs between the blocked kernel and the
    # single-pass oracle; the histogram itself is exact (pad-count corrected)
    assert got == pytest.approx(want, abs=1e-4)


def test_hist_counts_padded_equals_unpadded():
    """Regression for the dead NaN-pad write: sentinel-padded counts must
    match the same call blocked without padding, bin for bin."""
    from repro.kernels.entropy_hist import hist_counts
    x = _rand((5000,), jnp.float32, 19)
    lo = jnp.float32(float(jnp.mean(x)) - 4.0)
    inv_w = jnp.float32(256 / 8.0)
    padded = hist_counts(x, lo, inv_w, bx=2048)    # pad = 1144
    exact = hist_counts(x, lo, inv_w, bx=1000)     # divides evenly, no pad
    np.testing.assert_array_equal(np.asarray(padded), np.asarray(exact))
    assert float(jnp.sum(padded)) == 5000.0        # no phantom pad counts


# ---------------------------------------------- batched (E, m, n) variants
@pytest.mark.parametrize("shape", [(3, 256, 512), (2, 128, 128)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_batched_p_q_kernels_vs_vmapped_ref(shape, dtype):
    E, m, n = shape
    rank = 16
    g, e = _rand(shape, dtype, 20), _rand(shape, dtype, 21)
    q = _rand((E, n, rank), jnp.float32, 22)
    p_hat = _rand((E, m, rank), jnp.float32, 23)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(lr.ef_lowrank_p_batched(g, e, q, interpret=True)),
        np.asarray(jax.vmap(ref.ef_lowrank_p)(g, e, q)),
        rtol=tol, atol=tol * 10)
    np.testing.assert_allclose(
        np.asarray(lr.ef_lowrank_q_batched(g, e, p_hat, interpret=True)),
        np.asarray(jax.vmap(ref.ef_lowrank_q)(g, e, p_hat)),
        rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("dtype", DTYPES)
def test_batched_decompress_kernel_vs_vmapped_ref(dtype):
    E, m, n, rank = 3, 256, 512, 8
    g, e = _rand((E, m, n), dtype, 24), _rand((E, m, n), dtype, 25)
    p_hat = _rand((E, m, rank), jnp.float32, 26)
    q = _rand((E, n, rank), jnp.float32, 27)
    gh, ne = lr.decompress_residual_batched(p_hat, q, g, e, interpret=True)
    ghr, ner = jax.vmap(ref.decompress_residual)(p_hat, q, g, e)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(gh, np.float32),
                               np.asarray(ghr, np.float32), rtol=tol, atol=tol * 10)
    np.testing.assert_allclose(np.asarray(ne, np.float32),
                               np.asarray(ner, np.float32), rtol=tol, atol=tol * 10)


def test_batched_gram_schmidt_panel():
    E, m, r = 4, 256, 16
    p = _rand((E, m, r), jnp.float32, 28)
    got = lr.gram_schmidt_panel_batched(p, interpret=True)
    for i in range(E):
        eye = np.asarray(got[i].T @ got[i])
        np.testing.assert_allclose(eye, np.eye(r), atol=2e-4)
        want = ref.gram_schmidt(p[i])
        overlap = np.abs(np.asarray(got[i].T @ want))
        np.testing.assert_allclose(overlap, np.eye(r), atol=2e-3)


def test_batched_ops_fallback_untileable():
    """Non-128-multiple stacks route to the vmapped oracle — same numbers."""
    E, m, n, rank = 3, 100, 300, 8
    g, e = _rand((E, m, n), jnp.float32, 29), _rand((E, m, n), jnp.float32, 30)
    q = _rand((E, n, rank), jnp.float32, 37)
    np.testing.assert_allclose(np.asarray(ops.lowrank_p3(g, e, q)),
                               np.asarray(jax.vmap(ref.ef_lowrank_p)(g, e, q)),
                               rtol=1e-5)
    p = _rand((E, 252, rank), jnp.float32, 38)     # m % 8 != 0 -> QR fallback
    q3 = ops.orthonormalize3(p)
    for i in range(E):
        np.testing.assert_allclose(np.asarray(q3[i].T @ q3[i]), np.eye(rank),
                                   atol=2e-4)


FLASH_CASES = [
    # (B, Tq, Tk, H, Hkv, Dh, bq, bk)
    (2, 256, 256, 4, 2, 64, 64, 64),
    (1, 512, 512, 8, 8, 128, 128, 128),
    (2, 128, 384, 4, 1, 32, 64, 128),   # cross-attn-like, Tq != Tk
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_sweep(case, causal, dtype):
    from repro.kernels.flash_attention import flash_attention
    B, Tq, Tk, H, Hkv, Dh, bq, bk = case
    if causal and Tq != Tk:
        pytest.skip("causal requires aligned q/k positions here")
    q = _rand((B, Tq, H, Dh), dtype, 31)
    k = _rand((B, Tk, Hkv, Dh), dtype, 32)
    v = _rand((B, Tk, Hkv, Dh), dtype, 33)
    got = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    want = ref.flash_reference(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


def test_flash_matches_model_blockwise():
    """The model's blockwise attention and the Pallas flash kernel agree."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models.layers import blockwise_attention
    q = _rand((2, 256, 4, 64), jnp.float32, 34)
    k = _rand((2, 256, 2, 64), jnp.float32, 35)
    v = _rand((2, 256, 2, 64), jnp.float32, 36)
    got = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    want = blockwise_attention(q, k, v, causal=True, block_q=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 128, 4, 2, 32), (1, 256, 8, 8, 64)])
@pytest.mark.parametrize("blocks", [(64, 64), (64, 128), (128, 64)])
def test_flash_backward_matches_autodiff(causal, shape, blocks):
    """custom_vjp flash bwd vs jax.grad of the full-materialization oracle."""
    from repro.kernels.flash_attention_bwd import flash_attention_train
    B, T, H, Hkv, D = shape
    bq, bk = blocks
    q = _rand((B, T, H, D), jnp.float32, 41)
    k = _rand((B, T, Hkv, D), jnp.float32, 42)
    v = _rand((B, T, Hkv, D), jnp.float32, 43)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention_train(q, k, v, causal, bq, bk)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(ref.flash_reference(q, k, v, causal=causal)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
