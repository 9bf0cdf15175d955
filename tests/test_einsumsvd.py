"""einsumsvd / randomized SVD / Gram orthogonalization unit + property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.einsumsvd import DirectSVD, RandomizedSVD, einsumsvd, truncation_error
from repro.core.orthogonalize import gram_qr, reshape_qr, orthogonalize_cols
from repro.core.rsvd import ImplicitOperator, randomized_svd


def _random_network(key, d1=3, d2=4, d3=5, d4=3, dtype=jnp.complex128):
    k1, k2 = jax.random.split(key)

    def rnd(k, shape):
        if jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
            ka, kb = jax.random.split(k)
            return (jax.random.normal(ka, shape) + 1j * jax.random.normal(kb, shape)).astype(dtype)
        return jax.random.normal(k, shape).astype(dtype)

    a = rnd(k1, (d1, d2, d3))
    b = rnd(k2, (d3, d4, d1))
    # network: contract over label c (=d3); operator rows 'ab', cols 'de'
    return [a, b], ["abc", "cde"], "ab", "de"


def test_implicit_operator_dense_matvec_consistency():
    tensors, subs, row, col = _random_network(jax.random.PRNGKey(0))
    op = ImplicitOperator(tensors, subs, row, col)
    dense = op.dense()
    q = jax.random.normal(jax.random.PRNGKey(1), op.col_shape + (3,))
    q = q.astype(op.dtype)
    got = op.matvecs(q)
    want = jnp.tensordot(dense, q, axes=[[2, 3], [0, 1]])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-12)
    p = jax.random.normal(jax.random.PRNGKey(2), op.row_shape + (3,)).astype(op.dtype)
    got_r = op.rmatvecs(p)
    mat = dense.reshape(op.row_size, op.col_size)
    want_r = (mat.conj().T @ p.reshape(op.row_size, 3)).reshape(op.col_shape + (3,))
    np.testing.assert_allclose(np.asarray(got_r), np.asarray(want_r), atol=1e-12)


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.complex128])
def test_randomized_svd_matches_direct(dtype):
    tensors, subs, row, col = _random_network(jax.random.PRNGKey(3), dtype=dtype)
    op = ImplicitOperator(tensors, subs, row, col)
    rank = min(op.row_size, op.col_size)  # full rank -> exact
    u1, s1, v1 = DirectSVD()(op, rank)
    u2, s2, v2 = RandomizedSVD(niter=6)(op, rank, key=jax.random.PRNGKey(9))
    s1, s2 = np.asarray(s1), np.asarray(s2)
    # compare significant singular values only: the gram_final variant floors
    # null-space values at the Gram eps (sqrt(1e-13)*s0) instead of ~1e-16
    sig = s1 > 1e-8 * s1[0]
    np.testing.assert_allclose(s1[sig], s2[sig], rtol=1e-8)
    assert np.all(s2[~sig] < 1e-5 * s1[0])
    assert float(truncation_error(op.dense(), u2, s2, v2)) < 1e-8


def test_truncated_rsvd_error_near_optimal():
    tensors, subs, row, col = _random_network(jax.random.PRNGKey(4))
    op = ImplicitOperator(tensors, subs, row, col)
    for rank in (2, 4, 6):
        ud, sd, vd = DirectSVD()(op, rank)
        ur, sr, vr = RandomizedSVD(niter=8, oversample=8)(op, rank,
                                                          key=jax.random.PRNGKey(1))
        e_direct = float(truncation_error(op.dense(), ud, sd, vd))
        e_rand = float(truncation_error(op.dense(), ur, sr, vr))
        # paper Fig. 10 claim: implicit rSVD adds no significant extra error
        assert e_rand <= e_direct * 1.05 + 1e-10


def test_einsumsvd_absorb_modes():
    tensors, subs, row, col = _random_network(jax.random.PRNGKey(5))
    rank = 4
    u, s, v = einsumsvd(DirectSVD(), tensors, subs, row, col, rank, absorb="none")
    l_both, r_both = einsumsvd(DirectSVD(), tensors, subs, row, col, rank, absorb="both")
    recon1 = jnp.einsum("abk,k,kde->abde", u, s, v)
    recon2 = jnp.einsum("abk,kde->abde", l_both, r_both)
    np.testing.assert_allclose(np.asarray(recon1), np.asarray(recon2), atol=1e-12)


@settings(deadline=None, max_examples=20)
@given(m=st.integers(2, 9), n=st.integers(1, 6), seed=st.integers(0, 2**31 - 1))
def test_gram_qr_property(m, n, seed):
    """Property: gram_qr reconstructs A and produces an isometry (tall case)."""
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    a = (jax.random.normal(k1, (m, m, n)) + 1j * jax.random.normal(k2, (m, m, n)))
    q, r = gram_qr(a, 1)
    recon = jnp.tensordot(q, r, axes=[[2], [0]])
    np.testing.assert_allclose(np.asarray(recon), np.asarray(a), atol=1e-9)
    if m * m >= n:
        qtq = jnp.tensordot(q.conj(), q, axes=[[0, 1], [0, 1]])
        np.testing.assert_allclose(np.asarray(qtq), np.eye(n), atol=1e-8)


def test_gram_qr_matches_reshape_qr_subspace():
    a = jax.random.normal(jax.random.PRNGKey(0), (7, 3, 4)).astype(jnp.float64)
    for qr in (gram_qr, reshape_qr):
        q, r = qr(a, 2)
        recon = jnp.tensordot(q, r, axes=[[1, 2], [0, 1]])
        np.testing.assert_allclose(np.asarray(recon), np.asarray(a), atol=1e-10)


def test_gram_qr_rank_deficient():
    """Wide/rank-deficient case: reconstruction must still be exact."""
    a = jnp.zeros((2, 3, 4), dtype=jnp.complex128).at[0, 1, 2].set(1.0)
    q, r = gram_qr(a, 2)
    recon = jnp.tensordot(q, r, axes=[[1, 2], [0, 1]])
    np.testing.assert_allclose(np.asarray(recon), np.asarray(a), atol=1e-9)


def test_orthogonalize_cols():
    t = jax.random.normal(jax.random.PRNGKey(1), (6, 5, 3)).astype(jnp.float64)
    q = orthogonalize_cols(t)
    qtq = jnp.tensordot(q, q, axes=[[0, 1], [0, 1]])
    np.testing.assert_allclose(np.asarray(qtq), np.eye(3), atol=1e-10)


def _padded_rank_one(dtype, pad):
    """A 64x16 rank-1 matrix whose 15 padding directions hold ``pad`` —
    the shape of a full-update environment sweep over a bond padded past
    its rank."""
    rng = np.random.default_rng(5)
    u = rng.normal(size=(64, 1)) + 1j * rng.normal(size=(64, 1))
    v = rng.normal(size=(1, 16)) + 1j * rng.normal(size=(1, 16))
    mat = u @ v
    mat[:, 1:] = pad * (rng.normal(size=(64, 15)) > 0)
    return jnp.asarray(mat, dtype=dtype)


@pytest.mark.parametrize("dtype", [jnp.complex64, jnp.complex128])
def test_direct_svd_scale_is_exact_and_flushes_below_resolution(dtype):
    """DirectSVD factors ``2^-k A`` into the same u, vh and s / 2^k, and
    entries below the dtype's resolution relative to the largest are
    zeroed before the factorization, so the padded rank-1 matrix factors
    like its zero-padded twin."""
    mat = _padded_rank_one(dtype, 0.01 * float(jnp.finfo(dtype).eps))
    zero = _padded_rank_one(dtype, 0.0)
    op = lambda m: ImplicitOperator([m], ["ab"], "a", "b")
    u, s, vh = DirectSVD()(op(mat), 4)
    uz, sz, vhz = DirectSVD()(op(zero), 4)
    for got, want in ((u, uz), (s, sz), (vh, vhz)):
        assert np.all(np.isfinite(np.asarray(got)))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    us, ss, vhs = DirectSVD()(op(zero * 2.0 ** -40), 4)
    np.testing.assert_array_equal(np.asarray(us), np.asarray(uz))
    np.testing.assert_array_equal(np.asarray(vhs), np.asarray(vhz))
    np.testing.assert_array_equal(np.asarray(ss), np.asarray(sz) * 2.0 ** -40)


@pytest.mark.parametrize("dtype", [jnp.complex64, jnp.complex128])
def test_direct_svd_right_factor_is_the_projection_of_u(dtype, monkeypatch):
    """DirectSVD keeps the SVD's ``u`` and takes ``s`` and ``vh`` from the
    rows of ``u^H A``, so ``u diag(s) vh`` reproduces A to rounding even
    from an SVD whose right vectors are paired with the left ones only to
    1e-3.  (The TPU's SVD pairs them to ~1e-6 in complex64, which put
    served amplitudes 2.1e-5 off the statevector on a v5e.)"""
    import importlib
    esvd = importlib.import_module("repro.core.einsumsvd")
    rng = np.random.default_rng(3)
    a = rng.normal(size=(24, 40)) + 1j * rng.normal(size=(24, 40))
    q, r = np.linalg.qr(np.eye(24) + 1e-3 * rng.normal(size=(24, 24)))
    rot = q * np.sign(np.diag(r))            # within 1e-3 of the identity
    exact_svd = esvd.svd_reg

    def mispaired(m):
        u, s, vh = exact_svd(m)
        return u, s, jnp.asarray(rot, dtype=m.dtype) @ vh

    monkeypatch.setattr(esvd, "svd_reg", mispaired)
    esvd._truncated_svd.clear_cache()
    try:
        u, s, vh = DirectSVD()(
            ImplicitOperator([jnp.asarray(a, dtype=dtype)], ["ab"], "a", "b"),
            24)
    finally:
        esvd._truncated_svd.clear_cache()
    u, s, vh = np.asarray(u), np.asarray(s), np.asarray(vh)
    eps = float(jnp.finfo(dtype).eps)
    assert np.linalg.norm((u * s) @ vh - a) / np.linalg.norm(a) < 100 * eps
    np.testing.assert_allclose(np.linalg.norm(vh, axis=1), 1.0,
                               rtol=100 * eps)
