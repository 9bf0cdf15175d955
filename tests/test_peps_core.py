"""Core PEPS correctness: operator application vs the statevector oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import peps as P
from repro.core import statevector as sv
from repro.core import gates as G
from repro.core.peps import DirectUpdate, QRUpdate, apply_operator
from repro.core.einsumsvd import DirectSVD, RandomizedSVD

OPS = [("H", [0]), ("H", [4]), ("CX", [0, 1]), ("ISWAP", [1, 4]), ("T", [4]),
       ("CX", [4, 5]), ("SQRT_Y", [2]), ("ISWAP", [0, 3]), ("CZ", [3, 4]),
       ("SQRT_W", [5]), ("CX", [2, 5])]


def _run_circuit(update):
    state = P.computational_zeros(2, 3)
    ref = sv.zeros(6)
    for name, sites in OPS:
        g = G.gate(name)
        state = apply_operator(state, g, sites, update)
        ref = sv.apply_gate(ref, g, sites)
    return state, ref


@pytest.mark.parametrize("update,tol", [
    (DirectUpdate(rank=8), 1e-12),
    (QRUpdate(rank=8, gram=True), 1e-12),
    (QRUpdate(rank=8, gram=False), 1e-12),
    (QRUpdate(rank=8, svd=RandomizedSVD(niter=4)), 1e-8),
])
def test_update_paths_match_statevector(update, tol):
    state, ref = _run_circuit(update)
    out = P.to_statevector(state)
    assert float(jnp.max(jnp.abs(out - ref))) < tol


def test_gates_unitary():
    for name in ("X", "Y", "Z", "H", "S", "T", "SQRT_X", "SQRT_Y", "SQRT_W"):
        g = G.gate(name)
        np.testing.assert_allclose(g @ g.conj().T, np.eye(2), atol=1e-14)
    for name in ("CX", "CZ", "SWAP", "ISWAP"):
        g = G.gate(name).reshape(4, 4)
        np.testing.assert_allclose(g @ g.conj().T, np.eye(4), atol=1e-14)


def test_amplitude_exact_matches_statevector():
    state, ref = _run_circuit(DirectUpdate(rank=8))
    for bits in ([[0, 1, 0], [1, 0, 1]], [[0, 0, 0], [0, 0, 0]], [[1, 1, 1], [1, 1, 1]]):
        amp = P.amplitude_exact(state, np.array(bits))
        expected = ref[tuple(np.array(bits).flatten())]
        assert abs(complex(amp) - complex(expected)) < 1e-12


@pytest.mark.parametrize("sites", [[0, 5], [2, 3], [5, 0], [1, 5], [2, 0]])
def test_swap_chain_routing(sites):
    state, ref = _run_circuit(DirectUpdate(rank=8))
    state2 = apply_operator(state, G.gate("CX"), sites, DirectUpdate(rank=32))
    ref2 = sv.apply_gate(ref, G.gate("CX"), sites)
    assert float(jnp.max(jnp.abs(P.to_statevector(state2) - ref2))) < 1e-10


def test_normalize_sites_tracks_scale():
    state, ref = _run_circuit(QRUpdate(rank=8))
    scaled = P.normalize_sites(state)
    out = P.to_statevector(scaled)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-12


def test_log_scale_in_amplitudes():
    state, ref = _run_circuit(QRUpdate(rank=8))
    scaled = P.normalize_sites(state)
    bits = np.array([[0, 1, 0], [1, 0, 1]])
    amp = P.amplitude_exact(scaled, bits)
    assert abs(complex(amp) - complex(ref[tuple(bits.flatten())])) < 1e-12


def test_random_peps_shapes():
    st = P.random_peps(3, 4, 3, jax.random.PRNGKey(0))
    assert st.sites[0][0].shape == (2, 1, 1, 3, 3)
    assert st.sites[1][1].shape == (2, 3, 3, 3, 3)
    assert st.sites[2][3].shape == (2, 3, 3, 1, 1)
    assert st.max_bond() == 3


@pytest.mark.parametrize("dtype", ["complex64", "complex128", "float32"])
def test_random_peps_honours_dtype(dtype):
    """Every site comes out in the requested dtype (the per-site norm is a
    numpy float64 and used to promote complex64 sites to complex128)."""
    s = P.random_peps(2, 3, 2, jax.random.PRNGKey(1), dtype=dtype)
    assert {t.dtype for row in s.sites for t in row} == {jnp.dtype(dtype)}


def test_peps_is_pytree():
    st = P.random_peps(2, 2, 2, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(st)
    assert len(leaves) == 4
    st2 = jax.tree_util.tree_map(lambda x: 2.0 * x, st)
    assert isinstance(st2, P.PEPS)
    np.testing.assert_allclose(np.asarray(st2.sites[0][0]),
                               2 * np.asarray(st.sites[0][0]))
