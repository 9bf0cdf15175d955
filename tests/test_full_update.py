"""Full-update ITE: parity with exact ITE, accuracy vs the simple update,
planner cache behavior, and dispatch errors (ISSUE 2 tentpole)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bmps as B
from repro.core import peps as P
from repro.core import planner
from repro.core import full_update as FU
from repro.core.environments import row_environments, strip_boundary
from repro.core.expectation import strip_value
from repro.core.ite import ITEResult, ite_run, ite_statevector
from repro.core.observable import tfi_hamiltonian
from repro.core.peps import DirectUpdate, FullUpdate, QRUpdate, apply_operator


def _hit_rate(stats):
    total = stats["fused_hits"] + stats["fused_misses"]
    return stats["fused_hits"] / max(total, 1)


# ------------------------------------------------------------ environment ----

def test_strip_boundary_closes_to_strip_value():
    """Contracting left and right strip boundaries at the same cut must
    reproduce the full strip scalar (cross-check of the env machinery)."""
    state = P.random_peps(3, 4, 2, jax.random.PRNGKey(0))
    top, bottom = row_environments(state, B.BMPS(8), jax.random.PRNGKey(1))
    i = 1
    bra = [state.sites[i]]
    want = complex(strip_value(top[i], bottom[i], bra, bra))
    for cut in range(state.ncol + 1):
        left = strip_boundary(top[i], bottom[i], bra, bra, cut, from_left=True)
        right = strip_boundary(top[i], bottom[i], bra, bra, cut, from_left=False)
        got = complex(jnp.einsum("abcd,abcd->", left, right))
        assert abs(got - want) <= 1e-10 * max(abs(want), 1e-300), (cut, got, want)


def test_bond_environment_norm_consistency():
    """Closing the bond environment with the reduced tensors of the *current*
    sites must reproduce <psi|psi> (up to boundary truncation error)."""
    state = P.random_peps(3, 3, 2, jax.random.PRNGKey(2))
    upd = FullUpdate(rank=2, chi=16)
    envs = row_environments(state, FU.env_option(upd), jax.random.PRNGKey(3))
    want = complex(B.norm_squared(state, B.BMPS(16), jax.random.PRNGKey(4)))
    for s0, s1, axes_a, axes_b in [
        ((1, 0), (1, 1), (1, 2, 3, 0, 4), (1, 3, 4, 0, 2)),   # horizontal
        ((0, 1), (1, 1), (1, 2, 4, 0, 3), (2, 3, 4, 0, 1)),   # vertical
    ]:
        a = state.sites[s0[0]][s0[1]]
        b = state.sites[s1[0]][s1[1]]
        qa, ra = FU._reduced_split(a, axes_a)
        qb, rb = FU._reduced_split(b, axes_b)
        env = FU.bond_environment(state, s0, s1, qa, qb, envs)
        got = complex(planner.cached_einsum(
            "ABCDabcd,ABpk,CDqk,abpK,cdqK->",
            env, ra.conj(), rb.conj(), ra, rb))
        assert abs(got - want) <= 1e-6 * abs(want), (s0, s1, got, want)


def test_positive_fix_is_psd_projection():
    key = jax.random.PRNGKey(5)
    m = jax.random.normal(key, (16, 16), dtype=jnp.float64)
    env = (m @ m.T - 3.0 * jnp.eye(16)).reshape(2, 2, 2, 2, 2, 2, 2, 2)
    fixed = FU.positive_fix(env).reshape(16, 16)
    w = np.linalg.eigvalsh(np.asarray(fixed))
    assert w.min() >= -1e-12
    assert abs(w.max() - 1.0) < 1e-12  # normalized to unit spectral norm


def test_stale_envs_detected_and_refreshed():
    """Environments cached before a bond grew must be detected as
    shape-stale (silently broadcasting their dim-1 axes would corrupt the
    metric) and transparently refreshed by full_update_bond."""
    state = P.computational_zeros(2, 2)
    upd = FullUpdate(rank=2, chi=8)
    envs = row_environments(state, FU.env_option(upd), jax.random.PRNGKey(0))
    assert FU.envs_compatible(state, (1, 0), (1, 1), envs)
    # grow the vertical bond (0,0)-(1,0): row 1's u-dims no longer match
    grown = apply_operator(state, P._gates.CX, [0, 2], QRUpdate(rank=2))
    assert not FU.envs_compatible(grown, (1, 0), (1, 1), envs)
    FU.drain_fidelities()
    out = FU.full_update_bond(grown, P._gates.CX, (1, 0), (1, 1), upd,
                              jax.random.PRNGKey(1), envs=envs)
    fids = FU.drain_fidelities()
    assert out.sites[1][0].shape[4] == 2
    assert len(fids) == 1 and 0.99 <= fids[0] <= 1.0 + 1e-9


# -------------------------------------------------------------- accuracy ----

def test_full_update_product_state_fidelity_is_one():
    """On a bond-dim-1 state a rank-2 update loses nothing: fidelity ~ 1."""
    FU.drain_fidelities()  # isolate from earlier tests
    state = P.computational_zeros(2, 2)
    state = apply_operator(state, np.kron(P._gates.H, P._gates.H).reshape(2, 2, 2, 2),
                           [0, 1], FullUpdate(rank=2, chi=8))
    fids = FU.drain_fidelities()
    assert len(fids) == 1
    assert abs(fids[0] - 1.0) < 1e-8


@pytest.mark.parametrize("nrow,ncol", [(2, 2), (2, 3)])
def test_full_update_ite_matches_statevector(nrow, ncol):
    """2x2/2x3 TFI ground energy via full-update ITE vs exact ITE."""
    obs = tfi_hamiltonian(nrow, ncol, jz=-1.0, hx=-3.5)
    _, e_ref = ite_statevector(nrow, ncol, obs, tau=0.05, steps=80)
    res = ite_run(P.computational_zeros(nrow, ncol), obs, tau=0.05, steps=80,
                  update=FullUpdate(rank=2, chi=8), contract=B.BMPS(8),
                  measure_every=80)
    assert abs(res.energies[-1] - e_ref) < 2e-3 * abs(e_ref)
    # fidelity estimate rides along and stays physical
    assert res.fidelities is not None and len(res.fidelities) == 1
    assert 0.9 <= res.fidelities[-1] <= 1.0 + 1e-9


def test_full_update_ite_complex64_matches_statevector():
    """Full-update ITE in complex64 stays finite and on the exact energy.

    Regression: the ALS ridge (``als_eps=1e-12`` of trace/d) vanished
    against complex64 rounding, and the rank-deficient normal matrices of
    the first product-state bonds solved to NaN.  Tolerance: the
    complex128 run's own error plus the documented complex64 budget per
    full-update step."""
    from repro.core.observable import j1j2_hamiltonian
    from repro.core.precision import error_budget
    obs = j1j2_hamiltonian(2, 3)
    steps, tau = 2, 0.05
    _, e_ref = ite_statevector(2, 3, obs, tau=tau, steps=steps)
    update = FullUpdate(rank=2, chi=8, env_refresh_every=len(obs))
    errs = {}
    for dtype in (jnp.complex128, jnp.complex64):
        res = ite_run(P.computational_zeros(2, 3, dtype=dtype), obs, tau=tau,
                      steps=steps, update=update, contract=B.BMPS(8),
                      measure_every=steps)
        errs[dtype] = abs(res.energies[-1] - e_ref) / abs(e_ref)
    tol = errs[jnp.complex128] + steps * error_budget("full_update_ite_step",
                                                      "mixed")
    assert errs[jnp.complex64] <= tol, errs


def test_full_update_beats_simple_update_at_fixed_bond():
    """At equal bond dimension and Trotter steps, the environment-aware
    update must reach a strictly lower energy error (2x3 TFI, D=2)."""
    obs = tfi_hamiltonian(2, 3, jz=-1.0, hx=-3.5)
    _, e_ref = ite_statevector(2, 3, obs, tau=0.05, steps=80)
    kw = dict(tau=0.05, steps=80, contract=B.BMPS(8), measure_every=80)
    res_qr = ite_run(P.computational_zeros(2, 3), obs,
                     update=QRUpdate(rank=2), **kw)
    res_fu = ite_run(P.computational_zeros(2, 3), obs,
                     update=FullUpdate(rank=2, chi=8), **kw)
    err_qr = abs(res_qr.energies[-1] - e_ref)
    err_fu = abs(res_fu.energies[-1] - e_ref)
    assert err_fu < err_qr, (err_fu, err_qr)


# ---------------------------------------------------------------- planner ----

def test_full_update_planner_cache_across_trotter_steps():
    """After the shapes stabilize, every ALS solve replays compiled code."""
    obs = tfi_hamiltonian(2, 2, jz=-1.0, hx=-3.5)
    state = P.computational_zeros(2, 2)
    upd = FullUpdate(rank=2, chi=8)
    kw = dict(tau=0.05, contract=B.BMPS(8), measure_every=100)
    warm = ite_run(state, obs, steps=2, update=upd, **kw)
    res = ite_run(warm.state, obs, steps=3, update=upd, **kw)
    assert res.planner_stats["fused_misses"] == 0
    assert _hit_rate(res.planner_stats) == 1.0


def test_fused_fn_respects_fusion_toggle():
    calls = []

    def builder():
        calls.append(1)
        return lambda x: x + 1

    planner.reset_stats()
    f1 = planner.fused_fn("test-tag", (1, 2), builder)
    f2 = planner.fused_fn("test-tag", (1, 2), builder)
    assert f1 is f2 and len(calls) == 1
    s = planner.stats()
    assert s["fused_misses"] >= 1 and s["fused_hits"] >= 1
    with planner.disabled():
        planner.fused_fn("test-tag", (1, 2), builder)
        planner.fused_fn("test-tag", (1, 2), builder)
    assert len(calls) == 3  # no caching while disabled


def test_int_einsum_matches_plain_einsum():
    a = jax.random.normal(jax.random.PRNGKey(6), (3, 4, 5))
    b = jax.random.normal(jax.random.PRNGKey(7), (5, 4, 2))
    want = jnp.einsum("abc,cbd->ad", a, b)
    got = planner.int_einsum(a, [10, 20, 30], b, [30, 20, 40], [10, 40])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-12)


# --------------------------------------------------------------- dispatch ----

def test_unknown_update_type_raises_type_error():
    @dataclasses.dataclass(frozen=True)
    class BogusUpdate:
        rank: int = 2

    state = P.computational_zeros(2, 2)
    with pytest.raises(TypeError, match="BogusUpdate"):
        apply_operator(state, P._gates.CX, [0, 1], BogusUpdate())
    obs = tfi_hamiltonian(2, 2)
    with pytest.raises(TypeError, match="BogusUpdate"):
        ite_run(state, obs, tau=0.05, steps=1, update=BogusUpdate(),
                contract=B.BMPS(4))


def test_direct_update_still_dispatches():
    state = P.computational_zeros(2, 2)
    out = apply_operator(state, P._gates.CX, [0, 1], DirectUpdate(rank=2))
    assert out.sites[0][0].shape[4] == 2


# ------------------------------------------------------------------- slow ----

@pytest.mark.slow
def test_full_update_4x4_acceptance():
    """ISSUE 2 acceptance: 4x4 TFI at D=3, equal Trotter steps — full update
    strictly below the simple update's energy error, planner fused hit rate
    > 90% after the first step."""
    obs = tfi_hamiltonian(4, 4, jz=-1.0, hx=-3.5)
    _, e_ref = ite_statevector(4, 4, obs, tau=0.05, steps=60)
    kw = dict(tau=0.05, contract=B.BMPS(16), measure_every=30)
    res_qr = ite_run(P.computational_zeros(4, 4), obs, steps=30,
                     update=QRUpdate(rank=3), **kw)
    upd = FullUpdate(rank=3, chi=12, env_refresh_every=40)
    first = ite_run(P.computational_zeros(4, 4), obs, steps=1,
                    update=upd, **kw)
    rest = ite_run(first.state, obs, steps=29, update=upd, **kw)
    err_qr = abs(res_qr.energies[-1] - e_ref)
    err_fu = abs(rest.energies[-1] - e_ref)
    assert err_fu < err_qr, (err_fu, err_qr)
    assert err_fu < 1e-3 * abs(e_ref)
    assert _hit_rate(rest.planner_stats) > 0.90, rest.planner_stats
    assert all(0.9 <= f <= 1.0 + 1e-9 for f in rest.fidelities)


@pytest.mark.slow
def test_batched_full_update_evolution():
    """Ensemble full-update TEBD under vmap (the sharding entry point)."""
    import jax.tree_util as jtu
    from repro.core.sharding import batched_evolve_full

    protos = [P.random_peps(3, 3, 2, jax.random.PRNGKey(i), dtype=jnp.complex64)
              for i in range(2)]
    batched = jtu.tree_map(lambda *xs: jnp.stack(xs), *protos)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    out = batched_evolve_full(batched, keys, chi_env=6)
    leaf = out.sites[1][1]
    assert leaf.shape[0] == 2
    assert bool(jnp.all(jnp.isfinite(leaf)))
