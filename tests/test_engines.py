"""Boundary-engine layer: refactor equivalence, variational accuracy, dispatch.

Three contracts (see repro/core/engines/__init__.py and ISSUE 6):

1. **Exactness and mode agreement** — zip-up through the engine layer
   matches ``contract_exact_onelayer`` to <= 1e-12 wherever chi covers the
   exact boundary bond, single-device, distributed and SPMD sweeps agree
   within one run, and replaying a contraction after warm-up ticks zero
   planner-cache misses (identical signatures).
2. **Variational accuracy** — the ALS-fitted boundary is exact when chi
   covers the exact bond dimension (matches ``contract_exact_onelayer`` /
   dense contraction to 1e-8) and beats zip-up at truncating chi.
3. **Dispatch** — engine/option errors are ``TypeError``/``ValueError`` that
   name the registered alternatives (the PR 2 convention); the SPMD
   wavefront rejects non-block engines at construction.

The SPMD marshalling test (no device-0 staging in ``spmd.absorb_rows``)
needs >= 2 devices and skips otherwise; ``make test-engines`` runs this
file under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bmps, peps, planner, spmd
from repro.core.bmps import BMPS
from repro.core.distributed import DistributedBMPS
from repro.core.engines import (BoundaryEngine, get_engine,
                                registered_engines)
from repro.core.engines.variational import VariationalEngine
from repro.core.engines.zipup import ZipUpEngine
from repro.core.environments import top_environments


def _rel(a, b):
    a, b = complex(a), complex(b)
    return abs(a - b) / max(abs(b), 1e-300)


def _state(nrow, ncol, bond, seed, scale=2.0):
    s = peps.random_peps(nrow, ncol, bond, jax.random.PRNGKey(seed))
    return peps.PEPS([[t * scale for t in row] for row in s.sites])


K17 = jax.random.PRNGKey(17)


# ------------------------------------------------------------ registry ----

def test_registry_and_resolution():
    engines = registered_engines()
    assert set(engines) >= {"zipup", "variational"}
    assert isinstance(get_engine("zipup"), ZipUpEngine)
    assert isinstance(get_engine("variational"), VariationalEngine)
    assert get_engine("zipup").supports_blocks
    assert not get_engine("variational").supports_blocks
    # instances pass through (non-default hyper-parameters)
    eng = VariationalEngine(sweeps=4)
    assert get_engine(eng) is eng
    assert isinstance(get_engine(eng), BoundaryEngine)


def test_unknown_engine_typeerror_lists_registered():
    with pytest.raises(TypeError, match=r"zipup.*variational|variational.*zipup"):
        get_engine("zip-up")
    with pytest.raises(TypeError, match="registered engines"):
        get_engine(42)
    # option construction fails fast, single-device and distributed
    with pytest.raises(TypeError, match="registered engines"):
        BMPS(8, engine="nope")
    with pytest.raises(TypeError, match="registered engines"):
        DistributedBMPS(8, engine="nope")


def test_unknown_option_typeerror_lists_engines():
    rows = peps.random_onelayer(2, 2, 2, jax.random.PRNGKey(0))
    with pytest.raises(TypeError, match=r"BMPS.*zipup|zipup.*BMPS"):
        bmps.contract_onelayer(rows, object())


def test_spmd_wavefront_rejects_nonblock_engine():
    for mode in ("spmd", "auto"):
        with pytest.raises(ValueError, match="supports_blocks"):
            DistributedBMPS(8, wavefront=mode, engine="variational")
    # block engine + any wavefront is fine
    DistributedBMPS(8, wavefront="spmd", engine="zipup")


# ----------------------- accuracy against exact contraction, and modes ----
#
# At a chi that covers the exact boundary bond, zip-up is exact up to
# complex128 rounding; measured errors are 1e-16 to 1e-14 (4x4 D=3
# one-layer at chi=27=D^3, D=2 two-layer grids at chi=16/64), so 1e-12
# leaves two orders of margin.  Execution modes (single device, host halo
# pipeline, compiled SPMD superstep) run the identical einsumsvd sequence
# and must agree within one run to the same 1e-12 even where chi truncates.
# Nothing here is pinned to one toolchain's random streams or truncations.

EXACT_TOL = 1e-12


def test_zipup_onelayer_matches_exact():
    rows = peps.random_onelayer(4, 4, 3, jax.random.PRNGKey(42))
    exact = bmps.contract_exact_onelayer(rows)
    for option in (BMPS(27), BMPS.randomized(27)):
        v = bmps.contract_onelayer(rows, option, key=K17)
        assert _rel(v, exact) <= EXACT_TOL, option


def test_zipup_twolayer_matches_exact():
    s33 = peps.random_peps(3, 3, 2, jax.random.PRNGKey(7))
    ket = peps.random_peps(3, 3, 2, jax.random.PRNGKey(8))
    s44 = peps.random_peps(4, 4, 2, jax.random.PRNGKey(12))
    exact = bmps.contract_exact_onelayer
    v = bmps.norm_squared(s33, BMPS(16), key=K17)
    assert _rel(v, exact(bmps.merge_layers(s33.sites, s33.sites))) <= EXACT_TOL
    v = bmps.norm_squared(s44, BMPS.randomized(64), key=K17)
    assert _rel(v, exact(bmps.merge_layers(s44.sites, s44.sites))) <= EXACT_TOL
    v = bmps.inner(s33, ket, BMPS.randomized(16), key=K17)
    assert _rel(v, exact(bmps.merge_layers(s33.sites, ket.sites))) <= EXACT_TOL
    bits = np.arange(16) % 2
    v = bmps.amplitude(s44, bits, BMPS.randomized(16), key=K17)
    assert _rel(v, peps.amplitude_exact(s44, bits)) <= EXACT_TOL


def test_zipup_distributed_matches_single_device():
    """single device == host halo pipeline == SPMD superstep, truncating.

    chi=8 = D^4/2 saturates the boundary after row 0, so rows 1-2 run in
    the superstep (on one device as its degenerate n=1 chain)."""
    from repro.core import spmd
    s44 = peps.random_peps(4, 4, 2, jax.random.PRNGKey(12))
    single = bmps.norm_squared(s44, BMPS.randomized(8), key=K17)
    for wavefront in ("host", "spmd"):
        opt = DistributedBMPS.randomized(8, n_shards=2, block=1,
                                         wavefront=wavefront)
        spmd.reset_stats()
        v = bmps.norm_squared(s44, opt, key=K17)
        assert _rel(v, single) <= EXACT_TOL, wavefront
        assert (spmd.stats()["rows_spmd"] > 0) == (wavefront == "spmd")


def test_engine_layer_replay_ticks_nothing():
    """Warm the planner through the re-exported pre-refactor entry points,
    then contract through the engine layer: identical signatures mean the
    replay adds zero path/fused misses."""
    rows = peps.random_onelayer(4, 4, 2, jax.random.PRNGKey(5))
    opt = BMPS.randomized(6, niter=2, oversample=4)
    # pre-refactor call style: explicit row sweep via the re-exported names
    keys = bmps._keys(K17, 4)
    svec = [t.reshape(t.shape[1:]) for t in rows[0]]
    for i in range(1, 4):
        svec = bmps._zipup_row(svec, rows[i], opt.chi, opt.svd, keys[i])
    warm = bmps._mps_to_scalar(svec)
    before = planner.stats()
    v = bmps.contract_onelayer(rows, opt, key=K17)
    delta = planner.stats_since(before)
    assert delta["path_misses"] == 0 and delta["fused_misses"] == 0
    assert complex(v) == complex(warm)


# ----------------------------------------------- variational accuracy ----

def test_variational_exact_at_full_chi_onelayer():
    # chi >= exact boundary bond => the fit reproduces the exact contraction
    for (nrow, ncol, bond, chi, seed) in [(3, 3, 3, 27, 1), (4, 4, 2, 16, 2)]:
        rows = peps.random_onelayer(nrow, ncol, bond, jax.random.PRNGKey(seed))
        exact = bmps.contract_exact_onelayer(rows)
        v = bmps.contract_onelayer(rows, BMPS(chi, engine="variational"),
                                   key=jax.random.PRNGKey(3))
        assert _rel(v, exact) <= 1e-8


def test_variational_exact_at_full_chi_twolayer():
    st = _state(3, 3, 2, seed=7, scale=1.0)
    merged = bmps.merge_layers(st.sites, st.sites)
    dense = complex(bmps.contract_exact_onelayer(merged)) * \
        float(jnp.exp(2.0 * st.log_scale))
    v = bmps.norm_squared(st, BMPS(40, engine="variational"), key=K17)
    assert _rel(v, dense) <= 1e-8


def test_variational_beats_zipup_at_truncating_chi():
    rows = peps.random_onelayer(4, 4, 3, jax.random.PRNGKey(42))
    exact = bmps.contract_exact_onelayer(rows)
    key = K17
    zip_err = _rel(bmps.contract_onelayer(rows, BMPS(8), key), exact)
    var_err = _rel(bmps.contract_onelayer(
        rows, BMPS(8, engine="variational"), key), exact)
    assert var_err < zip_err


def test_variational_cache_hit_rate():
    st = _state(4, 4, 2, seed=9)
    opt = BMPS.randomized(6, niter=2, oversample=4, engine="variational")
    bmps.norm_squared(st, opt, key=K17)            # warm-up
    before = planner.stats()
    bmps.norm_squared(st, opt, key=K17)            # replay
    delta = planner.stats_since(before)
    assert delta["path_misses"] == 0 and delta["fused_misses"] == 0
    hits = delta["path_hits"] + delta["fused_hits"]
    assert hits > 50                               # > 99% hit rate


def test_variational_engine_instance_option():
    rows = peps.random_onelayer(3, 3, 2, jax.random.PRNGKey(4))
    exact = bmps.contract_exact_onelayer(rows)
    v = bmps.contract_onelayer(rows, BMPS(4, engine=VariationalEngine(sweeps=3)),
                               key=K17)
    assert _rel(v, exact) < 1.0                    # smoke: runs + sane


def test_environments_respect_engine():
    st = _state(3, 3, 2, seed=7, scale=1.0)
    merged = bmps.merge_layers(st.sites, st.sites)
    dense = complex(bmps.contract_exact_onelayer(merged))
    envs = top_environments(st.sites, st.sites,
                            BMPS(40, engine="variational"), key=K17)
    assert len(envs) == st.nrow + 1
    closed = bmps._twolayer_final_scalar(envs[st.nrow])
    assert _rel(closed, dense) <= 1e-8


# ----------------------------------------------- distributed dispatch ----

def test_distributed_variational_matches_single_device():
    st = _state(4, 4, 2, seed=3)
    key = jax.random.PRNGKey(7)
    single = bmps.norm_squared(st, BMPS(8, engine="variational"), key)
    for n_shards, block in [(2, 1), (2, 2), (3, 1)]:
        opt = DistributedBMPS(8, n_shards=n_shards, block=block,
                              engine="variational")
        v = bmps.norm_squared(st, opt, key)
        assert _rel(v, single) <= 1e-10


def test_distributed_variational_environments():
    st = _state(3, 4, 2, seed=6)
    key = jax.random.PRNGKey(11)
    ref = top_environments(st.sites, st.sites,
                           BMPS(8, engine="variational"), key)
    envs = top_environments(st.sites, st.sites,
                            DistributedBMPS(8, n_shards=2, block=1,
                                            engine="variational"), key)
    assert len(envs) == len(ref)
    for lv_a, lv_b in zip(ref, envs):
        for a, b in zip(lv_a, lv_b):
            assert float(jnp.max(jnp.abs(a - b))) <= 1e-12


# ------------------------------------------------- SPMD marshalling ----

@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices (make test-engines forces 8)")
def test_spmd_entry_marshalling_no_dev0_staging(monkeypatch):
    """ROADMAP PR 5 follow-up: ``spmd.absorb_rows`` must not build the
    stacked superstep operands on device 0 and then redistribute — each
    shard's chunk is committed straight to its owner and the global array is
    assembled with ``make_array_from_single_device_arrays``.  Asserted by
    recording every ``jax.device_put`` during a superstep-engaging sweep:
    no call may use a Sharding target (the old redistribution), and the
    single-device targets must cover every mesh device."""
    st = _state(5, 8, 2, seed=3)
    chi, key = 8, jax.random.PRNGKey(7)
    opt = DistributedBMPS.randomized(chi, niter=2, oversample=4, n_shards=2,
                                     wavefront="auto")
    ref = bmps.norm_squared(st, BMPS.randomized(chi, niter=2, oversample=4),
                            key)

    calls = []
    real_put = jax.device_put

    def recording_put(x, device=None, **kw):
        calls.append(device)
        return real_put(x, device, **kw)

    monkeypatch.setattr(jax, "device_put", recording_put)
    before = dict(spmd.stats())
    val = bmps.norm_squared(st, opt, key)
    monkeypatch.undo()

    assert spmd.stats()["superstep_calls"] > before["superstep_calls"], \
        "sweep never engaged the SPMD superstep — marshalling not exercised"
    shardings = [d for d in calls
                 if d is not None and not isinstance(d, jax.Device)]
    assert not shardings, \
        f"absorb_rows staged+redistributed via Sharding targets: {shardings}"
    targets = {d for d in calls if isinstance(d, jax.Device)}
    assert len(targets) >= 2, "operands were not spread across devices"
    assert _rel(val, ref) <= 1e-10
