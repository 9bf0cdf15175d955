"""Serving test tier (ISSUE 9): batched query serving vs per-query truth.

Three families, mirroring the module contract of ``repro.core.serving``:

* **Equivalence** — served amplitudes (prefix cache + batched final-row
  close) match per-query ``bmps.amplitude`` to <= 1e-10 across bitstrings,
  grid shapes, chi, both boundary engines and ragged batch sizes; served
  expectations match ``expectation.expectation``.
* **Concurrency** — threaded clients against >= 2 states: no lost,
  duplicated or cross-wired responses, arrival-order independence, and
  cache counters that reconcile against the query log.
* **Cache lifecycle** — re-registration invalidates (stale environments
  would be silently wrong answers), ``max_states`` LRU eviction
  re-materializes, prefix LRU eviction recomputes, and eviction never
  corrupts an in-flight batch.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import bmps as B
from repro.core import peps as P
from repro.core import planner
from repro.core.distributed import DistributedBMPS
from repro.core.einsumsvd import DirectSVD, RandomizedSVD
from repro.core.environments import onelayer_prefix_environment
from repro.core.expectation import expectation
from repro.core.observable import Observable
from repro.core.serving import DEFAULT_BUCKETS, LRUCache, ServingEngine

OPT = B.BMPS(8, DirectSVD())


@pytest.fixture(scope="module")
def state33():
    return P.random_peps(3, 3, 2, jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def state33b():
    return P.random_peps(3, 3, 2, jax.random.PRNGKey(8))


@pytest.fixture(scope="module")
def state23():
    return P.random_peps(2, 3, 2, jax.random.PRNGKey(9))


def _bits(state, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2, (n, state.nrow, state.ncol))


def _direct(state, bits_batch, option=OPT):
    return np.array([complex(B.amplitude(state, b, option))
                     for b in bits_batch])


def _assert_close(served, direct, tol=1e-10):
    served = np.asarray(served)
    scale = max(1.0, float(np.abs(direct).max()))
    assert np.abs(served - direct).max() <= tol * scale


# ---------------------------------------------------------------------------
# Equivalence: served == per-query bmps.amplitude
# ---------------------------------------------------------------------------

def test_final_row_amplitudes_matches_per_query(state33):
    bits = _bits(state33, 6, seed=1)
    bits[:, :-1] = bits[0, :-1]  # shared prefix
    env = onelayer_prefix_environment(state33, bits[0, :-1], OPT)
    out = B.final_row_amplitudes(env, state33.sites[-1],
                                 bits[:, -1, :], state33.log_scale)
    _assert_close(out, _direct(state33, bits))


def test_bmps_amplitudes_mixed_prefixes(state33):
    bits = _bits(state33, 7, seed=2)  # several distinct prefixes
    out = B.amplitudes(state33, bits, OPT)
    _assert_close(out, _direct(state33, bits))


def test_served_batch_matches_per_query(state33):
    with ServingEngine(start=False) as eng:
        eng.register_state("a", state33, OPT)
        bits = _bits(state33, 9, seed=3)
        _assert_close(eng.amplitude_batch("a", bits), _direct(state33, bits))


@settings(max_examples=6, deadline=None)
@given(nrow=st.integers(2, 3), ncol=st.integers(2, 3),
       chi=st.sampled_from([2, 4, 8]), seed=st.integers(0, 10**6))
def test_property_served_equals_per_query(nrow, ncol, chi, seed):
    state = P.random_peps(nrow, ncol, 2, jax.random.PRNGKey(seed % 97))
    option = B.BMPS(chi, DirectSVD())
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, (rng.integers(1, 6), nrow, ncol))
    with ServingEngine(start=False) as eng:
        eng.register_state("s", state, option)
        _assert_close(eng.amplitude_batch("s", bits),
                      _direct(state, bits, option))


@pytest.mark.parametrize("engine", ["zipup", "variational"])
def test_served_both_engines(state33, engine):
    option = B.BMPS(4, DirectSVD(), engine=engine)
    bits = _bits(state33, 5, seed=4)
    with ServingEngine(start=False) as eng:
        eng.register_state("s", state33, option)
        _assert_close(eng.amplitude_batch("s", bits),
                      _direct(state33, bits, option))


def test_served_randomized_svd(state33):
    option = B.BMPS(4, RandomizedSVD(niter=4, oversample=8))
    bits = _bits(state33, 5, seed=5)
    with ServingEngine(start=False) as eng:
        eng.register_state("s", state33, option)
        _assert_close(eng.amplitude_batch("s", bits),
                      _direct(state33, bits, option))


def test_served_complex64_randomized_vs_statevector():
    """complex64 served == direct == statevector with the randomized SVD.

    Regression: the Gram-QR eigenvalue clamp had an absolute floor
    (``1e-6 * max(|lam|, 1)`` in complex64), so every boundary whose Gram
    spectrum sat below 1 was truncated to noise; on this 4x4 depth-4 RQC
    (bond 4, chi=16 is exact) served and direct amplitudes differed by a
    relative 1e7.  The tolerance is the complex128 run's error (~1e-14)
    plus the documented complex64 amplitude budget."""
    from repro.core import statevector as sv
    from repro.core.circuits import (apply_circuit_exact_peps,
                                     apply_circuit_statevector,
                                     random_circuit)
    from repro.core.precision import error_budget
    circ = random_circuit(4, 4, 4, seed=7)
    state = apply_circuit_exact_peps(
        P.computational_zeros(4, 4, dtype=jnp.complex64), circ)
    vec = np.asarray(apply_circuit_statevector(sv.zeros(16), circ))
    bits = _bits(state, 6)
    exact = np.array([vec[tuple(b.reshape(-1))] for b in bits])
    option = B.BMPS(16, RandomizedSVD())
    with ServingEngine(start=False) as engine:
        engine.register_state("s", state, option)
        served = np.asarray(engine.amplitude_batch("s", bits))
    direct = _direct(state, bits, option)
    tol = 1e-13 + error_budget("amplitude", "mixed")
    rel = lambda a, b: np.linalg.norm(a - b) / np.linalg.norm(b)
    assert rel(served, direct) <= tol
    assert rel(served, exact) <= tol
    assert rel(direct, exact) <= tol


def test_served_complex64_with_the_tpus_svd_vs_statevector(monkeypatch):
    """complex64 served == direct == statevector with the SVD a TPU runs
    (``jnp.linalg.svd`` there is a QDWH polar factor, then eigh), executed
    here on the CPU through JAX's own implementation of it.

    Regression: that SVD pairs its left and right vectors loosely, so
    ``u diag(s) vh`` misses its input by up to ~5e-6 in complex64, and the
    served amplitudes of the ``repro.launch.serve`` default state (4x4
    depth-8 RQC, exact chi=256) came out 3.3e-5 off the statevector here
    and 2.1e-5 on a v5e, against 2.8e-6 with LAPACK.  ``DirectSVD`` now
    takes ``s`` and ``vh`` from ``u^H A``.  The tolerance is the complex128
    run's error (~1e-14) plus the documented complex64 amplitude budget."""
    import importlib

    from jax._src.tpu.linalg import svd as tpu_svd

    from repro.core import statevector as sv
    from repro.core.circuits import apply_circuit_statevector, random_circuit
    from repro.core.precision import error_budget
    from repro.launch.serve import build_states
    esvd = importlib.import_module("repro.core.einsumsvd")
    state = build_states(1, 4, 8, seed=7, dtype=jnp.complex64)[0]
    vec = np.asarray(apply_circuit_statevector(
        sv.zeros(16), random_circuit(4, 4, 8, seed=7)))
    bits = _bits(state, 6)
    exact = np.array([vec[tuple(b.reshape(-1))] for b in bits])
    option = B.BMPS(256)
    monkeypatch.setattr(esvd, "svd_reg",
                        lambda m: tpu_svd.svd(m, full_matrices=False))
    esvd._truncated_svd.clear_cache()
    try:
        with ServingEngine(start=False) as engine:
            engine.register_state("s", state, option)
            served = np.asarray(engine.amplitude_batch("s", bits))
        direct = _direct(state, bits, option)
    finally:
        esvd._truncated_svd.clear_cache()
    tol = 1e-13 + error_budget("amplitude", "mixed")
    rel = lambda a, b: np.linalg.norm(a - b) / np.linalg.norm(b)
    assert rel(served, direct) <= tol
    assert rel(served, exact) <= tol
    assert rel(direct, exact) <= tol


@pytest.mark.parametrize("n", [1, 5, 150])
def test_served_ragged_batch_sizes(state33, n):
    # 1 (smallest bucket), 5 (not a bucket multiple), 150 (> largest bucket)
    with ServingEngine(start=False) as eng:
        eng.register_state("a", state33, OPT)
        bits = _bits(state33, n, seed=n)
        bits[:, :-1] = bits[0, :-1]  # one group, so chunking is exercised
        _assert_close(eng.amplitude_batch("a", bits), _direct(state33, bits))


def test_served_single_query_layouts(state33):
    with ServingEngine(start=False) as eng:
        eng.register_state("a", state33, OPT)
        bits = _bits(state33, 1, seed=6)[0]
        want = complex(B.amplitude(state33, bits, OPT))
        got_grid = complex(eng.amplitude("a", bits))
        got_flat = complex(eng.amplitude("a", bits.reshape(-1)))
        assert got_grid == got_flat
        _assert_close(np.array([got_grid]), np.array([want]))


def test_served_one_row_state():
    state = P.random_peps(1, 4, 2, jax.random.PRNGKey(12))
    bits = _bits(state, 4, seed=7)
    with ServingEngine(start=False) as eng:
        eng.register_state("row", state, OPT)
        _assert_close(eng.amplitude_batch("row", bits), _direct(state, bits))


def test_served_respects_log_scale(state33):
    scaled = P.PEPS([[t for t in row] for row in state33.sites],
                    log_scale=0.7)
    bits = _bits(state33, 3, seed=8)
    with ServingEngine(start=False) as eng:
        eng.register_state("s", scaled, OPT)
        _assert_close(eng.amplitude_batch("s", bits), _direct(scaled, bits))


def test_served_expectation_matches_direct(state33):
    obs = Observable.Z(0) + Observable.XX(0, 1) + Observable.ZZ(1, 4)
    with ServingEngine(start=False) as eng:
        eng.register_state("s", state33, OPT)
        got = complex(eng.expectation("s", obs))
        want = complex(expectation(state33, obs, OPT))
        assert abs(got - want) <= 1e-12 * max(1.0, abs(want))


def test_served_expectation_custom_env_key(state33):
    obs = Observable.Z(4)
    key = jax.random.PRNGKey(33)
    with ServingEngine(start=False) as eng:
        eng.register_state("s", state33, OPT, env_key=key)
        got = complex(eng.expectation("s", obs))
        want = complex(expectation(state33, obs, OPT, key=key))
        assert abs(got - want) <= 1e-12 * max(1.0, abs(want))


def test_register_rejects_distributed_option(state33):
    with ServingEngine(start=False) as eng:
        with pytest.raises(TypeError):
            eng.register_state("d", state33, DistributedBMPS(4))
        with pytest.raises(TypeError):
            eng.register_state("d", state33, "not-an-option")


def test_bmps_amplitudes_rejects_distributed(state33):
    with pytest.raises(TypeError):
        B.amplitudes(state33, _bits(state33, 2), DistributedBMPS(4))


# ---------------------------------------------------------------------------
# Concurrency: threaded clients, >= 2 states
# ---------------------------------------------------------------------------

def test_threaded_no_lost_dup_or_crosswired(state33, state33b):
    with ServingEngine(window_ms=5.0) as eng:
        eng.register_state("a", state33, OPT)
        eng.register_state("b", state33b, OPT)
        states = {"a": state33, "b": state33b}
        results = {}
        res_lock = threading.Lock()

        def client(cid):
            rng = np.random.default_rng(100 + cid)
            futs = []
            for q in range(10):
                name = ("a", "b")[rng.integers(2)]
                bits = rng.integers(0, 2, (3, 3))
                futs.append((name, bits, eng.submit_amplitude(name, bits)))
            for name, bits, fut in futs:
                v = complex(fut.result(timeout=120))
                with res_lock:
                    results[(cid, name, bits.tobytes())] = v

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        assert len(results) == 6 * 10 or len(results) >= 40  # dedup by key
        for (cid, name, raw), v in results.items():
            bits = np.frombuffer(raw, dtype=np.int64).reshape(3, 3)
            want = complex(B.amplitude(states[name], bits, OPT))
            assert abs(v - want) <= 1e-10 * max(1.0, abs(want)), \
                f"cross-wired or corrupted response for client {cid}"
        st_ = eng.stats()
        assert st_["queries_amplitude"] == 60


def test_threaded_arrival_order_independence(state33):
    bits = _bits(state33, 8, seed=11)
    with ServingEngine(window_ms=5.0) as eng:
        eng.register_state("a", state33, OPT)
        futs = [eng.submit_amplitude("a", b) for b in bits]
        first = [complex(f.result(timeout=120)) for f in futs]
        futs = [eng.submit_amplitude("a", b) for b in reversed(bits)]
        second = [complex(f.result(timeout=120)) for f in reversed(futs)]
        assert first == second


def test_threaded_mixed_kinds(state33, state33b):
    obs = Observable.Z(0)
    with ServingEngine(window_ms=5.0) as eng:
        eng.register_state("a", state33, OPT)
        eng.register_state("b", state33b, OPT)
        bits = _bits(state33, 4, seed=12)
        amp_futs = [eng.submit_amplitude("a", b) for b in bits]
        exp_futs = [eng.submit_expectation(n, obs) for n in ("a", "b")]
        _assert_close(np.array([complex(f.result(120)) for f in amp_futs]),
                      _direct(state33, bits))
        want_a = complex(expectation(state33, obs, OPT))
        want_b = complex(expectation(state33b, obs, OPT))
        assert abs(complex(exp_futs[0].result(120)) - want_a) <= 1e-12 * max(1.0, abs(want_a))
        assert abs(complex(exp_futs[1].result(120)) - want_b) <= 1e-12 * max(1.0, abs(want_b))


def test_stats_reconcile_with_query_log(state33):
    with ServingEngine(start=False) as eng:
        eng.register_state("a", state33, OPT)
        bits = _bits(state33, 4, seed=13)
        bits[:2, :-1] = bits[0, :-1]  # exactly 3 distinct prefixes
        bits[2:, :-1] = bits[2, :-1]
        prefixes = {b[:-1].tobytes() for b in bits}
        eng.amplitude_batch("a", bits)
        eng.amplitude_batch("a", bits)  # identical second round: all hits
        st_ = eng.stats()
        ps = st_["per_state"]["a"]
        assert st_["queries_amplitude"] == 8
        assert st_["batches"] == 2
        # one counted lookup per query group; first round misses every
        # distinct prefix, second round hits every one
        assert ps["prefix_misses"] == len(prefixes)
        assert ps["prefix_hits"] == len(prefixes)
        # a 3-row state absorbs one row per fresh prefix (row 0 is the base)
        assert st_["rows_absorbed"] == len(prefixes)


def test_threaded_stats_consistency(state33, state33b):
    with ServingEngine(window_ms=5.0) as eng:
        eng.register_state("a", state33, OPT)
        eng.register_state("b", state33b, OPT)
        per_state_prefixes = {"a": set(), "b": set()}
        futs = []
        rng = np.random.default_rng(14)
        for q in range(30):
            name = ("a", "b")[q % 2]
            bits = rng.integers(0, 2, (3, 3))
            per_state_prefixes[name].add(bits[:-1].tobytes())
            futs.append(eng.submit_amplitude(name, bits))
        for f in futs:
            f.result(timeout=120)
        st_ = eng.stats()
        assert st_["queries_amplitude"] == 30
        for name in ("a", "b"):
            ps = st_["per_state"][name]
            lookups = ps["prefix_hits"] + ps["prefix_misses"]
            # one counted lookup per executed query group
            assert len(per_state_prefixes[name]) <= lookups <= 15
            assert ps["prefix_misses"] == len(per_state_prefixes[name])


def test_submit_unknown_state_resolves_to_error(state33):
    with ServingEngine() as eng:
        eng.register_state("a", state33, OPT)
        fut = eng.submit_amplitude("nope", np.zeros((3, 3), dtype=int))
        with pytest.raises(KeyError):
            fut.result(timeout=120)
        # the engine survives: later queries still serve
        good = eng.submit_amplitude("a", np.zeros((3, 3), dtype=int))
        complex(good.result(timeout=120))


def test_submit_bad_shape_resolves_to_error(state33):
    with ServingEngine() as eng:
        eng.register_state("a", state33, OPT)
        fut = eng.submit_amplitude("a", np.zeros((2, 2), dtype=int))
        with pytest.raises(ValueError):
            fut.result(timeout=120)


# ---------------------------------------------------------------------------
# Cache lifecycle
# ---------------------------------------------------------------------------

def test_reregister_invalidates_prefix_envs(state33, state33b):
    bits = _bits(state33, 3, seed=15)
    with ServingEngine(start=False) as eng:
        eng.register_state("s", state33, OPT)
        old = np.asarray(eng.amplitude_batch("s", bits))
        eng.register_state("s", state33b, OPT)
        new = np.asarray(eng.amplitude_batch("s", bits))
        want = _direct(state33b, bits)
        # guard: the two states genuinely disagree, so a stale cached
        # environment would be visible as a wrong answer here
        assert np.abs(old - want).max() > 1e-6
        _assert_close(new, want)


def test_reregister_bumps_version_and_counters(state33, state33b):
    with ServingEngine(start=False) as eng:
        eng.register_state("s", state33, OPT)
        eng.amplitude_batch("s", _bits(state33, 2, seed=16))
        assert eng.stats()["per_state"]["s"]["version"] == 0
        eng.register_state("s", state33b, OPT)
        st_ = eng.stats()
        assert st_["per_state"]["s"]["version"] == 1
        assert st_["invalidations"] == 1
        assert st_["per_state"]["s"]["prefix_size"] == 0  # fresh cache


def test_max_states_lru_eviction_rematerializes(state33, state33b, state23):
    bits33 = _bits(state33, 2, seed=17)
    with ServingEngine(start=False, max_states=1) as eng:
        eng.register_state("a", state33, OPT)
        eng.register_state("b", state33b, OPT)
        eng.register_state("c", state23, OPT)
        first = np.asarray(eng.amplitude_batch("a", bits33))
        eng.amplitude_batch("b", bits33)  # evicts a's caches
        st_ = eng.stats()
        assert st_["state_evictions"] == 1
        assert st_["per_state"]["a"]["materialized"] is False
        assert st_["per_state"]["a"]["prefix_size"] == 0
        assert st_["per_state"]["b"]["materialized"] is True
        # "a" stays registered; the next query re-materializes, same values
        again = np.asarray(eng.amplitude_batch("a", bits33))
        assert np.array_equal(first, again)
        _assert_close(again, _direct(state33, bits33))


def test_prefix_lru_eviction_recomputes(state33):
    bits = _bits(state33, 6, seed=18)  # distinct prefixes overflow cache=2
    with ServingEngine(start=False, max_prefixes=2) as eng:
        eng.register_state("s", state33, OPT)
        first = np.asarray(eng.amplitude_batch("s", bits))
        st_ = eng.stats()["per_state"]["s"]
        assert st_["prefix_evictions"] > 0
        assert st_["prefix_size"] <= 2
        again = np.asarray(eng.amplitude_batch("s", bits))
        assert np.array_equal(first, again)
        _assert_close(again, _direct(state33, bits))


def test_eviction_never_corrupts_inflight(state33, state33b):
    """Churn registrations + state eviction while a client hammers queries."""
    bits = _bits(state33, 2, seed=19)
    want = _direct(state33, bits)
    stop = threading.Event()
    errors = []

    with ServingEngine(window_ms=0.5, max_states=1) as eng:
        eng.register_state("a", state33, OPT)
        eng.register_state("b", state33b, OPT)

        def churn():
            while not stop.is_set():
                # same tensors re-registered: values must be unaffected
                eng.register_state("a", state33, OPT)
                eng.amplitude_batch("b", bits)  # evicts a's caches

        t = threading.Thread(target=churn)
        t.start()
        try:
            for _ in range(15):
                futs = [eng.submit_amplitude("a", b) for b in bits]
                got = np.array([complex(f.result(timeout=120)) for f in futs])
                if np.abs(got - want).max() > 1e-10 * max(1.0, np.abs(want).max()):
                    errors.append(got)
        finally:
            stop.set()
            t.join()
    assert not errors


def test_unregister(state33):
    with ServingEngine(start=False) as eng:
        eng.register_state("s", state33, OPT)
        eng.unregister("s")
        with pytest.raises(KeyError):
            eng.amplitude("s", np.zeros((3, 3), dtype=int))
        with pytest.raises(KeyError):
            eng.unregister("s")
        assert eng.registered() == []


# ---------------------------------------------------------------------------
# Infrastructure: bucketing, stats, fused-cache reuse, lifecycle
# ---------------------------------------------------------------------------

def test_stats_keys_present_before_any_query():
    with ServingEngine(start=False) as eng:
        st_ = eng.stats()
        for key in ("queries_amplitude", "queries_expectation", "batches",
                    "rows_absorbed", "state_evictions", "invalidations",
                    "padded_queries", "per_state", "states"):
            assert key in st_
        assert st_["states"] == 0


def test_chunk_ladder():
    eng = ServingEngine(start=False, bucket_sizes=(1, 2, 4))
    assert eng._chunks(1) == [1]
    assert eng._chunks(3) == [4]
    assert eng._chunks(4) == [4]
    assert eng._chunks(5) == [4, 1]
    assert eng._chunks(11) == [4, 4, 4]
    eng.close()
    assert DEFAULT_BUCKETS == tuple(sorted(DEFAULT_BUCKETS))


def test_padding_counter(state33):
    with ServingEngine(start=False, bucket_sizes=(4,)) as eng:
        eng.register_state("s", state33, OPT)
        bits = _bits(state33, 3, seed=20)
        bits[:, :-1] = bits[0, :-1]  # one group of 3 -> one padded 4-bucket
        out = eng.amplitude_batch("s", bits)
        assert out.shape == (3,)
        assert eng.stats()["padded_queries"] == 1
        _assert_close(out, _direct(state33, bits))


def test_fused_close_cache_reuse(state33):
    with ServingEngine(start=False) as eng:
        eng.register_state("s", state33, OPT)
        bits = _bits(state33, 4, seed=21)
        bits[:, :-1] = bits[0, :-1]
        eng.amplitude_batch("s", bits)  # compiles the 4-bucket close
        before = planner.stats()
        eng.amplitude_batch("s", bits)
        delta = planner.stats_since(before)
        assert delta["fused_misses"] == 0
        assert delta["fused_hits"] >= 1


def test_obs_env_cache_counters(state33):
    obs = Observable.Z(0)
    with ServingEngine(start=False) as eng:
        eng.register_state("s", state33, OPT)
        eng.expectation("s", obs)
        eng.expectation("s", obs)
        ps = eng.stats()["per_state"]["s"]
        assert ps["obs_env_builds"] == 1
        assert ps["obs_env_hits"] == 1
        assert eng.stats()["queries_expectation"] == 2


def test_close_is_idempotent_and_blocks_submit(state33):
    eng = ServingEngine()
    eng.register_state("s", state33, OPT)
    eng.close()
    eng.close()  # idempotent
    with pytest.raises(RuntimeError):
        eng.submit_amplitude("s", np.zeros((3, 3), dtype=int))
    with pytest.raises(RuntimeError):
        eng.register_state("t", state33, OPT)


def test_pending_requests_drain_on_close(state33):
    eng = ServingEngine(window_ms=50.0)
    eng.register_state("s", state33, OPT)
    bits = _bits(state33, 6, seed=22)
    futs = [eng.submit_amplitude("s", b) for b in bits]
    eng.close()  # must drain, not drop
    got = np.array([complex(f.result(timeout=120)) for f in futs])
    _assert_close(got, _direct(state33, bits))


def test_constructor_validation():
    with pytest.raises(ValueError):
        ServingEngine(max_states=0)
    with pytest.raises(ValueError):
        ServingEngine(bucket_sizes=())
    with pytest.raises(ValueError):
        ServingEngine(bucket_sizes=(0, 2))


def test_lru_cache_unit():
    c = LRUCache(2)
    assert c.get("x") is None          # counted miss
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1             # counted hit, refreshes "a"
    c.put("c", 3)                      # evicts "b" (LRU)
    assert c.peek("b") is None         # peek: uncounted
    assert c.peek("a") == 1
    s = c.stats()
    assert (s["hits"], s["misses"], s["evictions"], s["size"]) == (1, 1, 1, 2)
    with pytest.raises(ValueError):
        LRUCache(0)
