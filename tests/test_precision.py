"""Per-precision error-budget tier (ISSUE 7 satellite).

Every budget asserted here comes from ONE table —
``repro.core.precision.ERROR_BUDGETS`` — which ``docs/contraction.md``
embeds verbatim (:func:`repro.core.precision.budget_table_markdown`).  The
first test asserts the doc contains exactly the rendered table, so docs and
tests cannot drift; the rest *measure* each workload against its budget:

* the **exact** lane re-pins the goldens (bit-compatible construction:
  ``BMPS(chi)`` and ``BMPS(chi, precision="exact")`` are equal options);
* the **mixed** lane measures each acceptance workload against the
  exact-path result of the *identical* contraction (same chi, engine, PRNG
  key), isolating the precision policy from the truncation error;
* the **bf16 kernel** lane forces the Pallas sites with bf16 multiplicands
  and bounds their error against the f32 dense references.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bmps as B
from repro.core import peps as P
from repro.core import statevector as sv
from repro.core.circuits import (apply_circuit_exact_peps,
                                 apply_circuit_statevector, random_circuit)
from repro.core.einsumsvd import DirectSVD, RandomizedSVD, einsumsvd
from repro.core.ite import ite_run
from repro.core.observable import tfi_hamiltonian
from repro.core.peps import FullUpdate, QRUpdate
from repro.core.precision import (ERROR_BUDGETS, EXACT, MIXED,
                                  PrecisionWrapped, budget_table_markdown,
                                  error_budget, policy_of, resolve_precision,
                                  wrap_svd)

K17 = jax.random.PRNGKey(17)
DOCS = Path(__file__).resolve().parent.parent / "docs" / "contraction.md"


def _rel(a, b):
    return abs(complex(a) - complex(b)) / abs(complex(b))


# ---------------------------------------------------------------- table ----

def test_budget_table_docs_no_drift():
    """docs/contraction.md embeds exactly the rendered ERROR_BUDGETS table.

    A substring assertion on the full rendering: change a budget (or a case
    description) in code without regenerating the doc — or vice versa — and
    this fails, naming the stale side."""
    table = budget_table_markdown()
    doc = DOCS.read_text()
    assert table in doc, (
        "docs/contraction.md is out of sync with precision.ERROR_BUDGETS —"
        " paste the current budget_table_markdown() into the doc:\n" + table)


def test_budget_table_lists_every_workload():
    table = budget_table_markdown()
    for name in ERROR_BUDGETS:
        assert f"`{name}`" in table


def test_error_budget_lookup():
    assert error_budget("amplitude", "exact") == 1e-12
    assert error_budget("amplitude", MIXED) == ERROR_BUDGETS["amplitude"]["mixed"]
    with pytest.raises(KeyError, match="no budget"):
        error_budget("nonsense_workload", "exact")


# --------------------------------------------------------------- policy ----

def test_resolve_precision_rejects_unknown():
    with pytest.raises(TypeError, match=r"exact.*mixed|mixed.*exact"):
        resolve_precision("fast")
    with pytest.raises(TypeError):
        resolve_precision(32)
    assert resolve_precision("exact") is EXACT
    assert resolve_precision(MIXED) is MIXED


def test_wrap_svd_exact_is_identity():
    """The exact policy returns the bare option — bit-identical construction."""
    opt = DirectSVD()
    assert wrap_svd(opt, "exact") is opt
    assert policy_of(opt) is EXACT


def test_wrap_svd_idempotent_both_directions():
    opt = RandomizedSVD()
    mixed = wrap_svd(opt, "mixed")
    assert isinstance(mixed, PrecisionWrapped) and mixed.inner is opt
    assert policy_of(mixed) is MIXED
    # re-wrapping unwraps first: mixed->mixed keeps one layer, mixed->exact
    # returns the bare option
    assert wrap_svd(mixed, "mixed").inner is opt
    assert wrap_svd(mixed, "exact") is opt


def test_bmps_exact_option_equals_prepolicy_option():
    """``BMPS(chi)`` before and after the precision field build equal
    options — the svd is NOT wrapped under the default exact policy."""
    assert B.BMPS(8) == B.BMPS(8, precision="exact")
    assert isinstance(B.BMPS(8).svd, DirectSVD)
    assert isinstance(B.BMPS(8, precision="mixed").svd, PrecisionWrapped)


def test_distributed_bmps_threads_precision():
    from repro.core.distributed import DistributedBMPS
    opt = DistributedBMPS(8, precision="mixed")
    assert isinstance(opt.svd, PrecisionWrapped)
    with pytest.raises(TypeError):
        DistributedBMPS(8, precision="double")


def test_einsumsvd_precision_kwarg_roundtrips_dtype():
    """``einsumsvd(..., precision="mixed")`` demotes around the solve and
    promotes back: output dtypes match the exact path, values within the
    storage-demotion error."""
    a = jax.random.normal(jax.random.PRNGKey(0), (6, 5, 7), jnp.float64)
    b = jax.random.normal(jax.random.PRNGKey(1), (7, 4, 3), jnp.float64)
    args = ([a, b], ["abc", "cde"])
    ue, se, ve = einsumsvd(DirectSVD(), *args, row="ab", col="de",
                           rank=4, absorb="none", key=K17)
    um, sm, vm = einsumsvd(DirectSVD(), *args, row="ab", col="de",
                           rank=4, absorb="none", key=K17, precision="mixed")
    assert um.dtype == ue.dtype and sm.dtype == se.dtype and vm.dtype == ve.dtype
    np.testing.assert_allclose(np.asarray(sm), np.asarray(se), rtol=1e-5)


# ----------------------------------------------------------- exact lane ----

def test_exact_budget_contract_onelayer():
    """The exact lane meets its documented budget against the exact
    contraction at a chi covering the exact bond (27 = D^3 for this 4x4
    D=3 grid); measured errors are ~1e-15."""
    tol = error_budget("contract_onelayer", "exact")
    rows = P.random_onelayer(4, 4, 3, jax.random.PRNGKey(42))
    exact = B.contract_exact_onelayer(rows)
    v = B.contract_onelayer(rows, B.BMPS(27, precision="exact"), key=K17)
    assert _rel(v, exact) <= tol
    v = B.contract_onelayer(rows, B.BMPS.randomized(27, precision="exact"),
                            key=K17)
    assert _rel(v, exact) <= tol


# ----------------------------------------------------------- mixed lane ----
#
# Each workload compares precision="mixed" against the exact-path result of
# the IDENTICAL contraction (same chi, engine, PRNG key), so the measured
# number is the precision error alone, not the truncation error.

def test_mixed_budget_contract_onelayer():
    tol = error_budget("contract_onelayer", "mixed")
    rows = P.random_onelayer(4, 4, 3, jax.random.PRNGKey(42))
    e = B.contract_onelayer(rows, B.BMPS(8), key=K17)
    m = B.contract_onelayer(rows, B.BMPS(8, precision="mixed"), key=K17)
    assert _rel(m, e) <= tol, f"direct: {_rel(m, e):.3e} > {tol:.0e}"
    e = B.contract_onelayer(rows, B.BMPS.randomized(8), key=K17)
    m = B.contract_onelayer(rows, B.BMPS.randomized(8, precision="mixed"),
                            key=K17)
    assert _rel(m, e) <= tol, f"randomized: {_rel(m, e):.3e} > {tol:.0e}"


@pytest.fixture(scope="module")
def tfi44():
    obs = tfi_hamiltonian(4, 4, jz=-1.0, hx=-3.5)
    run = ite_run(P.computational_zeros(4, 4), obs, steps=10, tau=0.05,
                  update=QRUpdate(rank=3), contract=B.BMPS(16),
                  measure_every=10)
    return obs, run.state


def test_mixed_budget_contract_twolayer(tfi44):
    tol = error_budget("contract_twolayer", "mixed")
    _, state = tfi44
    e = B.norm_squared(state, B.BMPS(8), K17)
    m = B.norm_squared(state, B.BMPS(8, precision="mixed"), K17)
    assert _rel(m, e) <= tol, f"{_rel(m, e):.3e} > {tol:.0e}"


def test_mixed_budget_amplitude_rqc():
    circ = random_circuit(3, 3, 8, seed=3)
    state = apply_circuit_exact_peps(P.computational_zeros(3, 3), circ)
    bits = np.zeros((3, 3), dtype=int)
    e = B.amplitude(state, bits, B.BMPS(8), K17)
    m = B.amplitude(state, bits, B.BMPS(8, precision="mixed"), K17)
    # exact lane: the exact path reproduces the statevector amplitude
    vec = apply_circuit_statevector(sv.zeros(9), circ)
    exact = complex(vec[(0,) * 9])
    assert _rel(e, exact) <= error_budget("amplitude", "exact")
    tol = error_budget("amplitude", "mixed")
    assert _rel(m, e) <= tol, f"{_rel(m, e):.3e} > {tol:.0e}"


def test_mixed_budget_full_update_ite_step(tfi44):
    tol = error_budget("full_update_ite_step", "mixed")
    obs, _ = tfi44

    def energy(precision):
        upd = FullUpdate(rank=3, chi=8,
                         svd=wrap_svd(DirectSVD(), precision),
                         env_svd=wrap_svd(DirectSVD(), precision))
        res = ite_run(P.computational_zeros(4, 4), obs, steps=1, tau=0.05,
                      update=upd, contract=B.BMPS(8, precision=precision),
                      measure_every=1)
        return res.energies[-1]

    ee, em = energy("exact"), energy("mixed")
    err = abs(em - ee) / abs(ee)
    assert err <= tol, f"{err:.3e} > {tol:.0e}"


def test_mixed_budget_kernel_bf16_gemm():
    """Forced-Pallas bf16-multiplicand gram/tall-apply vs the f32 dense
    references, bounded by the documented kernel budget."""
    from repro.kernels.gram import gram, gram_complex
    from repro.kernels.matvec import planar_matmul
    tol = error_budget("kernel_bf16_gemm", "mixed")
    a = jax.random.normal(jax.random.PRNGKey(0), (512, 24), jnp.float32)
    bmat = jax.random.normal(jax.random.PRNGKey(1), (24, 8), jnp.float32)

    def relf(got, want):
        got, want = np.asarray(got), np.asarray(want)
        dt = np.complex128 if np.iscomplexobj(want) else np.float64
        got, want = got.astype(dt), want.astype(dt)
        return np.linalg.norm(got - want) / np.linalg.norm(want)

    assert relf(gram(a, compute="bfloat16"), a.T @ a) <= tol
    assert relf(planar_matmul(a, bmat, compute="bfloat16"), a @ bmat) <= tol
    c = (a[:256] + 1j * a[256:]).astype(jnp.complex64)
    assert relf(gram_complex(c, compute="bfloat16"),
                c.conj().T @ c) <= tol


def test_mixed_scaling_handles_unnormalized_operands():
    """The per-solve operand scaling inside PrecisionWrapped keeps badly
    scaled networks solvable: without it, tensors with ~1e-5 magnitudes
    push the demoted f32 spectrum under the Gram-QR eigenvalue clamp and
    the randomized solve collapses to ~zero.

    The reference is the identical exact solve on PRE-normalized operands
    with the scale folded back into s.  The unnormalized exact path must
    match it too: the Gram-QR eigenvalue clamp is relative to the largest
    eigenvalue, so a ~1e-20 Gram spectrum is solved, not clamped to a
    floor (an absolute floor once returned sqrt(1e-13) for every singular
    value here)."""
    a = 1e-5 * jax.random.normal(jax.random.PRNGKey(2), (40, 6, 9),
                                 jnp.float64)
    b = 1e-5 * jax.random.normal(jax.random.PRNGKey(3), (9, 6, 30),
                                 jnp.float64)
    _, s_ref, _ = einsumsvd(RandomizedSVD(), [a * 1e5, b * 1e5],
                            ["abc", "cde"], row="ab", col="de",
                            rank=4, absorb="none", key=K17)
    s_ref = np.asarray(s_ref) * 1e-10
    _, sm, _ = einsumsvd(RandomizedSVD(), [a, b], ["abc", "cde"],
                         row="ab", col="de", rank=4, absorb="none",
                         key=K17, precision="mixed")
    np.testing.assert_allclose(np.asarray(sm), s_ref, rtol=1e-4)
    _, se, _ = einsumsvd(RandomizedSVD(), [a, b], ["abc", "cde"],
                         row="ab", col="de", rank=4, absorb="none", key=K17)
    np.testing.assert_allclose(np.asarray(se), s_ref, rtol=1e-10)
