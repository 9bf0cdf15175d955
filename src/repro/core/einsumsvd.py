"""The einsumsvd abstraction (paper Section II-C / IV-A).

``einsumsvd`` contracts a tensor network into one tensor and refactorizes it
into two tensors joined by a single truncated bond.  The *algorithm option*
decides how:

* :class:`DirectSVD` — materialize theta, matricize, LAPACK SVD (baseline).
* :class:`RandomizedSVD` — implicit randomized SVD (Alg. 4): theta is never
  formed; asymptotically cheaper and single-pass (IBMPS / two-layer IBMPS).

All paths truncate to a *static* rank (jit-friendly); an optional relative
``cutoff`` additionally zeroes trailing singular values (shape-preserving).

Planner architecture (see :mod:`repro.core.planner`)
----------------------------------------------------
The hot path is plan-cached and fused, keyed by the **network signature**
``(subscripts, shapes, dtypes, row, col)``:

* *Signature keying* — every einsumsvd subnetwork with the same structure
  (e.g. all interior sites of a BMPS zip-up row, across rows and sweeps)
  maps to one cache entry; a different shape/dtype/split is a different
  entry.
* *Fusion boundary* — with ``RandomizedSVD(fused=True)`` (the default) the
  whole refactorization (sketch -> power iterations -> Gram-QR final +
  small SVD) is one jit-compiled function per signature; the contraction
  paths inside it are memoized by the planner's path cache, which also
  serves the unfused and :class:`DirectSVD` paths through
  ``ImplicitOperator``.
* *Kernel dispatch rule* — the big-operand GEMMs of the solve (the Gram
  matrices of the orthogonalization steps, the tall-apply reconstitutions/
  projections of the rSVD chain, and the zip-up first-column/pair-merge
  einsums of the engines) are registered as sites in
  :mod:`repro.kernels.dispatch` and route to their Pallas kernels when the
  operand is tall-skinny (``nbig >= 8 * nsmall``, small side <= 512),
  32-bit, and a TPU backend is active; otherwise the exact dense
  contraction runs.  ``set_kernel_backend`` forces either path (globally
  or per site); f64/c128 operands stay dense unconditionally.  The full
  dispatch state is folded into the planner's fused-cache keys.

Precision (see :mod:`repro.core.precision`)
-------------------------------------------
``einsumsvd(..., precision="mixed")`` (or a wrapped option from
``precision.wrap_svd``) demotes the operand tensors one storage tier
around the solve (f64 -> f32, c128 -> c64), runs the Pallas kernel sites
with bf16 multiplicands / f32 accumulation, and promotes the factors back.
The default ``"exact"`` is the identity — bit-identical to the pre-policy
code path.

The same engines seed the full update's ALS bond optimization
(:mod:`repro.core.full_update`): the reduced gate-applied network is split
here first, then refined in the neighborhood-environment metric.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import planner
from repro.core.rsvd import ImplicitOperator, randomized_svd
from repro.core.svd_grad import sqrt_reg, svd_reg


def _apply_cutoff(s: jnp.ndarray, cutoff: float) -> jnp.ndarray:
    if cutoff <= 0.0:
        return s
    return jnp.where(s >= cutoff * s[0], s, 0.0)


def _scaled_svd(mat: jnp.ndarray, rank: int):
    """Rank-``rank`` SVD of ``mat`` after scaling it to unit range and
    zeroing the entries below the dtype's resolution relative to its
    largest entry, with ``s`` and ``vh`` recomputed from ``u``.

    The scale is a power of two (exact in binary floating point, undone on
    ``s``).  The zeroed entries perturb ``mat`` by less than the SVD's own
    backward error.  Without them, the complex64 environment sweeps of a
    full-update ITE came out non-finite on a TPU v5e (its SVD on
    rank-deficient padded matrices); with them the same run is finite.

    ``u`` is the SVD's; the rows of ``u^H a`` give ``s`` (their norms) and
    ``vh`` (their directions), so ``u @ diag(s) @ vh == u u^H a`` to
    rounding however well the SVD paired its left and right vectors.  A
    TPU's ``jnp.linalg.svd`` (QDWH polar factor, then eigh; JAX's code for
    it, run on a CPU) reproduces the complex64 matrices of a 4x4 RQC
    amplitude sweep only to 5.5e-6, while its ``u u^H a`` is within 6e-7;
    on a v5e the served amplitudes were 2.1e-5 off the statevector without
    the projection and 1.9e-6 with it (PERF.md).  Both changes are
    forward-only: the derivative stays ``svd_reg``'s."""
    real = jnp.finfo(mat.dtype).dtype
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(mat)))
    _, exp = jnp.frexp(amax)
    exp = jnp.where(amax > 0, exp, 0)
    a = mat * jnp.ldexp(jnp.ones((), real), -exp)
    # the flush is forward-only: the gradient still reaches the entries
    # that are zero at a degenerate point
    a = a - jax.lax.stop_gradient(
        jnp.where(jnp.abs(a) < jnp.finfo(real).eps, a, jnp.zeros((), a.dtype)))
    u, s, vh = svd_reg(a)
    u, s, vh = u[:, :rank], s[:rank], vh[:rank]
    w = jax.lax.stop_gradient(u.conj().T @ a)
    norm = jnp.linalg.norm(w, axis=1)
    safe = jnp.where(norm > 0, norm, 1.0)
    w = jnp.where(norm[:, None] > 0, w / safe[:, None].astype(w.dtype), vh)
    s = s + jax.lax.stop_gradient(norm - s)
    vh = vh + jax.lax.stop_gradient(w - vh)
    return u, s * jnp.ldexp(jnp.ones((), real), exp), vh


@dataclasses.dataclass(frozen=True)
class DirectSVD:
    """Explicitly contract theta, then truncated dense SVD."""
    cutoff: float = 0.0

    def __call__(self, op: ImplicitOperator, rank: int, key=None):
        theta = op.dense()
        m, n = op.row_size, op.col_size
        rank = min(rank, m, n)
        u, s, vh = _truncated_svd(theta, m, n, rank, tuple(op.row_shape),
                                  tuple(op.col_shape), self.cutoff)
        return u, s, vh


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _truncated_svd(theta, m, n, rank, row_shape, col_shape, cutoff):
    # One program per shape: reshape, SVD, truncation.  svd_reg's JVP is
    # regularized for (near-)degenerate / zero singular values
    # (core/svd_grad.py) — this seam is what jax.grad(vqe_energy_peps)
    # differentiates through.
    u, s, vh = _scaled_svd(theta.reshape(m, n), rank)
    s = _apply_cutoff(s, cutoff)
    return (u.reshape(row_shape + (rank,)), s,
            vh.reshape((rank,) + col_shape))


@dataclasses.dataclass(frozen=True)
class RandomizedSVD:
    """Implicit randomized SVD (paper Alg. 4).

    ``gram_final`` replaces the paper's dense k x Ncol final SVD with a
    Gram-QR whose eigendecomposition gives the SVD (beyond-paper; see
    EXPERIMENTS.md SSPerf).

    ``fused`` (default) runs the whole solve as one jit-compiled function
    per network signature, reused across all structurally-identical
    einsumsvd calls (see :mod:`repro.core.planner`).  ``fused=False`` is the
    op-by-op reference path; both produce the same result for the same key.
    """
    niter: int = 4
    oversample: int = 8
    cutoff: float = 0.0
    gram_final: bool = True
    fused: bool = True

    def __call__(self, op: ImplicitOperator, rank: int, key=None):
        if self.fused:
            u, s, v = planner.fused_randomized_svd(
                op, rank, n_iter=self.niter, oversample=self.oversample,
                key=key, gram_final=self.gram_final)
        else:
            u, s, v = randomized_svd(op, rank, self.niter, self.oversample,
                                     key, gram_final=self.gram_final)
        s = _apply_cutoff(s, self.cutoff)
        return u, s, v


def einsumsvd(
    option,
    tensors: Sequence[jnp.ndarray],
    subscripts: Sequence[str],
    row: str,
    col: str,
    rank: int,
    absorb: str = "both",
    key=None,
    precision=None,
) -> Tuple[jnp.ndarray, ...]:
    """Contract the network and refactorize into (left, right) along a new bond.

    Parameters
    ----------
    option:      DirectSVD() or RandomizedSVD(...).
    tensors, subscripts: the network (einsum-style labels, one string/tensor).
    row, col:    dangling labels that go to the left / right factor.
    rank:        truncation bond dimension (static).
    absorb:      'both' (sqrt(s) into each factor — simple update convention),
                 'left', 'right', or 'none' (returns (u, s, v)).
    precision:   optional policy name/instance (``"exact"`` | ``"mixed"``)
                 applied to the option for this call (see
                 :mod:`repro.core.precision`).  ``None`` keeps whatever
                 policy the option already carries.

    Returns (left, right) — or (u, s, v) when absorb='none'.  The new bond is
    the LAST axis of ``left`` and the FIRST axis of ``right``.
    """
    if precision is not None:
        from repro.core.precision import wrap_svd
        option = wrap_svd(option, precision)
    op = ImplicitOperator(tensors, subscripts, row, col)
    # Every truncation in the library funnels through this seam — boundary
    # zip-up rows, the variational engine's fits, full-update bond seeds —
    # so the runtime guard's detect/escalate/retry loop wraps exactly here.
    # Unguarded (no active RuntimeGuard), this is option(op, rank, key).
    from repro.core.runtime_guard import guarded_solve
    u, s, v = guarded_solve(option, op, rank, key)
    if absorb == "none":
        return u, s, v
    return absorb_factors(u, s, v, absorb)


def absorb_factors(u: jnp.ndarray, s: jnp.ndarray, v: jnp.ndarray,
                   absorb: str = "both") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold the singular values into the factors (einsumsvd conventions).

    ``absorb='both'`` splits ``sqrt(s)`` into each factor (the simple-update
    gauge, also the ALS seed gauge of the full update); ``'left'``/``'right'``
    put all of ``s`` on one side.  ``u``'s LAST and ``v``'s FIRST axis are
    the shared bond."""
    if absorb == "both":
        # sqrt_reg == jnp.sqrt forward; its derivative at the exact zeros of
        # a rank-deficient bond is the (finite) symmetric subgradient 0.
        sq = sqrt_reg(s)
        return u * sq, sq[(slice(None),) + (None,) * (v.ndim - 1)] * v
    if absorb == "left":
        return u * s, v
    if absorb == "right":
        return u, s[(slice(None),) + (None,) * (v.ndim - 1)] * v
    raise ValueError(f"bad absorb={absorb!r}")


def truncation_error(op_dense: jnp.ndarray, u, s, v) -> jnp.ndarray:
    """Frobenius-norm relative error of a refactorization (test utility)."""
    rank = s.shape[0]
    left = u.reshape(-1, rank)
    right = v.reshape(rank, -1)
    approx = (left * s) @ right
    exact = op_dense.reshape(left.shape[0], right.shape[1])
    return jnp.linalg.norm(approx - exact) / jnp.maximum(jnp.linalg.norm(exact), 1e-300)
