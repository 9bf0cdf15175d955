"""Reshape-avoiding orthogonalization via Gram matrices (paper Alg. 5).

On a distributed tensor, matricizing for QR forces a full data redistribution
(Cyclops) / an all-to-all re-layout (GSPMD).  The paper instead forms the
small Gram matrix ``G = A*A`` with a *contraction* over the big modes, which
the backend executes as a GEMM with no reshape of the big operand, then
eigendecomposes G locally and reconstitutes the isometry with one more GEMM.

All functions are jit-safe (static shapes, `eigh` only on the small matrix).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.svd_grad import eigh_reg
from repro.kernels import dispatch as _dispatch

_EPS = {jnp.float32.dtype: 1e-6, jnp.float64.dtype: 1e-13,
        jnp.complex64.dtype: 1e-6, jnp.complex128.dtype: 1e-13,
        jnp.bfloat16.dtype: 1e-3}


def _eps_for(dtype) -> float:
    return _EPS.get(jnp.dtype(dtype), 1e-6)


# --------------------------------------------------------------------------
# Kernel dispatch (tall-skinny hot paths -> Pallas kernels)
# --------------------------------------------------------------------------
#
# Two dispatch sites (registered in repro.kernels.dispatch, which owns the
# shared gate: f32/bf16/c64 only — the kernels accumulate in f32, so
# routing f64 there would silently halve precision — and, in auto mode,
# tall-skinny shapes on a real TPU backend; CPU CI stays dense/exact):
#
#   * "gram"       — G = A^H A of Alg. 5: the streaming-Gram kernel
#     (src/repro/kernels/gram.py), G resident in VMEM while A streams.
#   * "tall_apply" — the reconstitution Q = A P (and the final rSVD
#     projections in core/rsvd.py): the streaming tall-apply kernel
#     (src/repro/kernels/matvec.py), small matrix resident, A streams.
#
# Together the two sites cover every big-operand GEMM of one rSVD power
# iteration.  set_gram_backend/gram_backend/gram_dispatch_stats are the
# PR 1 names, kept as thin aliases of the registry-wide controls; see
# tests/test_planner.py + tests/test_dispatch.py.


def set_gram_backend(mode: str) -> str:
    """Select the kernel backend mode: 'auto' (shape/dtype/backend-gated
    Pallas), 'pallas' (force kernels), or 'dense'.  Returns the previous
    mode.  Alias of ``repro.kernels.dispatch.set_kernel_backend`` (global
    mode), kept for the PR 1 API."""
    if mode not in ("auto", "pallas", "dense"):
        raise ValueError(f"bad gram backend {mode!r}")
    return _dispatch.set_kernel_backend(mode)


def gram_backend() -> str:
    """The currently-selected global kernel backend mode."""
    return _dispatch.kernel_backend()


def gram_dispatch_stats() -> dict:
    """Per-site pallas/dense call counters (all sites, not just gram)."""
    return _dispatch.dispatch_stats()


def reset_gram_dispatch_stats() -> None:
    _dispatch.reset_dispatch_stats()


def _gram_dense(a: jnp.ndarray, big_axes, nbig: int, nsmall: int):
    g = jnp.tensordot(a.conj(), a, axes=(big_axes, big_axes))
    return g.reshape(nsmall, nsmall)


def _gram_pallas(a: jnp.ndarray, big_axes, nbig: int, nsmall: int):
    from repro.kernels.gram import gram, gram_complex
    mat = a.reshape(nbig, nsmall)
    compute = _dispatch.kernel_compute()
    if jnp.issubdtype(a.dtype, jnp.complexfloating):
        return gram_complex(mat, compute=compute)
    return gram(mat, compute=compute)


_dispatch.register_kernel(
    "gram", pallas=_gram_pallas, dense=_gram_dense,
    supported=lambda a, big_axes, nbig, nsmall:
        _dispatch.dtype_supported(a.dtype),
    auto=lambda a, big_axes, nbig, nsmall:
        _dispatch.tall_skinny_auto(nbig, nsmall))


def _gram_matrix(a: jnp.ndarray, big_axes: Tuple[int, ...],
                 nbig: int, nsmall: int) -> jnp.ndarray:
    """G = A^H A as an (nsmall, nsmall) matrix, Pallas-dispatched."""
    return _dispatch.dispatch("gram", a, big_axes, nbig, nsmall)


def _tall_project_dense(a: jnp.ndarray, mat: jnp.ndarray, n_small: int):
    small_shape = a.shape[a.ndim - n_small:]
    small_axes = tuple(range(a.ndim - n_small, a.ndim))
    p = mat.reshape(small_shape + (mat.shape[1],))
    return jnp.tensordot(a, p, axes=(small_axes, tuple(range(n_small))))


def _tall_project_pallas(a: jnp.ndarray, mat: jnp.ndarray, n_small: int):
    from repro.kernels.matvec import planar_matmul
    big_shape = a.shape[: a.ndim - n_small]
    nbig = 1
    for s in big_shape:
        nbig *= s
    out = planar_matmul(a.reshape(nbig, mat.shape[0]), mat,
                        compute=_dispatch.kernel_compute())
    return out.reshape(big_shape + (mat.shape[1],))


def _tall_project_nbig(a: jnp.ndarray, n_small: int) -> int:
    nbig = 1
    for s in a.shape[: a.ndim - n_small]:
        nbig *= s
    return nbig


_dispatch.register_kernel(
    "tall_apply", pallas=_tall_project_pallas, dense=_tall_project_dense,
    supported=lambda a, mat, n_small:
        _dispatch.dtype_supported(a.dtype, mat.dtype),
    auto=lambda a, mat, n_small:
        _dispatch.tall_skinny_auto(_tall_project_nbig(a, n_small),
                                   max(mat.shape)))


def tall_project(a: jnp.ndarray, mat: jnp.ndarray,
                 n_small: int) -> jnp.ndarray:
    """Contract ``a``'s trailing ``n_small`` axes with the 2D matrix ``mat``.

    ``mat`` is ``(nsmall, q)`` with ``nsmall`` the product of the trailing
    axes; the result has shape ``big_shape + (q,)``.  This is the streaming
    "apply a small matrix to a tall operand" step of the rSVD chain —
    Pallas-dispatched (site ``"tall_apply"``); the dense path is the exact
    pre-kernel ``tensordot``."""
    return _dispatch.dispatch("tall_apply", a, mat, n_small)


def gram_factors(a: jnp.ndarray, n_small: int):
    """The Gram trick's factors ``(q, x, sqrt_lam)`` of :func:`gram_qr`:
    ``a == q . R`` with ``R = diag(sqrt_lam) @ x^H`` over the small modes,
    ``x`` unitary (``nsmall x nsmall``) and ``sqrt_lam`` the clamped square
    roots of the Gram eigenvalues, in eigh's (ascending) order.  ``R^H =
    x diag(sqrt_lam)`` is an SVD up to the column order, which
    :func:`repro.core.rsvd.randomized_svd` uses instead of a dense SVD."""
    big_shape = a.shape[: a.ndim - n_small]
    nbig = 1
    for s in big_shape:
        nbig *= s
    nsmall = 1
    for s in a.shape[a.ndim - n_small:]:
        nsmall *= s

    big_axes = tuple(range(a.ndim - n_small))
    # G_{cc'} = sum_big conj(A)_{big,c} A_{big,c'} — contraction, no reshape of A
    # (or the Pallas streaming-Gram kernel when the operand qualifies).
    g_mat = _gram_matrix(a, big_axes, nbig, nsmall)  # small, local
    # eigh_reg == jnp.linalg.eigh forward; its regularized JVP keeps the
    # gradient finite when G is rank-deficient (clusters of exactly zero
    # eigenvalues — the squared singular values of a padded bond).  The
    # eps clamp below additionally stops the gradient through the noise
    # directions (jnp.maximum passes the gradient to the clamp side).
    lam, x = eigh_reg(g_mat)
    # The clamp is relative to the largest eigenvalue (tiny only guards the
    # all-zero matrix): an absolute floor turned every Gram spectrum below 1,
    # i.e. every small-scale network, into clamp noise.
    eps = jnp.maximum(_eps_for(a.dtype) * jnp.max(jnp.abs(lam)),
                      jnp.finfo(lam.real.dtype).tiny)
    lam = jnp.maximum(lam.real, eps)
    sqrt_lam = jnp.sqrt(lam)
    p_mat = x / sqrt_lam[None, :]                      # P = R^{-1} = X L^{-1/2}
    # Q = A P (contraction over the small modes — big modes untouched;
    # Pallas-dispatched via the "tall_apply" site, dense path identical to
    # the pre-kernel tensordot).
    q = tall_project(a, p_mat, n_small).reshape(a.shape)
    return q, x, sqrt_lam


def gram_qr(a: jnp.ndarray, n_small: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """QR-equivalent factorization A = Q @ R via the Gram trick (Alg. 5).

    ``a`` is treated as an operator from its *last* ``n_small`` modes (the
    small space) to its leading modes (the big space).  Returns:

    * ``q`` with the same shape as ``a`` — isometric over the big modes
      (on the row space of A),
    * ``r`` of shape ``small + small`` such that ``a == q . r`` (contraction
      over the last ``n_small`` modes of ``q`` with the first ``n_small`` of
      ``r``).

    No reshape touches the big modes: G = A*A is formed by a contraction, the
    eigendecomposition happens on the small G only.
    """
    small_shape = a.shape[a.ndim - n_small:]
    q, x, sqrt_lam = gram_factors(a, n_small)
    r_mat = (sqrt_lam[:, None] * x.conj().T)           # R = sqrt(L) X^H
    return q, r_mat.reshape(small_shape + small_shape)


def orthogonalize_cols(t: jnp.ndarray) -> jnp.ndarray:
    """Orthonormalize a sketch tensor over its last axis via the Gram trick.

    ``t`` has shape ``(*dims, k)``; returns ``q`` of the same shape with
    ``q^H q = I_k`` (over the leading modes).  This is the `orthogonalize`
    inside randomized SVD (paper Alg. 4 lines 2/4/5).
    """
    q, _ = gram_qr(t, 1)
    return q


def reshape_qr(a: jnp.ndarray, n_small: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Baseline: matricize + LAPACK QR (the approach Alg. 5 avoids).

    Same contract as :func:`gram_qr`; used for benchmarking the trade-off
    (paper Fig. 7b) and in tests as a reference.
    """
    big_shape = a.shape[: a.ndim - n_small]
    small_shape = a.shape[a.ndim - n_small:]
    nbig = 1
    for s in big_shape:
        nbig *= s
    nsmall = 1
    for s in small_shape:
        nsmall *= s
    mat = a.reshape(nbig, nsmall)
    q_mat, r_mat = jnp.linalg.qr(mat, mode="reduced")
    k = q_mat.shape[1]
    if k != nsmall:
        # wide case (nbig < nsmall): zero-pad so the inner bond stays nsmall
        q_mat = jnp.pad(q_mat, ((0, 0), (0, nsmall - k)))
        r_mat = jnp.pad(r_mat, ((0, nsmall - k), (0, 0)))
    q = q_mat.reshape(big_shape + small_shape)
    r = r_mat.reshape(small_shape + small_shape)
    return q, r
