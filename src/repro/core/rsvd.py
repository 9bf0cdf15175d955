"""Randomized SVD with an implicit tensor-network operator (paper Alg. 4).

The operator ``A : C^{col} -> C^{row}`` is given as an *uncontracted* tensor
network; ``A q`` and ``A* p`` are evaluated by attaching the sketch to the
network and contracting with an optimal path — the dense A is never formed.
This is what turns BMPS into IBMPS (and two-layer BMPS into two-layer IBMPS):
the asymptotic win of Table II comes purely from never materializing theta.
"""
from __future__ import annotations

import string
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import planner
from repro.core.orthogonalize import orthogonalize_cols, tall_project
from repro.core.svd_grad import svd_reg

_ALL_LABELS = string.ascii_letters


class ImplicitOperator:
    """A linear operator defined by a tensor network.

    Parameters
    ----------
    tensors:     the network's tensors.
    subscripts:  one label string per tensor (a la einsum), e.g. ``"aebc"``.
    row:         labels of the operator's output (row) modes.
    col:         labels of the operator's input (column) modes.

    Every label that appears in ``subscripts`` but not in ``row``/``col`` is
    summed over.  ``row`` and ``col`` must be dangling (appear exactly once).
    """

    def __init__(self, tensors: Sequence[jnp.ndarray], subscripts: Sequence[str],
                 row: str, col: str):
        assert len(tensors) == len(subscripts)
        self.tensors = list(tensors)
        self.subscripts = list(subscripts)
        self.row = row
        self.col = col
        label_dims = {}
        for t, sub in zip(self.tensors, self.subscripts):
            assert t.ndim == len(sub), (t.shape, sub)
            for ax, ch in enumerate(sub):
                d = t.shape[ax]
                if ch in label_dims and label_dims[ch] != d:
                    raise ValueError(f"label {ch}: dim mismatch {label_dims[ch]} vs {d}")
                label_dims[ch] = d
        self.label_dims = label_dims
        used = set("".join(self.subscripts))
        free = [c for c in _ALL_LABELS if c not in used]
        if not free:
            raise ValueError("ran out of einsum labels")
        self._sketch = free[0]
        self.dtype = jnp.result_type(*[t.dtype for t in self.tensors])

    @property
    def row_shape(self) -> Tuple[int, ...]:
        return tuple(self.label_dims[c] for c in self.row)

    @property
    def col_shape(self) -> Tuple[int, ...]:
        return tuple(self.label_dims[c] for c in self.col)

    @property
    def row_size(self) -> int:
        n = 1
        for s in self.row_shape:
            n *= s
        return n

    @property
    def col_size(self) -> int:
        n = 1
        for s in self.col_shape:
            n *= s
        return n

    def _einsum(self, extra_subs: List[str], extra_tensors: List[jnp.ndarray],
                out: str, conjugate: bool = False) -> jnp.ndarray:
        subs = self.subscripts + extra_subs
        tensors = [t.conj() for t in self.tensors] if conjugate else self.tensors
        tensors = tensors + extra_tensors
        expr = ",".join(subs) + "->" + out
        # Plan-cached path: the optimal-path search runs once per distinct
        # (expr, shapes) instead of once per matvec (see core/planner.py).
        return planner.cached_einsum(expr, *tensors)

    def dense(self) -> jnp.ndarray:
        """Materialize A as a tensor of shape row_shape + col_shape."""
        return self._einsum([], [], self.row + self.col)

    def matvecs(self, q: jnp.ndarray) -> jnp.ndarray:
        """A @ q for a sketch q of shape col_shape + (k,)."""
        z = self._sketch
        return self._einsum([self.col + z], [q], self.row + z)

    def rmatvecs(self, p: jnp.ndarray) -> jnp.ndarray:
        """A^H @ p for a sketch p of shape row_shape + (k,)."""
        z = self._sketch
        return self._einsum([self.row + z], [p], self.col + z, conjugate=True)


def _random_sketch(key, shape, dtype):
    if jnp.issubdtype(dtype, jnp.complexfloating):
        kr, ki = jax.random.split(key)
        real_dt = jnp.float64 if dtype == jnp.complex128 else jnp.float32
        re = jax.random.uniform(kr, shape, real_dt, -1.0, 1.0)
        im = jax.random.uniform(ki, shape, real_dt, -1.0, 1.0)
        return (re + 1j * im).astype(dtype)
    return jax.random.uniform(key, shape, dtype, -1.0, 1.0)


def randomized_svd(
    op: ImplicitOperator,
    rank: int,
    n_iter: int = 4,
    oversample: int = 8,
    key=None,
    gram_final: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Alg. 4: truncated SVD of an implicit operator, single pass over tensors.

    Returns ``(u, s, v)`` with ``u``: row_shape+(rank,), ``s``: (rank,),
    ``v``: (rank,)+col_shape, such that ``A ~= u @ diag(s) @ v``.

    ``gram_final`` (beyond-paper, EXPERIMENTS.md SSPerf): the paper's Alg. 4
    line 7 runs a dense SVD of the k x Ncol matrix ``P^H A`` — on a
    distributed backend that is a large matricized factorization, exactly
    what Alg. 5 exists to avoid.  Instead we Gram-QR the tall ``T = A^H P``
    (all-GEMM, reshape-free), whose k x k R factor is ``diag(sqrt(L)) X^H``
    with ``X`` unitary: ``A ~= P (Q_t R_t)^H = (P X) sqrt(L) Q_t^H`` is the
    SVD, read off the Gram eigendecomposition with no dense SVD at all.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    max_rank = min(op.row_size, op.col_size)
    rank = min(rank, max_rank)
    k = min(rank + oversample, max_rank)

    # Differentiation contract: the random sketch is a PRNG constant
    # (stop_gradient territory by construction — it carries no dependence
    # on the operator), but the power iteration itself IS differentiated:
    # every orthogonalization routes through the regularized Gram-QR chain
    # (eigh_reg + the eps clamp in core/orthogonalize.py), so the tangent
    # of the converged range basis P tracks how A's row space rotates under
    # dA.  Stopping the gradient at P instead would amputate exactly the
    # rank-growing components of dA (the part of the perturbation that
    # leaves the captured range) — measured as a 100% loss on some VQE
    # gradient components (see docs/vqe.md and tests/test_vqe_grad.py).
    q = _random_sketch(key, op.col_shape + (k,), op.dtype)
    p = orthogonalize_cols(op.matvecs(q))
    for _ in range(n_iter):
        q = orthogonalize_cols(op.rmatvecs(p))
        p = orthogonalize_cols(op.matvecs(q))

    # B = P^H A  (as a k x col matrix), computed via A^H P (one more pass).
    t = op.rmatvecs(p)                               # col_shape + (k,)
    if gram_final:
        from repro.core.orthogonalize import gram_factors
        # T = q_t R with R^H = x diag(sqrt_lam), x unitary and sqrt_lam in
        # eigh's ascending order, so A ~= P T^H = (P x) diag(sqrt_lam) q_t^H
        # is an SVD once the columns are reversed: no dense k x k SVD (on a
        # TPU, jnp.linalg.svd inside the SPMD superstep's shard_map crashes
        # the compiler).
        q_t, x, sqrt_lam = gram_factors(t, 1)        # q_t: col+(k,)
        s = sqrt_lam[::-1][:rank]
        # Final projection: tall operand x small matrix — the tall-apply
        # kernel site (dense path is the exact pre-kernel tensordot).
        u = tall_project(p, x[:, ::-1][:, :rank], 1)  # row_shape+(rank,)
        v = jnp.moveaxis(q_t[..., ::-1][..., :rank].conj(), -1, 0)
        return u, s, v
    b = t.conj().reshape(op.col_size, k).T           # (k, ncol)
    u_small, s, vh = svd_reg(b)
    u_small, s, vh = u_small[:, :rank], s[:rank], vh[:rank]
    u = tall_project(p, u_small, 1)                  # row_shape+(rank,)
    v = vh.reshape((rank,) + op.col_shape)
    return u, s, v
