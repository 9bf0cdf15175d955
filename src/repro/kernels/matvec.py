"""Streaming tall-apply kernel: C = A @ B with tall A and a small resident B.

This is the implicit-matvec shape of the rSVD power-iteration chain (paper
Alg. 4/5): every reconstitution ``Q = A P`` in the Gram-QR orthogonalization
and the final projections ``P u_small`` / ``q_t^* vh^T`` of
``core/rsvd.randomized_svd`` multiply a *tall* matricized operand
``(nbig, nsmall)`` by a small ``(nsmall, q)`` matrix.  Unlike the general
``tiled_matmul`` (M/N/K grid), B here fits VMEM whole: the grid runs over
row tiles of A only, B stays resident, and each tile emits its output slab
in one MXU pass with f32 accumulation — the same streaming structure as the
``gram`` kernel, which handles the other half of the chain (G = A^H A).

Complex operands use the planar trick in ONE real GEMM instead of four:

    [Re C | Im C] = [Re A | Im A] @ [[Re B, Im B], [-Im B, Re B]]

(Pallas-TPU has no complex dtype.)  ``compute`` optionally demotes the
multiplicands (``"bfloat16"`` under the mixed precision policy);
accumulation stays f32.  ``interpret=None`` autodetects (compiled on TPU,
interpret elsewhere).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block index of an untiled axis.  A bare ``0`` traces as int64 once x64 is
# enabled (``import repro.core`` enables it), and Mosaic rejects an index map
# that returns mixed int32/int64 indices.
_ZERO = np.int32(0)


def _tall_apply_kernel(a_ref, b_ref, o_ref, *, compute):
    a_blk, b_blk = a_ref[...], b_ref[...]
    if compute is not None:
        a_blk, b_blk = a_blk.astype(compute), b_blk.astype(compute)
    o_ref[...] = jnp.dot(a_blk, b_blk,
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _pad_axis(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("bm", "interpret", "compute"))
def _tall_apply(a: jnp.ndarray, b: jnp.ndarray, bm: int, interpret: bool,
                compute) -> jnp.ndarray:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = jnp.result_type(a.dtype, b.dtype)
    a_p = _pad_axis(_pad_axis(a, bm, 0), 128, 1)
    b_p = _pad_axis(_pad_axis(b, 128, 0), 128, 1)
    mp, kp = a_p.shape
    _, np_ = b_p.shape
    kernel = functools.partial(
        _tall_apply_kernel,
        compute=None if compute is None else jnp.dtype(compute))
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i: (i, _ZERO)),
            pl.BlockSpec((kp, np_), lambda i: (_ZERO, _ZERO)),
        ],
        out_specs=pl.BlockSpec((bm, np_), lambda i: (i, _ZERO)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]


def tall_apply(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 256,
               interpret: Optional[bool] = None,
               compute=None) -> jnp.ndarray:
    """C = A @ B for real tall A (M, K) and small resident B (K, N)."""
    if interpret is None:
        from repro.kernels.dispatch import interpret_default
        interpret = interpret_default()
    return _tall_apply(a, b, bm, bool(interpret),
                       None if compute is None else jnp.dtype(compute).name)


def planar_matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 256,
                  interpret: Optional[bool] = None,
                  compute=None) -> jnp.ndarray:
    """C = A @ B through the tall-apply kernel, complex via one planar GEMM.

    Real operands go straight to :func:`tall_apply`.  Complex operands are
    planar-decomposed into a single doubled real GEMM (module docstring) —
    the kernel entry point for every complex matricized contraction of the
    zip-up / rSVD sites.
    """
    if not (jnp.issubdtype(a.dtype, jnp.complexfloating)
            or jnp.issubdtype(b.dtype, jnp.complexfloating)):
        return tall_apply(a, b, bm=bm, interpret=interpret, compute=compute)
    out_dtype = jnp.result_type(a.dtype, b.dtype)
    ar, ai = jnp.real(a), jnp.imag(a)
    br, bi = jnp.real(b), jnp.imag(b)
    n = b.shape[1]
    a2 = jnp.concatenate([ar, ai], axis=1)                       # (M, 2K)
    b2 = jnp.concatenate(
        [jnp.concatenate([br, bi], axis=1),
         jnp.concatenate([-bi, br], axis=1)], axis=0)            # (2K, 2N)
    c2 = tall_apply(a2, b2, bm=bm, interpret=interpret, compute=compute)
    return (c2[:, :n] + 1j * c2[:, n:]).astype(out_dtype)
