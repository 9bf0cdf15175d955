"""Gram-matrix kernel: G = A^T A for a tall operand (paper Alg. 5 hot-spot).

The reshape-avoiding orthogonalization reduces distributed QR to (i) one big
Gram contraction over the tall modes and (ii) a small local eigh.  Step (i)
is this kernel: the small G stays resident in VMEM while A streams through
in (bm x n) tiles — a reduction over the grid's sequential dimension.

``interpret=None`` (default) autodetects: compiled on TPU, interpret mode
elsewhere (see ``repro.kernels.dispatch.interpret_default`` for the
env/flag overrides).  ``compute`` optionally demotes the streamed tiles to
a narrower multiplicand dtype (``"bfloat16"`` under the mixed precision
policy) — accumulation stays f32 either way.
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


def _gram_kernel(a_ref, g_ref, acc_ref, *, compute):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = a_ref[...]
    if compute is not None:
        blk = blk.astype(compute)
    acc_ref[...] += jnp.dot(blk.T, blk, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(0) - 1)
    def _flush():
        g_ref[...] = acc_ref[...].astype(g_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret", "compute"))
def _gram(a: jnp.ndarray, bm: int, interpret: bool, compute) -> jnp.ndarray:
    m, n = a.shape
    pad_m = (-m) % bm
    if pad_m:
        a = jnp.pad(a, ((0, pad_m), (0, 0)))
    mp = a.shape[0]
    # lane-align the small dimension
    pad_n = (-n) % 128
    if pad_n:
        a = jnp.pad(a, ((0, 0), (0, pad_n)))
    np_ = a.shape[1]
    kernel = functools.partial(
        _gram_kernel, compute=None if compute is None else jnp.dtype(compute))
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm,),
        in_specs=[pl.BlockSpec((bm, np_), lambda k: (k, _ZERO))],
        out_specs=pl.BlockSpec((np_, np_), lambda k: (_ZERO, _ZERO)),
        out_shape=jax.ShapeDtypeStruct((np_, np_), a.dtype),
        scratch_shapes=[pltpu.VMEM((np_, np_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(a)
    return out[:n, :n]


def gram(a: jnp.ndarray, *, bm: int = 256, interpret: Optional[bool] = None,
         compute=None) -> jnp.ndarray:
    """G = A^T A for real A of shape (M, N) with M >> N (N <= ~512)."""
    if interpret is None:
        from repro.kernels.dispatch import interpret_default
        interpret = interpret_default()
    return _gram(a, bm, bool(interpret),
                 None if compute is None else jnp.dtype(compute).name)


def gram_complex(a: jnp.ndarray, *, bm: int = 256,
                 interpret: Optional[bool] = None,
                 compute=None) -> jnp.ndarray:
    """G = A^H A for complex A via planar decomposition (4 real Grams/GEMMs).

    Pallas-TPU has no complex dtype; the PEPS library calls this wrapper.
    The imaginary part is ``g_ri - g_ri.T`` — exactly antisymmetric by
    construction, matching the Hermiticity of the exact G.
    """
    from repro.kernels.tiled_matmul import tiled_matmul
    ar, ai = jnp.real(a), jnp.imag(a)
    g_rr = gram(ar, bm=bm, interpret=interpret, compute=compute)
    g_ii = gram(ai, bm=bm, interpret=interpret, compute=compute)
    g_ri = tiled_matmul(ar.T, ai, interpret=interpret, compute=compute)
    real = g_rr + g_ii
    imag = g_ri - g_ri.T
    return real + 1j * imag
