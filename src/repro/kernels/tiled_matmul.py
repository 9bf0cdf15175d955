"""MXU-aligned blocked matmul kernel (einsumsvd / IBMPS GEMM hot-spot).

The paper reports 60-70% of PEPS contraction time in GEMM; on TPU the same
GEMMs must be fed through the MXU with VMEM-resident tiles.  Grid is
(M/bm, N/bn, K/bk) with the K dimension sequential ("arbitrary") and a
float32 VMEM accumulator carried across K steps.

``interpret=None`` autodetects (compiled on TPU, interpret elsewhere; see
``repro.kernels.dispatch.interpret_default``); ``compute`` optionally
demotes the tile multiplicands (e.g. ``"bfloat16"``) while the accumulator
stays f32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, compute):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_blk, b_blk = a_ref[...], b_ref[...]
    if compute is not None:
        a_blk, b_blk = a_blk.astype(compute), b_blk.astype(compute)
    acc_ref[...] += jnp.dot(a_blk, b_blk, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret", "compute"))
def _tiled_matmul(a: jnp.ndarray, b: jnp.ndarray, bm: int, bn: int, bk: int,
                  interpret: bool, compute) -> jnp.ndarray:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = jnp.result_type(a.dtype, b.dtype)
    a_p = _pad_to(_pad_to(a, bm, 0), bk, 1)
    b_p = _pad_to(_pad_to(b, bk, 0), bn, 1)
    mp, kp = a_p.shape
    _, np_ = b_p.shape
    kernel = functools.partial(
        _matmul_kernel, compute=None if compute is None else jnp.dtype(compute))
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]


def tiled_matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128,
                 bn: int = 128, bk: int = 128,
                 interpret: Optional[bool] = None,
                 compute=None) -> jnp.ndarray:
    """C = A @ B with explicit BlockSpec tiling; pads to block multiples."""
    if interpret is None:
        from repro.kernels.dispatch import interpret_default
        interpret = interpret_default()
    return _tiled_matmul(a, b, bm, bn, bk, bool(interpret),
                         None if compute is None else jnp.dtype(compute).name)
