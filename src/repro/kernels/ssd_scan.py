"""Mamba2 SSD chunked scan kernel (state-space duality, arXiv:2405.21060).

Recurrence per (batch, head):  h_t = exp(a_t) h_{t-1} + B_t (x) x_t,
y_t = C_t . h_t  with h in R^{N x P}.  The chunked (SSD) form computes the
intra-chunk part as an attention-like masked GEMM and carries the chunk
state sequentially — mapping both halves onto the MXU.

Grid: (BH, L/C) with the chunk dimension sequential; the (N, P) state lives
in VMEM scratch across chunk steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, b_ref, c_ref, a_ref, y_ref, h_ref, *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)               # (C, P)
    bmat = b_ref[0].astype(jnp.float32)            # (C, N)
    cmat = c_ref[0].astype(jnp.float32)            # (C, N)
    a = a_ref[0, :, 0].astype(jnp.float32)         # (C,) log-decay (<= 0)

    cum = jnp.cumsum(a)                            # inclusive prefix sums
    total = cum[-1]
    # intra-chunk: scores[i,j] = exp(cum_i - cum_j) for i >= j
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(cum[:, None] - cum[None, :])
    l_mat = jnp.where(ii >= jj, decay, 0.0)
    s_mat = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32) * l_mat
    y_intra = jnp.dot(s_mat, x, preferred_element_type=jnp.float32)
    # inter-chunk: contribution of the incoming state
    h = h_ref[...]
    y_inter = jnp.dot(cmat * jnp.exp(cum)[:, None], h,
                      preferred_element_type=jnp.float32)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)
    # state update for the next chunk
    w = jnp.exp(total - cum)[:, None] * bmat       # (C, N)
    h_ref[...] = jnp.exp(total) * h + jnp.dot(w.T, x,
                                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray, a: jnp.ndarray,
             *, chunk: int = 128, interpret: bool = True) -> jnp.ndarray:
    """SSD scan over (BH, L, P) inputs with (BH, L, N) B/C and (BH, L) log-decay.

    ``a`` must already be the per-step log decay (dt * A_head, <= 0); ``x``
    the dt-scaled inputs.  L is padded to a chunk multiple internally."""
    bh, l, p = x.shape
    n = b.shape[-1]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad)))
    lp = x.shape[1]
    a3 = a[..., None]                               # (BH, L, 1) for blocking
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(bh, lp // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, 1), lambda h, i: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lp, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, b, c, a3)
    return out[:, :l]
