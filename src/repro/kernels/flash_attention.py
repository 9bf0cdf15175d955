"""Causal flash attention (online softmax) for the LM substrate.

Grid: (batch*q_heads, S/bq, S/bk) with the key dimension sequential.  Query
tile, running max/denominator and the output accumulator live in VMEM; the
KV index_map folds GQA head-grouping so grouped KV heads are streamed
without materializing the head-repeat.  Causal key blocks strictly in the
future are skipped via ``pl.when``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30
_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, causal: bool, kv_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip key blocks strictly in the future of every query in this block
    guard = (iq * bq + bq - 1) >= (ik * bk) if causal else True

    @pl.when(guard)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)          # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        kj = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kj < kv_len                        # mask padded keys
        if causal:
            qi = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            valid = valid & (qi >= kj)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[:, 0]                       # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])            # (bq, bk)
        scale = jnp.exp(m_prev - m_new)            # (bq,)
        l_new = scale * l_ref[:, 0] + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * scale[:, None] + \
            jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _flush():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    interpret: bool = True) -> jnp.ndarray:
    """Attention over (B, Hq, S, D) queries and (B, Hkv, S, D) keys/values.

    ``Hq`` must be a multiple of ``Hkv`` (GQA); softmax scale 1/sqrt(D)."""
    b, hq, s, d = q.shape
    _, hkv, sk, dk = k.shape
    assert d == dk and hq % hkv == 0
    group = hq // hkv
    scale = 1.0 / (d ** 0.5)
    q = (q * scale).reshape(b * hq, s, d)
    k = k.reshape(b * hkv, sk, d)
    v = v.reshape(b * hkv, sk, d)

    pad_q = (-s) % bq
    pad_k = (-sk) % bk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    sq, skk = qp.shape[1], kp.shape[1]

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                               kv_len=sk)
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, sq // bq, skk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            # GQA: fold the head-group mapping into the index map
            pl.BlockSpec((1, bk, d), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :s].reshape(b, hq, s, d)
