"""int8 error-feedback gradient compression for the cross-pod all-reduce.

At 2 pods the cross-pod gradient all-reduce is the only traffic on the
(slow, inter-pod) links; compressing it 2-4x directly shrinks the
collective roofline term.  Scheme: per-tensor scale = max|g|/127, quantize
to int8, all-reduce (psum) the int8 *as int32 accumulate*, dequantize, and
feed the quantization residual back into the next step (error feedback, so
the compression bias vanishes over time).

Used inside shard_map over the 'pod' axis (see launch/train.py); a pure
local (quantize->dequantize + residual) path is provided for tests.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def quantize(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    g32 = g.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compress_residual(g: jnp.ndarray, err: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Error-feedback compression step: returns (q, scale, new_err)."""
    corrected = g.astype(jnp.float32) + err
    q, scale = quantize(corrected)
    new_err = corrected - dequantize(q, scale)
    return q, scale, new_err


def psum_compressed(g: jnp.ndarray, err: jnp.ndarray, axis_name: str
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All-reduce mean of g over ``axis_name`` with int8 payload + error
    feedback.  Must run inside shard_map with ``axis_name`` in scope.

    A scalar max-|g| all-reduce first agrees on a SHARED scale, so the int8
    psum dequantizes exactly (up to rounding, which error feedback absorbs).
    Payload over the slow inter-pod link: 1 byte/grad instead of 2-4."""
    n = jax.lax.axis_size(axis_name)
    corrected = g.astype(jnp.float32) + err
    local_max = jnp.max(jnp.abs(corrected))
    scale = jax.lax.pmax(local_max, axis_name) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(corrected / scale), -127, 127).astype(jnp.int8)
    new_err = corrected - q.astype(jnp.float32) * scale
    q_sum = jax.lax.psum(q.astype(jnp.int32), axis_name)
    mean = q_sum.astype(jnp.float32) * scale / n
    return mean.astype(g.dtype), new_err


def init_error_state(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
