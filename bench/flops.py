"""Operations and bytes of the kernels from their operand shapes, and the
chip's published peaks.

A complex multiply-add counts 8 real flops and a real one 2, so a complex
product counted from its complex shapes and the same product run as real
planar operands (a doubled real GEMM) count the same.  Bytes are the
operands read once plus the result written once.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
             "c64": 8, "c128": 16, "s8": 1, "u8": 1, "pred": 1}


def itemsize(dtype: str) -> int:
    return _ITEMSIZE[dtype]


def _mac_flops(dtype: str) -> int:
    return 8 if dtype.startswith("c") else 2


def gram(n: int, k: int, dtype: str = "f32") -> tuple:
    """(flops, bytes) of G = A^H A for A of shape (n, k): G is (k, k)."""
    size = itemsize(dtype)
    return _mac_flops(dtype) * n * k * k, (n * k + k * k) * size


def tall_apply(n: int, k: int, m: int, dtype: str = "f32") -> tuple:
    """(flops, bytes) of C = A B for A (n, k) and B (k, m)."""
    size = itemsize(dtype)
    return _mac_flops(dtype) * n * k * m, (n * k + k * m + n * m) * size


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, device_kind: str) -> tuple:
    """(seconds, bound): the least time the chip could take, the larger of
    flops over peak FLOP/s and bytes over peak bandwidth, and which of the
    two bounds it (``"compute"`` or ``"memory"``)."""
    p = peaks(device_kind)
    t_flops = flops / p["flops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    return (max(t_flops, t_bytes),
            "compute" if t_flops >= t_bytes else "memory")


def roofline_parts(ops, kernel, device_kind: str) -> tuple:
    """(least seconds, spent seconds, bounds) of a kernel's events ``ops``
    (named by their HLO text): the least time of their work and their
    summed device time.  ``kernel(operands)`` gives (flops, bytes) from
    the operands' ``(dtype, dims)``."""
    from bench.trace import shapes
    least, spent, bounds = 0.0, 0, set()
    for op in ops:
        arrays = shapes(op.name)
        if len(arrays) < 2:
            raise ValueError(f"no operand shapes in {op.name[:200]!r}")
        flops, nbytes = kernel(arrays[1:])
        t, bound = least_seconds(flops, nbytes, device_kind)
        least += t
        spent += op.dur
        bounds.add(bound)
    return least, spent * 1e-9, bounds


def share(parts) -> tuple:
    """(percent, bound) of the roofline from ``roofline_parts`` results
    summed over one or more kernels; (None, None) without events."""
    least = sum(p[0] for p in parts)
    spent = sum(p[1] for p in parts)
    if not spent:
        return None, None
    bounds = set().union(*(p[2] for p in parts))
    return 100.0 * least / spent, "/".join(sorted(bounds))


def roofline_share(ops, kernel, device_kind: str):
    """(percent, bound) of the roofline that a kernel's events ``ops``
    reach: the least time of their work over their summed device time.
    (None, None) without events."""
    return share([roofline_parts(ops, kernel, device_kind)])
