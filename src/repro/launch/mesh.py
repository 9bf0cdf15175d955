"""Production mesh construction (assignment MULTI-POD DRY-RUN step 1).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state.  The single-pod mesh is 16x16 = 256 chips
(data, model); the multi-pod mesh adds a leading ``pod`` axis:
(2, 16, 16) = 512 chips.  The ``pod`` axis is pure data parallelism with
one (optionally compressed) cross-pod gradient all-reduce per step.
"""
from __future__ import annotations

import jax

_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """General mesh helper with Auto axis types (tests, elastic restarts)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(_AUTO,) * len(axes))


def make_host_mesh():
    """1x1 mesh on the local device (smoke tests / examples)."""
    return make_mesh((1, 1), ("data", "model"))


def peps_mesh(n_col_shards: int, batch: int = 1):
    """Mesh for intra-state distributed PEPS contraction: ``('col', 'batch')``.

    ``col`` is the column-shard axis consumed by
    :meth:`repro.core.distributed.DistributedBMPS.for_mesh`; ``batch`` (when
    > 1) slices the remaining devices across independent ensemble members,
    one column of the device grid per member.  Requires
    ``n_col_shards * batch`` available devices — on CPU, launch with e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

    The same mesh drives batched VQE: ``run_vqe(..., ensemble=k,
    mesh=peps_mesh(cols, batch))`` shards the vmapped member axis over the
    mesh's devices (:func:`repro.core.sharding.ensemble_sharding` splits it
    over every axis when ``k`` is divisible by the device count), so many
    circuits advance on many devices in one compiled program — see
    ``docs/vqe.md``.
    """
    return make_mesh((n_col_shards, batch), ("col", "batch"))


def col_mesh(devices):
    """1-D ``('col',)`` mesh over an explicit device list.

    Used by :mod:`repro.core.spmd` to run the compiled wavefront superstep
    over the devices the explicit-placement pipeline already owns.  Devices
    must be distinct — a ``Mesh`` cannot repeat a device — so the superstep
    plans its own equal-width split over the *distinct* device prefix
    rather than reusing a round-robin-wrapped host layout (blocking is
    value-invariant, so a different split changes nothing but placement).
    """
    import numpy as np
    arr = np.empty(len(devices), dtype=object)
    for i, d in enumerate(devices):
        arr[i] = d
    return jax.sharding.Mesh(arr, ("col",))


# Published peaks of one TPU v5e chip (Google Cloud documentation).  The
# production meshes above are v5e pods, and the dry-run roofline models that
# hardware from compiled programs (on stand-in devices); no code reads these
# as the peaks of the device it runs on.
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
