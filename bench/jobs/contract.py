"""Contraction job: <psi|psi> of the cell's seeded states, one
``repro.core.bmps.norm_squared`` call per unit, with the two-layer IBMPS
option ``BMPS.randomized(chi)``.

Inputs: ``params.states`` states drawn in turn from the seed by the
configuration's reference module, each with one site scaled by a distinct
power of two (so their norms differ by factors of 4 or more), and one
sketch key per state, built on the host and put on the chip (committed
arrays, so the compile cache's keys do not depend on placement).  Unit
``i`` contracts state ``i mod states``.  The check runs the plain
reference on the host copies of the states, after the window, and
compares every answer of the window with the norm of its input:
``|value - norm| / norm`` against the cell's limit ``norm_rel_err``.
"""
from __future__ import annotations

import jax
import numpy as np


class Job:
    CALL = "norm_squared"   # names the window's call span

    def __init__(self, cell: dict, config: dict, reference, seed: int,
                 device):
        from repro.core.bmps import BMPS
        from repro.core.peps import PEPS
        params = cell["params"]
        self.limit = cell["limits"]["norm_rel_err"]
        self.config, self.reference = config, reference
        rng = np.random.default_rng(seed)
        self.option = BMPS.randomized(params["chi"])
        scales = rng.permutation(np.arange(-3, 4))[:params["states"]]
        self.host = [reference.build_state(config, rng, int(s))
                     for s in scales]
        self.states, self.keys = [], []
        for sites in self.host:
            self.states.append(PEPS([[jax.device_put(t, device) for t in row]
                                     for row in sites]))
            key = np.array([0, rng.integers(2 ** 32)], dtype=np.uint32)
            self.keys.append(jax.device_put(key, device))

    def run_unit(self, i: int):
        from repro.core import bmps
        k = i % len(self.states)
        return bmps.norm_squared(self.states[k], self.option, self.keys[k])

    def warmup(self):
        for i in range(len(self.states)):
            jax.block_until_ready(self.run_unit(i))

    def release(self):
        """Free the device state before the reference runs."""
        self.states = self.keys = None

    def check(self, outputs: list) -> dict:
        from threadpoolctl import threadpool_limits
        # one BLAS thread: the reference's matrices are 512 wide, and a
        # thread per core made it three times slower on a 13-core host
        with threadpool_limits(limits=1):
            norms = [self.reference.norm(self.config, sites)
                     for sites in self.host]
        n = len(norms)
        errs = [abs(complex(v) - norms[i % n]) / norms[i % n]
                for i, v in enumerate(outputs)]
        worst = max(errs) if errs else float("inf")
        return {"attempted": len(errs),
                "failed": sum(1 for e in errs if not e <= self.limit),
                "checks": {"norm_rel_err": {"value": worst,
                                            "limit": self.limit}}}
