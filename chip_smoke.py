#!/usr/bin/env python3
"""Smoke run of the PEPS library's main path on a TPU, through its own API.

    python3 chip_smoke.py             # one chip: contract, ite and serve
    python3 chip_smoke.py --chips 4   # four chips: column-sharded contraction
    JAX_PLATFORMS=cpu python3 chip_smoke.py --c128   # errors behind C128_ERR

Every phase runs in complex64 (a TPU runs no complex128) through the
library's entry points, in this one process.  For each phase it prints the
device, the first-call and warm wall times (``block_until_ready``), the
executables built or loaded with their seconds and the persistent-cache
hits, the planner/dispatch/guard counters, and an accuracy check with its
tolerance.  The exact references (statevectors, and the complex128
contraction of the contract phase's input) run on the host's CPU.

Each tolerance is the complex128 run's own error against the exact
reference (``C128_ERR``) plus the documented complex64 allowance of the
workload (``repro.core.precision.ERROR_BUDGETS[...]["mixed"]``, measured
for complex64 storage with bfloat16 kernel multiplicands, so it also covers
the float32 multiplicands used here).

The last line of standard output is one JSON object, printed only when every
check passed.  No TPU, Pallas kernels in interpret mode, a runtime-guard
event, a failed check or an exception exit non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

C64 = "complex64"

# Error of the complex128 run of each phase against the exact reference, as
# printed by ``JAX_PLATFORMS=cpu python3 chip_smoke.py --c128`` (rounded up;
# PERF.md has the output).  Running them here would cost minutes of host CPU
# for numbers far below the complex64 budgets.
C128_ERR = {"contract": 1.3e-14, "ite": 4.9e-14, "serve": 6.9e-15}


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileMeter:
    """Counts executables built or loaded (with their seconds) and
    persistent-cache hits/misses through ``jax.monitoring``."""

    def __init__(self):
        from jax import monitoring
        self.executables = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.executables += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return (self.executables, self.seconds, self.cache_hits,
                self.cache_misses)

    def since(self, snap) -> dict:
        now = self.snapshot()
        return {"executables": now[0] - snap[0],
                "compile_s": round(now[1] - snap[1], 3),
                "cache_hits": now[2] - snap[2],
                "cache_misses": now[3] - snap[3]}


def _timed(fn):
    """(result, seconds) of ``fn()`` with every output array ready."""
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def _counters(before: dict) -> dict:
    """Non-zero dispatch counters and every guard counter since ``before``."""
    from repro.core import planner
    delta = planner.stats_since(before)
    out = {k: v for k, v in delta.items()
           if (k.startswith("pallas_") or k.startswith("dense_")) and v}
    out.update({k: v for k, v in delta.items() if k.startswith("guard_")})
    return out


def _report(name: str, device, first_s: float, warm_s: float,
            compiles: dict, counters: dict, check: dict) -> dict:
    rec = {"phase": name, "device": str(device),
           "first_s": round(first_s, 3), "warm_s": round(warm_s, 3),
           **compiles, "counters": counters, "check": check}
    print(f"[{name}] " + json.dumps(rec), flush=True)
    guards = {k: v for k, v in counters.items()
              if k.startswith("guard_") and v}
    _require(not guards, f"{name}: runtime guard fired: {guards}")
    _require(check["ok"], f"{name}: check failed: {check}")
    return rec


def _placed(build, device):
    """A PEPS built on the host CPU and put on ``device``.  The states are
    input data: building them gate by gate on the chip would compile a few
    hundred one-off programs that no phase measures."""
    import jax
    from repro.core.peps import PEPS
    with jax.default_device(jax.devices("cpu")[0]):
        state = build()
    sites = [[jax.device_put(t, device) for t in row] for row in state.sites]
    return PEPS(sites, float(state.log_scale))


def _budget(workload: str) -> float:
    from repro.core.precision import error_budget
    return error_budget(workload, "mixed")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_contract(meter: CompileMeter, device, dtype: str = C64) -> dict:
    """Two-layer IBMPS <psi|psi> of an 8x8, D=4 PEPS at chi=64.

    The state is a depth-4 random quantum circuit (one iSWAP round, so
    every bond is 4) evolved exactly from |0>: its norm is exactly 1, which
    complex64 can represent (a random 8x8 D=4 PEPS with unit-variance sites
    has <psi|psi> ~ 1e-58, below complex64's range).  chi=64 is the
    smallest boundary bond at D=4 whose tall operands (4096 rows) pass the
    kernels' tall-skinny gate; at chi=32 they stop at 2048.

    The check's reference is the exact norm of the input itself: the same
    complex64 sites contracted in complex128 on the host's CPU.  Evolved
    gate by gate in complex64, the state's norm is 1 only to the input's
    own rounding (4.0e-6 to 8.5e-6 on CPU, close to the tolerance), which
    exact 1 would charge to the contraction; it is printed as
    ``err_vs_1``."""
    import jax
    import jax.numpy as jnp
    from repro.core import planner
    from repro.core.bmps import BMPS, norm_squared
    from repro.core.circuits import apply_circuit_exact_peps, random_circuit
    from repro.core.peps import PEPS, computational_zeros

    nrow = ncol = 8
    circuit = random_circuit(nrow, ncol, 4, seed=13)
    option = BMPS.randomized(64)
    key = jax.random.PRNGKey(0)

    with jax.default_device(jax.devices("cpu")[0]):
        host = apply_circuit_exact_peps(
            computational_zeros(nrow, ncol, dtype=dtype), circuit)
        wide = PEPS([[t.astype(jnp.complex128) for t in row]
                     for row in host.sites], host.log_scale)
        ref = complex(norm_squared(wide, option, key))
    state = _placed(lambda: host, device)
    _require(state.max_bond() == 4 and state.dtype == dtype,
             f"state bond {state.max_bond()}, dtype {state.dtype}")
    before, snap = planner.stats(), meter.snapshot()
    val, first_s = _timed(lambda: norm_squared(state, option, key))
    compiles = meter.since(snap)
    _, warm_s = _timed(lambda: norm_squared(state, option, key))
    counters = _counters(before)

    err = abs(complex(val) - ref) / abs(ref)
    tol = C128_ERR["contract"] + _budget("contract_twolayer")
    check = {"value": str(complex(val)), "reference_c128": str(ref),
             "err": err, "tol": tol, "err_vs_1": abs(complex(val) - 1.0),
             "ok": err <= tol}
    return _report("contract", list(val.devices())[0], first_s, warm_s,
                   compiles, counters, check)


def phase_ite(meter: CompileMeter, cpu, device, dtype: str = C64):
    """Full-update ITE on the 4x4 J1-J2 lattice (paper Fig. 13), D=4,
    chi=16, two Trotter steps from |0>, against the exact Trotterized
    statevector, with the library's default engines (``FullUpdate`` and
    ``BMPS`` on the direct SVD).  Returns the report and the evolved state.

    |0> starts with its virtual bonds zero-padded to D, so every gate sees
    the same shapes: from bond 1 the bonds grow through 2 to 4 and each
    stage compiles its own programs (525 executables on CPU against 328,
    about a second each on a v5e)."""
    import jax
    import jax.numpy as jnp
    from repro.core import planner
    from repro.core.bmps import BMPS
    from repro.core.ite import ite_run, ite_statevector
    from repro.core.observable import j1j2_hamiltonian
    from repro.core.peps import PEPS, FullUpdate, computational_zeros

    n, steps, tau, bond, chi = 4, 2, 0.05, 4, 16
    obs = j1j2_hamiltonian(n, n)
    update = FullUpdate(rank=bond, chi=chi, env_refresh_every=len(obs))
    contract = BMPS(chi)

    def padded_zeros():
        def pad(t, i, j):       # (p, u, l, d, r); the outer legs stay 1
            w = bond - 1
            return jnp.pad(t, [(0, 0), (0, w * (i > 0)), (0, w * (j > 0)),
                               (0, w * (i < n - 1)), (0, w * (j < n - 1))])
        sites = computational_zeros(n, n, dtype=dtype).sites
        return PEPS([[pad(t, i, j) for j, t in enumerate(row)]
                     for i, row in enumerate(sites)])

    zeros = _placed(padded_zeros, device)

    def run():
        res = ite_run(zeros, obs, tau, steps, update=update,
                      contract=contract, measure_every=steps)
        return res, res.state.sites

    before, snap = planner.stats(), meter.snapshot()
    (res, _), first_s = _timed(run)
    compiles = meter.since(snap)
    (res_warm, sites), warm_s = _timed(run)
    counters = _counters(before)

    with jax.default_device(cpu):
        _, e_exact = ite_statevector(n, n, obs, tau, steps)
    e64 = res.energies[-1]
    err = abs(e64 - e_exact) / abs(e_exact)
    # the documented complex64 allowance is per full-update step
    tol = C128_ERR["ite"] + steps * _budget("full_update_ite_step")
    check = {"energy": e64, "energy_warm": res_warm.energies[-1],
             "exact": e_exact, "err": err, "tol": tol,
             "fidelities": res.fidelities, "ok": err <= tol}
    rec = _report("ite", list(sites[0][0].devices())[0], first_s, warm_s,
                  compiles, counters, check)
    return rec, res.state


def phase_serve(meter: CompileMeter, cpu, device, ground,
                dtype: str = C64) -> dict:
    """The query server at the ``repro.launch.serve`` defaults: a 4x4,
    depth-8 RQC (bond 16), amplitudes at chi=256 (the exact one-layer
    boundary bond, so amplitudes are exact), direct SVD, zip-up.

    Amplitudes are checked against the statevector and against direct
    ``amplitude()``.  The observable query goes to ``ground``, the ITE
    phase's state, registered with the ITE's own contraction (``BMPS(16)``,
    direct SVD), and is checked against direct ``expectation()``.  On the
    RQC state an observable would need the two-layer sweep of a D=16 state:
    at the CLI's chi=8 the direct SVD factors 2048x2048 matrices (a
    1024x1024 complex64 SVD alone takes minutes to compile for a v5e), and
    the randomized engine compiles some 90 programs no other phase shares."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import bmps as B
    from repro.core import planner
    from repro.core import statevector as sv
    from repro.core.circuits import apply_circuit_statevector, random_circuit
    from repro.core.expectation import expectation
    from repro.core.observable import Observable
    from repro.core.serving import ServingEngine
    from repro.launch.serve import build_states

    grid, layers, seed = 4, 8, 7
    amp_option = B.BMPS(256)
    obs_option = B.BMPS(16)
    obs = Observable.Z(0) + Observable.ZZ(0, 1)
    rng = np.random.default_rng(0)
    prefixes = rng.integers(0, 2, (4, grid - 1, grid))
    batches = [np.concatenate([prefixes[rng.integers(4, size=8)],
                               rng.integers(0, 2, (8, 1, grid))], axis=1)
               for _ in range(3)]

    def serve(engine, name, bits_batch):
        futs = [engine.submit_amplitude(name, b) for b in bits_batch]
        return jnp.stack([f.result(timeout=1200) for f in futs])

    state = _placed(lambda: build_states(1, grid, layers, seed=seed,
                                         dtype=dtype)[0], device)
    before, snap = planner.stats(), meter.snapshot()
    with ServingEngine(max_states=4, window_ms=2.0) as engine:
        engine.register_state("rqc0", state, amp_option)
        engine.register_state("ground", ground, obs_option)
        first, first_s = _timed(lambda: serve(engine, "rqc0", batches[0]))
        compiles = meter.since(snap)
        warm = []
        t0 = time.perf_counter()
        for b in batches[1:]:
            warm.append(jax.block_until_ready(serve(engine, "rqc0", b)))
        warm_s = (time.perf_counter() - t0) / len(warm)
        e_served, obs_first_s = _timed(
            lambda: engine.submit_expectation("ground", obs).result(
                timeout=1200))
        stats = engine.stats()
    served = np.concatenate([np.asarray(first)] +
                            [np.asarray(w) for w in warm])
    bits = np.concatenate(batches).reshape(-1, grid * grid)
    # direct amplitude() repeats the whole sweep per query: check two
    direct_idx = [0, len(bits) - 1]
    direct = np.asarray([B.amplitude(state, bits[i], amp_option)
                         for i in direct_idx])
    e_direct = expectation(ground, obs, obs_option)
    counters = _counters(before)

    circuit = random_circuit(grid, grid, layers, seed=seed)
    with jax.default_device(cpu):
        vec = np.asarray(apply_circuit_statevector(
            sv.zeros(grid * grid), circuit)).reshape(-1)
    index = [int("".join(str(int(b)) for b in row), 2) for row in bits]
    exact = vec[index]
    err = _rel(served, exact)
    err_direct = _rel(served[direct_idx], direct)
    err_obs = abs(complex(e_served) - complex(e_direct)) / max(
        abs(complex(e_direct)), 1e-300)
    tol = C128_ERR["serve"] + _budget("amplitude")
    tol_direct = _budget("amplitude")
    tol_obs = _budget("contract_twolayer")
    check = {"queries": int(bits.shape[0]), "err_vs_statevector": err,
             "tol": tol,
             "err_vs_direct": err_direct, "tol_direct": tol_direct,
             "observable": str(complex(e_served)),
             "observable_err_vs_direct": err_obs, "tol_observable": tol_obs,
             "observable_first_s": round(obs_first_s, 3),
             "prefix_hits": stats["per_state"]["rqc0"]["prefix_hits"],
             "prefix_misses": stats["per_state"]["rqc0"]["prefix_misses"],
             "ok": (err <= tol and err_direct <= tol_direct
                    and err_obs <= tol_obs)}
    return _report("serve", list(first.devices())[0], first_s, warm_s,
                   compiles, counters, check)


def four_chips(meter: CompileMeter) -> dict:
    """Column-sharded two-layer norm of an 8x16, D=3 PEPS at chi=32 over
    four shards, host and compiled (spmd) wavefronts, each against the
    single-device ``BMPS`` on the same complex64 state on the first chip.
    The sites are scaled by D/sqrt(2), which keeps the norm of a random
    PEPS within complex64's range (the tests' idiom).

    The reference runs on a chip, not on the host's CPU: chi=32 truncates
    this contraction (6% off chi=64), and a truncated sweep amplifies
    rounding-level differences about 1e4-fold (inputs perturbed by 1e-7
    move it by 1.1e-3 on CPU), so only the same arithmetic on the same
    backend can agree to the tolerance.  On CPU, sharded and single-device
    agree to 1.6e-7."""
    import jax
    import numpy as np
    from repro.core import planner, spmd
    from repro.core.bmps import BMPS, norm_squared
    from repro.core.distributed import (DistributedBMPS, halo_bytes_per_row,
                                        put_columns)
    from repro.core.peps import PEPS, random_peps

    devices = jax.devices()
    _require(len(devices) == 4, f"--chips 4 needs 4 devices, have {devices}")
    nrow, ncol, bond, chi = 8, 16, 3, 32
    key = jax.random.PRNGKey(0)
    with jax.default_device(jax.devices("cpu")[0]):
        raw = random_peps(nrow, ncol, bond, jax.random.PRNGKey(3), dtype=C64)
        scale = bond / 2 ** 0.5     # a Python float keeps complex64
        state = PEPS([[t * scale for t in row] for row in raw.sites])
    with jax.default_device(devices[0]):
        ref = norm_squared(_placed(lambda: state, devices[0]),
                           BMPS.randomized(chi), key)
    _require(state.dtype == C64, f"state dtype {state.dtype}")
    _require(bool(np.isfinite(complex(ref))) and abs(complex(ref)) > 0,
             f"single-device norm not representable: {complex(ref)}")
    out = {}
    for wavefront in ("host", "spmd"):
        option = DistributedBMPS.randomized(chi, n_shards=4,
                                            wavefront=wavefront)
        layout, devs = option.resolve(ncol)
        placed = put_columns(state.sites, layout, devs)
        shard_devices = sorted({str(d) for row in placed for t in row
                                for d in t.devices()})
        _require(len(shard_devices) == 4,
                 f"{wavefront}: shards on {shard_devices}")
        before, snap = planner.stats(), meter.snapshot()
        spmd.reset_stats()
        val, first_s = _timed(lambda: norm_squared(state, option, key))
        compiles = meter.since(snap)
        _, warm_s = _timed(lambda: norm_squared(state, option, key))
        # rows swept inside the compiled superstep: none on the host path
        rows_spmd = spmd.stats()["rows_spmd"]
        err = abs(complex(val) - complex(ref)) / abs(complex(ref))
        tol = _budget("contract_twolayer")
        check = {"value": str(complex(val)), "single_device": str(
                     complex(ref)), "err": err, "tol": tol,
                 "shard_devices": shard_devices,
                 "halo_bytes_per_row": halo_bytes_per_row(state, option),
                 "rows_spmd": rows_spmd,
                 "ok": err <= tol and (rows_spmd > 0) == (
                     wavefront == "spmd")}
        out[wavefront] = _report(f"four_chips_{wavefront}",
                                 list(val.devices())[0], first_s, warm_s,
                                 compiles, _counters(before), check)
    return out


# ---------------------------------------------------------------------------

def c128_errors() -> int:
    """The complex128 errors behind ``C128_ERR``: each one-chip phase in
    complex128 on the host CPU, against the same exact reference."""
    import jax
    cpu = jax.devices("cpu")[0]
    meter = CompileMeter()
    c128 = "complex128"
    with jax.default_device(cpu):
        recs = [phase_contract(meter, cpu, c128)]
        rec, ground = phase_ite(meter, cpu, cpu, c128)
        recs += [rec, phase_serve(meter, cpu, cpu, ground, c128)]
    print(json.dumps({
        "contract": recs[0]["check"]["err_vs_1"],
        "ite": recs[1]["check"]["err"],
        "serve": recs[2]["check"]["err_vs_statevector"]}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: contract, ite, serve; 4: column-sharded "
                         "contraction against single-device BMPS")
    ap.add_argument("--c128", action="store_true",
                    help="print the complex128 errors behind C128_ERR "
                         "(host CPU, no chip); prints no ok line")
    args = ap.parse_args(argv)

    if args.c128:
        return c128_errors()
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1
    from repro.core import planner
    from repro.kernels import dispatch
    cache_dir = planner.enable_compile_cache()
    meter = CompileMeter()
    dev = jax.devices()[0]
    print(f"[setup] device {dev} kind={dev.device_kind!r} "
          f"count={len(jax.devices())} compile_cache={cache_dir}",
          flush=True)
    if dispatch.interpret_default():
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    try:
        if args.chips == 4:
            four_chips(meter)
        else:
            cpu = jax.devices("cpu")[0]
            rec = phase_contract(meter, dev)
            _require(any(k.startswith("pallas_") and v
                         for k, v in rec["counters"].items()),
                     "contract: no Pallas kernel site engaged")
            _, ground = phase_ite(meter, cpu, dev)
            phase_serve(meter, cpu, dev, ground)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
