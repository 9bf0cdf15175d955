"""End-to-end driver 1: ground state of the J1-J2 model via imaginary time
evolution (paper Section VI-D1, Fig. 13).

Demonstrates both truncation tiers: the QR simple update (``--update qr``,
paper Alg. 1) and the environment-aware full update (``--update full``,
Lubasch et al. arXiv:1405.3259).  The default ``--update both`` runs the
two back to back at the same bond dimension and Trotter schedule and
prints the energy-error gap — the accuracy the neighborhood environment
buys at fixed D.

    PYTHONPATH=src python examples/ite_ground_state.py [--grid 3] [--steps 80]
"""
import argparse

import jax.numpy as jnp

from repro.core import bmps as B
from repro.core import planner
from repro.core.ite import ite_run, ite_statevector
from repro.core.observable import j1j2_hamiltonian
from repro.core.peps import FullUpdate, QRUpdate, computational_zeros
from repro.core.einsumsvd import RandomizedSVD


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=3)
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--tau", type=float, default=0.05)
    ap.add_argument("--bond", type=int, default=2)
    ap.add_argument("--chi", type=int, default=8)
    ap.add_argument("--update", choices=("qr", "full", "both"), default="both",
                    help="two-site truncation: QR simple update, "
                         "environment-aware full update, or both (A/B)")
    ap.add_argument("--env-refresh", type=int, default=None,
                    help="full update: gate applications between row-"
                         "environment refreshes (default: once per step)")
    ap.add_argument("--engine", choices=("zipup", "variational"),
                    default="zipup",
                    help="boundary engine for the evolution-time energy "
                         "measurements; the final state is always re-"
                         "measured with BOTH engines at equal chi and the "
                         "error gap printed")
    ap.add_argument("--dtype", choices=("complex128", "complex64"),
                    default="complex128",
                    help="site-tensor dtype (TPUs run complex64 only)")
    args = ap.parse_args()
    planner.enable_compile_cache()

    n = args.grid
    obs = j1j2_hamiltonian(n, n)  # J1=1.0, J2=0.5, h=0.2 (paper Fig. 13)
    print(f"J1-J2 model on {n}x{n}: {len(obs)} local terms")

    _, e_ref = ite_statevector(n, n, obs, args.tau, steps=2 * args.steps)
    print(f"statevector ITE reference energy: {e_ref:.6f}")

    cadence = args.env_refresh if args.env_refresh is not None else len(obs)
    updates = {
        "qr": QRUpdate(rank=args.bond),
        "full": FullUpdate(rank=args.bond, chi=max(2 * args.chi, 8),
                           env_refresh_every=cadence),
    }
    names = ("qr", "full") if args.update == "both" else (args.update,)

    errors = {}
    for name in names:
        print(f"-- update={name!r}")

        def progress(step, energy, state):
            print(f"  step {step:4d}  E = {energy:.6f}  "
                  f"(err {abs(energy-e_ref)/abs(e_ref):.2e})")

        res = ite_run(
            computational_zeros(n, n, dtype=jnp.dtype(args.dtype)), obs,
            args.tau, args.steps,
            update=updates[name],
            contract=B.BMPS(args.chi, RandomizedSVD(niter=2, oversample=4),
                            engine=args.engine),
            measure_every=max(args.steps // 8, 1), callback=progress)
        errors[name] = abs(res.energies[-1] - e_ref) / abs(e_ref)
        # engine A/B on the converged state: same chi, same key — the gap is
        # purely the boundary-absorption strategy (greedy vs ALS-fitted)
        from repro.core.expectation import expectation
        by_engine = {
            eng: float(expectation(res.state, obs,
                                   B.BMPS(args.chi, engine=eng)).real)
            for eng in ("zipup", "variational")}
        gaps = {eng: abs(e - e_ref) / abs(e_ref)
                for eng, e in by_engine.items()}
        print(f"  energy measured at chi={args.chi}: "
              f"zipup err {gaps['zipup']:.3e} vs "
              f"variational err {gaps['variational']:.3e}")
        line = (f"update={name!r} (r={args.bond}, chi={args.chi}) final "
                f"energy: {res.energies[-1]:.6f} vs reference {e_ref:.6f}")
        if res.fidelities:
            line += f"  [min bond fidelity {min(res.fidelities):.6f}]"
        print(line)

    if len(errors) == 2:
        gap = errors["qr"] / max(errors["full"], 1e-300)
        verdict = (f"full update is x{gap:.1f} more accurate" if gap >= 1.0
                   else f"full update is x{1.0 / gap:.1f} LESS accurate "
                        "(unexpected: try more steps or a tighter "
                        "--env-refresh)")
        print(f"\nenergy-error gap at D={args.bond}: "
              f"qr {errors['qr']:.3e} vs full {errors['full']:.3e} "
              f"-> {verdict}")


if __name__ == "__main__":
    main()
