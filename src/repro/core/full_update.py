"""Full (environment-aware) two-site updates for PEPS imaginary time evolution.

The QR simple update (``peps.QRUpdate``, paper Alg. 1) truncates the bond of
a two-site gate application as if the rest of the network were the identity.
The *full update* of Lubasch, Cirac & Bañuls (arXiv:1405.3259) — shown by
Liu et al. (arXiv:1908.09359) to be decisively more accurate for finite
PEPS — instead truncates in the metric of the two-site neighborhood
environment: the bond is optimized so that the *physical state* changes as
little as possible, not the local tensors.

Pipeline per bond (horizontal or vertical, no transpose trick — the
environment is orientation-specific):

1. **Reduced split** — Gram-QR both site tensors (paper Alg. 5) so only the
   small reduced tensors ``Ra``/``Rb`` carrying (physical, bond) participate
   in the optimization; the isometries ``Qa``/``Qb`` stay fixed.
2. **Neighborhood environment** — contract the cached top/bottom row
   environments (``environments.row_environments``) with a left/right strip
   boundary (``environments.strip_boundary``) and the ``Q`` isometries into
   the bond environment ``E`` over the bra/ket reduced bonds.
3. **Gauge / positive fix** — hermitize ``E`` and clamp its spectrum to be
   positive semi-definite (it is a fidelity metric; truncated boundary
   contractions break exact Hermiticity), then normalize by its largest
   eigenvalue.
4. **ALS** — seed the truncated pair with the existing einsumsvd split
   (``DirectSVD``/``RandomizedSVD``) of the gate-applied reduced network,
   then run a fixed number of alternating least-squares sweeps minimizing
   ``||theta - a.b||_E`` (regularized normal equations, static shapes).
5. **Reabsorb** the ``Q`` isometries and write the sites back.

Steps 3–4 are jit-fused into one compiled executable per network signature
via :func:`planner.fused_fn`; the environment/strip contractions of step 2
run through the planner's path cache.  Across sites and Trotter steps the
evolution loop replays compiled code, the same architecture as the fused
rSVD engine.

The ALS objective also yields the **bond truncation fidelity**

    F = |<ab|E|theta>|^2 / (<ab|E|ab> <theta|E|theta>)

— an O(1)-cost estimate of how faithfully the truncation preserved the
global state, logged per bond and surfaced in ``ite.ITEResult``.
"""
from __future__ import annotations

from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.core import planner
from repro.core.bmps import BMPS
from repro.core.einsumsvd import einsumsvd
from repro.core.environments import row_environments, strip_boundary
from repro.core.orthogonalize import gram_qr


# ---------------------------------------------------------------------------
# Fidelity log (drained by ite.ite_run; skipped under jit/vmap tracing)
# ---------------------------------------------------------------------------

_FIDELITY_LOG: List = []
# Callers that never drain (e.g. eager sharding dry-runs) must not leak: the
# log keeps only the most recent entries.  ite_run drains once per
# measurement window, far below this cap for any sane measure_every.
_FIDELITY_LOG_MAX = 4096


def drain_fidelities() -> List[float]:
    """Pop all bond fidelities logged since the last drain.

    Entries are stored as device scalars and only synced to host here, so
    logging a bond never blocks JAX's async dispatch."""
    out = [float(jnp.real(f)) for f in _FIDELITY_LOG]
    _FIDELITY_LOG.clear()
    return out


def _log_fidelity(f) -> None:
    if isinstance(f, jax.core.Tracer):  # vmapped/jitted caller: nothing to log
        return
    if len(_FIDELITY_LOG) >= _FIDELITY_LOG_MAX:
        del _FIDELITY_LOG[0]
    _FIDELITY_LOG.append(f)


def pending_fidelities() -> List[float]:
    """The undrained window, synced to host, WITHOUT clearing it.

    Checkpointing uses this: a mid-window snapshot must carry the bonds
    logged since the last measurement so a resumed run drains the same
    window the uninterrupted run would have."""
    return [float(jnp.real(f)) for f in _FIDELITY_LOG]


def restore_fidelities(values) -> None:
    """Replace the undrained window (resume path; pairs with
    :func:`pending_fidelities`)."""
    _FIDELITY_LOG.clear()
    _FIDELITY_LOG.extend(float(v) for v in values)


# ---------------------------------------------------------------------------
# Environment extraction
# ---------------------------------------------------------------------------

def env_option(update) -> BMPS:
    """The boundary-MPS option used for this update's row environments.

    ``FullUpdate.env_contract``, when set, wins — that is the seam through
    which distributed (column-sharded) environment sweeps enter full-update
    ITE; see :mod:`repro.core.distributed`."""
    if getattr(update, "env_contract", None) is not None:
        return update.env_contract
    return BMPS(update.chi, update.env_svd)


def envs_compatible(state, s0: Tuple[int, int], s1: Tuple[int, int],
                    envs) -> bool:
    """Do the cached row environments still fit the current bond dimensions?

    Environments go stale in two ways.  Value-staleness (tensors updated
    since the sweep) is the documented ``env_refresh_every`` trade-off.
    *Shape*-staleness — a bond has grown since the sweep, typical during the
    first ITE steps from a product state and along SWAP chains — is not
    survivable: einsum would either silently broadcast the environment's
    dim-1 axes (a meaningless metric) or fail on a dim mismatch.  Callers
    must refresh when this returns False."""
    (i0, j0), (i1, j1) = s0, s1
    top, bottom = envs
    rows = [i0] if i0 == i1 else [min(i0, i1), max(i0, i1)]
    t_env, b_env = top[rows[0]], bottom[rows[-1]]
    for c in range(state.ncol):
        u = state.sites[rows[0]][c].shape[1]
        d = state.sites[rows[-1]][c].shape[3]
        if t_env[c].shape[1] != u or t_env[c].shape[2] != u:
            return False
        if b_env[c].shape[1] != d or b_env[c].shape[2] != d:
            return False
    return True


def bond_environment(state, s0: Tuple[int, int], s1: Tuple[int, int],
                     qa, qb, envs) -> jnp.ndarray:
    """Neighborhood environment of the bond ``s0 -> s1`` (right or down).

    ``qa``/``qb`` are the reduced-split isometries of the two sites (their
    last two axes are the open reduced-bond pair).  ``envs`` is the
    ``(top, bottom)`` pair from :func:`environments.row_environments`.

    Returns ``E`` with eight axes: the bra reduced-bond pairs of a and b,
    then the ket pairs — ``(A1,A2,C1,C2,a1,a2,c1,c2)``.
    """
    (i0, j0), (i1, j1) = s0, s1
    top, bottom = envs
    sites = state.sites
    if i0 == i1:                                         # horizontal bond
        i, j = i0, j0
        t_env, b_env = top[i], bottom[i]
        bra = [sites[i]]
        left = strip_boundary(t_env, b_env, bra, bra, j, from_left=True)
        right = strip_boundary(t_env, b_env, bra, bra, j + 2, from_left=False)
        # labels: open bra pair (11,12 / 13,14), open ket pair (15,16 / 17,18)
        return planner.int_einsum(
            left, [1, 2, 3, 4],                          # (t, bra_l, ket_l, bt)
            t_env[j], [1, 5, 6, 7],
            t_env[j + 1], [7, 8, 9, 10],
            qa.conj(), [5, 2, 20, 11, 12],               # (u, l, d, A1, A2)
            qa, [6, 3, 21, 15, 16],
            qb.conj(), [8, 22, 24, 13, 14],              # (U, D, R, C1, C2)
            qb, [9, 23, 25, 17, 18],
            b_env[j], [4, 20, 21, 26],
            b_env[j + 1], [26, 22, 23, 27],
            right, [10, 24, 25, 27],
            [11, 12, 13, 14, 15, 16, 17, 18])
    # vertical bond: two-row strip, rows i0 and i0+1
    i, j = i0, j0
    t_env, b_env = top[i], bottom[i + 1]
    bra = [sites[i], sites[i + 1]]
    left = strip_boundary(t_env, b_env, bra, bra, j, from_left=True)
    right = strip_boundary(t_env, b_env, bra, bra, j + 1, from_left=False)
    return planner.int_einsum(
        left, [1, 2, 3, 4, 5, 6],        # (t, braA_l, ketA_l, braB_l, ketB_l, bt)
        t_env[j], [1, 7, 8, 9],
        qa.conj(), [7, 2, 20, 11, 12],                   # (u, l, r, A1, A2)
        qa, [8, 3, 21, 15, 16],
        qb.conj(), [4, 22, 24, 13, 14],                  # (l, d, r, C1, C2)
        qb, [5, 23, 25, 17, 18],
        b_env[j], [6, 22, 23, 27],
        right, [9, 20, 21, 24, 25, 27],
        [11, 12, 13, 14, 15, 16, 17, 18])


def positive_fix(env: jnp.ndarray) -> jnp.ndarray:
    """Hermitize + clamp the bond environment to PSD, normalized to ||.||=1.

    ``env`` is the 8-axis tensor of :func:`bond_environment`; the matrix view
    groups (bra pairs | ket pairs).  Truncated (and randomized) boundary
    contractions leave E only approximately Hermitian/positive; using it
    raw can steer the ALS toward unphysical solutions (Lubasch et al.,
    Section IV-B2)."""
    sh = env.shape
    d = sh[0] * sh[1] * sh[2] * sh[3]
    m = env.reshape(d, d)
    m = 0.5 * (m + m.conj().T)
    w, v = jnp.linalg.eigh(m)
    w = jnp.maximum(w.real, 0.0)
    scale = jnp.maximum(jnp.max(w), jnp.finfo(env.real.dtype).tiny)
    m = (v * (w / scale)) @ v.conj().T
    return m.reshape(sh)


# ---------------------------------------------------------------------------
# ALS bond optimization (jit-fused per signature)
# ---------------------------------------------------------------------------

def _env_overlap(env, p, q):
    """<p|E|q> for pair tensors (a,b,x,y,c,d) over the metric E."""
    return planner.cached_einsum("ABxyCD,ABCDabcd,abxycd->",
                                 p.conj(), env, q)


def _pair(a, b):
    """Merge reduced factors a:(a,b,x,m), b:(m,y,c,d) into (a,b,x,y,c,d)."""
    return planner.cached_einsum("abxm,mycd->abxycd", a, b)


def _regularized_solve(m, rhs, eps):
    d = m.shape[0]
    # The ridge must sit well above the dtype's rounding, or the rounding
    # noise in the rank-deficient directions of a low-rank bond grows sweep
    # over sweep: in complex64 the default 1e-12 vanishes against trace/d
    # and the first product-state bonds of an ITE solve to Inf/NaN.  The
    # floor (100 ulps) never binds in complex128.
    finfo = jnp.finfo(m.real.dtype)
    rel = max(eps, 100 * float(finfo.eps))
    reg = rel * (jnp.trace(m).real / d + finfo.tiny)
    return jnp.linalg.solve(m + reg * jnp.eye(d, dtype=m.dtype), rhs)


def _als_sweep(env, theta, a, b, eps):
    """One alternating sweep: re-solve a given b, then b given a."""
    # --- a given b:  M_a a = S_a, block-diagonal in the physical index x
    ma = planner.cached_einsum("MyCD,ABCDabcd,mycd->ABMabm",
                               b.conj(), env, b)
    sa = planner.cached_einsum("MyCD,ABCDabcd,abxycd->ABMx",
                               b.conj(), env, theta)
    da, db_, dm = a.shape[0], a.shape[1], a.shape[3]
    dx = a.shape[2]
    sol = _regularized_solve(ma.reshape(da * db_ * dm, da * db_ * dm),
                             sa.reshape(da * db_ * dm, dx), eps)
    a = jnp.moveaxis(sol.reshape(da, db_, dm, dx), 3, 2)
    # --- b given a
    mb = planner.cached_einsum("ABxM,ABCDabcd,abxm->MCDmcd",
                               a.conj(), env, a)
    sb = planner.cached_einsum("ABxM,ABCDabcd,abxycd->MCDy",
                               a.conj(), env, theta)
    dc, dd = b.shape[2], b.shape[3]
    dy = b.shape[1]
    sol = _regularized_solve(mb.reshape(dm * dc * dd, dm * dc * dd),
                             sb.reshape(dm * dc * dd, dy), eps)
    b = jnp.moveaxis(sol.reshape(dm, dc, dd, dy), 3, 1)
    return a, b


def _optimize_bond(env, theta, a0, b0, *, n_iter: int, eps: float,
                   positive: bool):
    """Positive-fix the environment, run ALS, return (a, b, fidelity)."""
    if positive:
        env = positive_fix(env)
    else:
        sh = env.shape
        d = sh[0] * sh[1] * sh[2] * sh[3]
        m = env.reshape(d, d)
        env = (0.5 * (m + m.conj().T)).reshape(sh)
    a, b = a0, b0
    for _ in range(n_iter):
        a, b = _als_sweep(env, theta, a, b, eps)
    # norm-balance the shared bond (cheap gauge hygiene for long evolutions)
    na = jnp.maximum(jnp.linalg.norm(a), jnp.finfo(a.real.dtype).tiny)
    nb = jnp.maximum(jnp.linalg.norm(b), jnp.finfo(b.real.dtype).tiny)
    g = jnp.sqrt(nb / na)
    a, b = a * g, b / g
    ab = _pair(a, b)
    num = _env_overlap(env, ab, theta)
    d1 = jnp.real(_env_overlap(env, ab, ab))
    d2 = jnp.real(_env_overlap(env, theta, theta))
    fid = jnp.abs(num) ** 2 / jnp.maximum(d1 * d2,
                                          jnp.finfo(a.real.dtype).tiny)
    return a, b, fid


def _fused_optimize(env, theta, a0, b0, update):
    sig = (tuple(env.shape), tuple(theta.shape), tuple(a0.shape),
           tuple(b0.shape), jnp.dtype(env.dtype).name,
           update.als_iters, update.als_eps, update.positive,
           jax.default_backend())
    builder = lambda: jax.jit(partial(_optimize_bond, n_iter=update.als_iters,
                                      eps=update.als_eps,
                                      positive=update.positive))
    return planner.fused_fn("full-update-als", sig, builder)(env, theta, a0, b0)


# ---------------------------------------------------------------------------
# The full update itself
# ---------------------------------------------------------------------------

def _reduced_split(t: jnp.ndarray, axes: Tuple[int, ...]):
    """Gram-QR ``t`` with its axes permuted to ``axes`` (last two = small)."""
    return gram_qr(jnp.transpose(t, axes), 2)


def full_update_bond(state, g, s0: Tuple[int, int], s1: Tuple[int, int],
                     update, key, envs=None):
    """Apply a two-site gate on adjacent sites with the full update.

    ``envs`` is an optional cached ``(top, bottom)`` pair from
    :func:`environments.row_environments`; when omitted it is recomputed
    from the current state (exact cadence, maximum cost).  Returns the new
    state; the bond fidelity is appended to the module log (see
    :func:`drain_fidelities`)."""
    (i0, j0), (i1, j1) = s0, s1
    g = jnp.asarray(g, dtype=state.dtype)
    # canonical orientations: left->right or top->bottom
    if (i0 == i1 and j1 == j0 - 1) or (j0 == j1 and i1 == i0 - 1):
        gt = jnp.transpose(g, (1, 0, 3, 2))
        return full_update_bond(state, gt, s1, s0, update, key, envs)
    if not ((i0 == i1 and j1 == j0 + 1) or (j0 == j1 and i1 == i0 + 1)):
        raise ValueError(f"sites {s0}, {s1} are not adjacent")

    key, env_key, seed_key = jax.random.split(key, 3)
    if envs is None or not envs_compatible(state, s0, s1, envs):
        # missing, or shape-stale (a bond grew since the cached sweep —
        # first ITE steps, SWAP chains): recompute from the current state
        envs = row_environments(state, env_option(update), env_key)

    a = state.sites[i0][j0]
    b = state.sites[i1][j1]
    horizontal = i0 == i1
    if horizontal:
        # a:(p,u,l,d,k) bond=r ; b:(q,U,k,D,R) bond=l
        qa, ra = _reduced_split(a, (1, 2, 3, 0, 4))      # qa:(u,l,d,A1,A2)
        qb, rb = _reduced_split(b, (1, 3, 4, 0, 2))      # qb:(U,D,R,C1,C2)
    else:
        # a:(p,u,l,d,r) bond=d ; b:(q,u,l,d,r) bond=u
        qa, ra = _reduced_split(a, (1, 2, 4, 0, 3))      # qa:(u,l,r,A1,A2)
        qb, rb = _reduced_split(b, (2, 3, 4, 0, 1))      # qb:(l,d,r,C1,C2)

    env = bond_environment(state, s0, s1, qa, qb, envs)

    # gate-applied reduced pair and its rSVD/SVD seed (the simple-update
    # answer in the reduced gauge — the ALS starts from it and can only
    # improve in the environment metric)
    theta = planner.cached_einsum("xypq,abpk,cdqk->abxycd", g, ra, rb)
    left, right = einsumsvd(
        update.svd, [g, ra, rb], ["xypq", "abpk", "cdqk"],
        row="xab", col="ycd", rank=update.rank, absorb="both", key=seed_key)
    a0 = jnp.moveaxis(left, 0, 2)                        # (a,b,x,m)
    b0 = right                                           # (m,y,c,d)

    ar, br, fid = _fused_optimize(env, theta, a0, b0, update)

    # Runtime-guard hook: a non-finite ALS result or a truncation fidelity
    # below the configured floor retries the bond once from a deterministic
    # exact-SVD seed (rSVD seeds on ill-conditioned reduced networks are
    # where ALS divergence starts).  NaN after the retry raises a
    # structured GuardExhaustedError; a still-low fidelity is recorded as
    # degraded-but-accepted unless fidelity_strict.  See core/runtime_guard.
    from repro.core import runtime_guard
    guard = runtime_guard.current()
    if guard is not None and not isinstance(fid, jax.core.Tracer):
        cause = runtime_guard.check_bond(guard, ar, br, fid)
        if cause is not None:
            runtime_guard.bond_failure(guard, cause, retried=False,
                                       detail=f"bond {s0}->{s1}")
            from repro.core.einsumsvd import DirectSVD
            left, right = einsumsvd(
                DirectSVD(), [g, ra, rb], ["xypq", "abpk", "cdqk"],
                row="xab", col="ycd", rank=update.rank, absorb="both",
                key=seed_key)
            a0 = jnp.moveaxis(left, 0, 2)
            b0 = right
            ar, br, fid = _fused_optimize(env, theta, a0, b0, update)
            recheck = runtime_guard.check_bond(guard, ar, br, fid)
            if recheck is None:
                runtime_guard.bond_recovered(guard, cause)
            else:
                runtime_guard.bond_failure(
                    guard, recheck, retried=True,
                    detail=f"bond {s0}->{s1} fid={float(jnp.real(fid)):.3e}")

    _log_fidelity(fid)

    if horizontal:
        new_a = planner.cached_einsum("uldab,abxm->xuldm", qa, ar)
        new_b = planner.cached_einsum("UDRcd,mycd->yUmDR", qb, br)
    else:
        new_a = planner.cached_einsum("ulrab,abxm->xulmr", qa, ar)
        new_b = planner.cached_einsum("LDRcd,mycd->ymLDR", qb, br)

    new = state.copy()
    new.sites[i0][j0] = new_a
    new.sites[i1][j1] = new_b
    return new
