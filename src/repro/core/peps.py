"""PEPS state and operator application (paper Sections II-C, III-A, IV-A).

Site tensor layout: ``(p, u, l, d, r)`` — physical, up, left, down, right.
This module holds the **canonical leg-ordering diagram** for the whole
library; other modules (``bmps``, ``sharding``, ``distributed``, docs)
reference it rather than restating it::

                 (u)
                  |
           (l) --[T]-- (r)        T[p, u, l, d, r]
                  | \\
                 (d) (p)          p = physical leg (dim 2 for qubits)

    Grid, row-major; site (i, j) holds qubit i*ncol + j:

        (0,0) --- (0,1) --- (0,2)        u of row 0 and l of column 0
          |         |         |          are dim-1 boundary bonds; r/d
        (1,0) --- (1,1) --- (1,2)        bonds of interior sites carry
          |         |         |          the variational bond dimension.
        (2,0) --- (2,1) --- (2,2)

Boundary bonds have dimension 1.  Grid site ``(i, j)`` (row-major) holds the
qubit ``i*ncol + j``.

Two-site operator application implements three accuracy tiers:
* ``DirectUpdate`` — contract the full theta and einsumsvd it (Eq. 4),
* ``QRUpdate``    — Alg. 1: QR both sites first (via the reshape-avoiding
  Gram factorization of Alg. 5, or LAPACK QR), einsumsvd the small Rs, and
  re-absorb the Q factors.  This is the O(d^2 r^5) path.
* ``FullUpdate``  — environment-aware truncation (Lubasch et al.,
  arXiv:1405.3259): the bond is ALS-optimized in the metric of the cached
  two-site neighborhood environment (see :mod:`repro.core.full_update`).

A scalar ``log_scale`` rides along with the state so that imaginary-time
evolution can renormalize site tensors without losing track of amplitudes.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.einsumsvd import DirectSVD, RandomizedSVD, einsumsvd
from repro.core.orthogonalize import gram_qr, reshape_qr
from repro.core import gates as _gates


@jax.tree_util.register_pytree_node_class
class PEPS:
    """An nrow x ncol grid of site tensors (p, u, l, d, r)."""

    def __init__(self, sites: List[List[jnp.ndarray]], log_scale: float = 0.0):
        self.sites = sites
        self.log_scale = log_scale

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        flat = [t for row in self.sites for t in row]
        aux = (self.nrow, self.ncol, self.log_scale)
        return flat, aux

    @classmethod
    def tree_unflatten(cls, aux, flat):
        nrow, ncol, log_scale = aux
        it = iter(flat)
        sites = [[next(it) for _ in range(ncol)] for _ in range(nrow)]
        return cls(sites, log_scale)

    # -- basics ---------------------------------------------------------------
    @property
    def nrow(self) -> int:
        return len(self.sites)

    @property
    def ncol(self) -> int:
        return len(self.sites[0])

    @property
    def nsites(self) -> int:
        return self.nrow * self.ncol

    @property
    def dtype(self):
        return self.sites[0][0].dtype

    def copy(self) -> "PEPS":
        return PEPS([[t for t in row] for row in self.sites], self.log_scale)

    def site(self, flat_idx: int) -> jnp.ndarray:
        return self.sites[flat_idx // self.ncol][flat_idx % self.ncol]

    def coords(self, flat_idx: int) -> Tuple[int, int]:
        return flat_idx // self.ncol, flat_idx % self.ncol

    def max_bond(self) -> int:
        return max(max(t.shape[1:]) for row in self.sites for t in row)

    def conj(self) -> "PEPS":
        return PEPS([[t.conj() for t in row] for row in self.sites], self.log_scale)


def computational_zeros(nrow: int, ncol: int, dtype=jnp.complex128) -> PEPS:
    """|0...0> as a bond-dimension-1 PEPS."""
    t = np.zeros((2, 1, 1, 1, 1), dtype=dtype)
    t[0] = 1.0
    t = jnp.asarray(t)
    return PEPS([[t for _ in range(ncol)] for _ in range(nrow)])


def computational_basis(bits: np.ndarray, dtype=jnp.complex128) -> PEPS:
    bits = np.asarray(bits)
    nrow, ncol = bits.shape
    sites = []
    for i in range(nrow):
        row = []
        for j in range(ncol):
            t = np.zeros((2, 1, 1, 1, 1), dtype=dtype)
            t[int(bits[i, j])] = 1.0
            row.append(jnp.asarray(t))
        sites.append(row)
    return PEPS(sites)


def random_peps(nrow: int, ncol: int, bond: int, key, phys: int = 2,
                dtype=jnp.complex128) -> PEPS:
    """Random PEPS with uniform interior bond dimension (edges are 1)."""
    sites = []
    for i in range(nrow):
        row = []
        for j in range(ncol):
            u = 1 if i == 0 else bond
            d = 1 if i == nrow - 1 else bond
            l = 1 if j == 0 else bond
            r = 1 if j == ncol - 1 else bond
            key, k1, k2 = jax.random.split(key, 3)
            shape = (phys, u, l, d, r)
            # draw in the target precision: a complex64 state never passes
            # through complex128 (which a TPU cannot run)
            real = jnp.finfo(dtype).dtype
            if jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
                t = jax.lax.complex(jax.random.normal(k1, shape, real),
                                    jax.random.normal(k2, shape, real))
            else:
                t = jax.random.normal(k1, shape, real)
            row.append((t / np.sqrt(np.prod(shape), dtype=real)).astype(dtype))
        sites.append(row)
    return PEPS(sites)


def random_onelayer(nrow: int, ncol: int, bond: int, key,
                    dtype=jnp.complex128) -> List[List[jnp.ndarray]]:
    """Random PEPS *without physical indices* — (u, l, d, r) tensors.

    Used by the contraction benchmarks (paper Fig. 8 generates these
    directly to get more bond-dimension data points)."""
    p = random_peps(nrow, ncol, bond, key, phys=1, dtype=dtype)
    return [[t[0] for t in row] for row in p.sites]


# ---------------------------------------------------------------------------
# Update options
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DirectUpdate:
    """Contract full theta then einsumsvd (Eq. 4). O(d^3 r^9)-ish, baseline."""
    rank: int
    svd: object = DirectSVD()


@dataclasses.dataclass(frozen=True)
class QRUpdate:
    """Alg. 1 (QR-SVD), O(d^2 r^5). ``gram=True`` uses Alg. 5 orthogonalization
    (reshape-avoiding); ``gram=False`` uses matricize+LAPACK QR."""
    rank: int
    svd: object = DirectSVD()
    gram: bool = True


@dataclasses.dataclass(frozen=True)
class FullUpdate:
    """Environment-aware full update (Lubasch et al., arXiv:1405.3259).

    The bond truncation is optimized in the metric of the two-site
    neighborhood environment, extracted from cached row environments plus a
    left/right strip contraction (see :mod:`repro.core.full_update`).  More
    accurate than :class:`QRUpdate` at equal bond dimension; costs one
    environment contraction + a jit-fused ALS per bond.

    Parameters
    ----------
    rank:       truncated bond dimension.
    svd:        einsumsvd engine for the ALS seed split (the simple-update
                answer in the reduced gauge).
    chi:        boundary bond dimension of the row environments.
    env_svd:    einsumsvd engine for the environment sweeps.
    als_iters:  fixed number of ALS sweeps (static; part of the jit
                signature).
    als_eps:    relative Tikhonov regularization of the ALS normal matrices
                (floored at 100 ulps of the state's dtype).
    positive:   hermitize + eigenvalue-clamp the bond environment (the
                gauge/positive fix; strongly recommended).
    env_refresh_every: in ``ite.ite_run``, refresh the cached row
                environments every N gate applications (1 = before every
                two-site gate; larger values reuse staler environments,
                cluster-update style, for speed).  Independently of the
                cadence, environments are always refreshed when a bond
                dimension has grown since the cached sweep (see
                ``full_update.envs_compatible``).
    env_contract: full contraction option for the environment sweeps,
                overriding ``(chi, env_svd)`` when set.  Pass a
                :class:`repro.core.distributed.DistributedBMPS` to run the
                row-environment sweeps column-sharded across devices —
                this is how full-update ITE picks up intra-state
                distribution (values match single-device to rounding).
    """
    rank: int
    svd: object = DirectSVD()
    chi: int = 16
    env_svd: object = DirectSVD()
    als_iters: int = 6
    als_eps: float = 1e-12
    positive: bool = True
    env_refresh_every: int = 1
    env_contract: object = None


def check_update(update) -> None:
    """Validate a two-site update option (single source of the accepted set)."""
    if not isinstance(update, (DirectUpdate, QRUpdate, FullUpdate)):
        raise TypeError(
            f"unknown two-site update option {type(update).__name__!r}: "
            "expected DirectUpdate, QRUpdate, or FullUpdate")


# ---------------------------------------------------------------------------
# Operator application
# ---------------------------------------------------------------------------

def apply_single(state: PEPS, g, flat_site: int) -> PEPS:
    """One-site operator (Eq. 3) — contraction with the physical index."""
    i, j = state.coords(flat_site)
    g = jnp.asarray(g, dtype=state.dtype)
    new = state.copy()
    new.sites[i][j] = jnp.einsum("pq,quldr->puldr", g, state.sites[i][j])
    return new


def _two_site_horizontal(a, b, g, update, key):
    """Core update for neighbouring sites in a row. a:(p,u,l,d,k) b:(q,U,k,D,R).

    Returns (new_a, new_b) with the shared bond truncated to update.rank.
    """
    rank = update.rank
    if isinstance(update, DirectUpdate):
        # theta_{x u l d, y U D R} — einsumsvd over the 3-tensor network.
        left, right = einsumsvd(
            update.svd,
            [g, a, b],
            ["xypq", "puldk", "qUkDR"],
            row="xuld", col="yUDR",
            rank=rank, absorb="both", key=key,
        )
        new_a = left                                 # (x,u,l,d,m) == (p,u,l,d,r)
        new_b = jnp.moveaxis(right, 0, 2)            # (m,y,U,D,R) -> (y,U,m,D,R)
        return new_a, new_b

    if not isinstance(update, QRUpdate):
        check_update(update)  # FullUpdate never reaches here; reject the rest
        raise TypeError(f"{type(update).__name__} cannot be applied without "
                        "the whole-state context (internal dispatch error)")
    qr = gram_qr if update.gram else reshape_qr
    # Bring the small modes (p, k) last; QR over them.
    a_t = jnp.transpose(a, (1, 2, 3, 0, 4))          # (u,l,d,p,k)
    b_t = jnp.transpose(b, (1, 3, 4, 0, 2))          # (U,D,R,q,k)
    qa, ra = qr(a_t, 2)                               # qa:(u,l,d,α,β) ra:(α,β,p,k)
    qb, rb = qr(b_t, 2)                               # qb:(U,D,R,γ,δ) rb:(γ,δ,q,k)
    # einsumsvd on the small network {G, Ra, Rb} (paper step (2)->(4)).
    left, right = einsumsvd(
        update.svd,
        [jnp.asarray(g, dtype=a.dtype), ra, rb],
        ["xypq", "abpk", "cdqk"],
        row="xab", col="ycd",
        rank=rank, absorb="both", key=key,
    )
    # Reabsorb the Q factors (steps (4)->(5)).
    new_a = jnp.einsum("uldab,xabm->xuldm", qa, left)
    new_b = jnp.einsum("UDRcd,mycd->yUmDR", qb, right)
    return new_a, new_b


def _apply_two_site_adjacent(state: PEPS, g, s0: Tuple[int, int],
                             s1: Tuple[int, int], update, key,
                             envs=None) -> PEPS:
    if isinstance(update, FullUpdate):
        # the neighborhood environment is orientation-specific, so the full
        # update handles both orientations itself (no transpose trick)
        from repro.core import full_update as _fu
        return _fu.full_update_bond(state, g, s0, s1, update, key, envs=envs)
    (i0, j0), (i1, j1) = s0, s1
    g = jnp.asarray(g, dtype=state.dtype)
    new = state.copy()
    if i0 == i1 and j1 == j0 + 1:                     # horizontal, left-right
        a, b = state.sites[i0][j0], state.sites[i1][j1]
        na, nb = _two_site_horizontal(a, b, g, update, key)
        new.sites[i0][j0], new.sites[i1][j1] = na, nb
    elif i0 == i1 and j1 == j0 - 1:                   # horizontal, reversed
        gt = jnp.transpose(g, (1, 0, 3, 2))           # swap the two qubits
        return _apply_two_site_adjacent(state, gt, s1, s0, update, key, envs)
    elif j0 == j1 and i1 == i0 + 1:                   # vertical, top-bottom
        # Conjugate by axis swaps: a's (d<->r), b's (u<->l) turn the vertical
        # bond into the canonical horizontal layout.
        a = jnp.transpose(state.sites[i0][j0], (0, 1, 2, 4, 3))
        b = jnp.transpose(state.sites[i1][j1], (0, 2, 1, 3, 4))
        na, nb = _two_site_horizontal(a, b, g, update, key)
        new.sites[i0][j0] = jnp.transpose(na, (0, 1, 2, 4, 3))
        new.sites[i1][j1] = jnp.transpose(nb, (0, 2, 1, 3, 4))
    elif j0 == j1 and i1 == i0 - 1:                   # vertical, reversed
        gt = jnp.transpose(g, (1, 0, 3, 2))
        return _apply_two_site_adjacent(state, gt, s1, s0, update, key, envs)
    else:
        raise ValueError(f"sites {s0}, {s1} are not adjacent")
    return new


def _swap_path(s0: Tuple[int, int], s1: Tuple[int, int]) -> List[Tuple[int, int]]:
    """Lattice path from s1's position to a neighbour of s0 (row then column)."""
    (i0, j0), (i1, j1) = s0, s1
    path = [(i1, j1)]
    i, j = i1, j1
    # walk rows: to row i0 (if columns differ) or to the adjacent row
    while (i != i0) if j != j0 else (abs(i - i0) > 1):
        i += 1 if i0 > i else -1
        path.append((i, j))
    # walk columns until horizontally adjacent
    while abs(j - j0) > 1:
        j += 1 if j0 > j else -1
        path.append((i, j))
    return path


def apply_operator(state: PEPS, g, flat_sites: Sequence[int],
                   update: Optional[object] = None, key=None,
                   envs=None) -> PEPS:
    """Apply a 1- or 2-site operator on arbitrary sites.

    Non-adjacent two-site operators are routed with SWAP chains (paper
    Section II-C1); each SWAP uses the same truncating update.

    ``envs`` (FullUpdate only): cached ``(top, bottom)`` row environments to
    truncate against; omitted, they are recomputed from the current state
    per bond.  Along a SWAP chain the same environments are reused — they go
    slightly stale as the chain progresses (cluster-update trade-off).
    """
    if key is None:
        key = jax.random.PRNGKey(np.bitwise_xor.reduce(
            np.asarray([17, *flat_sites], dtype=np.uint32)))
    if len(flat_sites) == 1:
        return apply_single(state, g, flat_sites[0])
    if len(flat_sites) != 2:
        raise ValueError("only 1- and 2-site operators are supported")
    if update is None:
        update = QRUpdate(rank=max(4, state.max_bond()))
    check_update(update)

    s0, s1 = state.coords(flat_sites[0]), state.coords(flat_sites[1])
    if _adjacent(s0, s1):
        return _apply_two_site_adjacent(state, g, s0, s1, update, key, envs)

    # SWAP-chain routing: walk s1 next to s0, apply, walk back.
    path = _swap_path(s0, s1)
    swap = jnp.asarray(_gates.SWAP, dtype=state.dtype)
    keys = jax.random.split(key, 2 * len(path) + 1)
    ki = 0
    for a, b in zip(path[:-1], path[1:]):
        state = _apply_two_site_adjacent(state, swap, a, b, update, keys[ki],
                                         envs); ki += 1
    state = _apply_two_site_adjacent(state, g, s0, path[-1], update, keys[ki],
                                     envs); ki += 1
    for a, b in zip(reversed(path[1:]), reversed(path[:-1])):
        state = _apply_two_site_adjacent(state, swap, a, b, update, keys[ki],
                                         envs); ki += 1
    return state


def _adjacent(s0, s1) -> bool:
    return abs(s0[0] - s1[0]) + abs(s0[1] - s1[1]) == 1


def normalize_sites(state: PEPS) -> PEPS:
    """Rescale every site tensor to unit max-|entry|, tracking log_scale.

    Keeps ITE numerically bounded; amplitudes are recovered by multiplying
    contraction results with exp(log_scale)."""
    new_sites = []
    log_scale = state.log_scale
    for row in state.sites:
        new_row = []
        for t in row:
            s = jnp.max(jnp.abs(t))
            s = jnp.where(s == 0, 1.0, s)
            new_row.append(t / s)
            log_scale = log_scale + jnp.log(s)
        new_sites.append(new_row)
    return PEPS(new_sites, log_scale)


# ---------------------------------------------------------------------------
# Exact contraction (reference paths for small grids)
# ---------------------------------------------------------------------------

def to_statevector(state: PEPS) -> jnp.ndarray:
    """Exact contraction to a (2,)*n state tensor (small grids only)."""
    nrow, ncol = state.nrow, state.ncol
    # boundary: axes = [phys... (row-major so far)] + [down bond per column]
    bound = jnp.ones((1,) * ncol, dtype=state.dtype)
    n_phys = 0
    for i in range(nrow):
        # insert l_run (dim 1) before the u-block:
        # axes now: [phys (n_phys)] + [l_run] + [u_0..u_{ncol-1}]
        bound = bound.reshape(bound.shape[:n_phys] + (1,) + bound.shape[n_phys:])
        for j in range(ncol):
            t = state.sites[i][j]  # (p,u,l,d,r)
            # axes: [phys (n_phys=base+j)] + [d_new (j)] + [l_run] + [u_j..]
            l_ax = n_phys + j
            u_ax = l_ax + 1
            bound = jnp.tensordot(bound, t, axes=[[l_ax, u_ax], [2, 1]])
            # result axes: [phys][d_new]*j [u_{j+1}..] + (p,d,r)
            # move (p, d, r): p -> phys block end... simpler: move p,d,r into place
            nb = bound.ndim
            p_ax, d_ax, r_ax = nb - 3, nb - 2, nb - 1
            # target: [phys.. p] [d_new.. d] [r_run] [u_{j+1}..]
            bound = jnp.moveaxis(bound, (p_ax, d_ax, r_ax),
                                 (n_phys, n_phys + 1 + j, n_phys + 2 + j))
            n_phys += 1
        # after the row: axes [phys][d_0..d_{ncol-1}][r_run(dim1)]
        bound = bound.reshape(bound.shape[:-1])  # drop r_run (dim 1)
    # drop the final down bonds (all dim 1)
    bound = bound.reshape(bound.shape[:n_phys])
    return bound * jnp.exp(state.log_scale).astype(bound.dtype)


def amplitude_exact(state: PEPS, bits: np.ndarray) -> jnp.ndarray:
    """<bits|psi> by exact one-layer boundary contraction (no truncation)."""
    bits = np.asarray(bits).reshape(state.nrow, state.ncol)
    nrow, ncol = state.nrow, state.ncol
    # project physical indices
    rows = []
    for i in range(nrow):
        row = []
        for j in range(ncol):
            row.append(state.sites[i][j][int(bits[i, j])])  # (u,l,d,r)
        rows.append(row)
    # boundary vector over down bonds
    bound = jnp.ones((1,) * ncol, dtype=state.dtype)
    for i in range(nrow):
        bound = bound.reshape((1,) + bound.shape)  # l_run axis in front
        for j in range(ncol):
            t = rows[i][j]  # (u,l,d,r)
            # bound axes: [l_run] ... wait keep: [d_new_0..d_new_{j-1}, l_run, u_j..]
            bound = jnp.tensordot(bound, t, axes=[[j, j + 1], [1, 0]])
            # appended axes (d, r) -> put d at position j, r at j+1 (new l_run)
            nb = bound.ndim
            bound = jnp.moveaxis(bound, (nb - 2, nb - 1), (j, j + 1))
        bound = bound.reshape(bound.shape[:-1])  # drop r_run (dim 1)
    val = bound.reshape(())
    return val * jnp.exp(state.log_scale).astype(val.dtype)
