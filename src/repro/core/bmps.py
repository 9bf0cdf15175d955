"""Boundary-MPS contraction of PEPS (paper Alg. 2/3, Section III-B, IV-A).

Three contraction pipelines, all reducing a 2D network to a scalar through
a pluggable **boundary engine** (:mod:`repro.core.engines`):

* ``contract_onelayer``   — Alg. 2 on a PEPS with no physical indices.
  With ``DirectSVD`` this is the paper's **BMPS**; with ``RandomizedSVD``
  it is **IBMPS** (theta never materialized).
* ``contract_twolayer``   — <bra|ket> keeping the two layers implicit
  (**two-layer IBMPS** when randomized).  The pair bonds of the MPO rows are
  never merged; only the *boundary* carries merged/truncated bonds.
* ``contract_exact_onelayer`` — no-truncation boundary contraction
  (exponential; reference for small grids).

Leg ordering
------------
PEPS site tensors follow the canonical ``(p, u, l, d, r)`` convention — see
the ASCII diagram in :mod:`repro.core.peps` (the single source of truth for
leg ordering).  Boundary-MPS tensors produced here are

* one-layer: ``(l, d, r)`` — left bond, down (dangling), right bond;
* two-layer: ``(l, d_bra, d_ket, r)`` — the bra/ket pair axes stay separate.

Boundary engines
----------------
How a row is absorbed at fixed chi is the job of the **engine** named by
the option's ``engine`` field (default ``"zipup"``):

* ``"zipup"`` (:mod:`repro.core.engines.zipup`) — the paper's zip-up: one
  einsumsvd per column, greedy truncation.  Its row absorption decomposes
  into shard-local *column-block kernels* (:func:`zipup_block` /
  :func:`zipup_block_twolayer`, re-exported here): each absorbs a
  contiguous block of columns, taking the running carry tensor V from the
  block to its left and returning the carry for the block to its right.
  ``_zipup_row*`` run a whole row as one block (``first=last=True``);
  :mod:`repro.core.distributed` composes the same kernels across a device
  mesh with host-issued halos, and :mod:`repro.core.spmd` composes them
  column-at-a-time inside a compiled ``shard_map`` superstep with
  ``ppermute`` halos (chi-saturated rows).  Because the kernels are
  per-site identical to the single-device sweep — same einsumsvd
  subnetworks, same PRNG keys — every execution mode reproduces
  single-device values to rounding and replays the same planner cache
  entries (docs/contraction.md walks the full stack).
* ``"variational"`` (:mod:`repro.core.engines.variational`) — ALS-fitted
  fixed-chi boundary MPS (zip-up-seeded), globally optimal at fixed chi
  where zip-up is greedy; more accurate per chi at a constant-factor FLOP
  premium.  Row-global (no block kernels): distributed sweeps run it
  row-local, and the SPMD wavefront rejects it.

High-level entry points (``amplitude``/``norm_squared``/``inner`` and the
``contract_*`` functions) accept either a :class:`BMPS` option or a
:class:`repro.core.distributed.DistributedBMPS` option and dispatch
accordingly.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import jax
import jax.numpy as jnp

from repro.core.einsumsvd import DirectSVD, RandomizedSVD
from repro.core.engines import get_engine
# Re-exports: the zip-up machinery moved to repro.core.engines.zipup in the
# engine-layer refactor; these names are part of this module's public
# surface (distributed/spmd compose the block kernels, tests import the
# row/scalar helpers) and stay importable from here indefinitely.
from repro.core.engines.zipup import (  # noqa: F401
    _init_twolayer_boundary,
    _keys,
    _mps_to_scalar,
    _twolayer_final_scalar,
    _zipup_row,
    _zipup_row_twolayer,
    trivial_twolayer_boundary,
    zipup_block,
    zipup_block_twolayer,
)


@dataclasses.dataclass(frozen=True)
class BMPS:
    """Contraction option: boundary-MPS with the given einsumsvd engine.

    ``svd=DirectSVD()`` reproduces the paper's BMPS; ``svd=RandomizedSVD()``
    gives IBMPS / two-layer IBMPS.  ``chi`` is the truncation bond dim m.
    ``constrain_carry`` (distributed runs): callable applied to the zip-up
    carry V between einsumsvd steps — used to pin its sharding.
    ``engine`` selects the boundary-absorption strategy: a registered name
    (``"zipup"`` — the default greedy truncation — or ``"variational"``,
    the ALS-fitted boundary) or a :class:`~repro.core.engines.BoundaryEngine`
    instance for non-default hyper-parameters.

    All interior sites of a zip-up row share one network signature, so with
    the (default) fused RandomizedSVD the whole sweep reuses a single
    jit-compiled refactorization per row position class — the planner cache
    (repro.core.planner) turns the per-site einsumsvd into a compiled-call
    replay across sites, rows, and sweeps.  The variational engine's local
    updates live in the same cache regime (``planner.fused_fn``).

    ``precision`` selects the numerical policy (:mod:`repro.core.precision`):
    ``"exact"`` (default — bit-identical to the pre-policy code) or
    ``"mixed"`` (one-tier storage demotion around each einsumsvd solve,
    bf16 multiplicands in the Pallas kernel sites, f32 accumulation).  The
    ``svd`` option is wrapped at construction, so engines, the distributed
    halo pipeline, the SPMD superstep, and the full update all inherit the
    policy with no signature changes.
    """
    chi: int
    svd: object = DirectSVD()
    constrain_carry: object = None
    engine: object = "zipup"
    precision: object = "exact"

    def __post_init__(self):
        get_engine(self.engine)  # fail fast on unknown engines
        from repro.core.precision import resolve_precision, wrap_svd
        policy = resolve_precision(self.precision)  # fail fast on bad names
        object.__setattr__(self, "svd", wrap_svd(self.svd, policy))

    @classmethod
    def randomized(cls, chi: int, niter: int = 4, oversample: int = 8,
                   fused: bool = True, **kw) -> "BMPS":
        """IBMPS / two-layer IBMPS option with the fused implicit engine."""
        return cls(chi, svd=RandomizedSVD(niter=niter, oversample=oversample,
                                          fused=fused), **kw)


def _distributed_module(option):
    """Return :mod:`repro.core.distributed` iff ``option`` is distributed.

    The import is lazy (distributed composes this module's kernels);
    anything that is neither a :class:`BMPS` nor a ``DistributedBMPS`` is a
    caller bug and raises immediately — a ``TypeError`` naming the accepted
    option types and the registered boundary engines (the repo's
    option-dispatch convention) — instead of failing deep in a sweep."""
    if isinstance(option, BMPS):
        return None
    from repro.core import distributed
    if isinstance(option, distributed.DistributedBMPS):
        return distributed
    from repro.core.engines import registered_engines
    raise TypeError(
        f"unknown contraction option {type(option).__name__!r}: expected a "
        f"BMPS or DistributedBMPS (engine= one of "
        f"{sorted(registered_engines())}), got {option!r}")


# ---------------------------------------------------------------------------
# One-layer: PEPS without physical indices, site tensors (u, l, d, r)
# ---------------------------------------------------------------------------

def contract_onelayer(rows: Sequence[Sequence[jnp.ndarray]], option: BMPS,
                      key=None) -> jnp.ndarray:
    """Alg. 2: contract an (u,l,d,r)-site PEPS to a scalar."""
    dist = _distributed_module(option)
    if dist is not None:
        return dist.contract_onelayer(rows, option, key)
    eng = get_engine(option.engine)
    nrow = len(rows)
    keys = _keys(key, max(nrow, 2))
    # initial boundary MPS = row 0 with u squeezed: (l, d, r)
    svec = [t.reshape(t.shape[1], t.shape[2], t.shape[3]) for t in rows[0]]
    for i in range(1, nrow):
        svec = eng.absorb_onelayer(svec, rows[i], option.chi, option.svd,
                                   keys[i])
    return eng.final_scalar_onelayer(svec)


def contract_exact_onelayer(rows: Sequence[Sequence[jnp.ndarray]]) -> jnp.ndarray:
    """Exact (no truncation) boundary contraction — exponential bond growth."""
    bound = jnp.ones((1,) * len(rows[0]), dtype=rows[0][0].dtype)
    for row in rows:
        bound = bound.reshape((1,) + bound.shape)  # l_run in front
        for j, t in enumerate(row):
            bound = jnp.tensordot(bound, t, axes=[[j, j + 1], [1, 0]])
            nb = bound.ndim
            bound = jnp.moveaxis(bound, (nb - 2, nb - 1), (j, j + 1))
        bound = bound.reshape(bound.shape[:-1])
    return bound.reshape(())


def merge_layers(bra_rows, ket_rows) -> List[List[jnp.ndarray]]:
    """Explicitly merge <bra| and |ket> into a one-layer PEPS with pair bonds.

    This is the memory-hungry O(r1^4 r2^4) object the two-layer algorithms
    avoid; exposed for baselines and tests."""
    out = []
    for bra_row, ket_row in zip(bra_rows, ket_rows):
        row = []
        for tb, tk in zip(bra_row, ket_row):
            pair = jnp.einsum("puldr,pULDR->uUlLdDrR", tb.conj(), tk)
            s = pair.shape
            row.append(pair.reshape(s[0] * s[1], s[2] * s[3], s[4] * s[5], s[6] * s[7]))
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# Two-layer: <bra|ket> with layers kept implicit (two-layer IBMPS)
# ---------------------------------------------------------------------------

def contract_twolayer(bra_rows, ket_rows, option: BMPS, key=None) -> jnp.ndarray:
    """<bra|ket> keeping the two layers implicit.

    ``bra_rows``/``ket_rows`` are grids of (p,u,l,d,r) site tensors.  The bra
    is conjugated internally.  The sweep starts from a trivial boundary so the
    FIRST row is truncated as well — the boundary bond never exceeds chi
    (the merged-pair r^4 init the naive path would carry is avoided)."""
    dist = _distributed_module(option)
    if dist is not None:
        return dist.contract_twolayer(bra_rows, ket_rows, option, key)
    eng = get_engine(option.engine)
    nrow = len(bra_rows)
    keys = _keys(key, max(nrow, 2))
    svec = trivial_twolayer_boundary(len(bra_rows[0]), bra_rows[0][0].dtype)
    for i in range(nrow):
        svec = eng.absorb_twolayer(svec, bra_rows[i], ket_rows[i],
                                   option.chi, option.svd, keys[i],
                                   option.constrain_carry)
    return eng.final_scalar_twolayer(svec)


# ---------------------------------------------------------------------------
# High-level entry points on PEPS states
# ---------------------------------------------------------------------------

def amplitude(state, bits, option: BMPS, key=None) -> jnp.ndarray:
    """<bits|psi> via approximate one-layer contraction (BMPS/IBMPS)."""
    import numpy as np
    bits = np.asarray(bits).reshape(state.nrow, state.ncol)
    rows = [[state.sites[i][j][int(bits[i, j])] for j in range(state.ncol)]
            for i in range(state.nrow)]
    val = contract_onelayer(rows, option, key)
    return val * jnp.exp(state.log_scale).astype(val.dtype)


# ---------------------------------------------------------------------------
# Batched amplitudes: shared boundary prefix + vmapped final-row close
# ---------------------------------------------------------------------------
#
# The one-layer <x|psi> network of an nrow-row PEPS depends on the bits of
# row i only from the absorption of row i onwards, so queries that share
# the bits of rows 0..nrow-2 share the entire boundary sweep — only the
# final row differs.  And because the final row's dangling (down) bonds all
# have dimension 1, absorbing it never needs truncation: the einsumsvd
# matrices have row dimension <= chi, so the zip-up at the last row is
# rank-lossless and the closing scalar equals the *exact* transfer-matrix
# product of the boundary MPS with the selected final-row tensors.  That
# exact product is a chain of small einsums with no SVDs — trivially
# batchable over the queries' final-row bits.  This pair of facts is the
# serving engine's "environment prefix cache" contract
# (:mod:`repro.core.serving`, docs/serving.md).

def final_row_amplitudes(env, row_sites, bits, log_scale=0.0) -> jnp.ndarray:
    """Batched exact close of a boundary MPS against final-row selections.

    ``env`` is the one-layer boundary MPS after absorbing rows
    ``0..nrow-2`` (tensors ``(l, d, r)``, the "prefix" environment);
    ``row_sites`` the final row's ``(p, u, l, d, r)`` site tensors (their
    down bonds must be dim 1); ``bits`` an integer array ``(B, ncol)`` of
    final-row bit selections.  Returns the ``(B,)`` amplitudes, including
    the state's ``exp(log_scale)`` factor.

    The whole chain — per-column physical-index gather + batched transfer
    einsums — is one jit-compiled function per ``(shapes, B)`` signature
    via :func:`repro.core.planner.fused_fn`, so a serving loop that pads
    batches to a fixed bucket size replays a single compiled executable.
    """
    from repro.core import planner
    bits = jnp.asarray(bits, dtype=jnp.int32)
    if bits.ndim != 2:
        raise ValueError(f"bits must be (B, ncol), got shape {bits.shape}")
    B = int(bits.shape[0])
    ncol = len(env)
    dtype = row_sites[0].dtype
    for t in row_sites:
        if t.shape[3] != 1:
            raise ValueError(
                "final_row_amplitudes needs a bottom row (down bonds dim 1); "
                f"got down bond {t.shape[3]}")
    sig = ("serve_close", ncol, B,
           tuple(tuple(t.shape) for t in env),
           tuple(tuple(t.shape) for t in row_sites),
           jnp.dtype(dtype).name, jax.default_backend())

    def build():
        @jax.jit
        def run(env_ts, site_ts, bits_arr, log_scale_arr):
            acc = jnp.ones((B, 1, 1), dtype=dtype)
            for j in range(ncol):
                sel = jnp.take(site_ts[j], bits_arr[:, j], axis=0)
                sel = sel[:, :, :, 0, :]  # (B, u, l, r): squeeze the dim-1 down bond
                # acc (x=batch, b=env bond, c=row bond) x env_j (b, u, r')
                # x sel (x, u, c, s) -> (x, r', s); plan-cached per shape class.
                acc = planner.cached_einsum("xbc,bur,xucs->xrs",
                                            acc, env_ts[j], sel)
            vals = acc.reshape(B)
            return vals * jnp.exp(log_scale_arr).astype(vals.dtype)
        return run

    fn = planner.fused_fn("serve_close", sig, build)
    # log_scale in the sites' own real precision: a float64 scalar would put
    # emulated float64 arithmetic into a complex64 program on a TPU
    return fn(list(env), list(row_sites), bits,
              jnp.asarray(log_scale, dtype=jnp.finfo(dtype).dtype))


def amplitudes(state, bits_batch, option: BMPS, key=None) -> jnp.ndarray:
    """Batched <x|psi>: one boundary sweep per shared row prefix.

    ``bits_batch`` is ``(B, nrow*ncol)`` or ``(B, nrow, ncol)``.  Queries
    are grouped by the bits of rows ``0..nrow-2``; each group pays one
    boundary-MPS prefix sweep (identical keys/engine/einsumsvd sequence to
    per-query :func:`amplitude`), then one batched exact final-row close
    (:func:`final_row_amplitudes`).  Per query this matches
    ``amplitude(state, bits, option, key)`` to rounding.

    This is the uncached batched entry point; :mod:`repro.core.serving`
    adds the LRU environment prefix cache, batch bucketing and the
    request queue on top of the same primitives.
    """
    import numpy as np
    from repro.core.environments import onelayer_prefix_environment
    if _distributed_module(option) is not None:
        raise TypeError("batched amplitudes serve single-device BMPS options")
    bits_arr = np.asarray(bits_batch)
    B = bits_arr.shape[0]
    bits_arr = bits_arr.reshape(B, state.nrow, state.ncol)
    groups: dict = {}
    for idx in range(B):
        prefix = tuple(tuple(int(b) for b in row) for row in bits_arr[idx][:-1])
        groups.setdefault(prefix, []).append(idx)
    vals = [None] * B
    for prefix, idxs in groups.items():
        env = onelayer_prefix_environment(state, prefix, option, key)
        fb = jnp.asarray(bits_arr[idxs, -1, :].astype(np.int32))
        out = final_row_amplitudes(env, state.sites[-1], fb, state.log_scale)
        for k, i in enumerate(idxs):
            vals[i] = out[k]
    return jnp.stack(vals)


def norm_squared(state, option: BMPS, key=None) -> jnp.ndarray:
    """<psi|psi> via two-layer contraction."""
    val = contract_twolayer(state.sites, state.sites, option, key)
    return val * jnp.exp(2.0 * state.log_scale).astype(val.dtype)


def inner(bra, ket, option: BMPS, key=None) -> jnp.ndarray:
    """<bra|ket> via two-layer contraction (both PEPS)."""
    val = contract_twolayer(bra.sites, ket.sites, option, key)
    scale = jnp.exp(bra.log_scale + ket.log_scale)
    return val * scale.astype(val.dtype)
