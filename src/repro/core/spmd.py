"""Compiled SPMD wavefront superstep for chi-saturated rows (docs/contraction.md).

:mod:`repro.core.distributed` runs the boundary-MPS zip-up as an
explicit-placement pipeline: the host issues one ``zipup_block*`` call per
(row, block) and JAX's async dispatch overlaps them into a wavefront.  That
is the only *general* option — the truncated zip-up is shape-polymorphic
while bonds ramp ``1 -> chi`` — but for the **chi-saturated steady state**
(interior rows whose boundary shapes are a fixed point of the absorption)
every shard's per-column work is shape-uniform, and the whole wavefront can
move into ONE compiled SPMD program: this module builds that program with
``shard_map``, exchanging halos with ``lax.ppermute`` instead of host-driven
``device_put``.

The superstep preserves the library's distribution contract: the identical
sequence of einsumsvd calls with identical operands and PRNG keys as the
single-device sweep (and therefore as the explicit-placement pipeline).  It
is pure re-scheduling; values match to rounding, enforced at 1e-10 in
``tests/test_spmd.py``.

How a sequential zip-up becomes SPMD
------------------------------------
Three ideas, in order of load-bearing-ness:

1. **Per-column micro-steps on the existing kernels.**  ``zipup_block`` /
   ``zipup_block_twolayer`` called with a single-column block ARE the
   per-column einsumsvd steps (``first=True`` = the column-0 carry init,
   ``last=True`` with an empty block = the closing reshape).  The superstep
   is assembled from exactly these calls, so planner signatures — and the
   arithmetic — are the same as every other execution mode.

2. **Wavefront over supersteps.**  With ``n`` shards of ``w`` columns each
   and ``R`` saturated rows, superstep ``t`` has shard ``s`` absorbing its
   block of row ``t - s`` (sub-steps ``j = 0..w-1``, one svd each).  Two
   ``ppermute`` collectives per superstep move the halos:

   * *forward* (end of superstep): the zip-up carry ``V`` goes ``s -> s+1``
     — shard ``s+1`` consumes it for the same row one superstep later;
   * *backward* (after sub-step 0): the einsumsvd at a block's first column
     emits the boundary tensor of the *previous* block's last column (the
     zip-up's one-column output lag); it goes ``s -> s-1`` mid-superstep,
     arriving before the receiver's sub-step ``w-1`` consumes that slot.
     This intra-superstep hop is why blocks need ``w >= 2``: with ``w = 1``
     the emission would be produced and consumed in the same sub-step.

   All ``R + n - 1`` supersteps run inside one ``lax.fori_loop`` — the
   wavefront schedule is compiled, not host-issued.

3. **Uniform containers, true-shape slices.**  An SPMD region needs
   shape-uniform per-shard arrays, but the lattice edges keep small bonds
   forever (the bond ``k`` columns from an edge saturates at
   ``min(chi, r^2k)``, not chi).  Zero-padding *operands* would change the
   randomized-SVD sketches and break equivalence — so padding here is
   **storage only**: boundary tensors live zero-padded in a uniform
   container stack, and every einsumsvd reads statically-sliced true-shape
   tensors out of it.  The ramp columns' svds (static shapes known at trace
   time) are included in the trace alongside the uniform ones; every shard
   executes them, only the edge shards keep the results (``jnp.where`` on
   ``axis_index``) — the price of shape uniformity, amortized as
   ``O(ramp/w)`` redundant work.

Applicability (what "chi-saturated" means operationally)
--------------------------------------------------------
A run of rows is handed to the superstep iff, per :func:`plan_run`:

* **stationary** — absorbing the row maps the boundary shapes to
  themselves (checked by ``jax.eval_shape`` on the micro-steps, so the
  check can never disagree with the real kernels);
* **layout-uniform** — ``ncol`` splits into ``n`` equal blocks of
  ``w >= 2`` columns on ``n`` *distinct* devices, with the non-uniform
  (ramp/edge) columns confined to the first and last block: the uniform
  svd-column run ``[jl, jr)`` must satisfy ``jl <= w - 1`` and
  ``jr >= (n-1) w + 1``.  The superstep picks the largest such ``n``
  dividing ``ncol`` (it need not equal the host pipeline's shard count —
  blocking invariance means any split computes the same values);
* **uniform rows batch** — consecutive rows with identical PEPS column
  shapes extend the batch ``R``.

Bond-ramp rows (early rows, where shapes are NOT stationary) always stay on
the explicit-placement pipeline; ``DistributedBMPS(wavefront="auto"|"spmd")``
does this handoff per row and :func:`stats` counts both sides.

Planner-cache interaction
-------------------------
The superstep program is cached per (kernel, plan, R, collect, devices,
backend) — see :func:`stats`.  *Inside* the trace, each micro-step reaches
:func:`repro.core.planner.fused_randomized_svd` with the same network
signature as the host path, so after any warm-up sweep the trace replays
100% cached fused solvers (ticked at trace time; a replayed superstep ticks
nothing — it is one compiled call).  Plan shape-analysis runs under
``planner.disabled()`` and touches no cache.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import planner
from repro.core.bmps import _keys, zipup_block, zipup_block_twolayer
from repro.launch.mesh import col_mesh

_AXIS = "col"

_PLAN_CACHE: dict = {}
_FN_CACHE: dict = {}
_MISSING = object()

_STATS = {
    "plans": 0,              # plan analyses run (cache misses)
    "superstep_builds": 0,   # compiled superstep programs built
    "superstep_calls": 0,    # superstep invocations (compiled replays)
    "rows_spmd": 0,          # rows absorbed inside the SPMD superstep
    "rows_host": 0,          # rows absorbed by the explicit-placement path
}                            # (rows_* tick only in "spmd"/"auto" sweeps)


def stats() -> dict:
    """Copy of the superstep counters (plus cache sizes)."""
    out = dict(_STATS)
    out["plan_cache_size"] = len(_PLAN_CACHE)
    out["fn_cache_size"] = len(_FN_CACHE)
    return out


def reset_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


def clear() -> None:
    """Drop plan + compiled-program caches and counters."""
    _PLAN_CACHE.clear()
    _FN_CACHE.clear()
    reset_stats()


def note_host_rows(n: int) -> None:
    """Record rows a "spmd"/"auto" sweep handed to the host pipeline."""
    _STATS["rows_host"] += n


# ---------------------------------------------------------------------------
# Per-column micro-steps, layered on the shard-local bmps kernels
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Kernel:
    """One zip-up micro-step = a ``zipup_block*`` call with a 1-column block.

    ``init`` absorbs column 0 into the carry (no svd), ``step`` runs exactly
    one einsumsvd (emitting the PREVIOUS column's boundary tensor — the
    zip-up lag), ``close`` folds the final carry into the last tensor.
    Because these are the block kernels themselves, the einsumsvd
    subscripts, operand shapes and key consumption are identical to the
    single-device and host-wavefront sweeps by construction.
    """
    name: str
    nsites: int  # site operands per column: 1 one-layer, 2 two-layer

    def _block(self, v, svs, site_cols, chi, svd, keys, first, last):
        if self.nsites == 1:
            return zipup_block(v, svs, site_cols[0], chi, svd, keys,
                               first=first, last=last)
        return zipup_block_twolayer(v, svs, site_cols[0], site_cols[1],
                                    chi, svd, keys, first=first, last=last)

    def init(self, sv0, sites0, chi, svd, key):
        _, v = self._block(None, [sv0], [[t] for t in sites0], chi, svd,
                           [key], True, False)
        return v

    def step(self, v, svj, sitesj, chi, svd, key):
        out, v2 = self._block(v, [svj], [[t] for t in sitesj], chi, svd,
                              [key], False, False)
        return out[0], v2

    def close(self, v, chi, svd):
        out, _ = self._block(v, [], [[] for _ in range(self.nsites)],
                             chi, svd, [], False, True)
        return out[0]


ONE_LAYER = _Kernel("onelayer", 1)
TWO_LAYER = _Kernel("twolayer", 2)


# ---------------------------------------------------------------------------
# Shape plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RowPlan:
    """Static shape program of one saturated-row absorption.

    ``sv_shapes[c]`` / ``site_shapes[c]`` are the TRUE per-column operand
    shapes; ``sv_cont`` / ``site_cont[k]`` the uniform storage containers
    (elementwise max).  ``[jl, jr)`` is the uniform svd-column run; columns
    outside it are the ramp/edge specials executed on the first/last shard.
    ``n = 1`` is the degenerate single-shard plan: the whole row chained in
    one compiled program (no collectives), used by ``wavefront="spmd"`` when
    no uniform multi-shard split exists.
    """
    ncol: int
    n: int
    w: int
    jl: int
    jr: int
    sv_shapes: Tuple[Tuple[int, ...], ...]
    site_shapes: Tuple[Tuple[Tuple[int, ...], ...], ...]
    sv_u: Tuple[int, ...]
    site_u: Tuple[Tuple[int, ...], ...]
    v_u: Tuple[int, ...]
    sv_cont: Tuple[int, ...]
    site_cont: Tuple[Tuple[int, ...], ...]
    dtype: str


def _cut(x, shape):
    """Statically slice the true-shape tensor out of a padded container."""
    if tuple(x.shape) == tuple(shape):
        return x
    return lax.slice(x, (0,) * x.ndim, tuple(shape))


def _grow(x, shape):
    """Zero-pad a tensor into its container slot (storage only — every
    consumer slices back to the true shape before computing)."""
    if tuple(x.shape) == tuple(shape):
        return x
    return jnp.pad(x, [(0, c - d) for d, c in zip(x.shape, shape)])


def _eval_row(kernel, chi, svd, sv_shapes, site_shapes, dtype):
    """Shape program of one row absorption via ``jax.eval_shape``.

    Returns ``(emits, vins)``: ``emits[c]`` the output boundary-tensor shape
    for slot ``c``; ``vins[c]`` the carry shape ENTERING column ``c``'s svd
    (``vins[1]`` = the init output, ``vins[ncol]`` = the close input).
    Runs under ``planner.disabled()`` so analysis touches no cache."""
    ncol = len(sv_shapes)
    kst = jax.ShapeDtypeStruct((2,), np.uint32)
    S = lambda sh: jax.ShapeDtypeStruct(tuple(sh), dtype)
    emits: List = [None] * ncol
    vins: List = [None] * (ncol + 1)
    with planner.disabled():
        vins[1] = jax.eval_shape(
            lambda sv, st, k: kernel.init(sv, list(st), chi, svd, k),
            S(sv_shapes[0]), tuple(S(s) for s in site_shapes[0]), kst)
        for c in range(1, ncol):
            e, v = jax.eval_shape(
                lambda v_, sv, st, k: kernel.step(v_, sv, list(st), chi, svd, k),
                vins[c], S(sv_shapes[c]),
                tuple(S(s) for s in site_shapes[c]), kst)
            emits[c - 1] = tuple(e.shape)
            vins[c + 1] = v
        fin = jax.eval_shape(lambda v_: kernel.close(v_, chi, svd), vins[ncol])
        emits[ncol - 1] = tuple(fin.shape)
    return emits, [None] + [tuple(v.shape) for v in vins[1:]]


def _uniform_run(flags: Sequence[bool]) -> Tuple[int, int]:
    """Longest contiguous True run as ``[jl, jr)`` (``(0, 0)`` if none)."""
    best = (0, 0)
    start = None
    for i, f in enumerate(list(flags) + [False]):
        if f and start is None:
            start = i
        elif not f and start is not None:
            if i - start > best[1] - best[0]:
                best = (start, i)
            start = None
    return best


def _distinct_devices(devices):
    seen, out = set(), []
    for d in devices:
        if d.id not in seen:
            seen.add(d.id)
            out.append(d)
    return tuple(out)


def _make_plan(kernel, chi, svd, sv_shapes, site_shapes, dtype, n_shards,
               ndev, allow_single) -> Optional[RowPlan]:
    ncol = len(sv_shapes)
    if ncol < 2:
        return None
    emits, vins = _eval_row(kernel, chi, svd, sv_shapes, site_shapes, dtype)
    if any(tuple(emits[c]) != tuple(sv_shapes[c]) for c in range(ncol)):
        return None  # not a shape fixed point: a bond-ramp row
    mid = ncol // 2
    sv_u, site_u, v_u = sv_shapes[mid], site_shapes[mid], vins[mid]
    flags = [False] + [
        sv_shapes[c] == sv_u and site_shapes[c] == site_u
        and vins[c] == v_u and vins[c + 1] == v_u
        and sv_shapes[c - 1] == sv_u
        for c in range(1, ncol)]
    jl, jr = _uniform_run(flags)
    # the close always runs as special work on the last shard (it emits the
    # final boundary tensor from the carry), so the wave program needs at
    # least one special right column: keep column ncol-1 out of the uniform
    # run even when its shapes happen to match the interior (e.g. bond 1)
    jr = min(jr, ncol - 1)
    layout = None
    for n in range(min(n_shards, ndev, ncol // 2), 1, -1):
        if ncol % n:
            continue
        w = ncol // n
        # specials confined to the edge blocks; the block-boundary slots and
        # the sub-step-0 emissions crossing shard edges must be uniform
        if jr > jl and jl <= w - 1 and jr >= (n - 1) * w + 1:
            layout = (n, w)
            break
    if layout is None:
        if not allow_single:
            return None
        layout = (1, ncol)
    sv_cont = tuple(max(s[i] for s in sv_shapes)
                    for i in range(len(sv_shapes[0])))
    site_cont = tuple(
        tuple(max(site_shapes[c][k][i] for c in range(ncol))
              for i in range(len(site_shapes[0][k])))
        for k in range(len(site_shapes[0])))
    return RowPlan(ncol=ncol, n=layout[0], w=layout[1], jl=jl, jr=jr,
                   sv_shapes=tuple(sv_shapes), site_shapes=tuple(site_shapes),
                   sv_u=tuple(sv_u), site_u=tuple(site_u), v_u=tuple(v_u),
                   sv_cont=sv_cont, site_cont=site_cont,
                   dtype=np.dtype(dtype).name)


def plan_run(kernel, svec_cols, grids, start, chi, svd, n_shards, devices,
             mode) -> Tuple[int, Optional[RowPlan]]:
    """Longest run of rows from ``start`` the superstep can absorb.

    ``grids`` is a tuple of site grids (1 one-layer; 2 two-layer bra/ket).
    Returns ``(0, None)`` when row ``start`` is not applicable (ramp row,
    no uniform layout, wrapped devices); otherwise ``(R, plan)`` where rows
    ``start..start+R-1`` share the plan's shapes."""
    nrow = len(grids[0])
    ncol = len(svec_cols)
    sv_shapes = tuple(tuple(t.shape) for t in svec_cols)

    def row_sig(i):
        return tuple(tuple(tuple(g[i][c].shape) for g in grids)
                     for c in range(ncol))

    sig0 = row_sig(start)
    uniq = _distinct_devices(devices)
    allow_single = (mode == "spmd")
    key = (kernel.name, sv_shapes, sig0, str(np.dtype(svec_cols[0].dtype)),
           chi, svd, min(n_shards, len(uniq)), allow_single)
    plan = _PLAN_CACHE.get(key, _MISSING)
    if plan is _MISSING:
        _STATS["plans"] += 1
        plan = _make_plan(kernel, chi, svd, sv_shapes, sig0,
                          svec_cols[0].dtype, n_shards, len(uniq),
                          allow_single)
        _PLAN_CACHE[key] = plan
    if plan is None:
        return 0, None
    run = 1
    while start + run < nrow and row_sig(start + run) == sig0:
        run += 1
    return run, plan


# ---------------------------------------------------------------------------
# Superstep program builders
# ---------------------------------------------------------------------------

def _build_chain(kernel, chi, svd, plan: RowPlan, R: int, collect: bool):
    """Degenerate n=1 program: R whole-row absorptions in one fori_loop.

    No collectives — this is the single-device sweep compiled end to end
    (identical arithmetic, zero per-site dispatch overhead)."""
    ncol = plan.ncol

    def run(svg, keys_g, *sites_g):
        def superstep(t, state):
            sv, out = state
            srow = [lax.dynamic_index_in_dim(g, t, 0, False) for g in sites_g]
            krow = lax.dynamic_index_in_dim(keys_g, t, 0, False)

            def site_at(c):
                return [_cut(g[c], plan.site_shapes[c][k])
                        for k, g in enumerate(srow)]

            v = kernel.init(_cut(sv[0], plan.sv_shapes[0]), site_at(0),
                            chi, svd, krow[0])
            for c in range(1, ncol):
                e, v = kernel.step(v, _cut(sv[c], plan.sv_shapes[c]),
                                   site_at(c), chi, svd, krow[c])
                sv = sv.at[c - 1].set(_grow(e, plan.sv_cont))
            fin = kernel.close(v, chi, svd)
            sv = sv.at[ncol - 1].set(_grow(fin, plan.sv_cont))
            if collect:
                out = lax.dynamic_update_index_in_dim(out, sv, t, 0)
            return sv, out

        out0 = (jnp.zeros((R, ncol) + plan.sv_cont, svg.dtype) if collect
                else jnp.zeros((), svg.dtype))
        sv, out = lax.fori_loop(0, R, superstep, (svg, out0))
        return (sv, out) if collect else (sv,)

    return jax.jit(run)


def _build_wave(kernel, chi, svd, plan: RowPlan, R: int, collect: bool,
                devices):
    """The n>=2 shard_map wavefront superstep (module docstring, idea 2)."""
    n, w, ncol = plan.n, plan.w, plan.ncol
    jl, jr = plan.jl, plan.jr
    jrl = jr - (n - 1) * w           # local index of the first right special
    T = R + n - 1
    perm_fwd = [(i, i + 1) for i in range(n - 1)]
    perm_bwd = [(i, i - 1) for i in range(1, n)]
    mesh = col_mesh(devices)

    def body(svg, keys_g, *sites_g):
        # per-shard views: svg (w, *sv_cont), keys (R, w, 2),
        # sites_g[k] (R, w, *site_cont[k])
        s = lax.axis_index(_AXIS)

        def superstep(t, state):
            sv, vin, out = state
            r = t - s
            valid = jnp.logical_and(r >= 0, r < R)
            rc = jnp.clip(r, 0, R - 1)
            srow = [lax.dynamic_index_in_dim(g, rc, 0, False)
                    for g in sites_g]
            krow = lax.dynamic_index_in_dim(keys_g, rc, 0, False)

            def site_true(j, c):
                # column c's true-shape operands, read from local slot j
                return [_cut(g[j], plan.site_shapes[c][k])
                        for k, g in enumerate(srow)]

            def site_uni(j):
                return [_cut(g[j], plan.site_u[k])
                        for k, g in enumerate(srow)]

            # left-chain register: the column-0 carry init (real on shard 0)
            lv = kernel.init(_cut(sv[0], plan.sv_shapes[0]), site_true(0, 0),
                             chi, svd, krow[0])

            # sub-step 0: uniform svd at local column 0.  Emits the LEFT
            # neighbor's last slot (the zip-up lag) — the backward halo.
            emit_u, v = kernel.step(vin, _cut(sv[0], plan.sv_u), site_uni(0),
                                    chi, svd, krow[0])
            back = lax.ppermute(_grow(emit_u, plan.sv_cont), _AXIS, perm_bwd)
            nbv = jnp.logical_and(
                s < n - 1,
                jnp.logical_and(t - s - 1 >= 0, t - s - 1 < R))
            sv = sv.at[w - 1].set(jnp.where(nbv, back, sv[w - 1]))
            if collect:
                # that halo is slot w-1 of the boundary AFTER row t-s-1:
                # patch the level written (stale) one superstep ago
                rb = jnp.clip(t - s - 1, 0, R - 1)
                cur = lax.dynamic_index_in_dim(out, rb, 0, False)
                cur = cur.at[w - 1].set(jnp.where(nbv, back, cur[w - 1]))
                out = lax.dynamic_update_index_in_dim(out, cur, rb, 0)

            rv = None
            for j in range(1, w):
                if j == jl:
                    # the ramp chain converged to the uniform carry shape:
                    # shard 0 rejoins the uniform path
                    v = jnp.where(s == 0, lv, v)
                if j == jrl:
                    rv = v  # carry entering the right specials (real on n-1)
                emit_u, v = kernel.step(v, _cut(sv[j], plan.sv_u),
                                        site_uni(j), chi, svd, krow[j])
                emit_c = _grow(emit_u, plan.sv_cont)
                if j < jl:
                    le, lv = kernel.step(lv, _cut(sv[j], plan.sv_shapes[j]),
                                         site_true(j, j), chi, svd, krow[j])
                    emit_c = jnp.where(s == 0, _grow(le, plan.sv_cont),
                                       emit_c)
                if j >= jrl:
                    c = (n - 1) * w + j
                    re_, rv = kernel.step(rv, _cut(sv[j], plan.sv_shapes[c]),
                                          site_true(j, c), chi, svd, krow[j])
                    emit_c = jnp.where(s == n - 1, _grow(re_, plan.sv_cont),
                                       emit_c)
                sv = sv.at[j - 1].set(jnp.where(valid, emit_c, sv[j - 1]))
            fin = kernel.close(rv, chi, svd)
            sv = sv.at[w - 1].set(jnp.where(
                jnp.logical_and(valid, s == n - 1),
                _grow(fin, plan.sv_cont), sv[w - 1]))
            if collect:
                cur = lax.dynamic_index_in_dim(out, rc, 0, False)
                out = lax.dynamic_update_index_in_dim(
                    out, jnp.where(valid, sv, cur), rc, 0)
            # forward halo: the carry moves to the next shard for the same
            # row's next block (shard n-1's send has no target and drops)
            vout = lax.ppermute(v, _AXIS, perm_fwd)
            return sv, vout, out

        dt = svg.dtype
        out0 = (jnp.zeros((R, w) + plan.sv_cont, dt) if collect
                else jnp.zeros((), dt))
        sv, _, out = lax.fori_loop(
            0, T, superstep, (svg, jnp.zeros(plan.v_u, dt), out0))
        return (sv, out) if collect else (sv,)

    nsites = kernel.nsites
    in_specs = (P(_AXIS), P(None, _AXIS)) + (P(None, _AXIS),) * nsites
    out_specs = (P(_AXIS), P(None, _AXIS)) if collect else (P(_AXIS),)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _get_fn(kernel, chi, svd, plan: RowPlan, R: int, collect: bool, devices):
    key = (kernel.name, chi, svd, plan, R, collect,
           tuple(d.id for d in devices), jax.default_backend())
    fn = _FN_CACHE.get(key)
    if fn is None:
        _STATS["superstep_builds"] += 1
        fn = (_build_chain(kernel, chi, svd, plan, R, collect)
              if plan.n == 1 else
              _build_wave(kernel, chi, svd, plan, R, collect, devices))
        _FN_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def absorb_rows(kernel, svec_cols, grid_slices, chi, svd, plan: RowPlan,
                row_keys, devices, collect: bool = False):
    """Absorb ``len(row_keys)`` saturated rows in one compiled superstep.

    ``grid_slices`` is a tuple of per-row site-grid slices (pass the SAME
    list object twice for <psi|psi> so the bra/ket stack is built once).
    Returns ``(new_svec_cols, levels)`` where ``levels`` (``collect=True``)
    is one boundary per absorbed row, true-shaped, on the default device —
    matching :func:`repro.core.distributed.gather_columns` conventions."""
    R = len(row_keys)
    ncol = plan.ncol
    mdevs = _distinct_devices(devices)[:plan.n]
    sites_g: List = []
    if plan.n == 1:
        dev0 = mdevs[0]
        svg = jnp.stack([_grow(jax.device_put(t, dev0), plan.sv_cont)
                         for t in svec_cols])
        keys_g = jnp.stack([_keys(jax.device_put(k, dev0), ncol)
                            for k in row_keys])
        for k, g in enumerate(grid_slices):
            if k and g is grid_slices[0]:
                sites_g.append(sites_g[0])
                continue
            sites_g.append(jnp.stack([
                jnp.stack([_grow(jax.device_put(g[i][c], dev0),
                                 plan.site_cont[k]) for c in range(ncol)])
                for i in range(R)]))
    else:
        # Marshal each shard's column chunk DIRECTLY on its owner device and
        # assemble the global arrays with make_array_from_single_device_arrays
        # — nothing stages through device 0 (each operand moves at most once,
        # from wherever the halo pipeline left it to its superstep owner).
        from jax.sharding import NamedSharding
        mesh = col_mesh(mdevs)
        n, w = plan.n, plan.w

        def assemble(gshape, spec, locals_):
            return jax.make_array_from_single_device_arrays(
                gshape, NamedSharding(mesh, spec), locals_)

        svg = assemble((ncol,) + plan.sv_cont, P(_AXIS), [
            jnp.stack([_grow(jax.device_put(svec_cols[c], mdevs[s]),
                             plan.sv_cont) for c in range(s * w, (s + 1) * w)])
            for s in range(n)])
        # per-row column keys: the split is computed once (deterministic on
        # any device) and each shard receives only its chunk
        keys_rows = [_keys(k, ncol) for k in row_keys]
        keys_g = assemble((R,) + keys_rows[0].shape, P(None, _AXIS), [
            jax.device_put(jnp.stack([kr[s * w:(s + 1) * w]
                                      for kr in keys_rows]), mdevs[s])
            for s in range(n)])
        for k, g in enumerate(grid_slices):
            if k and g is grid_slices[0]:
                sites_g.append(sites_g[0])
                continue
            sites_g.append(assemble((R, ncol) + plan.site_cont[k],
                                    P(None, _AXIS), [
                jnp.stack([
                    jnp.stack([_grow(jax.device_put(g[i][c], mdevs[s]),
                                     plan.site_cont[k])
                               for c in range(s * w, (s + 1) * w)])
                    for i in range(R)])
                for s in range(n)]))
    fn = _get_fn(kernel, chi, svd, plan, R, collect, mdevs)
    res = fn(svg, keys_g, *sites_g)
    _STATS["superstep_calls"] += 1
    _STATS["rows_spmd"] += R
    sv_out = res[0]
    new_cols = [_cut(sv_out[c], plan.sv_shapes[c]) for c in range(ncol)]
    levels = None
    if collect:
        env_out = res[1]
        d0 = jax.local_devices()[0]
        levels = [[jax.device_put(_cut(env_out[r, c], plan.sv_shapes[c]), d0)
                   for c in range(ncol)] for r in range(R)]
    return new_cols, levels
