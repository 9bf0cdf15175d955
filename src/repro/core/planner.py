"""Contraction-plan cache and fused einsumsvd engine (the library's hot path).

A BMPS sweep calls ``einsumsvd`` once per site, and every site of a row (bar
the edges) presents the *same* tensor-network structure: same subscripts,
same shapes, same dtype, same row/col split.  The paper (arXiv:2006.15234,
Alg. 4) and Lubasch et al. (arXiv:1405.3259) exploit exactly this repeated
subnetwork structure; the seed implementation instead re-derived an
``optimize="optimal"`` einsum path on every ``matvecs``/``rmatvecs`` call of
every power iteration and never reused compiled code across sites.

This module fixes both with two memoization layers, keyed by a **network
signature**:

``signature = (subscripts, shapes, dtypes, row, col [, solver config])``

1. **Path cache** — :func:`contraction_path` memoizes the opt_einsum
   contraction path for an einsum expression + operand shapes.
   :class:`~repro.core.rsvd.ImplicitOperator` routes every contraction
   through :func:`cached_einsum`, so the path search runs once per distinct
   network shape instead of once per matvec.
2. **Fused-solver cache** — :func:`fused_randomized_svd` jit-compiles the
   whole randomized-SVD refactorization (sketch -> power iterations ->
   Gram-QR final) as ONE function per signature.  All sites / rows / sweeps
   of ``contract_onelayer``, ``contract_twolayer`` and the ITE/VQE loops
   that share a signature reuse the same compiled executable.

Hit/miss counters are kept per layer (:func:`stats`) so tests and benchmarks
can assert cache behavior.  Counters tick at Python dispatch time: a fused
HIT means a previously-built compiled function was re-invoked.

:func:`disabled` temporarily switches both layers off, restoring the seed
behavior — used by ``benchmarks/bench_planner.py`` for A/B timing.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import opt_einsum

# --------------------------------------------------------------------------
# Configuration + counters
# --------------------------------------------------------------------------

_CONFIG = {
    "path_cache": True,   # memoize einsum contraction paths
    "fusion": True,       # jit-fuse randomized_svd per network signature
}

_PATH_CACHE: Dict[tuple, list] = {}
_FUSED_CACHE: Dict[tuple, object] = {}

_COUNTERS = {
    "path_hits": 0,
    "path_misses": 0,
    "path_uncached": 0,    # path searches with the cache disabled
    "path_preloaded": 0,   # entries installed by load_path_cache
    "fused_hits": 0,
    "fused_misses": 0,
}


def stats() -> Dict[str, int]:
    """Current cache counters + sizes (copies; safe to hold)."""
    out = dict(_COUNTERS)
    out["path_cache_size"] = len(_PATH_CACHE)
    out["fused_cache_size"] = len(_FUSED_CACHE)
    from repro.core import orthogonalize as _orth
    out.update(_orth.gram_dispatch_stats())
    from repro.core import runtime_guard as _guard
    out.update(_guard.global_counters())
    return out


def stats_since(before: Dict[str, int]) -> Dict[str, int]:
    """Counter deltas relative to an earlier :func:`stats` snapshot.

    Cache sizes (``*_cache_size``) stay absolute; everything else is the
    difference.  Lets callers measure a window without resetting the
    process-global counters."""
    now = stats()
    return {k: v if k.endswith("_cache_size") else v - before.get(k, 0)
            for k, v in now.items()}


def reset_stats() -> None:
    for k in _COUNTERS:
        _COUNTERS[k] = 0
    from repro.core import orthogonalize as _orth
    _orth.reset_gram_dispatch_stats()
    from repro.core import runtime_guard as _guard
    _guard.reset_global_counters()


def clear() -> None:
    """Drop both caches (and counters).  Compiled executables are released."""
    _PATH_CACHE.clear()
    _FUSED_CACHE.clear()
    reset_stats()


@contextlib.contextmanager
def disabled():
    """Temporarily restore the seed behavior (no path cache, no fusion)."""
    prev = dict(_CONFIG)
    _CONFIG["path_cache"] = False
    _CONFIG["fusion"] = False
    try:
        yield
    finally:
        _CONFIG.update(prev)


def configure(*, path_cache: bool = None, fusion: bool = None) -> Dict[str, bool]:
    """Flip individual layers; returns the previous configuration."""
    prev = dict(_CONFIG)
    if path_cache is not None:
        _CONFIG["path_cache"] = path_cache
    if fusion is not None:
        _CONFIG["fusion"] = fusion
    return prev


# --------------------------------------------------------------------------
# Signatures
# --------------------------------------------------------------------------

def network_signature(subscripts: Sequence[str],
                      shapes: Sequence[Tuple[int, ...]],
                      dtypes: Sequence,
                      row: str, col: str) -> tuple:
    """Hashable identity of an einsumsvd subnetwork.

    Two calls with equal signatures are guaranteed to contract identically:
    same labels, operand shapes, operand dtypes and row/col split."""
    return (
        tuple(subscripts),
        tuple(tuple(s) for s in shapes),
        tuple(jnp.dtype(d).name for d in dtypes),
        row,
        col,
    )


# --------------------------------------------------------------------------
# Layer 1: contraction-path cache
# --------------------------------------------------------------------------

def _path_optimizer(n_operands: int) -> str:
    # "optimal" enumerates orderings factorially — fine for the <=6-tensor
    # einsumsvd subnetworks, hopeless for the 8-10-tensor neighborhood
    # environments of the full update.  opt_einsum's dynamic-programming
    # search is exact w.r.t. contraction cost and scales to ~20 tensors.
    return "optimal" if n_operands <= 6 else "dp"


def contraction_path(expr: str, shapes: Tuple[Tuple[int, ...], ...]) -> list:
    """Optimal contraction path for ``expr`` over operands of ``shapes``.

    Memoized on (expr, shapes); the search itself runs on shapes only (no
    array data), via opt_einsum."""
    if not _CONFIG["path_cache"]:
        _COUNTERS["path_uncached"] += 1
        path, _ = opt_einsum.contract_path(expr, *shapes, shapes=True,
                                           optimize=_path_optimizer(len(shapes)))
        return path
    key = (expr, shapes)
    hit = _PATH_CACHE.get(key)
    if hit is not None:
        _COUNTERS["path_hits"] += 1
        return hit
    _COUNTERS["path_misses"] += 1
    path, _ = opt_einsum.contract_path(expr, *shapes, shapes=True,
                                       optimize=_path_optimizer(len(shapes)))
    _PATH_CACHE[key] = path
    return path


def cached_einsum(expr: str, *tensors: jnp.ndarray) -> jnp.ndarray:
    """``jnp.einsum`` along a plan-cached optimal path."""
    path = contraction_path(expr, tuple(tuple(t.shape) for t in tensors))
    return jnp.einsum(expr, *tensors, optimize=path)


# --------------------------------------------------------------------------
# Persistent path cache (warm-starting a restarted replica)
# --------------------------------------------------------------------------
#
# The path cache is pure data — (expr, shapes) -> a list of pairwise
# contraction steps — so unlike the fused cache (compiled executables,
# process-bound) it survives serialization.  A restarted replica preloads
# the file and replays an identical workload with zero path-search misses;
# the jit compiles still happen, but the opt_einsum dp searches (the
# dominant single-thread cost of a cold full-update start) do not.
#
# The file is JSON with a sha256 checksum over the canonicalized entries.
# Loading is load-or-ignore: any corruption — truncation, checksum
# mismatch, an unknown format version, plain bad JSON — degrades to a cold
# start with a RuntimeWarning, never a crash.  Entries are validated
# structurally (a path step is a tuple of operand indices) before install.

PATH_CACHE_FORMAT = 1


def _path_entries_canonical(entries: list) -> str:
    return json.dumps(entries, sort_keys=True, separators=(",", ":"))


def save_path_cache(path: str) -> int:
    """Serialize the in-memory path cache to ``path`` (atomic write).

    Returns the number of entries written."""
    entries = sorted(
        [expr, [list(s) for s in shapes], [list(step) for step in plan]]
        for (expr, shapes), plan in _PATH_CACHE.items()
    )
    payload = {
        "format": PATH_CACHE_FORMAT,
        "checksum": hashlib.sha256(
            _path_entries_canonical(entries).encode()).hexdigest(),
        "entries": entries,
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return len(entries)


def load_path_cache(path: str) -> int:
    """Preload contraction paths from ``path`` into the in-memory cache.

    Returns the number of entries installed (0 on a missing/corrupt/stale
    file — cold start with a RuntimeWarning, never an exception).  Installed
    entries tick ``path_preloaded``; subsequent lookups count as hits, so a
    fully warm-started workload shows ``path_misses == 0``."""
    try:
        with open(path) as f:
            payload = json.load(f)
        if payload["format"] != PATH_CACHE_FORMAT:
            raise ValueError(f"unknown path-cache format {payload['format']!r}")
        entries = payload["entries"]
        digest = hashlib.sha256(
            _path_entries_canonical(entries).encode()).hexdigest()
        if digest != payload["checksum"]:
            raise ValueError("path-cache checksum mismatch")
        installed = 0
        staged = {}
        for expr, shapes, plan in entries:
            if not isinstance(expr, str):
                raise ValueError("path-cache entry: expr must be a string")
            key = (expr, tuple(tuple(int(d) for d in s) for s in shapes))
            staged[key] = [tuple(int(i) for i in step) for step in plan]
            installed += 1
    except FileNotFoundError:
        return 0
    except (OSError, ValueError, KeyError, TypeError,
            json.JSONDecodeError) as e:
        warnings.warn(
            f"ignoring unusable planner path cache {path!r} ({e!r}): "
            f"cold start", RuntimeWarning)
        return 0
    _PATH_CACHE.update(staged)
    _COUNTERS["path_preloaded"] += installed
    return installed


#: Where :func:`enable_compile_cache` keeps JAX's persistent compilation
#: cache when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed directory in
#: the checkout (listed in ``.gitignore``).  The path is part of what lets a
#: later process find the entries, so it must not move between runs.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, "_build", "jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process.

    Entry points call this once at start-up; importing the library never
    does.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
    itself, and nothing else is set here); otherwise the cache lives in
    :data:`COMPILE_CACHE_DIR`.  Most executables of this library compile in
    well under a second, below JAX's default one-second write threshold, so
    the threshold is dropped to zero and every executable is cached.
    Returns the cache directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.normpath(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


_INT_LABELS = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"


def int_einsum(*args) -> jnp.ndarray:
    """Interleaved-format einsum with integer labels, along a plan-cached path.

    ``int_einsum(t0, labels0, t1, labels1, ..., out_labels)`` where each
    ``labels`` is a sequence of hashable (integer) axis labels.  The labels
    are remapped to a canonical subscript string so that structurally-equal
    networks built from *different* label counters share one cache entry —
    this is what lets the strip/environment contractions of
    ``expectation.strip_value`` and ``full_update`` hit the path cache across
    columns, sites and sweeps.

    Falls back to ``jnp.einsum(..., optimize="auto")`` (uncached) if the
    network uses more than 52 distinct labels.
    """
    *pairs, out = args
    tensors = list(pairs[0::2])
    labels = list(pairs[1::2])
    mapping: Dict = {}

    def lab(ls):
        for l in ls:
            if l not in mapping:
                mapping[l] = _INT_LABELS[len(mapping)]
        return "".join(mapping[l] for l in ls)

    try:
        expr = ",".join(lab(ls) for ls in labels) + "->" + lab(out)
    except IndexError:  # > 52 distinct labels: interleaved fallback
        _COUNTERS["path_uncached"] += 1
        flat = []
        for t, ls in zip(tensors, labels):
            flat += [t, list(ls)]
        flat.append(list(out))
        return jnp.einsum(*flat, optimize="auto")
    return cached_einsum(expr, *tensors)


# --------------------------------------------------------------------------
# Generic fused-function cache (shared by the rSVD engine and full update)
# --------------------------------------------------------------------------

def fused_fn(tag: str, signature: tuple, builder):
    """Memoized compiled callable per ``(tag,) + signature``.

    ``builder()`` is invoked once per distinct signature and should return a
    (typically ``jax.jit``-wrapped) function; later calls with an equal
    signature replay the cached callable.  Hits/misses tick the same
    ``fused_*`` counters as :func:`fused_randomized_svd`, so benchmarks and
    tests can assert cache behavior across *all* fused engines.  The caller
    is responsible for folding every trace-time decision (shapes, dtypes,
    static solver config, device backend) into ``signature``.

    With fusion disabled (:func:`disabled` / :func:`configure`), the builder
    result is neither cached nor counted — callers get a fresh (still
    correct, typically uncompiled) function each time.
    """
    if not _CONFIG["fusion"]:
        return builder()
    key = (tag,) + tuple(signature)
    fn = _FUSED_CACHE.get(key)
    if fn is None:
        _COUNTERS["fused_misses"] += 1
        fn = builder()
        _FUSED_CACHE[key] = fn
    else:
        _COUNTERS["fused_hits"] += 1
    return fn


# --------------------------------------------------------------------------
# Layer 2: fused randomized-SVD solver cache
# --------------------------------------------------------------------------

def _build_fused(subscripts: Tuple[str, ...], row: str, col: str,
                 rank: int, n_iter: int, oversample: int, gram_final: bool):
    from repro.core.rsvd import ImplicitOperator, randomized_svd

    @jax.jit
    def run(tensors: List[jnp.ndarray], key):
        op = ImplicitOperator(tensors, list(subscripts), row, col)
        return randomized_svd(op, rank, n_iter=n_iter, oversample=oversample,
                              key=key, gram_final=gram_final)

    return run


def fused_randomized_svd(op, rank: int, n_iter: int = 4, oversample: int = 8,
                         key=None, gram_final: bool = True):
    """Randomized SVD of an :class:`ImplicitOperator`, jit-fused per signature.

    The entire Alg. 4 pipeline — random sketch, power iterations (with
    Gram-QR orthogonalizations), final Gram-QR + small dense SVD — compiles
    to one executable, cached on the network signature + solver config and
    reused by every einsumsvd call with the same structure.  Numerically
    identical to :func:`repro.core.rsvd.randomized_svd` (same ops, traced).
    """
    from repro.core.rsvd import randomized_svd
    from repro.kernels import dispatch as _dispatch
    if key is None:
        key = jax.random.PRNGKey(0)
    if not _CONFIG["fusion"]:
        return randomized_svd(op, rank, n_iter=n_iter, oversample=oversample,
                              key=key, gram_final=gram_final)
    sig = network_signature(op.subscripts,
                            [t.shape for t in op.tensors],
                            [t.dtype for t in op.tensors],
                            op.row, op.col)
    # Kernel-dispatch state (backend mode, per-site overrides, compute
    # dtype, interpret mode) is a trace-time decision baked into the
    # compiled executable, so its full signature (and the device backend)
    # must be part of the key — otherwise set_kernel_backend() /
    # set_kernel_compute() would be silently ignored for already-compiled
    # signatures.
    sig = sig + (rank, n_iter, oversample, gram_final,
                 _dispatch.backend_signature(), jax.default_backend())
    fn = _FUSED_CACHE.get(sig)
    if fn is None:
        _COUNTERS["fused_misses"] += 1
        fn = _build_fused(tuple(op.subscripts), op.row, op.col,
                          rank, n_iter, oversample, gram_final)
        _FUSED_CACHE[sig] = fn
    else:
        _COUNTERS["fused_hits"] += 1
    return fn(list(op.tensors), key)
