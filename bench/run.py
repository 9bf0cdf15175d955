#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell ``bench/cells/<cell>.json`` names its
configuration ``bench/configs/<config>.json`` (with its plain reference
``bench/configs/<config>.py`` beside it) and its job ``bench/jobs/<job>.py``;
the metrics a cell reports are the entries of ``BENCHMARK.json`` that list
it, each read by ``bench/metrics/<metric>.py``.

One run: refuse to go on without a TPU or with Pallas in interpret mode;
keep JAX's persistent compilation cache in ``_build/jax_cache`` of the
checkout; build the cell's inputs from ``--seed`` on the host and put them
on the chip; warm up the cell's own shapes (set-up ends here); call the
job's unit of work until ``--seconds`` have passed, each call ended by
``block_until_ready``; then compare every answer of the window with the
plain reference.  With ``--trace 1`` the window (at most ``TRACE_SECONDS``)
runs under the profiler and the per-layer metrics are read from its trace.  The last line of standard
output is one JSON object; the compared numbers and their limits are the
last lines of standard error and the last key of that object.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / "_build" / "jax_cache"
TRACE_DIR = ROOT / "_build" / "bench_trace"
# A traced run measures this long at most (and at least one unit, so one
# unit in the contraction cells): the trace holds every device operation,
# loop bodies included, about three million a second in the contraction
# cells.  A 2 s trace filled the profiler's buffer (340 MB) and took 160 s
# to stop on a v5e.
TRACE_SECONDS = 0.1


class BenchError(Exception):
    """The run cannot be made or cannot be trusted; no result is printed."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots and
    dashes, so they are loaded by path and not imported by name)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> tuple:
    """(cell, config, reference module) of the cell called ``name``."""
    path = BENCH / "cells" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no cell {path}")
    cell = load_json(path)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    return cell, config, load_module("configs", cell["config"])


def cell_metrics(spec: dict, cell: str) -> tuple:
    """The end-to-end and per-layer metric entries of ``BENCHMARK.json``
    that the cell reports.  An entry without ``workloads`` is reported by
    every cell (an end-to-end metric) or by every cell that reports the
    end-to-end metric it ``moves`` (a per-layer metric)."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if cell in m.get("workloads", [cell] if m["moves"] in names
                              else [])]
    return e2e, layer


def require_chip(chips: int):
    """The devices of this run: ``chips`` TPUs, or BenchError."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX backend is {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX has "
                         f"{len(devices)}")
    import repro.core  # noqa: F401  (before the dispatch module)
    from repro.kernels import dispatch
    if dispatch.interpret_default():
        raise BenchError("Pallas kernels would run in interpret mode")
    return devices[:chips]


def _log(*parts):
    print("[bench]", *parts, file=sys.stderr, flush=True)


class _GcClock:
    """``gc.callbacks`` entry: collections and their seconds, the longest
    one's with its generation."""

    def __init__(self):
        self.count, self.seconds, self.longest = 0, 0.0, (0.0, -1)
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            dt = time.perf_counter() - self._t
            self.count += 1
            self.seconds += dt
            self.longest = max(self.longest, (dt, info["generation"]))

    def __str__(self):
        return (f"{self.count} collections {self.seconds:.4f} s, longest "
                f"{self.longest[0]:.4f} s (generation {self.longest[1]})")


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _counters(before: dict) -> dict:
    """Which kernel sites were engaged (the dispatch counters tick per
    trace, so they say yes or no and not how often), and every guard
    counter (0 on a clean run)."""
    from repro.core import planner
    delta = planner.stats_since(before)
    out = {k: bool(v) for k, v in delta.items()
           if k.startswith(("pallas_", "dense_")) and k.endswith("_calls")}
    out.update({k: v for k, v in delta.items() if k.startswith("guard_")})
    return out


def run_cell(cell: dict, config: dict, reference, seed: int,
             seconds: float, trace: bool, spec: dict, meter,
             devices, t0: float = None) -> dict:
    """One run of a cell on ``devices``; returns the result object.

    ``t0`` is the process's start (set-up is counted from it); the chip
    check is the caller's, so that tests can drive a run on the CPU."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core import planner

    from bench import trace as tr

    t0 = T0 if t0 is None else t0
    job_mod = load_module("jobs", cell["job"])
    e2e, layer = cell_metrics(spec, cell["name"])
    meter_snap = meter.snapshot()
    stats_before = planner.stats()

    with TraceAnnotation("bench.place"):
        job = job_mod.Job(cell, config, reference, seed, devices[0])
    job.warmup()
    setup = meter.since(meter_snap)

    trace_dir = None
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
        trace_dir = TRACE_DIR / f"{cell['name']}.{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(
            str(trace_dir), profiler_options=tr.profile_options())
    # Set-up's objects are collected now and frozen, so that no collection
    # inside the window walks them; the window's own collections are timed.
    gc.collect()
    gc.freeze()
    gc_clock = _GcClock()
    gc.callbacks.append(gc_clock)
    window_snap = meter.snapshot()
    outputs, ends = [], []
    call_span = f"bench.call.{job.CALL}"
    with TraceAnnotation("bench.window"):
        start = time.perf_counter()
        setup_s = start - t0
        while True:
            with TraceAnnotation(call_span):
                out = job.run_unit(len(outputs))
            with TraceAnnotation("bench.wait"):
                out = jax.block_until_ready(out)
            outputs.append(out)
            ends.append(time.perf_counter())
            if ends[-1] - start >= seconds:
                break
        window_s = ends[-1] - start
    gc.callbacks.remove(gc_clock)
    gc.unfreeze()
    if trace:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        t_stop = time.perf_counter() - t_stop
    in_window = meter.since(window_snap)
    memory_peak = _memory_peak(devices)
    counters = _counters(stats_before)

    ctx = {"cell": cell, "config": config, "units": len(outputs),
           "device_kind": devices[0].device_kind,
           "window_s": window_s, "setup_s": setup_s,
           "setup_executables": setup["executables"], "trace": None}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {}
    if trace:
        t_read = time.perf_counter()
        xplane = tr.find_xplane(trace_dir)
        size = os.path.getsize(xplane)
        reduced = tr.reduce(xplane)
        shutil.rmtree(trace_dir, ignore_errors=True)
        _log(f"trace: stop_trace {t_stop:.1f} s, {size} bytes read in "
             f"{time.perf_counter() - t_read:.1f} s")
        ctx["trace"] = reduced
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s()
        result["breakdown"] = reduced.breakdown()
    wanted = layer if trace else e2e
    metrics = {}
    for m in wanted:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    units = [b - a for a, b in zip([start] + ends[:-1], ends)]
    units_s = sorted(units)
    _log(f"cell {cell['name']} seed {seed} units {len(outputs)} "
         f"window_s {window_s} setup_s {setup_s} setup {setup} "
         f"window {in_window} unit_s min {units_s[0]:.4f} "
         f"median {units_s[len(units_s) // 2]:.4f} max {units_s[-1]:.4f} "
         f"(unit {units.index(units_s[-1])}) gc {gc_clock}")
    _log("counters " + json.dumps(counters))
    if in_window["executables"]:
        _log(f"WARNING: {in_window['executables']} executables compiled "
             f"or loaded inside the measured window")

    job.release()
    t_check = time.perf_counter()
    verdict = job.check(outputs)
    _log(f"check {time.perf_counter() - t_check:.1f} s")
    guards = {k: v for k, v in counters.items()
              if k.startswith("guard_") and v}
    correct = (verdict["failed"] == 0 and not guards
               and all(v["value"] <= v["limit"]
                       for v in verdict["checks"].values()))
    for name, v in verdict["checks"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": verdict["attempted"],
              "failed": verdict["failed"], "metrics": metrics,
              "device": device, **result, "checks": verdict["checks"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The cache lives in the checkout, at a path fixed before JAX starts,
    # and the library's own set-up takes it from this variable; the TPU
    # runtime's logs go to the checkout too, not to its default in /tmp.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "_build" / "tpu_logs"))
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    try:
        spec = load_json(ROOT / "BENCHMARK.json")
        cell, config, reference = load_cell(args.workload)
        devices = require_chip(cell["chips"])
        from repro.core import planner
        planner.enable_compile_cache()
        from bench.meter import CompileMeter
        meter = CompileMeter()
        result = run_cell(cell, config, reference, args.seed, args.seconds,
                          bool(args.trace), spec, meter, devices)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
