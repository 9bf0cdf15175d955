#!/usr/bin/env python3
"""Readings behind a cell's correctness limits, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 \
        --precision high

Runs the cell as ``bench/run.py`` does (same job, window and check), once
per seed in one process, with JAX's default matmul precision set to
``--precision`` after the library has set its own.  The configuration
states float32 products at ``highest``; ``high`` (three bfloat16 passes) is
the next precision below it and is the control: it has to come out as not
correct.  Mosaic refuses a ``high`` dot inside a Pallas kernel, so in a
cell whose kernels engage the control also takes ``--dense-kernels``: the
program's own dense path at every kernel site.  ``--precision highest``
gives the sound runs whose largest
reading is the lower end of each limit.  One JSON line per seed, then a
summary line with the largest and smallest reading of each number.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None, require_tpu: bool = True) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--precision", choices=("highest", "high", "default"),
                    required=True)
    ap.add_argument("--dense-kernels", action="store_true",
                    help="run every kernel site on its dense path")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "_build" / "jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "_build" / "tpu_logs"))
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from bench import run as R
    from bench.meter import CompileMeter

    spec = R.load_json(ROOT / "BENCHMARK.json")
    cell, config, reference = R.load_cell(args.workload)
    if require_tpu:
        devices = R.require_chip(cell["chips"])
    else:
        devices = jax.devices()[:cell["chips"]]
    from repro.core import planner
    planner.enable_compile_cache()
    meter = CompileMeter()
    readings = {}
    from repro.kernels import dispatch
    sound = jax.config.jax_default_matmul_precision
    kernels = dispatch.set_kernel_backend(
        "dense" if args.dense_kernels else dispatch.kernel_backend())
    jax.config.update("jax_default_matmul_precision", args.precision)
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = R.run_cell(cell, config, reference, seed, args.seconds,
                             False, spec, meter, devices,
                             t0=time.perf_counter())
            line = {"seed": seed, "precision": args.precision,
                    "correct": res["correct"], "attempted": res["attempted"],
                    "failed": res["failed"], "checks": res["checks"]}
            print(json.dumps(line), flush=True)
            for name, v in res["checks"].items():
                readings.setdefault(name, []).append(v["value"])
    finally:
        jax.config.update("jax_default_matmul_precision", sound)
        dispatch.set_kernel_backend(kernels)
    summary = {"workload": args.workload, "precision": args.precision,
               "dense_kernels": args.dense_kernels,
               "max": {k: max(v) for k, v in readings.items()},
               "min": {k: min(v) for k, v in readings.items()}}
    print(json.dumps(summary), flush=True)
    return summary


if __name__ == "__main__":
    main()
