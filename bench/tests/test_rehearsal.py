"""CPU rehearsal of the harness: every cell of ``BENCHMARK.json`` loads by
name and runs its job's loop at a tiny size (Pallas in interpret mode),
and ``bench/run.py`` refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run as R
from bench.tests import tiny

CELLS = tiny.cell_names()


def test_benchmark_json_names_files_that_exist():
    spec = tiny.spec()
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert (R.ROOT / c["file"]).is_file()
        assert R.load_json(R.ROOT / c["file"])["name"] == c["name"]
    for w in spec["workloads"]:
        cell = R.load_json(R.BENCH / "cells" / f"{w['name']}.json")
        assert (cell["name"], cell["config"], cell["chips"], cell["why"]) \
            == (w["name"], w["config"], w["chips"], w["why"])
        assert w["config"] in configs
        assert (R.BENCH / "jobs" / f"{cell['job']}.py").is_file()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (R.BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_at_tiny_size(name):
    res = tiny.run(name)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    e2e, _ = R.cell_metrics(tiny.spec(), name)
    assert set(res["metrics"]) == {m["name"] for m in e2e}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_per_layer_metrics(name):
    res = tiny.run(name, trace=True)
    assert res["correct"] is True
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    _, layer = R.cell_metrics(tiny.spec(), name)
    got = set(res["metrics"])
    assert got <= {m["name"] for m in layer}
    # the kernels' events carry Mosaic custom calls only on a TPU
    assert got >= {m["name"] for m in layer if "roofline" not in m["name"]}


def test_contract_loop_with_the_pallas_sites_in_interpret_mode():
    from repro.kernels import dispatch
    assert dispatch.interpret_default()
    prev = dispatch.set_kernel_backend("pallas")
    try:
        res = tiny.run("contract.rpeps8x8-d4.chi64")
    finally:
        dispatch.set_kernel_backend(prev)
    assert res["correct"] is True


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_run_refuses_without_a_tpu():
    proc = _run_cli(R.ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(R.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(R.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run_cli(tmp_path))
