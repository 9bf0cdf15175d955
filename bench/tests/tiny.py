"""Cells of ``BENCHMARK.json`` cut to sizes a CPU test can hold, per job:
the same files, loaded by name, with the sizes below replaced."""
import copy
import functools

from bench import run as R

TINY = {
    "contract": {"config": {"nrow": 3, "ncol": 3}, "params": {"chi": 16,
                                                              "states": 2}},
}


def spec() -> dict:
    return R.load_json(R.ROOT / "BENCHMARK.json")


def cell_names() -> list:
    return [w["name"] for w in spec()["workloads"]]


_LOAD_CELL = R.load_cell  # the harness's own, even where a test patches it


def load(name: str) -> tuple:
    cell, config, reference = _LOAD_CELL(name)
    cut = TINY[cell["job"]]
    cell, config = copy.deepcopy(cell), copy.deepcopy(config)
    config.update(cut["config"])
    cell["params"].update(cut["params"])
    return cell, config, reference


def run(name: str, seconds: float = 1.0, trace: bool = False,
        seed: int = 2 ** 31 + 12345) -> dict:
    import time

    import jax

    cell, config, reference = load(name)
    meter = _meter()
    return R.run_cell(cell, config, reference, seed, seconds, trace, spec(),
                      meter, jax.devices()[:1], t0=time.perf_counter())


@functools.lru_cache(maxsize=None)
def _meter():
    """One compile meter per process: jax.monitoring keeps listeners."""
    from bench.meter import CompileMeter
    return CompileMeter()
