"""The control path: ``bench/control.py`` runs a cell with the default
matmul precision lowered to ``high`` after the library set ``highest``.

On the chip that control comes out as not correct (PERF.md has the
readings).  A CPU computes float32 products at full precision whatever the
setting, so here the test shows that the control runs the cell's own
check with the lowered setting in force, and that the sound setting is
restored for the programs that follow."""
import jax
import repro.core  # noqa: F401  (sets the sound precision)

from bench import control
from bench import run as R
from bench.tests import tiny


def test_control_runs_the_cell_at_the_lowered_precision(monkeypatch):
    seen = []
    honest_run = R.run_cell

    def spy(*args, **kw):
        seen.append(jax.config.jax_default_matmul_precision)
        return honest_run(*args, **kw)
    monkeypatch.setattr(R, "load_cell", tiny.load)
    monkeypatch.setattr(R, "run_cell", spy)
    before = jax.config.jax_default_matmul_precision
    summary = control.main(
        ["--workload", tiny.cell_names()[0], "--seeds", "5,6",
         "--seconds", "0.5", "--precision", "high"], require_tpu=False)
    assert seen == ["high", "high"]
    assert set(summary["max"]) == {"norm_rel_err"}
    assert summary["min"]["norm_rel_err"] <= summary["max"]["norm_rel_err"]
    assert jax.config.jax_default_matmul_precision == before
