"""A run whose timed path is broken underneath comes out as not correct:
the harness's look for a chip is skipped and the rest of a run is driven
at a tiny size, once for each fault the cell can have."""
import pytest

from bench.tests import tiny

CONTRACT = [n for n in tiny.cell_names() if n.startswith("contract.")]


@pytest.mark.parametrize("name", CONTRACT)
def test_answer_altered_where_it_is_produced(name, monkeypatch):
    from repro.core import bmps
    honest = bmps.norm_squared

    def altered(state, option, key=None):
        return honest(state, option, key) * (1 + 1e-2)
    monkeypatch.setattr(bmps, "norm_squared", altered)
    res = tiny.run(name)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]


@pytest.mark.parametrize("name", CONTRACT)
def test_stale_answer_for_every_other_state(name, monkeypatch):
    """Half of the states left out: every call answers with the first
    state's norm."""
    from repro.core import bmps
    honest = bmps.norm_squared
    first = {}

    def stale(state, option, key=None):
        if "v" not in first:
            first["v"] = honest(state, option, key)
        return first["v"]
    monkeypatch.setattr(bmps, "norm_squared", stale)
    res = tiny.run(name)
    assert res["correct"] is False
    assert 0 < res["failed"] < res["attempted"]
