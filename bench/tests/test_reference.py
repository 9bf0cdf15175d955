"""The plain reference of the random-PEPS configuration against a brute
force: on grids small enough to hold the whole state vector, its boundary
MPS norm equals <psi|psi> of the contracted state, and its states carry
the scale they are drawn with."""
import string

import numpy as np
import pytest

from bench import run as R

CONFIG = "rpeps8x8-d4"


def _reference():
    return (R.load_json(R.BENCH / "configs" / f"{CONFIG}.json"),
            R.load_module("configs", CONFIG))


def _state_vector(sites):
    """The PEPS contracted over every bond: one complex128 array with a
    leg per site."""
    letters = iter(string.ascii_letters)
    nrow, ncol = len(sites), len(sites[0])
    h = [[next(letters) for _ in range(ncol + 1)] for _ in range(nrow)]
    v = [[next(letters) for _ in range(ncol)] for _ in range(nrow + 1)]
    phys = [[next(letters) for _ in range(ncol)] for _ in range(nrow)]
    terms = [f"{phys[i][j]}{v[i][j]}{h[i][j]}{v[i + 1][j]}{h[i][j + 1]}"
             for i in range(nrow) for j in range(ncol)]
    out = "".join(p for row in phys for p in row)
    tensors = [t.astype(np.complex128) for row in sites for t in row]
    return np.einsum(",".join(terms) + "->" + out, *tensors, optimize=True)


@pytest.mark.parametrize("shape", [(2, 3), (3, 3)])
def test_norm_matches_the_state_vector(shape):
    config, reference = _reference()
    config = dict(config, nrow=shape[0], ncol=shape[1], bond=2)
    sites = reference.build_state(config, np.random.default_rng(7), 2)
    psi = _state_vector(sites)
    exact = float(np.vdot(psi, psi).real)
    assert reference.norm(config, sites) == pytest.approx(exact, rel=1e-12)


def test_scale_multiplies_the_norm_by_four_per_power_of_two():
    config, reference = _reference()
    config = dict(config, nrow=3, ncol=3, bond=2)
    one = reference.build_state(config, np.random.default_rng(3), 0)
    big = reference.build_state(config, np.random.default_rng(3), 3)
    assert reference.norm(config, big) == pytest.approx(
        64 * reference.norm(config, one), rel=1e-12)
