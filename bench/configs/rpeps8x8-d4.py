"""Plain reference of the ``rpeps8x8-d4`` configuration: the random PEPS's
site tensors drawn from a seed, and <psi|psi> by a boundary MPS in
complex128, with numpy alone.

Sites ``(p, u, l, d, r)`` have entries ``x + i y`` with ``x, y`` uniform
on [0, 1), divided by ``bond ** (legs / 2)`` (``legs``: the site's bonds
of dimension ``bond``), so that <psi|psi> is of order one at any grid
size; they are rounded once to the configuration's dtype.

The norm: the ket and bra layers merged site by site into one layer of
bond ``bond ** 2``; a boundary MPS swept from the top row down, each row
absorbed by a zip-up from the left with the boundary first brought to
right-canonical form; every truncation keeps the ``reference_chi``
leading left singular vectors, taken exactly from the eigenvectors of the
Gram matrix ``M M^H``.  The boundary is renormalised after every row and
the scales are kept as a logarithm.
"""
from __future__ import annotations

import numpy as np
import scipy.linalg


def build_state(config: dict, rng, log2_scale: int = 0) -> list:
    """The site tensors, ``sites[i][j]`` of shape (p, u, l, d, r) in the
    configuration's dtype.  One site, drawn from ``rng``, is multiplied by
    ``2**log2_scale`` (exact in floating point), which multiplies the norm
    by ``4**log2_scale``."""
    nrow, ncol, bond = config["nrow"], config["ncol"], config["bond"]
    dtype = np.dtype(config["dtype"])
    scaled = int(rng.integers(nrow * ncol))
    sites = []
    for i in range(nrow):
        row = []
        for j in range(ncol):
            open_ = (i > 0, j > 0, i < nrow - 1, j < ncol - 1)
            shape = (config["phys"],) + tuple(bond if o else 1
                                              for o in open_)
            t = rng.random(shape) + 1j * rng.random(shape)
            t = t / float(bond) ** (sum(open_) / 2)
            if i * ncol + j == scaled:
                t = t * 2.0 ** log2_scale
            row.append(t.astype(dtype))
        sites.append(row)
    return sites


def _leading(m: np.ndarray, chi: int) -> np.ndarray:
    """The ``chi`` leading left singular vectors of ``m`` (columns)."""
    if m.shape[0] <= chi:
        return np.eye(m.shape[0], dtype=m.dtype)
    n = m.shape[0]
    _, vecs = scipy.linalg.eigh(m @ m.conj().T, subset_by_index=[n - chi, n - 1],
                                driver="evr")
    return vecs[:, ::-1]


def _right_canonical(mps: list) -> list:
    """The same MPS (tensors ``(a, k, b, c)``) with every tensor but the
    first right-orthonormal."""
    mps = list(mps)
    for j in range(len(mps) - 1, 0, -1):
        a, k, b, c = mps[j].shape
        q, r = np.linalg.qr(mps[j].reshape(a, k * b * c).T)
        mps[j] = q.T.reshape(-1, k, b, c)
        mps[j - 1] = np.einsum("akbc,dc->akbd", mps[j - 1], r)
    return mps


def norm(config: dict, sites: list) -> float:
    """<psi|psi> of ``sites``, in float64."""
    chi = config["reference_chi"]
    nrow, ncol = len(sites), len(sites[0])
    # boundary tensors (a, ket down, bra down, c), from a trivial top
    mps = [np.ones((1, 1, 1, 1), np.complex128) for _ in range(ncol)]
    log_norm = 0.0
    for i in range(nrow):
        mps = _right_canonical(mps)
        carry = np.ones((1, 1, 1, 1), np.complex128)  # (x, a, ket l, bra l)
        new = []
        for j in range(ncol):
            ket = sites[i][j].astype(np.complex128)
            t = np.einsum("xalm,aubc->xlmubc", carry, mps[j], optimize=True)
            t = np.einsum("xlmubc,puldr->xmbcpdr", t, ket, optimize=True)
            t = np.einsum("xmbcpdr,pbmes->xdecrs", t, ket.conj(),
                          optimize=True)
            x, d, e, c, r, s = t.shape
            m = t.reshape(x * d * e, c * r * s)
            if j == ncol - 1:
                new.append(m.reshape(x, d, e, 1))
                break
            u = _leading(m, chi)
            new.append(u.reshape(x, d, e, -1))
            carry = (u.conj().T @ m).reshape(-1, c, r, s)
        for j, t in enumerate(new):
            scale = np.linalg.norm(t)
            log_norm += np.log(scale)
            new[j] = t / scale
        mps = new
    value = np.ones((1,), np.complex128)
    for t in mps:
        value = value @ t.reshape(t.shape[0], t.shape[-1])
    return float(value[0].real) * float(np.exp(log_norm))
