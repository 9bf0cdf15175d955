"""The five Pallas dispatch sites compile for a TPU v5e, with x64 enabled.

Each site is lowered at the widest shapes its auto gate admits (tall side
``PALLAS_MIN_BIG`` rows, small side ``PALLAS_MAX_SMALL``) and compiled by
the TPU compiler for a described, not attached, ``v5e:2x2`` topology, in
float32 and complex64 (planar).  Interpret-mode tests cannot see what this
catches: index maps that trace as int64 under x64, block shapes that are
not tile-aligned, and blocks that overflow the kernel's fast memory.

Two more tests check that the library's products compile at HIGHEST
precision for the chip (the TPU default is one bfloat16 pass), and that a
randomized SVD compiles inside a ``shard_map`` over four chips, as the SPMD
superstep runs it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

import repro.core  # noqa: F401  (x64 on, as every library user has it)
from repro.core import orthogonalize  # noqa: F401  (gram / tall_apply)
from repro.kernels import dispatch
from repro.kernels import zipup_block  # noqa: F401  (zip-up sites)

BIG, SMALL = dispatch.PALLAS_MIN_BIG, dispatch.PALLAS_MAX_SMALL

# site -> (argument shapes at the gate's widest admitted width, static args)
CASES = {
    "gram": ([(BIG, SMALL)], ((0,), BIG, SMALL)),
    "tall_apply": ([(BIG, SMALL), (SMALL, SMALL)], (1,)),
    # s0 (b, f, g) x o0 (f, c, h, k): tall side b*g, small side f = c*h*k
    "zipup_first_onelayer": ([(1, SMALL, BIG), (SMALL, 1, 16, SMALL // 16)],
                             ()),
    # s0 (b, f, F, g), bra (p, f, c, h, k), ket (p, F, C, H, K):
    # tall side b*g*c*h*k, small side max(f, p*F, C*H*K)
    "zipup_first_twolayer": ([(1, SMALL, SMALL // 2, 8),
                              (2, SMALL, 1, 16, 32),
                              (2, SMALL // 2, 1, 16, 32)], ()),
    # bra (p, u, l, d, r) x ket: tall side u*l*d*r, small side U*L*D*R
    "pair_merge": ([(2, 1, 16, 16, 16), (2, 1, 8, 8, 8)], ()),
}


@pytest.fixture(scope="module")
def v5e_topology():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of the way.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def v5e(v5e_topology):
    return SingleDeviceSharding(v5e_topology.devices[0])


@pytest.mark.parametrize("dtype", ["float32", "complex64"])
@pytest.mark.parametrize("site", sorted(CASES))
def test_site_compiles_for_v5e(v5e, site, dtype, monkeypatch):
    shapes, static = CASES[site]
    args = [jax.ShapeDtypeStruct(s, jnp.dtype(dtype), sharding=v5e)
            for s in shapes]
    # Steer the backend-keyed choices (auto gate, compiled vs interpret)
    # as they are on the chip; this process's own backend is the CPU.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not dispatch.interpret_default()
    before = dispatch.dispatch_stats()[f"pallas_{site}_calls"]
    compiled = jax.jit(
        lambda *a: dispatch.dispatch(site, *a, *static)).lower(*args).compile()
    assert dispatch.dispatch_stats()[f"pallas_{site}_calls"] == before + 1
    assert "tpu_custom_call" in compiled.as_text()


def test_products_compile_at_highest_precision(v5e):
    """complex64 products compile with HIGHEST operand precision for the
    chip, because ``import repro.core`` sets it.  Regression: at the TPU's
    default precision (one bfloat16 pass) the served amplitudes of a 4x4
    depth-8 RQC were off by 3e-2 relative on a v5e, and the state that
    exact circuit evolution built was off by as much."""
    from repro.core import planner
    shapes = [(8, 16, 16), (16, 2, 16), (8, 2, 16, 16)]   # serve close
    args = [jax.ShapeDtypeStruct(s, jnp.complex64, sharding=v5e)
            for s in shapes]
    compiled = jax.jit(lambda a, b, c: planner.cached_einsum(
        "xbc,bur,xucs->xrs", a, b, c)).lower(*args).compile()
    assert "operand_precision={highest,highest}" in compiled.as_text()


def test_randomized_svd_compiles_in_shard_map_for_v5e(v5e_topology):
    """The SPMD superstep (``core/spmd.py``) runs randomized SVDs inside a
    ``shard_map`` over the column shards.  Regression: the randomized SVD's
    final k x k ``jnp.linalg.svd`` (complex64, x64) crashed the TPU
    compiler there (a segfault in its sharding propagation), so the
    four-chip spmd wavefront could not compile.  The SVD is now read off
    the Gram eigendecomposition the randomized SVD already computes."""
    from repro.core.rsvd import ImplicitOperator, randomized_svd
    mesh = Mesh(np.array(v5e_topology.devices[:4]), ("c",))

    def body(a, b):
        op = ImplicitOperator([a[0], b[0]], ["ik", "kj"], "i", "j")
        u, s, v = randomized_svd(op, 8, key=jax.random.PRNGKey(0))
        return ((u * s.astype(u.dtype)) @ v)[None]

    spec = PartitionSpec("c")
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                               out_specs=spec, check_vma=False))
    args = [jax.ShapeDtypeStruct(shape, jnp.complex64,
                                 sharding=NamedSharding(mesh, spec))
            for shape in ((4, 64, 16), (4, 16, 48))]
    fn.lower(*args).compile()
