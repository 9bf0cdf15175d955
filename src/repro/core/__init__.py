"""Core PEPS library — the paper's contribution (Koala) in JAX.

Importing this package enables float64/complex128 support, which quantum
tensor-network arithmetic needs for meaningful accuracy studies, and sets
the default matmul precision to ``"highest"``: on TPU the default runs f32
and complex64 products as one bfloat16 pass (relative error ~4e-3 on a
512-wide complex64 product, against ~2e-7 at ``"highest"``), which breaks
the truncated contractions' accuracy.  On CPU both settings compute the
same.  LM-substrate modules (repro.models, repro.launch) use explicit
dtypes and are unaffected by x64.
"""
import jax

jax.config.update("jax_enable_x64", True)
jax.config.update("jax_default_matmul_precision", "highest")

from repro.core.peps import PEPS, computational_zeros, random_peps  # noqa: E402,F401
from repro.core.gates import GATES, gate, two_site_gate  # noqa: E402,F401
from repro.core.observable import Observable  # noqa: E402,F401
from repro.core.einsumsvd import DirectSVD, RandomizedSVD, einsumsvd  # noqa: E402,F401
