"""Record ``data/cpu_window.xplane.pb``, the small CPU trace that
``test_trace.py`` reduces.

    JAX_PLATFORMS=cpu python3 bench/tests/record_cpu_trace.py

Two jitted programs, called three times each inside a ``bench.window``
span, with the benchmark's call and wait spans around them, and a sleep
in the window that leaves the device idle for 50 ms.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

OUT = Path(__file__).resolve().parent / "data" / "cpu_window.xplane.pb"


def main():
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    from bench.trace import profile_options

    @jax.jit
    def sin_matmul(x):
        return jnp.sin(x) @ x

    @jax.jit
    def affine(x):
        return x * 2 + 1

    x = jnp.ones((128, 128), jnp.float32)
    jax.block_until_ready(affine(sin_matmul(x)))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=profile_options())
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.call.step"):
                y = affine(sin_matmul(x))
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready(y)
        with TraceAnnotation("bench.sleep"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, OUT)
    shutil.rmtree(tmp)
    print(OUT, os.path.getsize(OUT))


if __name__ == "__main__":
    main()
