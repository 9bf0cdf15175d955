"""Percent of the roofline reached by the gram site (``kernels/gram.py``)
on complex operands: ``gram_complex`` runs two ``_gram`` Pallas kernels
(the real and the imaginary part's Gram) and one ``_tiled_matmul`` Pallas
kernel (the cross term), and nothing else in the program calls
``tiled_matmul``.  The least time of their work (``bench/flops.py``,
peaks of ``bench/peaks.json``) over the summed device time of their
events.  The bound (compute or memory) and each kernel's share go to
standard error."""
import sys

from bench import flops

# the kernels' executions, named by their HLO instruction ("%_gram.3 = ...")
GRAM = r"^%_gram\b"
CROSS = r"^%_tiled_matmul\b"


def _gram(operands):
    (dtype, (n, k)), = operands[:1]
    return flops.gram(n, k, dtype)


def _cross(operands):
    (dtype, (n, k)), (_, (_, m)) = operands[:2]
    return flops.tall_apply(n, k, m, dtype)


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    kind = ctx["device_kind"]
    gram = flops.roofline_parts(trace.kernels_named(GRAM), _gram, kind)
    cross = flops.roofline_parts(trace.kernels_named(CROSS), _cross, kind)
    share, bound = flops.share([gram, cross])
    if share is not None:
        print(f"[bench] gram_roofline bound {bound}; _gram "
              f"{flops.share([gram])[0]} % over {gram[1]} s, _tiled_matmul "
              f"{flops.share([cross])[0]} % over {cross[1]} s",
              file=sys.stderr)
    return share
