"""Percent of the roofline reached by the ``tall_apply`` Pallas kernel
(``kernels/matvec.py``): least time of its work (``bench/flops.py``,
peaks of ``bench/peaks.json``) over the summed device time of its events.
The bound (compute or memory) goes to standard error."""
import sys

from bench import flops

# the kernel's executions, named by their HLO instruction ("%_tall_apply.3 = ...")
KERNEL = r"^%_tall_apply\b"


def _work(operands):
    (dtype, (n, k)), (_, (_, m)) = operands[:2]
    return flops.tall_apply(n, k, m, dtype)


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    share, bound = flops.roofline_share(trace.kernels_named(KERNEL), _work,
                                        ctx["device_kind"])
    if share is not None:
        print(f"[bench] tall_apply_roofline bound {bound}", file=sys.stderr)
    return share
