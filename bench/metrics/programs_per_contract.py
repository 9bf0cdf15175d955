"""XLA program executions on the device per contraction in the traced
window: host dispatch of small programs (zip-up per-column fused calls,
rSVD steps) shows here."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.programs or not ctx["units"]:
        return None
    return trace.program_count() / ctx["units"]
