"""Percent of the traced window in which no program ran on the device
(one minus the union of program executions over the window)."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.programs:
        return None
    return 100.0 * trace.idle_share()
