"""Seconds per contraction: the window's seconds over the whole
``norm_squared`` calls completed in it, each ended by
``block_until_ready``."""


def read(ctx):
    return ctx["window_s"] / ctx["units"]
