"""Executables compiled or loaded from the persistent cache during
set-up, counted through ``jax.monitoring``."""


def read(ctx):
    return ctx["setup_executables"]
