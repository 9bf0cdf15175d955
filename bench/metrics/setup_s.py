"""Set-up seconds: from the start of ``bench/run.py`` to the start of the
window (imports, compile or cache load, input build and placement,
warm-up)."""


def read(ctx):
    return ctx["setup_s"]
