"""XLA backend compiles (persistent-cache misses) inside the window."""

UNIT = "count"
LAYER = "compile"
MOVES = "job_s"


def read(ctx):
    return ctx["window_compiles"]
