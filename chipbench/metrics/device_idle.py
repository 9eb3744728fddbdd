"""Share of the traced window in which no operation ran on the device."""

UNIT = "fraction"
LAYER = "device"
MOVES = "job_s"


def read(ctx):
    return ctx["trace"]["idle_share"]
