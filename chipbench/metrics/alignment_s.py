"""Mean wall seconds per window job of the Alignment stage span (the span
syncs the stage's outputs, so it holds the stage's device time and its
host work)."""

UNIT = "s"
LAYER = "Alignment (assembly/alignment.py)"
MOVES = "job_s"


def read(ctx):
    return sum(j["timings"]["Alignment"] for j in ctx["jobs"]) / len(ctx["jobs"])
