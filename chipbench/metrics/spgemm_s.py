"""Mean wall seconds per window job of the SpGEMM stage span (the span
syncs the stage's outputs, so it holds the stage's device time and its
host work)."""

UNIT = "s"
LAYER = "SpGEMM (core/spgemm.py)"
MOVES = "job_s"


def read(ctx):
    return sum(j["timings"]["SpGEMM"] for j in ctx["jobs"]) / len(ctx["jobs"])
