"""Mean wall seconds per window job of the CreateSpMat stage span (the span
syncs the stage's outputs, so it holds the stage's device time and its
host work)."""

UNIT = "s"
LAYER = "CreateSpMat (assembly/counter.py)"
MOVES = "job_s"


def read(ctx):
    return sum(j["timings"]["CreateSpMat"] for j in ctx["jobs"]) / len(ctx["jobs"])
