"""Mean wall seconds per window job of the TrReduction stage span (the span
syncs the stage's outputs, so it holds the stage's device time and its
host work)."""

UNIT = "s"
LAYER = "TrReduction (core/transitive_reduction.py)"
MOVES = "job_s"


def read(ctx):
    return sum(j["timings"]["TrReduction"] for j in ctx["jobs"]) / len(ctx["jobs"])
