"""Mean wall seconds per window job of the BuildR stage span (the span
syncs the stage's outputs, so it holds the stage's device time and its
host work)."""

UNIT = "s"
LAYER = "BuildR (core/string_graph.py)"
MOVES = "job_s"


def read(ctx):
    return sum(j["timings"]["BuildR"] for j in ctx["jobs"]) / len(ctx["jobs"])
