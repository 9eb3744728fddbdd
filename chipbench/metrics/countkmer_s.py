"""Mean wall seconds per window job of the CountKmer stage span (the span
syncs the stage's outputs, so it holds the stage's device time and its
host work)."""

UNIT = "s"
LAYER = "CountKmer (assembly/counter.py, kmers.py)"
MOVES = "job_s"


def read(ctx):
    return sum(j["timings"]["CountKmer"] for j in ctx["jobs"]) / len(ctx["jobs"])
