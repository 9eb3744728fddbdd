"""Mean wall seconds per window job of the Contigs stage span (the span
syncs the stage's outputs, so it holds the stage's device time and its
host work)."""

UNIT = "s"
LAYER = "Contigs (assembly/contig_gen.py)"
MOVES = "job_s"


def read(ctx):
    return sum(j["timings"]["Contigs"] for j in ctx["jobs"]) / len(ctx["jobs"])
