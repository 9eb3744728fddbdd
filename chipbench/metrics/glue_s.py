"""Mean seconds per window job outside every stage span: host work between
stages (device scalar reads, transfer of the reads, contig
materialisation)."""

UNIT = "s"
LAYER = "pipeline (assembly/pipeline.py)"
MOVES = "job_s"


def read(ctx):
    return sum(j["wall_s"] - sum(j["timings"].values())
               for j in ctx["jobs"]) / len(ctx["jobs"])
