"""Blocking device→host reads per window job: the program counts each read
it takes through ``repro.obs.readback`` (``AssemblyResult.stats
["host_readbacks"]``) and, traced, marks each with a ``readback:<site>``
host span; this reads the mean count of those spans per job
(``chipbench/scopes.py``)."""

import scopes

UNIT = "count"
LAYER = "pipeline (assembly/pipeline.py)"
MOVES = "job_s"


def read(ctx):
    return scopes.readbacks(ctx)
