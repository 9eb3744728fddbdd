"""Mean device seconds per window job of SpGEMM's row merge: the exclusive
time of the ops under the ``merge_sorted_rows`` named scope and its
``sort``, ``combine`` and ``compact`` parts (``core/spmat.py``) in the
SpGEMM stage (``chipbench/scopes.py``)."""

import scopes

UNIT = "s"
LAYER = "SpGEMM: row merge (core/spmat.py)"
MOVES = "job_s"


def read(ctx):
    return scopes.seconds(ctx, "SpGEMM", "merge_sorted_rows")
