"""Mean device seconds per window job of SpGEMM's candidate expansion: the
exclusive time of the ops under the ``spgemm_expand`` named scope (the
B-row gather, the semiring multiply and the candidate mask of
``core/spgemm.py``) in the SpGEMM stage (``chipbench/scopes.py``)."""

import scopes

UNIT = "s"
LAYER = "SpGEMM: candidate expansion (core/spgemm.py)"
MOVES = "job_s"


def read(ctx):
    return scopes.seconds(ctx, "SpGEMM", "spgemm_expand")
