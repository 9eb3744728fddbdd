"""Mean device seconds per window job of the alignment's staging: the
exclusive time of the ops under the ``align_staging`` named scope (the read
gathers and ``revcomp`` of ``assembly/pipeline.py`` ``_align_block``; the
walk-order texts and lane pads of ``kernels/xdrop/xdrop.py``) in the
Alignment stage (``chipbench/scopes.py``)."""

import scopes

UNIT = "s"
LAYER = "Alignment: read and text staging (pipeline.py _align_block, xdrop.py _stage_text)"
MOVES = "job_s"


def read(ctx):
    return scopes.seconds(ctx, "Alignment", "align_staging")
