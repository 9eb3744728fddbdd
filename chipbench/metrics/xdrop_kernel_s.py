"""Mean device seconds per window job of the x-drop kernel: the
exclusive time of the ops under the ``xdrop_kernel`` named scope
(``kernels/xdrop/xdrop.py``, the ``xdrop_extend`` custom call) in the
Alignment stage (``chipbench/scopes.py``)."""

import scopes

UNIT = "s"
LAYER = "Alignment: x-drop kernel (kernels/xdrop/xdrop.py)"
MOVES = "job_s"


def read(ctx):
    return scopes.seconds(ctx, "Alignment", "xdrop_kernel")
