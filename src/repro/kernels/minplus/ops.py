"""Public wrapper for the min-plus kernel + backend-dispatch registration.

Both backends of the ``minplus_dense`` op share one signature
(``(a, b) -> n``, see core/backend.py); block sizes and interpret mode are
kernel-side tuning knobs the dispatcher's callers never see.
"""

from __future__ import annotations

from ...core.backend import register_op
from .minplus import minplus_pallas
from .ref import minplus_matmul_ref  # noqa: F401


def minplus_matmul(a, b, *, block_m: int = 128, block_n: int = 128,
                   block_k: int = 128, interpret: bool | str = "auto"):
    """Dense orientation-resolved min-plus matmul on the Pallas kernel."""
    return minplus_pallas(
        a, b, block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )


def _minplus_reference(a, b, *, block_m=None, block_n=None, block_k=None,
                       interpret=None):
    """Reference backend: block/interpret knobs accepted and ignored."""
    return minplus_matmul_ref(a, b)


register_op("minplus_dense", "pallas", minplus_matmul)
register_op("minplus_dense", "reference", _minplus_reference)
