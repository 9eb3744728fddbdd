"""Public wrapper for the x-drop kernel + backend-dispatch registration.

Both backends of the ``xdrop_extend`` op share one signature (see
core/backend.py): the oracle ignores the kernel-side tuning knobs
(``pairs_per_block``, ``interpret``).
"""

from __future__ import annotations

from ...core.backend import register_op, resolve_interpret
from .xdrop import xdrop_pallas
from .ref import xdrop_extend_batch_ref  # noqa: F401


def xdrop_extend_batch(a, base_a, step_a, len_a, b, base_b, step_b, len_b,
                       *, pairs_per_block: int | None = None,
                       interpret: bool | str = "auto", **kw):
    """Batched single-direction x-drop extension on the Pallas kernel.

    ``pairs_per_block=None`` picks the block size for the platform: one
    128-lane block when compiled, the whole batch in interpret mode (the
    grid loop is unrolled at trace time there, so fewer blocks = smaller
    HLO and one kernel instantiation)."""
    if pairs_per_block is None:
        pairs_per_block = int(a.shape[0]) if resolve_interpret(interpret) else 128
    return xdrop_pallas(
        a, base_a, step_a, len_a, b, base_b, step_b, len_b,
        pairs_per_block=max(1, pairs_per_block), interpret=interpret, **kw,
    )


def _xdrop_reference(a, base_a, step_a, len_a, b, base_b, step_b, len_b,
                     *, pairs_per_block=None, interpret=None, **kw):
    """Reference backend: kernel tuning knobs accepted and ignored."""
    return xdrop_extend_batch_ref(
        a, base_a, step_a, len_a, b, base_b, step_b, len_b, **kw
    )


register_op("xdrop_extend", "pallas", xdrop_extend_batch)
register_op("xdrop_extend", "reference", _xdrop_reference)
