"""Public wrapper for the pileup-vote kernel + backend-dispatch registration.

Both backends of the ``consensus`` op share one signature (see
core/backend.py): ``(draft, pieces, start, plen, *, min_depth, band,
interpret) -> (polished, depth, agree)``; the oracle ignores the kernel-side
tuning knobs (``band``, ``interpret``).
"""

from __future__ import annotations

from ...core.backend import register_op
from .pileup import pileup_pallas
from .ref import pileup_vote_ref  # noqa: F401


def pileup_vote(draft, pieces, start, plen, *, min_depth: int = 2,
                band: int = 512, interpret: bool | str = "auto"):
    """Banded pileup + majority vote on the Pallas kernel (DESIGN.md §2.8)."""
    return pileup_pallas(
        draft, pieces, start, plen, min_depth=min_depth, band=band,
        interpret=interpret,
    )


def _pileup_reference(draft, pieces, start, plen, *, min_depth: int = 2,
                      band=None, interpret=None):
    """Reference backend: kernel tuning knobs accepted and ignored."""
    return pileup_vote_ref(draft, pieces, start, plen, min_depth=min_depth)


register_op("consensus", "pallas", pileup_vote)
register_op("consensus", "reference", _pileup_reference)
