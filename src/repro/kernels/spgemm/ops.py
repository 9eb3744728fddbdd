"""Public wrapper for the fused SpGEMM kernel + backend-dispatch registration.

Both backends of the ``spgemm_ring_stages`` op share one signature
(``(offsets, a_cols, a_vals, b_cols, b_vals, *, semiring, capacity,
n_cols_out, interpret) -> (st_cols, st_vals, overflow)``, see
core/backend.py).  The Pallas path keeps the whole stage batch — panels,
candidate scratch and the per-stage output ELL buffers — VMEM-resident for
the duration of one call, so the ring SUMMA driver (``core.summa.summa_ring``)
pays one HBM round trip per *batch* of ``stages_per_call`` ring stages where
the oracle pays one per stage.

HBM-round-trip accounting: :func:`hbm_round_trips` makes the fused-vs-oracle
trade measurable the same way ``kernels/cc/ops.py`` does — the oracle needs
``stages`` trips, the fused path ``ceil(stages / stages_per_call)``
(``bench_overlap`` reports both, ``tests/test_kernels.py`` asserts the
inequality).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.backend import (
    default_impl,
    note_impl,
    register_op,
    resolve_interpret,
)
from ...core.semiring import Semiring
from .ref import spgemm_ring_stages_ref
from .spgemm import spgemm_ring_stages_pallas as _pallas_raw

# VMEM budget for the fused kernel's resident set (stacked panels + stage
# output buffers + the per-stage candidate expand/sort scratch); above it the
# pallas backend falls back to the oracle — documented behaviour,
# bit-identical either way.
VMEM_BUDGET_BYTES = 8 << 20

# The fused kernel runs core.spmat.merge_sorted_rows (a sort) and multi-vreg
# gathers in its body; the TPU compiler lowers neither, so compiled on a TPU
# the op runs its oracle — an explicit, recorded choice (op_impls,
# summa_backend), never a silent switch.
NO_TPU_LOWERING = "reference (no TPU lowering: in-kernel sort)"


def kernel_runs(interpret: bool | str = "auto") -> bool:
    """True iff the fused kernel can run on this platform: in interpret mode
    only (see ``NO_TPU_LOWERING``)."""
    return resolve_interpret(interpret)


def stage_impl(
    a_cols, a_vals, b_cols, b_vals, *, capacity: int, semiring: Semiring,
    interpret: bool | str = "auto",
) -> tuple[bool, str]:
    """``(fused, impl)`` for one stage batch of the pallas backend: whether
    the fused kernel runs, and the implementation recorded for the call.
    Operands may be arrays or ``ShapeDtypeStruct``s (only shapes are read)."""
    if not kernel_runs(interpret):
        return False, NO_TPU_LOWERING
    if not fused_path_fits(a_cols, a_vals, b_cols, b_vals,
                           capacity=capacity, semiring=semiring):
        return False, "reference (VMEM budget)"
    return True, default_impl("pallas")


def _words_per_slot(vals) -> int:
    """Scalar words per ELL slot of a value pytree whose leaves have leading
    dims (..., slot, *tail): 1 for the column id + the tail elements of every
    leaf (all value dtypes in the pipeline are 4-byte)."""
    words = 1
    for leaf in jax.tree.leaves(vals):
        t = 1
        for d in leaf.shape[2:]:
            t *= d
        words += t
    return words


def _value_words(vals, tail_from: int) -> int:
    """Per-slot value words of a pytree with ``tail_from`` leading dims."""
    words = 0
    for leaf in jax.tree.leaves(vals):
        t = 1
        for d in leaf.shape[tail_from:]:
            t *= d
        words += t
    return words


def _resident_bytes(
    stages: int, n: int, ka: int, nb: int, kb: int, capacity: int,
    a_vals, b_vals, semiring: Semiring,
) -> int:
    """VMEM-resident set of one fused call: A/B panel stacks, the stacked
    stage output buffers and the widest per-stage candidate buffer."""
    wa = 1 + _value_words(a_vals, 3)
    wb = 1 + _value_words(b_vals, 3)
    wc = 1 + _value_words(semiring.zero((1, 1)), 2)
    panels = stages * (n * ka * wa + nb * kb * wb)
    outputs = stages * n * capacity * wc
    scratch = n * ka * kb * wc  # candidate expand/sort buffer of one stage
    return 4 * (panels + outputs + scratch)


def fused_path_fits(
    a_cols: jnp.ndarray, a_vals, b_cols: jnp.ndarray, b_vals, *,
    capacity: int, semiring: Semiring,
) -> bool:
    """True iff :func:`spgemm_ring_stages_pallas` will actually run the fused
    kernel for this stage batch (False = its resident set exceeds
    ``VMEM_BUDGET_BYTES`` and it falls back to the oracle, paying one HBM
    round trip per stage).  ``summa_ring`` consults this so the
    ``spgemm_hbm_round_trips`` evidence stat is never fabricated on
    fallen-back sizes."""
    stages, n, ka = a_cols.shape
    _, nb, kb = b_cols.shape
    return (
        _resident_bytes(stages, n, ka, nb, kb, capacity, a_vals, b_vals,
                        semiring)
        <= VMEM_BUDGET_BYTES
    )


def spgemm_ring_stages_pallas(
    offsets: jnp.ndarray,
    a_cols: jnp.ndarray,
    a_vals,
    b_cols: jnp.ndarray,
    b_vals,
    *,
    semiring: Semiring,
    capacity: int,
    n_cols_out: int,
    interpret: bool | str = "auto",
):
    """Pallas backend of the ``spgemm_ring_stages`` op: the fused kernel with
    the VMEM-budget fallback.  Bit-identical stage buffers and overflow
    counts to :func:`~repro.kernels.spgemm.ref.spgemm_ring_stages_ref`."""
    fused, impl = stage_impl(a_cols, a_vals, b_cols, b_vals,
                             capacity=capacity, semiring=semiring,
                             interpret=interpret)
    if not fused:
        note_impl(impl)
        return spgemm_ring_stages_ref(
            offsets, a_cols, a_vals, b_cols, b_vals, semiring=semiring,
            capacity=capacity, n_cols_out=n_cols_out,
        )
    return _pallas_raw(
        offsets, a_cols, a_vals, b_cols, b_vals, semiring=semiring,
        capacity=capacity, n_cols_out=n_cols_out, interpret=interpret,
    )


def hbm_round_trips(stages: int, stages_per_call: int = 4) -> int:
    """HBM round trips the fused path needs for ``stages`` ring stages (the
    oracle needs ``stages``)."""
    return -(-int(stages) // max(1, stages_per_call))


register_op("spgemm_ring_stages", "reference", spgemm_ring_stages_ref)
register_op("spgemm_ring_stages", "pallas", spgemm_ring_stages_pallas)
