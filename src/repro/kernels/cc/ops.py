"""Public wrapper for the fused cc kernel + backend-dispatch registration.

Both backends of the ``cc_labels`` op share one signature
(``(cols, *, max_iters) -> (labels, iters)``, see core/backend.py).  The
Pallas path adds two kernel-side knobs the dispatcher's callers never see:
``rounds_per_call`` (how many hook/shortcut rounds stay fused in VMEM per
HBM round trip) and ``interpret``.

HBM-round-trip accounting: the oracle touches HBM once per round; the fused
path touches it once per *chunk* of ``rounds_per_call`` rounds, i.e.
``ceil(iters / rounds_per_call)`` times — ``hbm_round_trips`` makes this
measurable (bench_contigs reports both).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ...core.backend import note_impl, register_op
from ...core.spmat import next_pow2
from .cc import LANES, TILE, cc_rounds_pallas
from .ref import cc_labels_ref

# VMEM budget for the fused kernel's resident set (labels + both neighbour
# blocks); above it the pallas backend falls back to the oracle — documented
# behaviour, bit-identical either way.
VMEM_BUDGET_BYTES = 8 << 20


@partial(jax.jit, static_argnames=("k_in",))
def _transpose_ell_sized(cols: jnp.ndarray, *, k_in: int) -> jnp.ndarray:
    """ELL transpose with static in-capacity ``k_in`` (known ≥ max in-degree):
    row v of the result lists the sources u of the edges ``u→v``."""
    n, k = cols.shape
    m = cols >= 0
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
    dst = jnp.where(m, cols, n).reshape(-1)
    order = jnp.argsort(dst)  # stable: preserves (src, slot) order per dst
    ds = dst[order]
    ss = src.reshape(-1)[order]
    rank = jnp.arange(n * k) - jnp.searchsorted(ds, ds, side="left")
    out = (
        jnp.full((n + 1, k_in), -1, jnp.int32)
        .at[ds, jnp.clip(rank, 0, k_in - 1)]
        .set(ss)[:n]
    )
    return out


def _in_capacity(cols: jnp.ndarray) -> int:
    """Pow-2 in-capacity (≥ max in-degree) the ELL transpose will use."""
    n = cols.shape[0]
    m = cols >= 0
    safe = jnp.where(m, cols, n)
    in_deg = (
        jnp.zeros(n + 1, jnp.int32)
        .at[safe.reshape(-1)]
        .add(m.reshape(-1).astype(jnp.int32))[:n]
    )
    return next_pow2(int(jnp.max(in_deg)))


def transpose_ell(cols: jnp.ndarray) -> jnp.ndarray:
    """In-neighbour ELL of an out-neighbour ELL ``cols`` (n, K).

    The in-capacity is host-sized to the next power of two of the max
    in-degree (the §2.6 pow-2 staging idiom), so the number of distinct
    compiled shapes stays logarithmic.  Returns ``(n, k_in)`` int32, ``-1``
    padded, sources sorted ascending per row.
    """
    return _transpose_ell_sized(cols, k_in=_in_capacity(cols))


def _padded_rows(n: int) -> int:
    """Vertices padded to whole (8, 128) tiles."""
    return -(-n // TILE) * TILE


def _resident_bytes(n: int, k_out: int, k_in: int) -> int:
    """VMEM-resident set of the fused kernel: both neighbour slot-plane
    blocks + the label table, its output and two round temporaries."""
    return 4 * _padded_rows(n) * (k_out + k_in + 4)


def _planes(cols: jnp.ndarray) -> jnp.ndarray:
    """ELL ``(n, K)`` → ``(K, R, 128)`` slot planes over the tile-padded
    vertex range (padding vertices have no edges)."""
    n, k = cols.shape
    npad = _padded_rows(n)
    t = jnp.pad(cols.T.astype(jnp.int32), ((0, 0), (0, npad - n)),
                constant_values=-1)
    return t.reshape(k, npad // LANES, LANES)


def _compact(cols: jnp.ndarray) -> jnp.ndarray:
    """The ELL with each row's occupied slots first and the capacity cut to
    the next power of two of the fullest row — the same edge set in fewer
    slot planes (the kernel's work and VMEM scale with the slot count;
    labels are a min over the set, so slot order is immaterial)."""
    occupied = int(jnp.max(jnp.sum(cols >= 0, axis=1), initial=0))
    k = min(cols.shape[1], next_pow2(max(occupied, 1)))
    return -jnp.sort(-cols, axis=1)[:, :k]


def fused_path_fits(cols: jnp.ndarray) -> bool:
    """True iff :func:`cc_labels_pallas` will actually run the fused kernel
    for this adjacency (False = its resident set exceeds
    ``VMEM_BUDGET_BYTES`` and it falls back to the oracle, paying one HBM
    round trip per round).  Benchmarks consult this so fused-vs-oracle
    round-trip comparisons are never fabricated on fallen-back sizes."""
    cols = _compact(cols)
    n, k = cols.shape
    return _resident_bytes(n, k, _in_capacity(cols)) <= VMEM_BUDGET_BYTES


@partial(jax.jit, static_argnames=("rounds", "n_chunks", "rem", "interpret"))
def _drive_chunks(oc_planes, ic_planes, labels0, *, rounds, n_chunks, rem,
                  interpret):
    """Chunked driver: while changed, run ``rounds`` fused rounds per call
    (≤ ``n_chunks`` chunks), then at most one ``rem``-round tail call so the
    total never exceeds the caller's ``max_iters`` — exact parity with the
    oracle's capped ``while_loop``."""

    def body(carry):
        lab, _, it, chunks = carry
        lab2, chg2 = cc_rounds_pallas(
            oc_planes, ic_planes, lab, rounds=rounds, interpret=interpret
        )
        return lab2, chg2[0, 0] > 0, it + rounds, chunks + 1

    def cond(carry):
        _, changed, _, chunks = carry
        return changed & (chunks < n_chunks)

    lab, changed, iters, chunks = jax.lax.while_loop(
        cond, body, (labels0, jnp.bool_(True), jnp.int32(0), jnp.int32(0))
    )
    if rem:
        def tail(args):
            lab, iters, chunks = args
            lab2, _ = cc_rounds_pallas(
                oc_planes, ic_planes, lab, rounds=rem, interpret=interpret
            )
            return lab2, iters + rem, chunks + 1

        lab, iters, chunks = jax.lax.cond(
            changed, tail, lambda a: a, (lab, iters, chunks)
        )
    return lab, iters, chunks


def cc_labels_pallas(
    cols: jnp.ndarray,
    *,
    max_iters: int | None = None,
    rounds_per_call: int = 8,
    interpret: bool | str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused-kernel backend of the ``cc_labels`` op.

    Bit-identical labels to :func:`~repro.kernels.cc.ref.cc_labels_ref`; the
    returned iteration count is the number of rounds *executed* (a multiple
    of ``rounds_per_call`` plus a possible tail), which may exceed the
    oracle's exact rounds-to-convergence by up to ``rounds_per_call − 1``
    idempotent fixed-point rounds.  Falls back to the oracle when the
    VMEM-resident set (labels + out/in neighbour blocks) would exceed
    ``VMEM_BUDGET_BYTES``.
    """
    if max_iters is None:
        max_iters = cols.shape[0]
    cols = _compact(cols)
    n, k = cols.shape
    cols_t = transpose_ell(cols)
    k_in = cols_t.shape[1]
    fused = _resident_bytes(n, k, k_in) <= VMEM_BUDGET_BYTES
    if not fused:
        note_impl("reference (VMEM budget)")
        return cc_labels_ref(cols, max_iters=max_iters)
    rounds = max(1, min(rounds_per_call, max_iters))
    n_chunks = max_iters // rounds
    rem = max_iters % rounds
    npad = _padded_rows(n)
    lab, iters, _ = _drive_chunks(
        _planes(cols), _planes(cols_t),
        jnp.arange(npad, dtype=jnp.int32).reshape(npad // LANES, LANES),
        rounds=rounds, n_chunks=n_chunks, rem=rem, interpret=interpret,
    )
    return lab.reshape(-1)[:n], iters


def hbm_round_trips(iters: int, rounds_per_call: int = 8) -> int:
    """HBM round trips the fused path needs for ``iters`` executed rounds
    (the oracle needs ``iters``)."""
    return -(-int(iters) // max(1, rounds_per_call))


register_op("cc_labels", "reference", cc_labels_ref)
register_op("cc_labels", "pallas", cc_labels_pallas)
