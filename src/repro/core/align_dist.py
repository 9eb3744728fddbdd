"""Distributed x-drop extension along the candidate-pair axis (DESIGN.md
§2.12).

The alignment stage's compacted candidate bucket (``assembly/pipeline.py``)
is embarrassingly parallel per pair, so the distribution is a plain block
split of the bucket over the mesh's grid-row axes — the same
``("pod", "data")`` convention as ``components_dist`` — inside ONE shard_map
region with every exchanged word counted:

1. **gather_reads** — each device holds an ``n/P`` row shard of the read
   code matrix; a counting ppermute ring all-gather (``P−1`` hops per axis,
   nested axes telescope to ``(n/P)·(P−1)·L`` words per device) replicates
   the full matrix so any candidate pair can be gathered locally.
2. **extend** — the local ``bucket/P`` candidate slice gathers its read
   rows, orients strand-1 partners with ``revcomp``, and runs
   ``assembly.alignment.batch_extend`` — the existing ``kernels/xdrop`` op
   through the normal backend dispatch, so the op spans and the
   reference↔pallas parity contract are untouched.
3. **scatter_scores** — the five ``PairAlignment`` int32 outputs stack into
   one ``(5, bucket)`` buffer; each device writes only its own block
   (single-writer) and one ``psum`` allreduce replicates the result
   (ring allreduce ≙ reduce-scatter + all-gather =
   ``2·(5·bucket/P)·(P−1)`` words per device).

Accounting follows ``core/summa.py``: the cached program builder returns
``(fm, acct)``; the traced body resets ``acct`` and increments it next to
each exchange, so the measured ``exchange_words_align`` is exact and
data-independent — cross-checked against ``bench_comm_model.words_align``
by ``scripts/check_smoke_comm.py``.

Per-pair independence makes the split bit-safe: every bucket entry sees
exactly the inputs the local/GSPMD path feeds it, so scores, accepted-pair
sets and overflow counts are bit-identical (asserted in
``tests/test_align_dist.py`` on 2×2 and multipod meshes).
"""

from __future__ import annotations

from functools import lru_cache
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..obs import validated
from ..obs.trace import span
from .backend import (
    default_impl,
    record_impl,
    resolve_backend,
    resolve_interpret,
)
from .components_dist import default_row_mesh, infer_row_axes

#: arrays of a PairAlignment result (score, bi, ei, bj, ej) — the scatter
#: ships all five stacked as one (5, bucket) int32 buffer.
ALIGN_OUTPUTS = 5

#: cand dict keys, in the positional order the shard_map program takes them.
_CAND_KEYS = ("i", "j", "li", "lj", "pa", "pb", "strand")


def _pad_multiple(x: int, p: int) -> int:
    """Smallest multiple of ``p`` that is ≥ ``x``."""
    return -(-x // p) * p


@lru_cache(maxsize=None)
def _align_program(
    mesh, row_axes: Tuple[str, ...], n_pad: int, row_width: int,
    bucket_pad: int, backend: str, k: int, xdrop: int, match: int,
    mismatch: int, gap: int, band: int, max_steps: int,
):
    """Build (and cache) the jitted shard_map alignment program for one
    (mesh, axes, shape, backend, scoring) key.

    Returns ``(fm, acct)`` where ``acct`` is the trace-time exchange
    accounting dict (``core/summa.py`` convention): the traced body resets
    it at the start of every trace and increments it next to each exchange,
    so cached calls reuse the counted schedule and re-traces recount
    idempotently."""
    from ..assembly import alignment as al  # lazy: core must not import
    from ..assembly.kmers import revcomp  # assembly at module load

    p = 1
    for a in row_axes:
        p *= mesh.shape[a]
    blk = bucket_pad // p
    acct = {"words": 0, "rounds": 0}
    # score-scatter allreduce words per device: one psum of the replicated
    # (5, bucket_pad) buffer ≙ reduce-scatter + all-gather
    w_scatter = 2 * (ALIGN_OUTPUTS * bucket_pad // p) * (p - 1)

    def _counted_gather(x):
        """Ring all-gather of the row shard over every row axis (innermost
        first, mirroring ``components_dist._mesh_closures``), with the
        per-device words of each ppermute hop counted as it is traced."""
        for ax in reversed(row_axes):
            s_ax = mesh.shape[ax]
            if s_ax == 1:
                continue
            perm = [(t, (t + 1) % s_ax) for t in range(s_ax)]
            hop_words = int(np.prod(x.shape))
            parts = [x]
            cur = x
            for _ in range(s_ax - 1):
                acct["words"] += hop_words
                acct["rounds"] += 1
                cur = jax.lax.ppermute(cur, ax, perm)
                parts.append(cur)
            stacked = jnp.stack(parts)  # parts[s] holds shard (t − s) mod P
            t = jax.lax.axis_index(ax)
            order = (t - jnp.arange(s_ax, dtype=jnp.int32)) % s_ax
            x = jnp.take(stacked, order, axis=0).reshape(
                (-1,) + x.shape[1:]
            )
        return x

    def f(codes_l, i_l, j_l, li_l, lj_l, pa_l, pb_l, strand_l):
        acct["words"] = 0  # fresh trace: recount the schedule
        acct["rounds"] = 0
        idx = jnp.int32(0)
        for a in row_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)

        with span("Alignment", kind="phase", phase="gather_reads"):
            codes_full = _counted_gather(codes_l)

        with span("Alignment", kind="phase", phase="extend"):
            ai = codes_full[i_l]
            bj = codes_full[j_l]
            bj = jnp.where((strand_l == 1)[:, None], revcomp(bj, lj_l), bj)
            out = al.batch_extend(
                ai, li_l, bj, lj_l, pa_l, pb_l, k=k, backend=backend,
                xdrop=xdrop, match=match, mismatch=mismatch, gap=gap,
                band=band, max_steps=max_steps,
            )

        with span("Alignment", kind="phase", phase="scatter_scores"):
            stacked = jnp.stack(tuple(out)).astype(jnp.int32)  # (5, blk)
            buf = jnp.zeros((ALIGN_OUTPUTS, bucket_pad), jnp.int32)
            buf = jax.lax.dynamic_update_slice(
                buf, stacked, (jnp.int32(0), idx * blk)
            )
            if p > 1:
                acct["words"] += w_scatter
                acct["rounds"] += 1
            full = jax.lax.psum(buf, row_axes)
        return full

    cspec = P(row_axes)
    # Pallas interpret mode evaluates the kernel body op by op, binding its
    # literals unvarying next to the region's varying inputs, which the
    # varying-axes check rejects; the compiled kernel and the reference
    # extension both type-check, so only that combination turns it off.
    check_vma = not (backend == "pallas" and resolve_interpret("auto"))
    fm = jax.jit(
        shard_map(
            f, mesh=mesh,
            in_specs=(cspec,) * (1 + len(_CAND_KEYS)),
            out_specs=P(), check_vma=check_vma,
        )
    )
    return fm, acct


def align_bucket_shard_map(
    codes,
    cand: Dict[str, Any],
    *,
    k: int,
    mesh=None,
    row_axes: Optional[Tuple[str, ...]] = None,
    backend: str = "reference",
    xdrop: int = 15,
    match: int = 1,
    mismatch: int = -1,
    gap: int = -1,
    band: int = 33,
    max_steps: int = 512,
):
    """Run the compacted candidate bucket through the distributed x-drop
    extension (module docstring) and return ``(PairAlignment, stats)``.

    ``codes`` is the full (n, L) uint8 read matrix; ``cand`` is the
    pipeline's compaction dict (keys ``i, j, li, lj, pa, pb, strand``, all
    (bucket,) int32).  Reads are padded to a multiple of the row-device
    count P with zero rows and the bucket to a multiple of P with zero
    pairs; pad pairs compute the same deterministic garbage on every path
    and are sliced off, so the first ``bucket`` entries are bit-identical
    to the local path.  ``stats`` carries the measured
    ``exchange_words_align`` / ``exchange_rounds_align`` (the
    "align_exchange" schema group), exact against
    ``bench_comm_model.words_align``."""
    if mesh is None:
        mesh = default_row_mesh()
    row_axes = tuple(row_axes) if row_axes is not None else infer_row_axes(mesh)
    p = 1
    for a in row_axes:
        p *= mesh.shape[a]

    codes = jnp.asarray(codes, jnp.uint8)
    n, row_width = codes.shape
    bucket = int(cand["i"].shape[0])
    n_pad = _pad_multiple(n, p)
    bucket_pad = _pad_multiple(bucket, p)
    if n_pad != n:
        codes = jnp.concatenate(
            [codes, jnp.zeros((n_pad - n, row_width), codes.dtype)]
        )

    def _pad1(x):
        x = jnp.asarray(x, jnp.int32)
        if bucket_pad == bucket:
            return x
        return jnp.concatenate(
            [x, jnp.zeros((bucket_pad - bucket,), jnp.int32)]
        )

    fm, acct = _align_program(
        mesh, row_axes, n_pad, row_width, bucket_pad,
        resolve_backend(backend), k, xdrop, match, mismatch, gap, band,
        max_steps,
    )
    with span("Alignment", kind="phase", phase="pair_exchange", p=p,
              bucket=bucket_pad) as sp:
        full = sp.set_output(
            fm(codes, *(_pad1(cand[key]) for key in _CAND_KEYS))
        )

    from ..assembly.alignment import PairAlignment

    # the cached program dispatches xdrop_extend only when first traced
    record_impl("xdrop_extend", default_impl(resolve_backend(backend)))
    res = PairAlignment(*(full[t, :bucket] for t in range(ALIGN_OUTPUTS)))
    stats = validated({
        "exchange_words_align": acct["words"],
        "exchange_rounds_align": acct["rounds"],
    }, context="align_bucket_shard_map", require_groups=("align_exchange",))
    return res, stats
