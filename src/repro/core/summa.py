"""Distributed 2D Sparse SUMMA over semirings (paper §IV-D, §V-B).

Process-grid mapping
--------------------
CombBLAS organizes P processes as a √P×√P grid; we map grid **rows** onto the
mesh axes ``row_axes`` (``("data",)`` single-pod, ``("pod", "data")``
multi-pod) and grid **columns** onto ``col_axis`` ("model").

A distributed sparse matrix (``DistEll``) is a global ELL whose
(rows, capacity) arrays are sharded ``P(row_axes, col_axis)``: the capacity
axis is split into per-grid-column *blocks*, so the local shard of device
(i, j) is exactly CombBLAS's 2D block A_ij — entries of rows
``i·n/pr …`` whose (global) column ids fall in grid-column j's range.

Algorithms
----------
* ``summa_allgather`` — the broadcast-all SUMMA variant: all-gather A along
  grid rows' *column* axis (each device obtains its full block-row of A) and
  B along grid *rows* (full block-column of B), then one local semiring
  SpGEMM.  Moves the same words as staged SUMMA (W = am/√P per the paper's
  Table I) with √P× the panel memory — the right trade at dry-run scale and
  the baseline for §Perf.
* ``summa_ring`` — Cannon-style explicit-exchange ring for square grids:
  pre-skew once, then √P pipelined stages of (fused local semiring multiply ⊕
  ``ppermute`` rotate), the rotate for the next stage batch overlapping the
  in-flight multiply under XLA's latency-hiding scheduler.  The local
  multiply is the backend-dispatched ``spgemm_ring_stages`` op
  (``kernels/spgemm/``, DESIGN.md §2.11); per-stage buffers are reordered
  into canonical k-block order before the single final merge so the
  distributed product is bit-identical to the local ``spgemm`` even under
  the order-dependent overlap-semiring ⊕.  Every ``ppermute`` is accounted:
  ``exchange_words_summa``/``exchange_rounds_summa`` in the returned stats
  are the measured twins of ``bench_comm_model.words_summa`` (the paper's
  Table I W = am/√P term).  Non-square or multi-row-axis grids route loudly
  to ``summa_allgather`` (recorded in stats) instead of asserting.
* ``dist_transitive_reduction`` — Algorithm 2 with the N = R² square computed
  by distributed SUMMA, the row-max reduced with an all-reduce over the grid
  row, and the prune/element-wise steps local (they are "executed in-place so
  that they do not contribute to communication time", §V-D).
  ``summa="ring"`` (or :func:`dist_transitive_reduction_ring`) computes the
  square with the explicit-exchange ring instead of the all-gather panels.
* ``overlap_spgemm_shard_map`` — the pipeline's overlap-stage entry point
  (``PipelineConfig.distribution="shard_map"``): pad + distribute host-local
  A/Aᵀ, ring SUMMA, collect and canonically re-merge — bit-identical ELL
  output and overflow counts to the local ``spgemm`` whenever no per-block
  capacity truncates (the pipeline's static capacities guarantee that for
  the operands; output rows overflowing ``capacity`` truncate identically).
"""

from __future__ import annotations

import dataclasses
import math
from functools import lru_cache, partial
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..obs import schema, validated
from ..obs.trace import span
from .backend import dispatch, record_impl, resolve_backend
from .semiring import INF, Semiring, minplus_orient_semiring as MPSR, tree_where
from .spgemm import spgemm
from .spmat import EllMatrix, NO_COL, from_coo, merge_sorted_rows, prune


@dataclasses.dataclass
class DistEll:
    """A 2D-block-distributed ELL matrix (host-side handle)."""

    mat: EllMatrix  # global arrays, sharded P(row_axes, col_axis)
    mesh: Mesh
    row_axes: tuple  # mesh axes carrying grid rows, e.g. ("pod", "data")
    col_axis: str  # mesh axis carrying grid columns

    @property
    def pr(self) -> int:
        """Process-grid rows (product of the row-axis mesh sizes)."""
        return int(
            jnp.prod(jnp.array([self.mesh.shape[a] for a in self.row_axes]))
        )

    @property
    def pc(self) -> int:
        """Process-grid columns (the ``col_axis`` mesh size)."""
        return self.mesh.shape[self.col_axis]

    @property
    def block_capacity(self) -> int:
        """Per-column-block slot capacity (global capacity / ``pc``)."""
        return self.mat.capacity // self.pc

    def spec(self) -> P:
        """The ``PartitionSpec`` placing rows on ``row_axes``, slots on ``col_axis``."""
        return P(self.row_axes, self.col_axis)


def distribute_ell(
    rows: jnp.ndarray,
    cols: jnp.ndarray,
    vals: Any,
    valid: jnp.ndarray,
    *,
    n_rows: int,
    n_cols: int,
    block_capacity: int,
    semiring: Semiring,
    mesh: Mesh,
    row_axes: Sequence[str] = ("data",),
    col_axis: str = "model",
):
    """Build a DistEll from COO triplets.  Entries are bucketed by global
    column block (col // ceil(n_cols/pc)); each (row, block) gets
    ``block_capacity`` slots.  Returns (DistEll, overflow)."""
    pc = mesh.shape[col_axis]
    cb = -(-n_cols // pc)  # ceil
    blk = jnp.where(valid, cols // cb, 0)
    # rank key: one pseudo-row per (row, block)
    prow = rows * pc + blk
    m2, overflow = from_coo(
        prow,
        cols,
        vals,
        valid,
        n_rows=n_rows * pc,
        n_cols=n_cols,
        capacity=block_capacity,
        semiring=semiring,
    )
    g_cols = m2.cols.reshape(n_rows, pc * block_capacity)
    g_vals = jax.tree.map(
        lambda v: v.reshape((n_rows, pc * block_capacity) + v.shape[2:]), m2.vals
    )
    spec = P(tuple(row_axes), col_axis)
    sharding = NamedSharding(mesh, spec)
    mat = EllMatrix(
        cols=jax.device_put(g_cols, sharding),
        vals=jax.tree.map(lambda x: jax.device_put(x, sharding), g_vals),
        n_cols=n_cols,
    )
    return (
        DistEll(mat=mat, mesh=mesh, row_axes=tuple(row_axes), col_axis=col_axis),
        overflow,
    )


def collect(d: DistEll) -> EllMatrix:
    """Gather a DistEll to a host-local EllMatrix (tests / small outputs)."""
    return jax.tree.map(lambda x: jax.device_get(x), d.mat)


def _local_spgemm_panels(
    a_cols, a_vals, b_cols, b_vals, *, semiring, capacity, n_cols_out,
    b_row_offset=None, row_chunk=None,
):
    """Local multiply of an A panel (n_loc, KA; global m-ids) by a B panel
    (rows a contiguous global row-block starting at ``b_row_offset``, or the
    full m when offset is None)."""
    if b_row_offset is not None:
        nb = b_cols.shape[0]
        rebased = a_cols - b_row_offset
        in_range = (rebased >= 0) & (rebased < nb) & (a_cols >= 0)
        a_cols = jnp.where(in_range, rebased, NO_COL)
    a = EllMatrix(cols=a_cols, vals=a_vals, n_cols=b_cols.shape[0])
    b = EllMatrix(cols=b_cols, vals=b_vals, n_cols=n_cols_out)
    c, ovf = spgemm(a, b, semiring=semiring, capacity=capacity,
                    row_chunk=row_chunk)
    return c.cols, c.vals, ovf


@lru_cache(maxsize=None)
def _allgather_program(
    mesh: Mesh, row_axes: tuple, col_axis: str, semiring: Semiring,
    out_block_capacity: int, n_cols_out: int, row_chunk: int | None,
):
    """Build (and cache) the jitted all-gather SUMMA program for one
    (mesh, axes, semiring, capacity, out-width, chunking) key.

    Same motivation as :func:`_ring_program`: the pre-split code rebuilt
    ``jax.jit(shard_map(f))`` inside ``summa_allgather`` on every call, so
    the fresh closure identity defeated jit's cache and every overlap
    SpGEMM re-traced.  Shapes need not key — jit specializes per shape
    under one cached callable."""
    spec = P(row_axes, col_axis)

    def f(a_cols, a_vals, b_cols, b_vals):
        # Block-row panel of A: local shard already holds the device's column
        # block; gather the rest of the row (grid-column axis).
        # repro: noqa[R003] — XLA-scheduled all-gathers: the analytic
        # exchange_words_summa model covers them; stats are present-and-zero
        # for the explicit-exchange counters by contract.
        ac = jax.lax.all_gather(a_cols, col_axis, axis=1, tiled=True)
        av = jax.tree.map(
            lambda v: jax.lax.all_gather(v, col_axis, axis=1, tiled=True), a_vals
        )
        # Block-column panel of B: gather all grid rows.
        bc = b_cols
        bv = b_vals
        for ax in reversed(row_axes):
            bc = jax.lax.all_gather(bc, ax, axis=0, tiled=True)
            bv = jax.tree.map(
                lambda v: jax.lax.all_gather(v, ax, axis=0, tiled=True), bv
            )
        cc, cv, ovf = _local_spgemm_panels(
            ac, av, bc, bv,
            semiring=semiring,
            capacity=out_block_capacity,
            n_cols_out=n_cols_out,
            row_chunk=row_chunk,
        )
        return cc, cv, jax.lax.psum(ovf, (*row_axes, col_axis))

    return jax.jit(
        shard_map(
            f,
            mesh=mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec, P()),
        )
    )


def summa_allgather(
    a: DistEll, b: DistEll, *, semiring: Semiring, out_block_capacity: int,
    row_chunk: int | None = None, build_only: bool = False,
):
    """C = A ⊗ B (n×m · m×p). Returns (DistEll C, overflow).

    Per-device comm: one all-gather of A along the grid columns
    (words = nnz(A)·pc/P ≈ am/√P, matching Table I) and one all-gather of B
    along the grid rows (words = nnz(B)·pr/P)."""
    n_cols_out = b.mat.n_cols
    fm = _allgather_program(
        a.mesh, a.row_axes, a.col_axis, semiring, out_block_capacity,
        n_cols_out, row_chunk,
    )
    if build_only:
        return fm
    cc, cv, ovf = fm(a.mat.cols, a.mat.vals, b.mat.cols, b.mat.vals)
    cm = EllMatrix(cols=cc, vals=cv, n_cols=n_cols_out)
    return (
        DistEll(mat=cm, mesh=a.mesh, row_axes=a.row_axes,
                col_axis=a.col_axis),
        ovf,
    )


def _skew_a(mat: EllMatrix, pr: int, pc: int) -> EllMatrix:
    """Cannon pre-skew of A (host/global view): block (i, j) ← block
    (i, (i+j) mod pc).  The capacity axis carries the column blocks, so this
    is a per-block-row roll of block slices."""
    n, ktot = mat.cols.shape
    kb = ktot // pc
    nb = n // pr
    i_of_row = jnp.arange(n) // nb  # grid row per matrix row
    j_of_slot = jnp.arange(ktot) // kb
    s_of_slot = jnp.arange(ktot) % kb
    src_j = (i_of_row[:, None] + j_of_slot[None, :]) % pc
    idx = src_j * kb + s_of_slot[None, :]
    take = lambda x: jnp.take_along_axis(
        x, idx.reshape(idx.shape + (1,) * (x.ndim - 2)), axis=1
    )
    return EllMatrix(
        cols=take(mat.cols), vals=jax.tree.map(take, mat.vals), n_cols=mat.n_cols
    )


def _skew_b(mat: EllMatrix, pr: int, pc: int) -> EllMatrix:
    """Cannon pre-skew of B: block (i, j) ← block ((i+j) mod pr, j) — a
    per-block-column roll of row blocks."""
    n, ktot = mat.cols.shape
    kb = ktot // pc
    nb = n // pr
    i_of_row = jnp.arange(n) // nb
    r_in_blk = jnp.arange(n) % nb
    j_of_slot = jnp.arange(ktot) // kb
    src_i = (i_of_row[:, None] + j_of_slot[None, :]) % pr  # (n, ktot)
    src_row = src_i * nb + r_in_blk[:, None]
    take = lambda x: x[src_row, jnp.arange(ktot)[None, :]]
    return EllMatrix(
        cols=take(mat.cols), vals=jax.tree.map(take, mat.vals), n_cols=mat.n_cols
    )


def default_summa_mesh() -> Mesh:
    """2D ``("data", "model")`` grid over all visible devices, pr·pc = P with
    pr the largest divisor of P that is ≤ √P (4 → 2×2, 8 → 2×4, 9 → 3×3).
    Square whenever P is a perfect square — the shape ``summa_ring``'s
    explicit-exchange path needs; otherwise the ring routes to the recorded
    all-gather fallback."""
    devs = jax.devices()
    d = len(devs)
    pr = max(1, int(math.isqrt(d)))
    while d % pr:
        pr -= 1
    pc = d // pr
    return jax.make_mesh((pr, pc), ("data", "model"), devices=devs,
                         axis_types=(AxisType.Auto, AxisType.Auto))


def _slot_words(vals: Any) -> int:
    """Scalar (4-byte) words exchanged per occupied-or-not ELL slot: the
    int32 column id plus every value-leaf element behind it.  Used for the
    per-``ppermute`` word accounting; the analytic twin lives in
    ``benchmarks/bench_comm_model.words_summa``."""
    words = 1
    for leaf in jax.tree.leaves(vals):
        t = 1
        for d in leaf.shape[2:]:
            t *= d
        words += t
    return words


def distribute_ell_blocks(
    mat: EllMatrix,
    *,
    block_capacity: int,
    semiring: Semiring,
    mesh: Mesh,
    row_axes: Sequence[str] = ("data",),
    col_axis: str = "model",
):
    """Distribute an already-built (row-sorted) host EllMatrix into the 2D
    block layout without re-merging its entries.

    Unlike :func:`distribute_ell` this never needs the semiring ⊕ (entries of
    one ELL row are already unique and column-sorted, so a row's slice for
    grid-column block j is contiguous); ``semiring`` only supplies the zero
    fill for empty slots.  Entry → slot: block = col // ceil(n_cols/pc), rank
    = #same-block predecessors in the row, slot = block·capacity + rank.
    Returns (DistEll, overflow) where overflow counts entries beyond
    ``block_capacity`` in some (row, block) — zero whenever ``block_capacity``
    ≥ the source capacity, the pipeline's configuration."""
    pc = mesh.shape[col_axis]
    n, k = mat.cols.shape
    pr = 1
    for ax in row_axes:
        pr *= mesh.shape[ax]
    if n % pr:
        raise ValueError(
            f"distribute_ell_blocks: {n} rows not divisible by grid rows {pr}"
        )
    cb = -(-mat.n_cols // pc)  # ceil: global column ids per grid column
    valid = mat.cols >= 0
    blk = jnp.where(valid, mat.cols // cb, pc)  # pc = dummy block
    # Rank within (row, block): count same-block predecessors per slot.
    tril = jnp.tril(jnp.ones((k, k), dtype=bool), -1)
    rank = jnp.sum((blk[:, :, None] == blk[:, None, :]) & tril[None], axis=2)
    in_cap = valid & (rank < block_capacity)
    overflow = jnp.sum(valid & (rank >= block_capacity)).astype(jnp.int32)
    # One spare trailing column absorbs every masked-out scatter.
    slot = jnp.where(in_cap, blk * block_capacity + rank, pc * block_capacity)
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k))
    g_cols = (
        jnp.full((n, pc * block_capacity + 1), NO_COL, dtype=jnp.int32)
        .at[rows, slot].set(jnp.where(in_cap, mat.cols, NO_COL))[:, :-1]
    )
    zero_full = semiring.zero((n, pc * block_capacity + 1))
    g_vals = jax.tree.map(
        lambda z, v: z.at[rows, slot].set(v)[:, :-1], zero_full, mat.vals
    )
    spec = P(tuple(row_axes), col_axis)
    sharding = NamedSharding(mesh, spec)
    out = EllMatrix(
        cols=jax.device_put(g_cols, sharding),
        vals=jax.tree.map(lambda x: jax.device_put(x, sharding), g_vals),
        n_cols=mat.n_cols,
    )
    return (
        DistEll(mat=out, mesh=mesh, row_axes=tuple(row_axes), col_axis=col_axis),
        overflow,
    )


@lru_cache(maxsize=None)
def _ring_program(
    mesh: Mesh, row_axis: str, col_axis: str, pc: int, g: int,
    semiring: Semiring, out_block_capacity: int, n_cols_out: int,
    backend: str, n_loc: int, nb_b: int, wa_rot: int, wb_rot: int,
):
    """Build (and cache) the jitted shard_map ring program for one
    (mesh, grid, semiring, capacity, backend, shape) key.

    Caching is what makes repeated ``summa_ring`` calls steady-state: the
    old per-call ``jax.jit(shard_map(f))`` re-traced and re-compiled the
    whole ring every call (the pre-split ``BENCH_6.json`` overlap row is
    ~14 s of almost pure jit time; ``BENCH_7.json`` splits that into
    ``compile_ms`` vs steady-state ``ms``), and
    ``dist_transitive_reduction_ring`` paid it once per pass.  ``Semiring`` is a frozen dataclass and ``Mesh``
    hashes by value, so both key directly.

    Returns ``(fm, acct)`` where ``acct`` is the trace-time exchange
    accounting dict: the traced body resets it at the start of every trace
    and increments it next to each ``ppermute``, so after the first call it
    holds the per-device words/rounds of the deterministic schedule —
    cached calls reuse the dict, re-traces recount idempotently."""
    spec = P((row_axis,), col_axis)
    acct = {"words": 0, "rounds": 0}
    op = dispatch("spgemm_ring_stages", backend)
    left = [((t + 1) % pc, t) for t in range(pc)]  # rotate left/up

    def f(a_cols, a_vals, b_cols, b_vals):
        acct["words"] = 0  # fresh trace: recount the schedule
        acct["rounds"] = 0
        i = jax.lax.axis_index(row_axis)
        j = jax.lax.axis_index(col_axis)
        both = (row_axis, col_axis)

        def rotate(ac, av, bc, bv):
            # Trace-time accounting: these counters measure the per-device
            # words of every ppermute issued by one execution's schedule.
            acct["words"] += wa_rot + wb_rot
            acct["rounds"] += 1
            ac = jax.lax.ppermute(ac, col_axis, left)
            av = jax.tree.map(lambda v: jax.lax.ppermute(v, col_axis, left), av)
            bc = jax.lax.ppermute(bc, row_axis, left)
            bv = jax.tree.map(lambda v: jax.lax.ppermute(v, row_axis, left), bv)
            return ac, av, bc, bv

        cur = (a_cols, a_vals, b_cols, b_vals)
        chunks_cols, chunks_vals = [], []
        ovf = jax.lax.pcast(jnp.int32(0), both, to="varying")
        s = 0
        while s < pc:
            sc = min(g, pc - s)
            with span("SpGEMM", kind="phase", phase="ring_stage", s=s,
                      stages=sc):
                panels = [cur]
                for _ in range(sc - 1):
                    cur = rotate(*cur)
                    panels.append(cur)
                st_a_cols = jnp.stack([p[0] for p in panels])
                st_a_vals = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *[p[1] for p in panels]
                )
                st_b_cols = jnp.stack([p[2] for p in panels])
                st_b_vals = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *[p[3] for p in panels]
                )
                offsets = (((i + j + s + jnp.arange(sc)) % pc) * nb_b).astype(
                    jnp.int32
                )
                if s + sc < pc:
                    # Rotation feeding the NEXT batch, issued before the
                    # batch's multiply consumes its own (already stacked)
                    # panels — XLA is free to overlap the exchange with the
                    # in-flight compute.
                    cur = rotate(*cur)
                cc, cv, so = op(
                    offsets, st_a_cols, st_a_vals, st_b_cols, st_b_vals,
                    semiring=semiring, capacity=out_block_capacity,
                    n_cols_out=n_cols_out,
                )
            chunks_cols.append(cc)
            chunks_vals.append(cv)
            ovf = ovf + so
            s += sc
        st_cols = jnp.concatenate(chunks_cols, axis=0)  # (pc, n_loc, cap)
        st_vals = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *chunks_vals
        )
        # Canonical reorder: buffer q ← stage producing k-block q, so the
        # final merge sees candidates in ascending global-column order — the
        # exact sequence the local spgemm's a-slot-ascending expansion feeds
        # merge_sorted_rows (bit-parity for order-dependent ⊕).
        order = (jnp.arange(pc) - (i + j)) % pc
        st_cols = jnp.take(st_cols, order, axis=0)
        st_vals = jax.tree.map(lambda v: jnp.take(v, order, axis=0), st_vals)
        merged_cols = jnp.moveaxis(st_cols, 0, 1).reshape(
            n_loc, pc * out_block_capacity
        )
        merged_vals = jax.tree.map(
            lambda v: jnp.moveaxis(v, 0, 1).reshape(
                (n_loc, pc * out_block_capacity) + v.shape[3:]
            ),
            st_vals,
        )
        with span("SpGEMM", kind="phase", phase="stage_merge"):
            mc, mv, mo = merge_sorted_rows(
                merged_cols, merged_vals,
                capacity=out_block_capacity, semiring=semiring,
            )
        return mc, mv, jax.lax.psum(ovf + mo, both)

    fm = jax.jit(
        shard_map(
            f, mesh=mesh, in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec, P()),
        )
    )
    return fm, acct


def summa_ring(
    a: DistEll,
    b: DistEll,
    *,
    semiring: Semiring,
    out_block_capacity: int,
    backend: str = "auto",
    stages_per_call: int = 4,
    strict: bool = False,
):
    """Explicit-exchange Cannon ring SUMMA.  Returns (DistEll C, overflow,
    stats).

    Square single-row-axis grids run the ring: one host-side pre-skew, then
    pc stages grouped into batches of ``stages_per_call``.  Each batch is one
    call of the backend-dispatched ``spgemm_ring_stages`` op (the fused
    Pallas kernel keeps panels and stage outputs VMEM-resident for the whole
    batch); between batches a single ``ppermute`` rotation runs concurrently
    with the in-flight multiply under XLA's latency-hiding scheduler — the
    compute/communication overlap the paper attributes to staged SUMMA.

    Bit-parity: stage s on device (i, j) multiplies k-block (i+j+s) mod pc —
    a device-dependent order under which the overlap semiring's ⊕ (keep-first
    position pairs) is NOT invariant.  The op therefore returns per-stage
    buffers which are reordered into canonical ascending-k order and merged
    **once**, reproducing the exact candidate sequence of the local
    ``spgemm`` — bit-identical values and overflow counts.

    Stats: ``exchange_words_summa``/``exchange_rounds_summa`` are counted at
    trace time next to each ``ppermute`` (measured, per device); CI
    cross-checks them against ``bench_comm_model.words_summa``.
    ``spgemm_hbm_round_trips`` records what the resolved backend actually
    pays (the fused kernel: ceil(pc/stages_per_call); the per-stage
    reference: pc).

    Non-square or multi-row-axis grids cannot form the ring; they raise when
    ``strict`` and otherwise route to :func:`summa_allgather`, recording
    ``summa_algorithm="allgather_fallback"`` + the reason, with the exchange
    stats present and zero (that path has no explicit exchanges)."""
    mesh = a.mesh
    fallback_reason = None
    if len(a.row_axes) != 1:
        fallback_reason = f"multi-axis grid rows {a.row_axes}"
    else:
        (row_axis,) = a.row_axes
        col_axis = a.col_axis
        pr, pc = mesh.shape[row_axis], mesh.shape[col_axis]
        if pr != pc:
            fallback_reason = f"non-square grid {pr}x{pc}"
    if fallback_reason is not None:
        if strict:
            raise ValueError(
                "summa_ring requires a square grid with a single row axis: "
                + fallback_reason
            )
        out, ovf = summa_allgather(
            a, b, semiring=semiring, out_block_capacity=out_block_capacity
        )
        return out, ovf, validated({
            "summa_algorithm": "allgather_fallback",
            "summa_fallback_reason": fallback_reason,
            **schema.zero_defaults("summa_exchange"),
        }, context="summa_allgather_fallback",
            require_groups=("summa_exchange",))

    spec = P((row_axis,), col_axis)
    n_cols_out = b.mat.n_cols
    n_total = a.mat.cols.shape[0]
    m_total = b.mat.cols.shape[0]
    n_loc = n_total // pr
    nb_b = m_total // pr  # B block row count == A panel's rebased id range
    ka = a.block_capacity
    kb = b.block_capacity
    # Words moved by one rotation of both panels (per device, 4-byte scalars).
    wa_rot = n_loc * ka * _slot_words(a.mat.vals)
    wb_rot = nb_b * kb * _slot_words(b.mat.vals)

    resolved = resolve_backend(backend)
    with span("SpGEMM", kind="phase", phase="skew"):
        a_sk = _skew_a(a.mat, pr, pc)
        b_sk = _skew_b(b.mat, pr, pc)
    g = max(1, min(stages_per_call, pc))
    fm, acct = _ring_program(
        mesh, row_axis, col_axis, pc, g, semiring, out_block_capacity,
        n_cols_out, resolved, n_loc, nb_b, wa_rot, wb_rot,
    )
    with span("SpGEMM", kind="phase", phase="ring", pc=pc,
              stages_per_call=g) as sp:
        cc, cv, ovf = sp.set_output(
            fm(a_sk.cols, a_sk.vals, b_sk.cols, b_sk.vals)
        )
    cm = EllMatrix(cols=cc, vals=cv, n_cols=n_cols_out)
    fused = False
    impl = "reference"
    if resolved == "pallas":
        from ..kernels.spgemm.ops import stage_impl

        sds = jax.ShapeDtypeStruct
        chunk = min(g, pc)
        fused, impl = stage_impl(
            sds((chunk, n_loc, ka), jnp.int32),
            jax.tree.map(lambda v: sds((chunk, n_loc, ka) + v.shape[2:],
                                       v.dtype), a.mat.vals),
            sds((chunk, nb_b, kb), jnp.int32),
            jax.tree.map(lambda v: sds((chunk, nb_b, kb) + v.shape[2:],
                                       v.dtype), b.mat.vals),
            capacity=out_block_capacity, semiring=semiring,
        )
    # the cached ring program dispatches the op only when first traced
    record_impl("spgemm_ring_stages", impl)
    from ..kernels.spgemm.ops import hbm_round_trips

    stats = validated({
        "summa_algorithm": "ring",
        "summa_stages": pc,
        "summa_backend": resolved if fused else "reference",
        "exchange_words_summa": acct["words"],
        "exchange_rounds_summa": acct["rounds"],
        "spgemm_hbm_round_trips": hbm_round_trips(pc, g) if fused else pc,
        "spgemm_hbm_round_trips_reference": pc,
    }, context="summa_ring", require_groups=("summa_exchange",))
    return (
        DistEll(mat=cm, mesh=mesh, row_axes=a.row_axes, col_axis=col_axis),
        ovf,
        stats,
    )


def overlap_spgemm_shard_map(
    a: EllMatrix,
    b: EllMatrix,
    *,
    semiring: Semiring,
    operand_semiring: Semiring,
    capacity: int,
    mesh: Mesh | None = None,
    backend: str = "auto",
    stages_per_call: int = 4,
):
    """Distributed C = A ⊗ B for host-local ELL operands — the overlap
    stage's ``distribution="shard_map"`` path (``Pipeline`` calls this for
    the candidate SpGEMM, tests call it directly for parity).

    Pads both operands' rows up to a multiple of the grid rows (empty rows),
    block-distributes them with :func:`distribute_ell_blocks` at their full
    source capacities (so distribution itself can never overflow), runs
    :func:`summa_ring`, then collects and re-merges the block outputs into a
    host EllMatrix of ``capacity`` slots per row.  Bit-identical to
    ``spgemm(a, b, semiring=semiring, capacity=capacity)`` — values and
    overflow count — whenever no single column block contributes more than
    ``capacity`` entries to one output row (the final merge then sees the
    same candidate sequence the local expansion feeds it).

    ``operand_semiring`` supplies the zero fill for the operands' empty
    slots (the operands' value trees differ from the output's).  Returns
    (EllMatrix, overflow, stats) with the :func:`summa_ring` stats passed
    through."""
    if mesh is None:
        mesh = default_summa_mesh()
    if "model" not in mesh.axis_names or len(mesh.axis_names) < 2:
        raise ValueError(
            "overlap_spgemm_shard_map needs a 2D mesh with a 'model' column "
            f"axis; got axes {mesh.axis_names}.  Build one with "
            "default_summa_mesh() or launch.mesh.make_test_mesh."
        )
    col_axis = "model"
    row_axes = tuple(
        ax for ax in ("pod", "data") if ax in mesh.axis_names
    ) or (next(ax for ax in mesh.axis_names if ax != col_axis),)
    pr = 1
    for ax in row_axes:
        pr *= mesh.shape[ax]

    def pad_rows(mat: EllMatrix) -> tuple[EllMatrix, int]:
        n = mat.cols.shape[0]
        n_pad = -(-n // pr) * pr
        if n_pad == n:
            return mat, n
        pad = n_pad - n
        cols = jnp.concatenate(
            [mat.cols,
             jnp.full((pad, mat.cols.shape[1]), NO_COL, dtype=jnp.int32)]
        )
        zero = operand_semiring.zero((pad, mat.cols.shape[1]))
        vals = jax.tree.map(
            lambda v, z: jnp.concatenate([v, z]), mat.vals, zero
        )
        return EllMatrix(cols=cols, vals=vals, n_cols=mat.n_cols), n

    a_pad, n_rows = pad_rows(a)
    b_pad, _ = pad_rows(b)
    with span("SpGEMM", kind="phase", phase="distribute") as sp:
        da, ovf_da = distribute_ell_blocks(
            a_pad, block_capacity=a.capacity, semiring=operand_semiring,
            mesh=mesh, row_axes=row_axes, col_axis=col_axis,
        )
        db, ovf_db = distribute_ell_blocks(
            b_pad, block_capacity=b.capacity, semiring=operand_semiring,
            mesh=mesh, row_axes=row_axes, col_axis=col_axis,
        )
        sp.set_output((da.mat.cols, db.mat.cols))
    cd, ovf_ring, stats = summa_ring(
        da, db, semiring=semiring, out_block_capacity=capacity,
        backend=backend, stages_per_call=stages_per_call,
    )
    with span("SpGEMM", kind="phase", phase="collect_merge"):
        g = collect(cd)
        mc, mv, mo = merge_sorted_rows(
            g.cols, g.vals, capacity=capacity, semiring=semiring
        )
    out = EllMatrix(
        cols=mc[:n_rows],
        vals=jax.tree.map(lambda v: v[:n_rows], mv),
        n_cols=b.n_cols,
    )
    overflow = (
        jnp.int32(ovf_da) + jnp.int32(ovf_db) + jnp.int32(ovf_ring)
        + jnp.int32(mo)
    )
    return out, overflow, stats


# ---------------------------------------------------------------------------
# Distributed transitive reduction (Algorithm 2 on the mesh).
# ---------------------------------------------------------------------------


def dist_transitive_reduction(
    r: DistEll,
    fuzz: float = 200.0,
    *,
    n_block_capacity: int | None = None,
    max_iters: int = 10,
    fused: bool = False,
    row_chunk: int | None = None,
    build_only: bool = False,
    summa: str = "allgather",
):
    """Distributed Algorithm 2.  ``fused=True`` uses the sampled square
    (beyond-paper; N restricted to R's pattern — the A panel gather still
    happens, but no B-panel pattern growth and no stage sort).

    ``summa="ring"`` computes the N = R² square with the explicit-exchange
    ring (:func:`dist_transitive_reduction_ring`) instead of the all-gather
    panels; incompatible with ``fused``/``row_chunk``/``build_only`` (the
    ring iterates host-side so each iteration's exchanges are accounted)."""
    if summa not in ("allgather", "ring"):
        raise ValueError(f"unknown summa variant {summa!r}")
    if summa == "ring":
        if fused or build_only or row_chunk is not None:
            raise ValueError(
                "summa='ring' supports neither fused nor row_chunk nor "
                "build_only"
            )
        out, iters, nnz_f, _ = dist_transitive_reduction_ring(
            r, fuzz, n_block_capacity=n_block_capacity, max_iters=max_iters
        )
        return out, iters, nnz_f
    kb = r.block_capacity
    if n_block_capacity is None:
        n_block_capacity = min(kb * kb, 4 * kb)
    n_total = r.mat.n_cols
    fm = _tr_program(
        r.mesh, r.row_axes, r.col_axis, n_total, n_block_capacity,
        float(fuzz), max_iters, fused, row_chunk,
    )
    if build_only:
        return fm
    cols, vals, iters, nnz_f = fm(r.mat.cols, r.mat.vals)
    out = DistEll(
        mat=EllMatrix(cols=cols, vals=vals, n_cols=n_total),
        mesh=r.mesh,
        row_axes=r.row_axes,
        col_axis=r.col_axis,
    )
    return out, iters, nnz_f


@lru_cache(maxsize=None)
def _tr_program(
    mesh: Mesh, row_axes: tuple, col_axis: str, n_total: int,
    n_block_capacity: int, fuzz: float, max_iters: int, fused: bool,
    row_chunk: int | None,
):
    """Build (and cache) the jitted all-gather transitive-reduction program
    (the full ``while_loop`` fixed-point of Algorithm 2) for one
    (mesh, axes, capacity, fuzz, iteration-policy) key.

    Pre-split, ``dist_transitive_reduction`` rebuilt ``jax.jit(shard_map)``
    per call — every TR invocation in the cell pipeline re-traced the whole
    fixed-point loop (the R001/PR 7 hazard class)."""
    spec = P(row_axes, col_axis)

    def f(r_cols, r_vals):
        def nnz_of(cols):
            # repro: noqa[R003] — scalar nnz tally for the fixed-point
            # test, not a data exchange; excluded from the words model.
            return jax.lax.psum(
                jnp.sum(cols >= 0).astype(jnp.int32), (*row_axes, col_axis)
            )

        def body(carry):
            r_cols, r_vals, prev, cur, it = carry
            # --- N = R² (lines 3-4): allgather panels, local multiply ---
            # repro: noqa[R003] — XLA-scheduled all-gather variant:
            # unaccounted by design (summa='ring' is the measured path);
            # exchange stats stay present-and-zero per the schema contract.
            ac = jax.lax.all_gather(r_cols, col_axis, axis=1, tiled=True)
            av = jax.lax.all_gather(r_vals, col_axis, axis=1, tiled=True)
            bc, bv = r_cols, r_vals
            for ax in reversed(row_axes):
                bc = jax.lax.all_gather(bc, ax, axis=0, tiled=True)
                bv = jax.lax.all_gather(bv, ax, axis=0, tiled=True)
            a_loc = EllMatrix(cols=ac, vals=av, n_cols=n_total)
            b_loc = EllMatrix(cols=bc, vals=bv, n_cols=n_total)
            if fused:
                from .spgemm import spgemm_masked

                mask = EllMatrix(cols=r_cols, vals=r_vals, n_cols=n_total)
                n_at_r = spgemm_masked(a_loc, b_loc, mask, semiring=MPSR,
                                       row_chunk=row_chunk)
                got, found = n_at_r.vals, mask.mask
            else:
                n_loc, _ = spgemm(
                    a_loc, b_loc, semiring=MPSR, capacity=n_block_capacity,
                    row_chunk=row_chunk,
                )
                got, found = n_loc.lookup(MPSR, r_cols)
            # --- M = rowmax + fuzz (lines 5-7): local max, all-reduce row ---
            vals_m = jnp.where(jnp.isfinite(r_vals), r_vals, -INF)
            vals_m = jnp.where((r_cols >= 0)[:, :, None], vals_m, -INF)
            local_max = jnp.max(vals_m, axis=(1, 2))
            row_max = jax.lax.pmax(local_max, col_axis) + fuzz
            # --- I = M ≥ N with orientation checks (line 8) ---
            trans = (
                (got <= row_max[:, None, None])
                & jnp.isfinite(got)
                & found[:, :, None]
                & jnp.isfinite(r_vals)
            )
            # --- prune (line 9), local/in-place per §V-D ---
            new_vals = jnp.where(trans, INF, r_vals)
            dead = ~jnp.any(jnp.isfinite(new_vals), axis=-1) & (r_cols >= 0)
            pruned = prune(
                EllMatrix(cols=r_cols, vals=new_vals, n_cols=n_total), dead, MPSR
            )
            return (pruned.cols, pruned.vals, cur, nnz_of(pruned.cols), it + 1)

        def cond(carry):
            _, _, prev, cur, it = carry
            return (cur != prev) & (it < max_iters)

        init = (r_cols, r_vals, jnp.int32(-1), nnz_of(r_cols), jnp.int32(0))
        r_cols, r_vals, _, nnz_f, iters = jax.lax.while_loop(cond, body, init)
        return r_cols, r_vals, iters, nnz_f

    return jax.jit(
        shard_map(
            f, mesh=mesh, in_specs=(spec, spec),
            out_specs=(spec, spec, P(), P()),
        )
    )


@lru_cache(maxsize=None)
def _tr_prune_program(
    mesh: Mesh, row_axes: tuple, col_axis: str, n_total: int, fuzz: float,
):
    """Build (and cache) the jitted prune step of the ring transitive
    reduction (lines 5-9 of Algorithm 2, local per §V-D).

    The host-side pass loop of :func:`dist_transitive_reduction_ring` calls
    this program once per pass; pre-split it rebuilt ``jax.jit(shard_map)``
    every pass, so each TR pass paid a full re-trace on top of the ring."""
    spec = P(row_axes, col_axis)

    def prune_step(r_cols, r_vals, n_cols_blk, n_vals_blk):
        n_loc = EllMatrix(cols=n_cols_blk, vals=n_vals_blk, n_cols=n_total)
        got, found = n_loc.lookup(MPSR, r_cols)
        vals_m = jnp.where(jnp.isfinite(r_vals), r_vals, -INF)
        vals_m = jnp.where((r_cols >= 0)[:, :, None], vals_m, -INF)
        local_max = jnp.max(vals_m, axis=(1, 2))
        # repro: noqa[R003] — scalar row-max pmax + nnz psum: convergence
        # bookkeeping of the §V-D local prune, not a data exchange; the
        # ring program accounts every word that actually rotates.
        row_max = jax.lax.pmax(local_max, col_axis) + fuzz
        trans = (
            (got <= row_max[:, None, None])
            & jnp.isfinite(got)
            & found[:, :, None]
            & jnp.isfinite(r_vals)
        )
        new_vals = jnp.where(trans, INF, r_vals)
        dead = ~jnp.any(jnp.isfinite(new_vals), axis=-1) & (r_cols >= 0)
        pruned = prune(
            EllMatrix(cols=r_cols, vals=new_vals, n_cols=n_total), dead, MPSR
        )
        nnz = jax.lax.psum(
            jnp.sum(pruned.cols >= 0).astype(jnp.int32), (*row_axes, col_axis)
        )
        return pruned.cols, pruned.vals, nnz

    return jax.jit(
        shard_map(
            prune_step, mesh=mesh, in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec, P()),
        )
    )


def dist_transitive_reduction_ring(
    r: DistEll,
    fuzz: float = 200.0,
    *,
    n_block_capacity: int | None = None,
    max_iters: int = 10,
    backend: str = "auto",
    stages_per_call: int = 4,
):
    """Distributed Algorithm 2 with the N = R² square on the explicit
    exchange ring.  Returns (DistEll, iters, nnz, stats).

    Unlike the all-gather variant's single ``lax.while_loop``, the iteration
    loop runs host-side: each pass is one :func:`summa_ring` (whose
    ``ppermute`` exchanges are measured per call) followed by a jitted
    shard_map prune step — the lookup / fuzzed row-max pmax / prune pipeline
    of lines 5-9, local per §V-D.  Host-driving the loop is what lets the
    exchange accounting see every rotation; the fixed-point test (nnz
    unchanged) costs one scalar device→host read per pass.  Stats accumulate
    ``exchange_words_summa``/``exchange_rounds_summa`` across passes (zero
    when the grid routes to the all-gather fallback)."""
    mesh = r.mesh
    row_axes, col_axis = r.row_axes, r.col_axis
    kb = r.block_capacity
    if n_block_capacity is None:
        n_block_capacity = min(kb * kb, 4 * kb)
    n_total = r.mat.n_cols
    pf = _tr_prune_program(mesh, row_axes, col_axis, n_total, float(fuzz))

    cur = r
    nnz_cur = int(jnp.sum(r.mat.cols >= 0))
    prev = -1
    it = 0
    stats = {**schema.zero_defaults("summa_exchange"),
             "summa_algorithm": None}
    while nnz_cur != prev and it < max_iters:
        n_sq, _, st = summa_ring(
            cur, cur, semiring=MPSR, out_block_capacity=n_block_capacity,
            backend=backend, stages_per_call=stages_per_call,
        )
        cols, vals, nnz_new = pf(
            cur.mat.cols, cur.mat.vals, n_sq.mat.cols, n_sq.mat.vals
        )
        cur = DistEll(
            mat=EllMatrix(cols=cols, vals=vals, n_cols=n_total),
            mesh=mesh, row_axes=row_axes, col_axis=col_axis,
        )
        stats["exchange_words_summa"] += st["exchange_words_summa"]
        stats["exchange_rounds_summa"] += st["exchange_rounds_summa"]
        stats["summa_algorithm"] = st["summa_algorithm"]
        prev = nnz_cur
        nnz_cur = int(nnz_new)
        it += 1
    return cur, it, nnz_cur, stats
