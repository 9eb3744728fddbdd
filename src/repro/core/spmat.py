"""Static-capacity sparse matrices for TPU (ELL layout).

CombBLAS stores dynamically-sized CSC/DCSC blocks; XLA/TPU require static
shapes.  We therefore store a sparse ``n_rows × n_cols`` matrix as

  * ``cols``: ``(n_rows, capacity)`` int32, column index per slot, ``-1`` empty,
    **sorted ascending within each row** (invalid slots pushed to the end);
  * ``vals``: an arbitrary value pytree whose leaves have leading shape
    ``(n_rows, capacity, ...)`` — semiring values live here.

The capacity is semantically justified by the pipeline itself: k-mer frequency
is capped (max freq u), so A's columns have ≤u entries; overlap/string matrices
have bounded row density (paper Table III).  Overflow is *surfaced* via an
``overflow`` counter rather than silently dropped.

All constructors run under jit with static ``n_rows``/``n_cols``/``capacity``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .semiring import Semiring, tree_where

# numpy scalar (not a jnp array) so code using it can be traced inside Pallas
# kernel bodies — jax inlines numpy scalars as jaxpr literals where a device
# array would be a captured constant, which pallas_call rejects.
NO_COL = np.int32(-1)


def next_pow2(x: int) -> int:
    """Smallest power of two ≥ max(x, 1) — the shared bucket-padding policy
    (compacted alignment driver, contig-stage staging): pow-2 padding keeps
    the number of distinct compiled shapes logarithmic in the live count."""
    return 1 << max(0, int(x) - 1).bit_length()


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["cols", "vals"],
    meta_fields=["n_cols"],
)
@dataclasses.dataclass
class EllMatrix:
    """ELL sparse matrix: see module docstring.  A pytree (jit-transparent)."""

    cols: jnp.ndarray  # (n_rows, capacity) int32; -1 = empty; row-sorted
    vals: Any  # pytree, leaves (n_rows, capacity, ...)
    n_cols: int  # static

    @property
    def n_rows(self) -> int:
        return self.cols.shape[0]

    @property
    def capacity(self) -> int:
        return self.cols.shape[1]

    @property
    def mask(self) -> jnp.ndarray:
        return self.cols >= 0

    def nnz(self) -> jnp.ndarray:
        return jnp.sum(self.mask)

    def row_nnz(self) -> jnp.ndarray:
        return jnp.sum(self.mask, axis=1)

    def to_dense(self, semiring: Semiring) -> Any:
        """Densify values (absent -> semiring zero). Returns pytree of
        leaves with shape (n_rows, n_cols, ...)."""
        n, k = self.cols.shape
        # masked slots scatter to a dummy column so they never race
        safe = jnp.where(self.mask, self.cols, self.n_cols)
        rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k))
        zero = semiring.zero((n, self.n_cols + 1))

        def scat(z, v):
            return z.at[rows, safe].set(v)[:, : self.n_cols]

        return jax.tree.map(scat, zero, self.vals)

    def lookup(self, semiring: Semiring, query_cols: jnp.ndarray):
        """Row-wise sorted lookup: for each (i, q) return the value at
        ``self[i, query_cols[i, q]]`` (semiring zero if absent).

        query_cols: (n_rows, Q) int32 (may contain -1).
        Returns (vals pytree with leading (n_rows, Q), found mask).
        """
        n, k = self.cols.shape
        big = jnp.where(self.mask, self.cols, jnp.int32(2**30))
        q = query_cols
        pos = jax.vmap(jnp.searchsorted)(big, jnp.where(q >= 0, q, 0))
        pos = jnp.clip(pos, 0, k - 1)
        hit_col = jnp.take_along_axis(big, pos, axis=1)
        found = (hit_col == q) & (q >= 0)
        got = jax.tree.map(
            lambda v: jnp.take_along_axis(
                v, pos.reshape(pos.shape + (1,) * (v.ndim - 2)), axis=1
            ),
            self.vals,
        )
        zero = semiring.zero(q.shape)
        return tree_where(found, got, zero), found


def map_row_blocks(fn, inputs: Any, *, n_rows: int, row_chunk: int,
                   fills: Any = None):
    """Map ``fn`` over fixed-size row blocks of ``inputs`` with ``lax.map``.

    The shared chunking combinator behind ``spgemm``'s row-chunked paths and
    the pipeline's compacted alignment driver: it bounds peak memory of a
    per-row computation by processing ``row_chunk`` rows at a time while
    tracing ``fn`` exactly once.

    Args:
      fn: ``block -> (row_out, aux)`` where ``block`` is ``inputs`` restricted
        to ``row_chunk`` rows, ``row_out`` is a pytree whose leaves have
        leading dim ``row_chunk``, and ``aux`` is any per-block pytree
        (``None`` if unused).
      inputs: pytree of arrays with leading dim ``n_rows``.
      fills: pytree matching ``inputs`` of scalar pad values for the rows
        padded onto the last block (default 0 everywhere).

    Returns ``(row_out, aux)`` with ``row_out`` leaves reassembled to leading
    dim ``n_rows`` and ``aux`` leaves stacked over the ``ceil(n_rows /
    row_chunk)`` blocks (callers reduce, e.g. summing overflow counters).
    """
    nb = -(-n_rows // row_chunk)
    pad = nb * row_chunk - n_rows
    if fills is None:
        fills = jax.tree.map(lambda _: 0, inputs)

    def blockify(x, fill):
        xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                     constant_values=fill)
        return xp.reshape((nb, row_chunk) + x.shape[1:])

    blocks = jax.tree.map(blockify, inputs, fills)
    row_out, aux = jax.lax.map(fn, blocks)
    merged = jax.tree.map(
        lambda v: v.reshape((nb * row_chunk,) + v.shape[2:])[:n_rows], row_out
    )
    return merged, aux


def _split_planes(tree: Any, lead: int):
    """Value leaves → flags-shaped planes: every leaf of shape
    ``lead_shape + tail`` becomes ``prod(tail)`` arrays of ``lead_shape``.
    Returns ``(planes, join)``; ``join(planes)`` rebuilds the pytree.

    Narrow trailing axes (the overlap semiring's position pairs) under the
    row sorts, gathers and scans of the candidate merge make XLA's TPU
    compile time grow with the array size; 2-D planes do not."""
    leaves, treedef = jax.tree.flatten(tree)
    tails = [leaf.shape[lead:] for leaf in leaves]
    sizes = [int(np.prod(t)) for t in tails]

    def split(t):
        out = []
        for leaf, tail, size in zip(jax.tree.leaves(t), tails, sizes):
            if not tail:
                out.append(leaf)
                continue
            flat = leaf.reshape(leaf.shape[:lead] + (size,))
            out += [flat[..., i] for i in range(size)]
        return out

    def join(planes):
        out, i = [], 0
        for tail, size in zip(tails, sizes):
            if not tail:
                out.append(planes[i])
                i += 1
                continue
            stacked = jnp.stack(planes[i : i + size], axis=-1)
            out.append(stacked.reshape(stacked.shape[:lead] + tail))
            i += size
        return jax.tree.unflatten(treedef, out)

    return split(tree), split, join


def _segmented_combine(flags: jnp.ndarray, vals: Any, add, axis: int = 0) -> Any:
    """Inclusive segmented scan along ``axis``: combine vals within runs
    (flags==True starts a new run).  Returns scanned vals (run-prefix sums
    under ``add``); the last element of each run holds the run total."""
    planes, split, join = _split_planes(vals, flags.ndim)

    def op(x, y):
        fx, px = x
        fy, py = y
        vx, vy = join(px), join(py)
        return (fx | fy, split(tree_where(fy, vy, add(vx, vy))))

    if flags.ndim > 1:
        # Rows (the candidate merge): the same doubling steps unrolled, each
        # shifting the row by a static 2^i.  associative_scan's strided
        # slices along rows of sorted candidates took XLA's TPU code
        # generation minutes (seconds when the rows came from gathers).
        q = flags.shape[axis]
        idx = jax.lax.broadcasted_iota(jnp.int32, flags.shape, axis)
        f, p = flags, planes
        for i in range(max(1, (q - 1).bit_length())):
            s = 1 << i

            def back_by(x, s=s):
                return jnp.concatenate(
                    [jax.lax.slice_in_dim(x, 0, s, axis=axis),
                     jax.lax.slice_in_dim(x, 0, q - s, axis=axis)], axis=axis)

            back = idx >= s
            fb, vb = op((back_by(f), [back_by(x) for x in p]), (f, p))
            f = jnp.where(back, fb, f)
            p = [jnp.where(back, b, x) for b, x in zip(vb, p)]
        return join(p)
    # One long axis (from_coo): log2(n) doubling steps in a loop, each
    # combining with the element 2^i back.  associative_scan unrolls its
    # levels over slices whose XLA TPU compile time grows with the length
    # (minutes past a million entries).  Same result for an associative add.
    n = flags.shape[0]
    idx = jnp.arange(n)

    def step(i, carry):
        f, p = carry
        back = idx >= (1 << i)
        src = jnp.where(back, idx - (1 << i), 0)
        fb, vb = op((f[src], [q[src] for q in p]), (f, p))
        return (jnp.where(back, fb, f),
                [jnp.where(back, b, q) for b, q in zip(vb, p)])

    _, out = jax.lax.fori_loop(0, max(1, (n - 1).bit_length()), step,
                               (flags, planes))
    return join(out)


def _rank_in_row_sorted(rows_sorted: jnp.ndarray, kept: jnp.ndarray) -> jnp.ndarray:
    """Given row ids sorted ascending and a kept mask, rank of each kept entry
    among kept entries of the same row (0-based)."""
    c = jnp.cumsum(kept.astype(jnp.int32))
    base_idx = jnp.searchsorted(rows_sorted, rows_sorted, side="left")
    c_base = jnp.take(c, base_idx)
    kept_base = jnp.take(kept.astype(jnp.int32), base_idx)
    return c - c_base + kept_base - 1


@partial(jax.jit, static_argnames=("n_rows", "n_cols", "capacity", "semiring"))
@jax.named_scope("from_coo")
def from_coo(
    rows: jnp.ndarray,
    cols: jnp.ndarray,
    vals: Any,
    valid: jnp.ndarray,
    *,
    n_rows: int,
    n_cols: int,
    capacity: int,
    semiring: Semiring,
):
    """Build an EllMatrix from COO triplets, merging duplicate (row, col)
    entries with ``semiring.add`` (merge order = input order, stable).

    Returns (EllMatrix, overflow_count)."""
    e = rows.shape[0]
    rkey = jnp.where(valid, rows, n_rows)
    ckey = jnp.where(valid, cols, n_cols)
    if (n_rows + 1) * (n_cols + 1) <= 2**32:
        # one uint32 (row, col) key: XLA's TPU compile time of a long 1-D
        # sort grows with its key count (minutes for two past a million)
        key = (rkey.astype(jnp.uint32) * np.uint32(n_cols + 1)
               + ckey.astype(jnp.uint32))
        _, order = jax.lax.sort((key, jnp.arange(e, dtype=jnp.int32)),
                                num_keys=1, is_stable=True)
    else:
        order = jnp.lexsort((ckey, rkey))
    rs, cs = rkey[order], ckey[order]
    vs = jax.tree.map(lambda x: x[order], vals)
    valid_s = valid[order]

    prev_r = jnp.concatenate([jnp.full((1,), -2, rs.dtype), rs[:-1]])
    prev_c = jnp.concatenate([jnp.full((1,), -2, cs.dtype), cs[:-1]])
    new_run = (rs != prev_r) | (cs != prev_c)
    scanned = _segmented_combine(new_run, vs, semiring.add, axis=0)
    next_new = jnp.concatenate([new_run[1:], jnp.ones((1,), bool)])
    kept = next_new & valid_s  # last element of each (row,col) run

    rank = _rank_in_row_sorted(rs, kept)
    in_cap = kept & (rank < capacity)
    overflow = jnp.sum(kept & (rank >= capacity))

    # Masked entries scatter to a dummy row (n_rows) so they can never race
    # with a live write.
    safe_r = jnp.where(in_cap, rs, n_rows)
    safe_k = jnp.where(in_cap, rank, 0)
    out_cols = jnp.full((n_rows + 1, capacity), NO_COL)
    out_cols = out_cols.at[safe_r, safe_k].set(cs.astype(jnp.int32))[:n_rows]
    zero = semiring.zero((n_rows + 1, capacity))

    def scat(z, v):
        return z.at[safe_r, safe_k].set(v)[:n_rows]

    out_vals = jax.tree.map(scat, zero, scanned)
    return EllMatrix(cols=out_cols, vals=out_vals, n_cols=n_cols), overflow


@jax.named_scope("merge_sorted_rows")
def merge_sorted_rows(
    cand_cols: jnp.ndarray, cand_vals: Any, *, capacity: int, semiring: Semiring
):
    """Per-row candidate merge: given (n, Q) candidate columns (−1 = invalid)
    and value pytree (n, Q, ...), sort each row by column, ⊕-combine duplicates
    and compact into an ELL row of ``capacity`` slots.

    The workhorse of the local SpGEMM.  Returns (cols, vals, overflow).  Its
    device work runs under the ``merge_sorted_rows`` named scope, in three
    parts (``sort``, ``combine``, ``compact``) that a profiler trace times
    apart; the scopes are op metadata and change no computation.

    ``sort`` carries the value planes through stable row sorts keyed on the
    column, one (column, plane) sort per plane: equal columns keep their
    candidate order in each, so every plane gets the same permutation, on
    which an order-dependent ⊕ (the overlap semiring's first position pairs)
    relies.  Applying an argsort's order by gathers along the rows gives the
    same result at many times the TPU time.  XLA's TPU compiler lays a row
    sort of more than two operands (stability adds an index) along the
    lanes, whose code generation took minutes at these widths, and merges
    sorts that share a key into one; the barriers keep the sorts apart."""
    n, q = cand_cols.shape
    big = np.int32(2**30)  # numpy scalar: stays a literal under Pallas tracing
    with jax.named_scope("sort"):
        key = jnp.where(cand_cols >= 0, cand_cols, big)
        planes, _, join = _split_planes(cand_vals, 2)
        pairs = [jax.lax.sort(jax.lax.optimization_barrier((key, p)),
                              dimension=1, num_keys=1, is_stable=True)
                 for p in planes]
        cs = pairs[0][0]
        vs = join([v for _, v in pairs])
    with jax.named_scope("combine"):
        valid = cs < big
        prev = jnp.concatenate([jnp.full((n, 1), -2, cs.dtype), cs[:, :-1]],
                               axis=1)
        new_run = cs != prev
        scanned = _segmented_combine(new_run, vs, semiring.add, axis=1)
        next_new = jnp.concatenate([new_run[:, 1:], jnp.ones((n, 1), bool)],
                                   axis=1)
        kept = next_new & valid & ~semiring.is_zero(scanned)

    # Compact: stable argsort moves kept entries (already col-ascending) first.
    with jax.named_scope("compact"):
        ckey = jnp.where(kept, cs, big)
        order2 = jnp.argsort(ckey, axis=1)[:, :capacity]
        out_cols_raw = jnp.take_along_axis(ckey, order2, axis=1)
        out_cols = jnp.where(out_cols_raw < big,
                             out_cols_raw.astype(jnp.int32), NO_COL)
        planes, _, join = _split_planes(scanned, 2)
        out_vals = join([jnp.take_along_axis(p, order2, axis=1)
                         for p in planes])
        out_vals = tree_where(out_cols >= 0, out_vals,
                              semiring.zero((n, capacity)))
        overflow = jnp.sum(jnp.maximum(jnp.sum(kept, axis=1) - capacity, 0))
    return out_cols, out_vals, overflow


def ell_equal(a: EllMatrix, b: EllMatrix) -> bool:
    """Structural + value equality (host-side, for tests)."""
    if a.n_cols != b.n_cols or a.n_rows != b.n_rows:
        return False
    da = jax.tree.leaves(a.vals)
    db = jax.tree.leaves(b.vals)
    import numpy as np

    if not np.array_equal(np.asarray(a.cols), np.asarray(b.cols)):
        return False
    return all(
        np.allclose(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(da, db)
    )


def prune(mat: EllMatrix, drop: jnp.ndarray, semiring: Semiring) -> EllMatrix:
    """Remove entries where ``drop`` (n, capacity) is True, recompacting rows
    so they stay sorted-by-column (the paper's R ∘ ¬I, §IV-E)."""
    n, k = mat.cols.shape
    keep = mat.mask & ~drop
    big = np.int32(2**30)
    key = jnp.where(keep, mat.cols, big)
    order = jnp.argsort(key, axis=1)
    new_raw = jnp.take_along_axis(key, order, axis=1)
    new_cols = jnp.where(new_raw < big, new_raw, NO_COL)
    new_vals = jax.tree.map(
        lambda v: jnp.take_along_axis(
            v, order.reshape(order.shape + (1,) * (v.ndim - 2)), axis=1
        ),
        mat.vals,
    )
    new_vals = tree_where(new_cols >= 0, new_vals, semiring.zero((n, k)))
    return EllMatrix(cols=new_cols, vals=new_vals, n_cols=mat.n_cols)
