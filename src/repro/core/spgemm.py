"""Local semiring SpGEMM over static-capacity ELL matrices.

TPU adaptation of CombBLAS's hash/heap local multiply (paper §IV-D): the
row-expansion ``gather → sort-by-column → segmented-⊕ → compact`` pipeline is
branch-free and fully static-shaped.  For each row i of A we gather the B-rows
indexed by A's column slots, apply the semiring ⊗ to the (K_A × K_B) candidate
grid, then merge candidates sharing an output column with ⊕.

Also provides:
  * ``spgemm_masked`` — the *sampled* semiring product ``(A ⊗ B) ∘ pattern(M)``
    (an SDDMM analogue).  This is the beyond-paper optimization used by the
    fused transitive-reduction step: Algorithm 2 only ever reads N = R² at
    R's own nonzero positions, so we never materialize N's (much denser)
    pattern and skip the candidate sort entirely.
  * ``transpose`` — explicit ELL transpose (paper line 5, Aᵀ).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .semiring import Semiring, tree_where, INF
from .spmat import EllMatrix, NO_COL, from_coo, map_row_blocks, merge_sorted_rows


@partial(jax.jit, static_argnames=("semiring", "capacity", "row_chunk"))
def spgemm(
    a: EllMatrix, b: EllMatrix, *, semiring: Semiring, capacity: int,
    row_chunk: int | None = None,
):
    """C = A ⊗ B over ``semiring``; returns (EllMatrix C, overflow count).

    a: (n × m) with row capacity K_A;  b: (m × p) with row capacity K_B.
    Work/row = K_A·K_B candidates (static).  ``row_chunk`` bounds the
    candidate expand/sort buffer by mapping over row blocks — required at
    production scale where n·K_A·K_B would not fit HBM."""
    if row_chunk is not None and a.cols.shape[0] > row_chunk:
        return _spgemm_chunked(
            a, b, semiring=semiring, capacity=capacity, row_chunk=row_chunk
        )
    n, ka = a.cols.shape
    kb = b.cols.shape[1]
    # the scope names this device work in the profiler trace (op metadata)
    with jax.named_scope("spgemm_expand"):
        a_valid = a.mask
        safe = jnp.where(a_valid, a.cols, 0)

        b_cols_g = b.cols[safe]  # (n, KA, KB)
        b_vals_g = jax.tree.map(lambda v: v[safe], b.vals)

        a_vals_e = jax.tree.map(lambda v: v[:, :, None, ...], a.vals)
        cand_vals = semiring.mul(a_vals_e, b_vals_g)
        cand_valid = (
            a_valid[:, :, None]
            & (b_cols_g >= 0)
            & ~semiring.is_zero(cand_vals)
        )
        cand_cols = jnp.where(cand_valid, b_cols_g, NO_COL).reshape(n, ka * kb)
        cand_vals = jax.tree.map(
            lambda v: v.reshape((n, ka * kb) + v.shape[3:]), cand_vals
        )
    out_cols, out_vals, overflow = merge_sorted_rows(
        cand_cols, cand_vals, capacity=capacity, semiring=semiring
    )
    return EllMatrix(cols=out_cols, vals=out_vals, n_cols=b.n_cols), overflow


def _spgemm_chunked(a, b, *, semiring, capacity, row_chunk):
    n = a.cols.shape[0]

    def one(chunk):
        cc, cv = chunk
        am = EllMatrix(cols=cc, vals=cv, n_cols=a.n_cols)
        c, ovf = spgemm(am, b, semiring=semiring, capacity=capacity)
        return (c.cols, c.vals), ovf

    (oc, ov), ovfs = map_row_blocks(
        one, (a.cols, a.vals), n_rows=n, row_chunk=row_chunk,
        fills=(-1, jax.tree.map(lambda _: 0, a.vals)),
    )
    return EllMatrix(cols=oc, vals=ov, n_cols=b.n_cols), jnp.sum(ovfs)


@partial(jax.jit, static_argnames=("semiring", "row_chunk"))
def spgemm_masked(
    a: EllMatrix, b: EllMatrix, mask: EllMatrix, *, semiring: Semiring,
    row_chunk: int | None = None,
):
    if row_chunk is not None and a.cols.shape[0] > row_chunk:
        return _spgemm_masked_chunked(
            a, b, mask, semiring=semiring, row_chunk=row_chunk
        )
    return _spgemm_masked_impl(a, b, mask, semiring=semiring)


def _spgemm_masked_chunked(a, b, mask, *, semiring, row_chunk):
    n = a.cols.shape[0]

    def one(chunk):
        cc, cv, kc, kv = chunk
        am = EllMatrix(cols=cc, vals=cv, n_cols=a.n_cols)
        mm = EllMatrix(cols=kc, vals=kv, n_cols=mask.n_cols)
        return _spgemm_masked_impl(am, b, mm, semiring=semiring).vals, None

    vals, _ = map_row_blocks(
        one, (a.cols, a.vals, mask.cols, mask.vals), n_rows=n,
        row_chunk=row_chunk,
        fills=(-1, jax.tree.map(lambda _: 0, a.vals),
               -1, jax.tree.map(lambda _: 0, mask.vals)),
    )
    return EllMatrix(cols=mask.cols, vals=vals, n_cols=mask.n_cols)


def _spgemm_masked_impl(a: EllMatrix, b: EllMatrix, mask: EllMatrix, *,
                        semiring: Semiring):
    """Sampled semiring product: N = (A ⊗ B) restricted to pattern(mask).

    Returns an EllMatrix sharing ``mask``'s cols array whose values are
    ``⊕_k A[i,k] ⊗ B[k, mask.cols[i,q]]``.  No sort, no pattern growth:
    work/row = K_A·K_B candidate ⊗ plus a (K_A·K_B × K_mask) column match.
    """
    n, ka = a.cols.shape
    kb = b.cols.shape[1]
    km = mask.cols.shape[1]
    a_valid = a.mask
    safe = jnp.where(a_valid, a.cols, 0)
    b_cols_g = b.cols[safe]  # (n, KA, KB)
    b_vals_g = jax.tree.map(lambda v: v[safe], b.vals)
    a_vals_e = jax.tree.map(lambda v: v[:, :, None, ...], a.vals)
    cand_vals = semiring.mul(a_vals_e, b_vals_g)
    cand_valid = a_valid[:, :, None] & (b_cols_g >= 0) & ~semiring.is_zero(cand_vals)
    cand_cols = jnp.where(cand_valid, b_cols_g, NO_COL).reshape(n, ka * kb)
    cand_vals = jax.tree.map(lambda v: v.reshape((n, ka * kb) + v.shape[3:]), cand_vals)

    q = ka * kb

    def _log_reduce(vals, width):
        """⊕-reduce value pytree along axis 1 (length ``width``)."""
        cur = vals
        while width > 1:
            if width % 2:
                zpad = semiring.zero((n, 1))
                cur = jax.tree.map(
                    lambda x, z: jnp.concatenate(
                        [x, jnp.broadcast_to(z, (n, 1) + x.shape[2:])], axis=1
                    ),
                    cur,
                    zpad,
                )
                width += 1
            left = jax.tree.map(lambda x: x[:, 0::2], cur)
            right = jax.tree.map(lambda x: x[:, 1::2], cur)
            cur = semiring.add(left, right)
            width //= 2
        return jax.tree.map(lambda x: x[:, 0], cur)

    # Scan over mask slots so we never materialize an (n, Q, Km) value grid.
    def slot_body(_, slot_cols):  # slot_cols: (n,)
        hits = (cand_cols == slot_cols[:, None]) & (slot_cols[:, None] >= 0)
        contrib = tree_where(hits, cand_vals, semiring.zero((n, q)))
        return None, _log_reduce(contrib, q)

    _, out = jax.lax.scan(slot_body, None, mask.cols.T)
    out_vals = jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), out)  # (n, Km, ...)
    out_vals = tree_where(mask.cols >= 0, out_vals, semiring.zero((n, km)))
    return EllMatrix(cols=mask.cols, vals=out_vals, n_cols=mask.n_cols)


@partial(jax.jit, static_argnames=("capacity", "semiring"))
def transpose(a: EllMatrix, *, capacity: int, semiring: Semiring):
    """Explicit ELL transpose (paper Alg. 1 line 5). Returns (Aᵀ, overflow)."""
    n, k = a.cols.shape
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k)).reshape(-1)
    cols = a.cols.reshape(-1)
    valid = cols >= 0
    vals = jax.tree.map(lambda v: v.reshape((n * k,) + v.shape[2:]), a.vals)
    return from_coo(
        cols,
        rows,
        vals,
        valid,
        n_rows=a.n_cols,
        n_cols=n,
        capacity=capacity,
        semiring=semiring,
    )
