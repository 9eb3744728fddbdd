"""EllMatrix construction / merge / prune invariants, plus 0-1-principle
style edge cases for ``merge_sorted_rows`` — the per-row candidate merge is
load-bearing for both the local SpGEMM and the ring-SUMMA stage merge
(``core/summa.py``), so its duplicate-combine / pad / overflow semantics are
pinned directly here rather than only through end-to-end parity."""

from collections import Counter

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.semiring import (
    NUM_POS_PAIRS,
    count_semiring as CS,
    minplus_orient_semiring,
    overlap_semiring,
    tree_where,
)
from repro.core.spmat import (
    EllMatrix,
    NO_COL,
    _split_planes,
    from_coo,
    merge_sorted_rows,
    prune,
)


@settings(max_examples=30, deadline=None)
@given(
    st.lists(
        st.tuples(st.integers(0, 7), st.integers(0, 9), st.integers(1, 5)),
        min_size=1, max_size=60,
    )
)
def test_from_coo_matches_dense_accumulation(triples):
    rows = jnp.asarray([t[0] for t in triples])
    cols = jnp.asarray([t[1] for t in triples])
    vals = jnp.asarray([t[2] for t in triples], jnp.int32)
    ok = jnp.ones(len(triples), bool)
    m, ovf = from_coo(rows, cols, vals, ok, n_rows=8, n_cols=10,
                      capacity=10, semiring=CS)
    assert int(ovf) == 0
    dense = np.zeros((8, 10), np.int64)
    for r, c, v in triples:
        dense[r, c] += v
    got = np.asarray(m.to_dense(CS))
    np.testing.assert_array_equal(got, dense)
    # rows sorted by col, invalid at the end
    cols_np = np.asarray(m.cols)
    for r in range(8):
        valid = cols_np[r][cols_np[r] >= 0]
        assert (np.diff(valid) > 0).all()
        assert (cols_np[r][len(valid):] == -1).all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_from_coo_one_key_and_two_key_sorts_agree(seed):
    """Shapes with (n_rows+1)·(n_cols+1) ≤ 2^32 sort one uint32 (row, col)
    key, larger ones two keys; the same triplets give the same rows."""
    rng = np.random.default_rng(seed)
    e = 200
    rows = jnp.asarray(rng.integers(0, 12, e), jnp.int32)
    cols = jnp.asarray(rng.integers(0, 40, e), jnp.int32)
    vals = jnp.asarray(rng.integers(1, 5, e), jnp.int32)
    ok = jnp.asarray(rng.random(e) < 0.8)
    one, ovf1 = from_coo(rows, cols, vals, ok, n_rows=12, n_cols=40,
                         capacity=6, semiring=CS)
    two, ovf2 = from_coo(rows, cols, vals, ok, n_rows=12, n_cols=1 << 30,
                         capacity=6, semiring=CS)
    np.testing.assert_array_equal(np.asarray(one.cols), np.asarray(two.cols))
    np.testing.assert_array_equal(np.asarray(one.vals), np.asarray(two.vals))
    assert int(ovf1) == int(ovf2) > 0


def test_overflow_counted_not_dropped_silently():
    rows = jnp.zeros(10, jnp.int32)
    cols = jnp.arange(10)
    vals = jnp.ones(10, jnp.int32)
    m, ovf = from_coo(rows, cols, vals, jnp.ones(10, bool), n_rows=2,
                      n_cols=16, capacity=4, semiring=CS)
    assert int(ovf) == 6
    assert m.cols[0].tolist() == [0, 1, 2, 3]


def test_prune_recompacts():
    rows = jnp.asarray([0, 0, 0])
    cols = jnp.asarray([2, 5, 7])
    vals = jnp.asarray([1, 2, 3], jnp.int32)
    m, _ = from_coo(rows, cols, vals, jnp.ones(3, bool), n_rows=1, n_cols=8,
                    capacity=4, semiring=CS)
    drop = jnp.asarray([[False, True, False, False]])
    m2 = prune(m, drop, CS)
    assert m2.cols[0].tolist() == [2, 7, -1, -1]
    assert m2.vals[0].tolist()[:2] == [1, 3]


def test_lookup():
    rows = jnp.asarray([0, 0, 1])
    cols = jnp.asarray([2, 5, 3])
    vals = jnp.asarray([10, 20, 30], jnp.int32)
    m, _ = from_coo(rows, cols, vals, jnp.ones(3, bool), n_rows=2, n_cols=8,
                    capacity=4, semiring=CS)
    got, found = m.lookup(CS, jnp.asarray([[5, 2, 7], [3, -1, 0]]))
    assert found.tolist() == [[True, True, False], [True, False, False]]
    assert got.tolist()[0][:2] == [20, 10]


# ---------------------------------------------------------------------------
# merge_sorted_rows edge cases
# ---------------------------------------------------------------------------


def _merge(cols_rows, capacity):
    cand = jnp.asarray(cols_rows, jnp.int32)
    vals = jnp.ones(cand.shape, jnp.int32)
    return merge_sorted_rows(cand, vals, capacity=capacity, semiring=CS)


def test_merge_sorted_rows_duplicate_columns_at_capacity():
    # every column appears twice and the post-combine count exactly fills
    # the capacity: duplicates must combine (not spill) and overflow stays 0
    cols, vals, ovf = _merge([[9, 3, 5, 3, 7, 9, 5, 7]], capacity=4)
    assert cols.tolist() == [[3, 5, 7, 9]]
    assert vals.tolist() == [[2, 2, 2, 2]]
    assert int(ovf) == 0


def test_merge_sorted_rows_all_pad_rows():
    cols, vals, ovf = _merge([[-1] * 6, [-1] * 6], capacity=3)
    assert cols.tolist() == [[-1, -1, -1]] * 2
    assert vals.tolist() == [[0, 0, 0]] * 2
    assert int(ovf) == 0


def test_merge_sorted_rows_overflow_count_exact():
    # 6 distinct columns into capacity 4 → exactly 2 overflow; the kept
    # slots are the 4 smallest columns.  Duplicates combine BEFORE the
    # capacity cut, so a second row with 6 slots over 3 distinct columns
    # adds nothing to the overflow.
    cols, vals, ovf = _merge(
        [[11, 2, 7, 5, 13, 3], [4, 4, 6, 6, 8, 8]], capacity=4
    )
    assert cols.tolist()[0] == [2, 3, 5, 7]
    assert cols.tolist()[1] == [4, 6, 8, -1]
    assert vals.tolist()[1] == [2, 2, 2, 0]
    assert int(ovf) == 2


@settings(max_examples=30, deadline=None)
@given(
    st.lists(st.integers(-1, 7), min_size=1, max_size=12),
    st.integers(1, 8),
)
def test_merge_sorted_rows_matches_dedup_oracle(cols_list, capacity):
    # 0-1-principle spirit: unit counts, arbitrary column patterns — the
    # merge must equal the sorted-distinct-prefix oracle on every input.
    # capacity ≤ Q is the callers' invariant (Q is always a multiple of the
    # output capacity in both SpGEMM paths), so the draw is clamped.
    capacity = min(capacity, len(cols_list))
    cols, vals, ovf = _merge([cols_list], capacity)
    counts = Counter(c for c in cols_list if c >= 0)
    distinct = sorted(counts)
    exp_cols = distinct[:capacity] + [-1] * (capacity - len(distinct[:capacity]))
    exp_vals = [counts[c] for c in distinct[:capacity]]
    exp_vals += [0] * (capacity - len(exp_vals))
    assert cols.tolist() == [exp_cols]
    assert vals.tolist() == [exp_vals]
    assert int(ovf) == max(len(distinct) - capacity, 0)


def _scan_runs(flags, vals, add):
    """Segmented inclusive ⊕-scan along rows by ``associative_scan``."""
    planes, split, join = _split_planes(vals, 2)

    def op(x, y):
        (fx, px), (fy, py) = x, y
        vx, vy = join(px), join(py)
        return fx | fy, split(tree_where(fy, vy, add(vx, vy)))

    return join(jax.lax.associative_scan(op, (flags, planes), axis=1)[1])


def _merge_by_gathers(cand_cols, cand_vals, *, capacity, semiring):
    """Reference merge: an argsort of the column key, its order applied to
    the key and each value plane by gathers along the rows, runs combined by
    ``associative_scan``, then the capacity compaction."""
    n, _ = cand_cols.shape
    big = np.int32(2**30)
    key = jnp.where(cand_cols >= 0, cand_cols, big)
    order = jnp.argsort(key, axis=1)
    cs = jnp.take_along_axis(key, order, axis=1)
    planes, _, join = _split_planes(cand_vals, 2)
    vs = join([jnp.take_along_axis(p, order, axis=1) for p in planes])
    valid = cs < big
    prev = jnp.concatenate([jnp.full((n, 1), -2, cs.dtype), cs[:, :-1]],
                           axis=1)
    new_run = cs != prev
    scanned = _scan_runs(new_run, vs, semiring.add)
    next_new = jnp.concatenate([new_run[:, 1:], jnp.ones((n, 1), bool)],
                               axis=1)
    kept = next_new & valid & ~semiring.is_zero(scanned)
    ckey = jnp.where(kept, cs, big)
    order2 = jnp.argsort(ckey, axis=1)[:, :capacity]
    out_raw = jnp.take_along_axis(ckey, order2, axis=1)
    out_cols = jnp.where(out_raw < big, out_raw, NO_COL)
    planes, _, join = _split_planes(scanned, 2)
    out_vals = join([jnp.take_along_axis(p, order2, axis=1) for p in planes])
    out_vals = tree_where(out_cols >= 0, out_vals,
                          semiring.zero((n, capacity)))
    overflow = jnp.sum(jnp.maximum(jnp.sum(kept, axis=1) - capacity, 0))
    return out_cols, out_vals, overflow


def _cols(rng, n, q, n_cols, pad_share):
    cols = rng.integers(0, n_cols, (n, q))
    cols = np.where(rng.random((n, q)) < pad_share, -1, cols).astype(np.int32)
    cols[5] = 1  # one run the width of the row: every step of the scan
    return cols


def _count_case(rng):
    # 12 columns into capacity 8: ties in every row, overflow in most;
    # zero values test the is_zero drop; one row all pads
    cols = _cols(rng, 16, 64, 12, 0.25)
    cols[3] = -1
    vals = rng.integers(0, 4, cols.shape).astype(np.int32)
    return CS, cols, jnp.asarray(vals), 8


def _overlap_case(rng):
    # 6 columns over 128 candidates a row: ~20 duplicates a column, each
    # with its own position pairs, so which NUM_POS_PAIRS pairs survive ⊕
    # depends on the merge keeping candidate order within a column
    cols = _cols(rng, 16, 128, 6, 0.1)
    shape = cols.shape + (2,)
    vals = {"cnt": jnp.asarray(rng.integers(1, 3, cols.shape), jnp.int32),
            "apos": jnp.asarray(rng.integers(0, 10_000, shape), jnp.int32),
            "bpos": jnp.asarray(rng.integers(0, 10_000, shape), jnp.int32)}
    return overlap_semiring, cols, vals, 4


def _minplus_case(rng):
    # float planes with absent (inf) orientations and whole-inf candidates
    cols = _cols(rng, 16, 64, 10, 0.2)
    vals = rng.random(cols.shape + (4,)).astype(np.float32) * 100
    vals = np.where(rng.random(vals.shape) < 0.5, np.inf, vals)
    return minplus_orient_semiring, cols, jnp.asarray(vals), 6


@pytest.mark.parametrize("case", [_count_case, _overlap_case, _minplus_case],
                         ids=["count", "overlap", "minplus_orient"])
def test_merge_sorted_rows_bit_equal_to_gather_merge(case):
    semiring, cols, vals, capacity = case(np.random.default_rng(14))
    got = jax.jit(lambda c, v: merge_sorted_rows(
        c, v, capacity=capacity, semiring=semiring))(jnp.asarray(cols), vals)
    want = jax.jit(lambda c, v: _merge_by_gathers(
        c, v, capacity=capacity, semiring=semiring))(jnp.asarray(cols), vals)
    got_leaves, want_leaves = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got_leaves) == len(want_leaves)
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(np.asarray(g), np.asarray(w))
    if semiring is CS:  # the capacity cut is exercised
        assert int(got[2]) > 0
    if semiring is overlap_semiring:  # ⊕ drops position pairs
        assert int(jnp.max(got[1]["cnt"])) > NUM_POS_PAIRS
