"""EllMatrix construction / merge / prune invariants, plus 0-1-principle
style edge cases for ``merge_sorted_rows`` — the per-row candidate merge is
load-bearing for both the local SpGEMM and the ring-SUMMA stage merge
(``core/summa.py``), so its duplicate-combine / pad / overflow semantics are
pinned directly here rather than only through end-to-end parity."""

from collections import Counter

import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.semiring import count_semiring as CS
from repro.core.spmat import EllMatrix, from_coo, merge_sorted_rows, prune


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
