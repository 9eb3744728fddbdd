"""Sort-based counter vs python Counter; A/Aᵀ consistency."""

from collections import Counter

import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.assembly.counter import build_matrices, count_and_select, first_semiring
from repro.assembly.kmers import encode_seq, extract_kmers
from repro.core.spmat import ell_equal, from_coo


def _py_counts(seqs, k):
    comp = {"A": "T", "C": "G", "G": "C", "T": "A"}
    cnt = Counter()
    for s in seqs:
        for i in range(len(s) - k + 1):
            km = s[i : i + k]
            rc = "".join(comp[c] for c in reversed(km))
            cnt[min(km, rc)] += 1
    return cnt


@settings(max_examples=20, deadline=None)
@given(
    st.lists(st.text(alphabet="ACGT", min_size=10, max_size=30),
             min_size=2, max_size=8),
    st.sampled_from([5, 9]),
)
def test_counts_match_python(seqs, k):
    lmax = max(len(s) for s in seqs)
    codes = np.zeros((len(seqs), lmax), np.uint8)
    lens = np.zeros(len(seqs), np.int32)
    for i, s in enumerate(seqs):
        codes[i, : len(s)] = np.asarray(encode_seq(s))
        lens[i] = len(s)
    km = extract_kmers(jnp.asarray(codes), jnp.asarray(lens), k=k)
    kc = count_and_select(km, k=k, lower=1, upper=10**6)
    ref = _py_counts(seqs, k)
    assert int(kc.n_unique) == len(ref)
    # per-instance counts: group by count histogram
    got_hist = Counter()
    cnts = np.asarray(kc.count).reshape(-1)
    valid = np.asarray(km["valid"]).reshape(-1)
    # count each unique kmer once: via col_id first occurrence
    cols = np.asarray(kc.col_id)
    seen = {}
    for i in range(len(cols)):
        if valid[i] and cols[i] >= 0 and cols[i] not in seen:
            seen[cols[i]] = cnts[i]
    assert Counter(seen.values()) == Counter(ref.values())


def test_reliable_selection_and_matrices():
    seqs = ["ACGTACGTACGT", "ACGTACGTACGT", "TTTTTTTTTTTT"]
    lmax = max(len(s) for s in seqs)
    codes = np.zeros((len(seqs), lmax), np.uint8)
    lens = np.asarray([len(s) for s in seqs], np.int32)
    for i, s in enumerate(seqs):
        codes[i, : len(s)] = np.asarray(encode_seq(s))
    km = extract_kmers(jnp.asarray(codes), jnp.asarray(lens), k=5)
    kc = count_and_select(km, k=5, lower=2, upper=50)
    a, at, ovf_a, ovf_at = build_matrices(
        kc, n_reads=3, m_capacity=64, read_capacity=16, kmer_capacity=50
    )
    # A row nnz equals reliable instances deduped per (read, kmer)
    assert int(a.nnz()) > 0
    # Aᵀ consistency: every A entry appears in Aᵀ
    acols = np.asarray(a.cols)
    atcols = np.asarray(at.cols)
    for r in range(3):
        for q in acols[r][acols[r] >= 0]:
            assert r in atcols[q][atcols[q] >= 0]


@pytest.mark.parametrize("k,read_capacity", [(5, 4), (9, 64), (17, 16)])
def test_build_matrices_equals_from_coo(k, read_capacity):
    """A and Aᵀ, and their overflow counts, equal ``from_coo`` over the
    instance triplets: the definition they are built without."""
    rng = np.random.default_rng(k)
    genome = rng.integers(0, 4, 80).astype(np.uint8)
    n, lmax = 12, 40
    codes = np.zeros((n, lmax), np.uint8)
    lens = np.zeros(n, np.int32)
    for i in range(n):
        ln = int(rng.integers(k + 2, lmax + 1))
        st_ = int(rng.integers(0, 80 - ln + 1))
        seq = genome[st_:st_ + ln]
        codes[i, :ln] = 3 - seq[::-1] if i % 2 else seq
        lens[i] = ln
    km = extract_kmers(jnp.asarray(codes), jnp.asarray(lens), k=k)
    kc = count_and_select(km, k=k, lower=2, upper=6)
    assert int(kc.m_reliable) > 0
    a, at, ovf_a, ovf_at = build_matrices(
        kc, n_reads=n, m_capacity=256, read_capacity=read_capacity,
        kmer_capacity=6,
    )
    ok = kc.reliable & (kc.col_id >= 0)
    vals = {"pos": kc.pos_code}
    ref_a, ref_ovf_a = from_coo(
        kc.read_id, kc.col_id, vals, ok, n_rows=n, n_cols=256,
        capacity=read_capacity, semiring=first_semiring,
    )
    ref_at, ref_ovf_at = from_coo(
        kc.col_id, kc.read_id, vals, ok, n_rows=256, n_cols=n, capacity=6,
        semiring=first_semiring,
    )
    assert ell_equal(a, ref_a) and ell_equal(at, ref_at)
    assert (int(ovf_a), int(ovf_at)) == (int(ref_ovf_a), int(ref_ovf_at))
