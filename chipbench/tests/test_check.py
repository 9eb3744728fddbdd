"""The yardstick's references agree with the program's own test oracles
(which the benchmark never imports) on random graphs, and the truth check
of R counts what it says."""

import numpy as np
import pytest

import check
import quality
import sim


def _random_ell(seed, n=24, e=90, k=12):
    rng = np.random.default_rng(seed)
    cols = np.full((n, k), -1, np.int32)
    vals = np.full((n, k, 4), np.inf)
    fill = np.zeros(n, int)
    for _ in range(e):
        i, j = rng.integers(0, n, 2)
        if i == j or fill[i] >= k or j in cols[i]:
            continue
        cols[i, fill[i]] = j
        vals[i, fill[i], rng.integers(0, 4)] = float(rng.integers(1, 200))
        fill[i] += 1
    return cols, vals


class _Ell:
    def __init__(self, cols, vals):
        self.cols, self.vals = cols, vals


@pytest.mark.parametrize("seed", range(12))
@pytest.mark.parametrize("fuzz", [20.0, 100.0])
def test_myers_tr_matches_program_oracle(seed, fuzz):
    from repro.core.myers_baseline import from_ell, myers_transitive_reduction

    cols, vals = _random_ell(seed)
    want, _ = myers_transitive_reduction(from_ell(_Ell(cols, vals)),
                                         fuzz=fuzz, max_iters=8)
    got = check.entries(*check.myers_tr(*check.edge_table(cols, vals),
                                        cols.shape[0], fuzz, 8))
    want = {(i, j, c): v for (i, j), vs in want.items()
            for c, v in enumerate(vs) if np.isfinite(v)}
    assert got == want


@pytest.mark.parametrize("seed", range(8))
def test_unitig_walk_matches_program_walk(seed):
    from repro.assembly.contigs import extract_contigs

    rng = np.random.default_rng(seed)
    cols, vals = _random_ell(seed, n=30, e=50, k=4)
    vals = np.where(np.isfinite(vals), np.minimum(vals, 150), vals)
    lengths = rng.integers(160, 240, 30).astype(np.int32)
    codes = rng.integers(0, 4, (30, 240)).astype(np.uint8)
    contained = rng.random(30) < 0.2
    want = extract_contigs(_Ell(cols, vals.astype(np.float32)), codes,
                           lengths, contained)
    got = check.unitig_walk(*check.edge_table(cols, vals), lengths, codes,
                            contained)
    prog = [(tuple(2 * r + s for r, s in c.reads), c.codes) for c in want]
    assert check.count_contig_diffs(prog, got) == 0
    # a base altered where the contig is produced is counted
    if prog:
        bad = [(prog[0][0], prog[0][1] ^ 1)] + prog[1:]
        assert check.count_contig_diffs(bad, got) == 1


def _profile(**kw):
    p = {"genome_bp": 60000, "depth": 12, "mean_len": 2000, "sd_len": 500,
         "min_len": 300, "max_sd": 6, "error_rate": 0.0, "indel_frac": 0.6,
         "read_width": 4096}
    p.update(kw)
    return p


def _truth_r(reads, drop=0.0, seed=0):
    """R built from the truth itself: every dovetail of uncontained reads,
    with the true suffix; ``drop`` of the pairs left out."""
    lo, hi, st = reads.truth_start, reads.truth_end, reads.truth_strand
    rng = np.random.default_rng(seed)
    n = lo.shape[0]
    src, dst, val = [], [], []
    for i in range(n):
        for j in range(n):
            ov = min(hi[i], hi[j]) - max(lo[i], lo[j])
            if i == j or ov <= 0 or hi[j] <= hi[i] or lo[j] <= lo[i]:
                continue
            if rng.random() < drop:
                continue
            v = np.full(4, np.inf)  # i walked along the genome, then j
            v[2 * st[i] + st[j]] = hi[j] - hi[i]
            src.append(i), dst.append(j), val.append(v)
            v = np.full(4, np.inf)  # the complement: j, then i, reversed
            v[2 * (1 - st[j]) + (1 - st[i])] = lo[j] - lo[i]
            src.append(j), dst.append(i), val.append(v)
    return np.array(src), np.array(dst), np.array(val)


def test_r_edge_errors_counts_missing_edges():
    reads = sim.simulate(_profile(), 5)
    limits = {"overlap_lo": 200, "contained_margin": 0, "suffix_tol":
              [0.0, 1], "reach_bp": 1 << 20}
    sample = np.arange(reads.n_reads)
    full = _truth_r(reads)
    err, d = check.r_edge_errors(*full, reads, sample, limits)
    assert d["expected"] > 50 and d["false"] == 0 and err == 0
    half = _truth_r(reads, drop=0.5, seed=1)
    err, d = check.r_edge_errors(*half, reads, sample, limits)
    assert 0.3 < err < 0.7 and d["false"] == 0


def test_n50():
    assert quality.n50([10, 20, 30, 40]) == 30
    assert quality.n50([5]) == 5


def test_identity_of_the_truth_is_one():
    reads = sim.simulate(_profile(), 9)
    tmpl = reads.genome[reads.truth_start[0]: reads.truth_end[0]]
    tmpl = (3 - tmpl[::-1]) if reads.truth_strand[0] else tmpl

    class Contig:
        pass

    c = Contig()
    c.reads, c.codes, c.length = [(0, 0)], tmpl, len(tmpl)
    assert quality.identity(c, reads, 64) == 1.0
    c.codes = tmpl.copy()
    c.codes[::10] ^= 1
    assert quality.identity(c, reads, 64) == pytest.approx(0.9, abs=0.001)
