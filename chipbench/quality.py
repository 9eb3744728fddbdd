"""Assembly quality against the simulated truth (the yardstick's own copy).

N50 of contig lengths, and the identity of a contig against the genome
interval its reads were drawn from: 1 − banded edit distance / the longer
length, length-weighted over a seeded sample of contigs.
"""

from __future__ import annotations

import numpy as np


def n50(lengths) -> int:
    ls = sorted((int(x) for x in lengths), reverse=True)
    total, acc = sum(ls), 0
    for x in ls:
        acc += x
        if 2 * acc >= total:
            return x
    return 0


def banded_edit_distance(a, b, band: int) -> int:
    """Unit-cost edit distance over the cells with |i − j| ≤ band (widened
    to the length difference + 1); exact while the best path stays inside."""
    a, b = np.asarray(a), np.asarray(b)
    la, lb = len(a), len(b)
    if la == 0 or lb == 0:
        return la + lb
    band = max(int(band), abs(la - lb) + 1)
    ks = np.arange(-band, band + 1)  # slot k holds column j = i + k
    inf = la + lb + 1
    prev = np.where((ks >= 0) & (ks <= lb), np.abs(ks), inf)
    for i in range(1, la + 1):
        j = i + ks
        sub = np.where(a[i - 1] == b[np.clip(j - 1, 0, lb - 1)], 0, 1)
        cand = np.minimum(prev + sub, np.concatenate([prev[1:], [inf]]) + 1)
        cand = np.where((j >= 1) & (j <= lb), cand, inf)
        if i <= band:
            cand[band - i] = i
        prev = np.minimum(np.minimum.accumulate(cand - j) + j, inf)
    return int(prev[lb - la + band])


def truth_interval(contig, reads):
    """Genome interval and orientation of a contig: the union of its reads'
    templates; orientation by majority of read strand against chain strand."""
    rs = [r for r, _ in contig.reads]
    lo = int(reads.truth_start[rs].min())
    hi = int(reads.truth_end[rs].max())
    flips = [int(reads.truth_strand[r]) ^ int(s) for r, s in contig.reads]
    return lo, hi, int(2 * sum(flips) >= len(flips))


def identity(contig, reads, band: int) -> float:
    lo, hi, o = truth_interval(contig, reads)
    ref = reads.genome[lo:hi]
    if o:
        ref = (3 - ref)[::-1]
    longer = max(len(ref), contig.length)
    return 1.0 - banded_edit_distance(contig.codes, ref, band) / longer


def sampled_identity(contigs, reads, rng, bases: int, band: int):
    """Length-weighted identity over contigs taken in a seeded order until
    ``bases`` are measured.  Returns (identity, bases measured)."""
    num, den = 0.0, 0
    for i in rng.permutation(len(contigs)):
        c = contigs[i]
        if c.length == 0:
            continue
        num += identity(c, reads, band) * c.length
        den += c.length
        if den >= bases:
            break
    return (num / den if den else float("nan")), den
