"""Seeded long-read data sets for the benchmark (the yardstick's own copy).

The same read model as the assembler's own simulator (uniform i.i.d. genome,
reads at a target depth with normally distributed lengths, half of them on
the reverse strand, substitutions and single-base indels at a stated error
rate), vectorised per read, with two changes that keep every seed's work and
shapes alike:

* the read lengths are the same multiset for every seed (the normal
  quantiles at ``(i + 0.5) / n``, truncated), dealt out in a seeded order;
* every read set is padded to the configuration's fixed ``read_width``.

Errors are drawn per template base: a substitution, a deletion, or an
insertion before the base, with probabilities ``e·(1 − f)``, ``e·f/2`` and
``e·f/2`` for error rate ``e`` and indel share ``f``.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class ReadSet:
    codes: np.ndarray  # (n, read_width) uint8, A=0 C=1 G=2 T=3, zero padded
    lengths: np.ndarray  # (n,) int32
    truth_start: np.ndarray  # (n,) genome start of the read's template
    truth_end: np.ndarray  # (n,) genome end (exclusive)
    truth_strand: np.ndarray  # (n,) 0 forward / 1 reverse complement
    genome: np.ndarray  # (G,) uint8

    @property
    def n_reads(self) -> int:
        return self.codes.shape[0]


def n_reads(genome_bp: int, depth: float, mean_len: float) -> int:
    return max(2, int(round(depth * genome_bp / mean_len)))


def template_lengths(n: int, mean_len: float, sd_len: float, min_len: int,
                     max_len: float) -> np.ndarray:
    """The seed-independent multiset of template lengths, ascending."""
    nd = statistics.NormalDist(mean_len, sd_len)
    q = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    return np.clip(np.rint(q), min_len, max_len).astype(np.int64)


def _corrupt(tmpl: np.ndarray, rng, error_rate: float, indel_frac: float):
    u = rng.random(tmpl.shape[0])
    p_sub = error_rate * (1.0 - indel_frac)
    p_del = error_rate * indel_frac / 2
    sub = u < p_sub
    dele = (u >= p_sub) & (u < p_sub + p_del)
    ins = (u >= p_sub + p_del) & (u < error_rate)
    base = np.where(sub, (tmpl + rng.integers(1, 4, tmpl.shape[0])) % 4, tmpl)
    copies = 1 + ins.astype(np.int64) - dele.astype(np.int64)
    out = np.repeat(base.astype(np.uint8), copies)
    # an inserted base is the first of its template base's two copies
    first = np.cumsum(copies) - copies
    out[first[ins]] = rng.integers(0, 4, int(ins.sum()), dtype=np.uint8)
    return out


def simulate(profile: dict, seed: int) -> ReadSet:
    """Genome and reads of ``profile`` (a configuration's ``reads`` block)
    from ``seed``; any whole number, however large."""
    rng = np.random.default_rng(seed)
    g = int(profile["genome_bp"])
    genome = rng.integers(0, 4, size=g, dtype=np.uint8)
    mean, sd = float(profile["mean_len"]), float(profile["sd_len"])
    n = n_reads(g, profile["depth"], mean)
    lengths = rng.permutation(template_lengths(
        n, mean, sd, int(profile["min_len"]), mean + profile["max_sd"] * sd))
    lengths = np.minimum(lengths, g)
    starts = rng.integers(0, g - lengths + 1)
    strands = rng.integers(0, 2, size=n)
    width = int(profile["read_width"])
    codes = np.zeros((n, width), np.uint8)
    out_len = np.zeros(n, np.int32)
    for i in range(n):
        tmpl = genome[starts[i]: starts[i] + lengths[i]]
        if strands[i]:
            tmpl = 3 - tmpl[::-1]
        read = _corrupt(tmpl, rng, profile["error_rate"],
                        profile["indel_frac"])
        if read.shape[0] > width:
            raise ValueError(f"read {i} has {read.shape[0]} bases, more than "
                             f"read_width {width}")
        codes[i, : read.shape[0]] = read
        out_len[i] = read.shape[0]
    return ReadSet(codes=codes, lengths=out_len,
                   truth_start=starts.astype(np.int64),
                   truth_end=(starts + lengths).astype(np.int64),
                   truth_strand=strands.astype(np.int32), genome=genome)
