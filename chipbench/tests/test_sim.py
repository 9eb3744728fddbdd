"""The yardstick's vectorised simulator gives reads with the statistics of
the assembler's own simulator at the same parameters, and the same inputs
from the same seed."""

import numpy as np
import pytest

import quality
import sim

P = {"genome_bp": 300000, "depth": 6, "mean_len": 2000, "sd_len": 500,
     "min_len": 300, "max_sd": 6, "error_rate": 0.12, "indel_frac": 0.6,
     "read_width": 4096}


def _stats(codes, lengths, start, end, strand, genome, k=40):
    tl = end - start
    ident = []
    for i in range(k):
        t = genome[start[i]:end[i]]
        t = (3 - t[::-1]) if strand[i] else t
        r = codes[i, : lengths[i]]
        ident.append(1 - quality.banded_edit_distance(r, t, 64) / len(t))
    d = (lengths - tl) / tl
    return {"n": len(lengths), "mean_len": lengths.mean(),
            "rev": strand.mean(), "len_change_var": d.var(),
            "identity": np.mean(ident)}


def test_statistics_match_the_program_simulator():
    from repro.assembly.simulate import simulate_reads

    ours = sim.simulate(P, 11)
    theirs = simulate_reads(ours.genome, depth=P["depth"],
                            mean_len=P["mean_len"], std_len=P["sd_len"],
                            min_len=P["min_len"], error_rate=P["error_rate"],
                            indel_frac=P["indel_frac"], seed=12)
    a = _stats(ours.codes, ours.lengths, ours.truth_start, ours.truth_end,
               ours.truth_strand, ours.genome)
    b = _stats(theirs.codes, theirs.lengths, theirs.truth_start,
               theirs.truth_end, theirs.truth_strand, theirs.genome)
    assert a["n"] == b["n"]
    assert a["mean_len"] == pytest.approx(b["mean_len"], rel=0.03)
    assert a["rev"] == pytest.approx(b["rev"], abs=0.06)
    # indels change a read's length: variance e·f / L per base in both
    assert a["len_change_var"] == pytest.approx(b["len_change_var"], rel=0.3)
    # identity to the template: about 1 − e in both
    assert a["identity"] == pytest.approx(b["identity"], abs=0.01)
    assert a["identity"] == pytest.approx(1 - P["error_rate"], abs=0.02)


def test_same_seed_same_inputs_and_fixed_shapes():
    a, b, c = sim.simulate(P, 2**40 + 7), sim.simulate(P, 2**40 + 7), \
        sim.simulate(P, 5)
    assert np.array_equal(a.codes, b.codes)
    assert np.array_equal(a.truth_start, b.truth_start)
    assert a.codes.shape == c.codes.shape == (sim.n_reads(
        P["genome_bp"], P["depth"], P["mean_len"]), P["read_width"])
    # every seed deals out the same template lengths
    assert np.array_equal(np.sort(a.truth_end - a.truth_start),
                          np.sort(c.truth_end - c.truth_start))
