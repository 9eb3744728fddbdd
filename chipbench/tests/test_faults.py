"""A whole run, less the look for a chip, at a size a test can hold: sound,
it is correct; with the timed path broken underneath, or with the control
in the program's place, ``correct`` comes out false."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from conftest import BENCH

SEED = 2**33 + 17


@pytest.fixture
def spec(monkeypatch):
    monkeypatch.setattr(run, "KERNEL_IMPL", "pallas-interpret")
    # the host stands in for the chip: no look for a TPU, no compile cache
    monkeypatch.setattr(run, "open_chips", lambda n: jax.devices())
    s = run.cell_spec(json.load(open(os.path.join(
        run.ROOT, "BENCHMARK.json")))["workloads"][0]["name"])
    s["config"] = json.load(open(os.path.join(BENCH, "tests", "tiny.json")))
    s["limits"] = json.load(open(os.path.join(BENCH, "tests",
                                              "tiny_limits.json")))
    return s


def _run(spec):
    return run.measure(spec, SEED, 0.0, False, warmup=False)


def test_sound_run_is_correct(spec):
    res = _run(spec)
    assert res["correct"], res["checks"]
    assert res["detail"]["expected"] > 100
    assert res["checks"]["r_edge_errors"]["value"] < 0.1


def test_state_unchanged_transitive_reduction(spec, monkeypatch):
    from repro.assembly import pipeline

    real = pipeline.transitive_reduction_fused
    monkeypatch.setattr(pipeline, "transitive_reduction_fused",
                        lambda r, **kw: (r, real(r, **kw)[1]))
    res = _run(spec)
    assert not res["correct"]
    assert res["checks"]["s_vs_reference_tr"]["value"] > 0


def test_half_the_candidates_left_out(spec, monkeypatch):
    from repro.assembly import alignment

    real = alignment.batch_extend

    def half(*args, **kw):
        out = real(*args, **kw)
        gone = jnp.arange(out.score.shape[0]) % 2 == 1
        return out._replace(score=jnp.where(gone, 0, out.score))

    monkeypatch.setattr(alignment, "batch_extend", half)
    res = _run(spec)
    assert not res["correct"]
    assert res["checks"]["r_edge_errors"]["value"] > 0.3


def test_contig_base_altered(spec, monkeypatch):
    from repro.assembly import pipeline

    real = pipeline.generate_contigs

    def altered(*args, **kw):
        cset = real(*args, **kw)
        codes = np.asarray(cset.codes).copy()
        codes[0, 0] ^= 1
        cset.codes = jnp.asarray(codes)
        return cset

    monkeypatch.setattr(pipeline, "generate_contigs", altered)
    res = _run(spec)
    assert not res["correct"]
    assert res["checks"]["contigs_vs_reference_walk"]["value"] > 0


def test_control_is_not_correct(spec):
    spec["config"]["pipeline"].update(spec["limits"]["control"])
    res = _run(spec)
    assert not res["correct"]
    assert res["checks"]["r_edge_errors"]["value"] > \
        spec["limits"]["r_edge_errors"]
