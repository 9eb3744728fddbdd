"""Backend dispatch layer: resolution, registry, and the golden-assembly
parity guarantee — ``assemble()`` must produce identical (EllMatrix-equal)
R and S graphs and contig stats under ``backend="reference"`` and
``backend="pallas"`` (interpret mode on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.assembly.pipeline import PipelineConfig, assemble
from repro.assembly.simulate import simulate_genome, simulate_reads
from repro.core.backend import (
    _REGISTRY,
    available_backends,
    dispatch,
    note_impl,
    recording_impls,
    register_op,
    resolve_backend,
    resolve_interpret,
)
from repro.core.semiring import minplus_orient_semiring as SR
from repro.core.spmat import ell_equal, from_coo
from repro.core.transitive_reduction import transitive_reduction_fused


def _sim():
    rng = np.random.default_rng(3)
    g = simulate_genome(rng, 3000)
    return simulate_reads(g, depth=8, mean_len=400, std_len=60,
                          error_rate=0.02, seed=4)


def _cfg(backend):
    return PipelineConfig(
        m_capacity=1 << 15, upper=48, read_capacity=64, overlap_capacity=32,
        r_capacity=24, band=17, max_steps=512, align_chunk=1024, xdrop=25,
        backend=backend,
    )


@pytest.fixture(scope="module")
def both_results():
    rs = _sim()
    return (
        assemble(rs.codes, rs.lengths, _cfg("reference")),
        assemble(rs.codes, rs.lengths, _cfg("pallas")),
    )


def test_resolution_and_registry():
    assert resolve_backend("reference") == "reference"
    assert resolve_backend("pallas") == "pallas"
    expected = "pallas" if jax.default_backend() == "tpu" else "reference"
    assert resolve_backend("auto") == expected
    assert resolve_interpret("auto") == (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    with pytest.raises(ValueError):
        resolve_backend("cuda")
    for op in ("xdrop_extend", "minplus_dense", "contig_gen", "consensus"):
        assert available_backends(op) == ("pallas", "reference")
        assert callable(dispatch(op, "reference"))
        assert callable(dispatch(op, "pallas"))
    with pytest.raises(KeyError):
        dispatch("no_such_op", "reference")


def test_golden_assembly_backend_parity(both_results):
    res_ref, res_pal = both_results
    assert res_ref.stats["backend"] == "reference"
    assert res_pal.stats["backend"] == "pallas"
    assert ell_equal(res_ref.r_graph, res_pal.r_graph)
    assert ell_equal(res_ref.s_graph, res_pal.s_graph)
    assert res_ref.stats["contigs"] == res_pal.stats["contigs"]
    for key in ("n_aligned", "n_passed", "nnz_R", "nnz_S", "tr_iterations"):
        assert res_ref.stats[key] == res_pal.stats[key], key
    # the consensus stage rides the same parity contract (DESIGN.md §2.8):
    # identical polished tensors and quality stats per backend
    for key in ("consensus_depth_mean", "identity_estimate",
                "consensus_changed", "n_junction_shifted"):
        assert res_ref.stats[key] == res_pal.stats[key], key
    a, b = res_ref.consensus, res_pal.consensus
    n = a.n_contigs
    assert n == b.n_contigs
    # contig-tensor padding differs per backend (exact vs pow2 staging);
    # the live rows must agree exactly
    assert np.array_equal(
        np.asarray(a.lengths)[:n], np.asarray(b.lengths)[:n]
    )
    pc_ref, pc_pal = a.to_contigs(), b.to_contigs()
    assert len(pc_ref) == len(pc_pal)
    for x, y in zip(pc_ref, pc_pal):
        assert x.reads == y.reads
        assert x.length == y.length
        assert np.array_equal(x.codes, y.codes)


def test_alignment_candidates_compacted(both_results):
    """The alignment stage must evaluate the compacted bucket, not all
    n × overlap_capacity ELL slots."""
    for res in both_results:
        total = res.stats["align_candidates"]
        bucket = res.stats["align_bucket"]
        live = res.stats["n_aligned"]
        assert total == res.stats["n_reads"] * 32  # n × overlap_capacity
        assert bucket < total
        assert live <= bucket < 2 * max(live, 1)  # next pow2 of live count


def test_tr_backend_parity_on_random_graph():
    rng = np.random.default_rng(11)
    n, e = 24, 90
    rows = rng.integers(0, n, e)
    cols = rng.integers(0, n, e)
    ok = rows != cols
    combos = rng.integers(0, 4, e)
    vals = np.full((e, 4), np.inf, np.float32)
    vals[np.arange(e), combos] = rng.integers(1, 120, e)
    r, _ = from_coo(
        jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals),
        jnp.asarray(ok), n_rows=n, n_cols=n, capacity=12, semiring=SR,
    )
    s_ref, st_ref = transitive_reduction_fused(r, fuzz=60.0, backend="reference")
    s_pal, st_pal = transitive_reduction_fused(r, fuzz=60.0, backend="pallas")
    assert ell_equal(s_ref, s_pal)
    assert int(st_ref.iterations) == int(st_pal.iterations)
    assert int(st_ref.nnz_final) == int(st_pal.nnz_final)


def test_dispatch_records_the_implementation_that_ran():
    """Every dispatched call lands in ``recording_impls``: the backend's
    ordinary record, or what the implementation noted instead (an oracle
    fallback must never pass for the kernel)."""

    def fallback():
        note_impl("reference (budget)")
        return 2

    register_op("_probe", "reference", lambda: 1)
    register_op("_probe", "pallas", fallback)
    try:
        with recording_impls() as log:
            assert dispatch("_probe", "reference")() == 1
            assert dispatch("_probe", "pallas")() == 2
        dispatch("_probe", "reference")()  # outside: not recorded
    finally:
        _REGISTRY.pop(("_probe", "reference"))
        _REGISTRY.pop(("_probe", "pallas"))
    assert log == {"_probe": {"reference", "reference (budget)"}}
