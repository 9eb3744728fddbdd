"""Property-based tests for the alignment stack plus the distributed x-drop
extension (DESIGN.md §2.12).

Three layers, matching the stack:

* ``assembly.alignment.batch_extend`` — property tests through
  ``_hypothesis_compat``: reference↔pallas bit parity on random
  sequences/error profiles/scoring params, and the per-pair independence
  invariants (candidate-pair permutation and pad-slot count) that make the
  candidate-axis block split bit-safe in the first place;
* ``core.align_dist.align_bucket_shard_map`` on a degenerate P=1 mesh —
  in-process parity against a local ``batch_extend`` with the
  ``align_exchange`` metric group present-and-zero;
* subprocess multi-device parity (2×2 and multipod (2,2,2) meshes, and the
  full ``assemble()`` gspmd↔shard_map path on 4 devices), with the measured
  ``exchange_words_align`` asserted EXACTLY equal to the analytic
  ``bench_comm_model.words_align`` — the same contract
  ``scripts/check_smoke_comm.py`` enforces on CI artifacts.

Seeded determinism (the run-to-run half of the parity story) lives here too:
``assemble()`` at a fixed seed must be byte-identical across two runs and
across ``backend="reference"|"pallas"``.
"""

import os

import numpy as np
import jax.numpy as jnp
import pytest
from _dist_helpers import run_with_devices
from _hypothesis_compat import given, settings, st

from repro.assembly import alignment as al

_K = 7
_E = 4  # pairs per drawn example — fixed so jit caches persist across draws
_L = 96  # fixed code-row width, same reason


def _pair_batch(seed, err, e=_E, length=_L):
    """``e`` read pairs sharing a planted exact ``_K``-mer seed at
    (pa, pb), with the overlapping suffix of ``a`` copied into ``b`` (so the
    extension has signal) and substitution noise at rate ``err`` everywhere
    except the seed window."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 4, (e, length)).astype(np.uint8)
    b = rng.integers(0, 4, (e, length)).astype(np.uint8)
    la = rng.integers(_K + 8, length + 1, e).astype(np.int32)
    lb = rng.integers(_K + 8, length + 1, e).astype(np.int32)
    pa = (rng.integers(0, 1 << 30, e) % (la - _K)).astype(np.int32)
    pb = (rng.integers(0, 1 << 30, e) % (lb - _K)).astype(np.int32)
    for t in range(e):
        n_fwd = min(la[t] - pa[t], lb[t] - pb[t])
        b[t, pb[t]:pb[t] + n_fwd] = a[t, pa[t]:pa[t] + n_fwd]
        n_bwd = min(pa[t], pb[t])
        b[t, pb[t] - n_bwd:pb[t]] = a[t, pa[t] - n_bwd:pa[t]]
    noise = rng.random((e, length)) < err
    for t in range(e):
        noise[t, pb[t]:pb[t] + _K] = False  # keep the seed exact
    b = np.where(noise, (b + rng.integers(1, 4, (e, length))) % 4, b)
    return a.astype(np.uint8), la, b.astype(np.uint8), lb, pa, pb


def _extend(a, la, b, lb, pa, pb, backend="reference", band=17, **kw):
    return al.batch_extend(
        jnp.asarray(a), jnp.asarray(la), jnp.asarray(b), jnp.asarray(lb),
        jnp.asarray(pa), jnp.asarray(pb), k=_K, backend=backend, band=band,
        max_steps=128, **kw,
    )


# ---------------------------------------------------------------------------
# property layer: batch_extend
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    st.integers(0, 2**31 - 1),
    st.floats(0.0, 0.25),
    st.sampled_from([5, 20, 40]),
    st.sampled_from([(1, -1, -1), (2, -3, -2)]),
    st.sampled_from([17, 33]),
)
def test_batch_extend_ref_pallas_bit_parity(seed, err, xd, scoring, band):
    """The alignment-stack parity contract as a property: for random
    sequences, error rates, x-drop thresholds, scoring triples and bands the
    reference and pallas backends must agree on every PairAlignment field
    bit-for-bit (both extensions, both directions)."""
    match, mismatch, gap = scoring
    batch = _pair_batch(seed, err)
    kw = dict(xdrop=xd, match=match, mismatch=mismatch, gap=gap, band=band)
    ref = _extend(*batch, backend="reference", **kw)
    pal = _extend(*batch, backend="pallas", **kw)
    for name, x, y in zip(ref._fields, ref, pal):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(0, 3))
def test_batch_extend_permutation_and_pad_invariance(seed, n_pad):
    """Per-pair independence — the property that makes the candidate-axis
    block split of ``core/align_dist.py`` bit-safe: permuting the candidate
    pairs permutes the outputs identically, and appending zero pad slots
    never perturbs the live entries."""
    a, la, b, lb, pa, pb = _pair_batch(seed, 0.08)
    base = _extend(a, la, b, lb, pa, pb)

    perm = np.random.default_rng(seed ^ 0xA5A5).permutation(_E)
    permuted = _extend(a[perm], la[perm], b[perm], lb[perm], pa[perm],
                       pb[perm])
    for name, x, y in zip(base._fields, base, permuted):
        np.testing.assert_array_equal(np.asarray(x)[perm], np.asarray(y),
                                      err_msg=name)

    if n_pad:
        def _pad(x):
            z = np.zeros((n_pad,) + x.shape[1:], x.dtype)
            return np.concatenate([x, z])

        padded = _extend(*(_pad(x) for x in (a, la, b, lb, pa, pb)))
        for name, x, y in zip(base._fields, base, padded):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y)[:_E],
                                          err_msg=name)


# ---------------------------------------------------------------------------
# align_bucket_shard_map, degenerate P=1 mesh (in-process single device)
# ---------------------------------------------------------------------------


def test_align_bucket_single_device_matches_batch_extend():
    from repro.core.align_dist import align_bucket_shard_map

    a, la, b, lb, pa, pb = _pair_batch(11, 0.1, e=6)
    codes = np.concatenate([a, b], 0)  # reads 0..5 = a side, 6..11 = b side
    cand = {
        "i": np.arange(6), "j": 6 + np.arange(6), "li": la, "lj": lb,
        "pa": pa, "pb": pb, "strand": np.zeros(6, np.int32),
    }
    cand = {key: jnp.asarray(v, jnp.int32) for key, v in cand.items()}
    res, stats = align_bucket_shard_map(
        jnp.asarray(codes), cand, k=_K, backend="reference", band=17,
        max_steps=128,
    )
    exp = _extend(a, la, b, lb, pa, pb)
    for name, x, y in zip(exp._fields, exp, res):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)
    # present-and-zero on a single-device mesh: the align_exchange group is
    # emitted (schema contract) but no words move
    assert stats["exchange_words_align"] == 0
    assert stats["exchange_rounds_align"] == 0


def test_align_bucket_single_device_pallas_matches_reference():
    """The shard_map region runs the Pallas x-drop kernel too (its varying
    manual axes type-check), bit-identical to the reference extension."""
    from repro.core.align_dist import align_bucket_shard_map

    a, la, b, lb, pa, pb = _pair_batch(12, 0.1, e=6)
    codes = np.concatenate([a, b], 0)
    cand = {
        "i": np.arange(6), "j": 6 + np.arange(6), "li": la, "lj": lb,
        "pa": pa, "pb": pb, "strand": np.zeros(6, np.int32),
    }
    cand = {key: jnp.asarray(v, jnp.int32) for key, v in cand.items()}
    res, _ = align_bucket_shard_map(
        jnp.asarray(codes), cand, k=_K, backend="pallas", band=17,
        max_steps=128,
    )
    exp = _extend(a, la, b, lb, pa, pb)
    for name, x, y in zip(exp._fields, exp, res):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# multi-device parity + exact exchange accounting (subprocess)
# ---------------------------------------------------------------------------

_ROOT = os.path.join(os.path.dirname(__file__), "..")

# bucket (10) deliberately NOT a multiple of P → exercises the pad path;
# strand-1 pairs exercise the in-region revcomp orientation.
_DIST_CODE = """
import sys
sys.path.insert(0, __ROOT__)
import numpy as np, jax.numpy as jnp
from repro.assembly import alignment as al
from repro.assembly.kmers import revcomp
from repro.core.align_dist import align_bucket_shard_map
from repro.core.components_dist import infer_row_axes
from repro.launch.mesh import make_test_mesh
from benchmarks.bench_comm_model import words_align

K = 7
E, L = 10, 96
rng = np.random.default_rng(42)
a = rng.integers(0, 4, (E, L)).astype(np.uint8)
b = rng.integers(0, 4, (E, L)).astype(np.uint8)
la = rng.integers(K + 8, L + 1, E).astype(np.int32)
lb = rng.integers(K + 8, L + 1, E).astype(np.int32)
pa = (rng.integers(0, 1 << 30, E) % (la - K)).astype(np.int32)
pb = (rng.integers(0, 1 << 30, E) % (lb - K)).astype(np.int32)
for t in range(E):
    n = min(la[t] - pa[t], lb[t] - pb[t])
    b[t, pb[t]:pb[t] + n] = a[t, pa[t]:pa[t] + n]
noise = rng.random((E, L)) < 0.08
for t in range(E):
    noise[t, pb[t]:pb[t] + K] = False
b = np.where(noise, (b + rng.integers(1, 4, (E, L))) % 4, b).astype(np.uint8)
strand = (np.arange(E) % 2).astype(np.int32)  # odd pairs arrive strand-1

# the stored partner row is the reverse complement of the oriented b the
# local oracle aligns; align_bucket_shard_map re-orients in-region
stored_b = np.asarray(revcomp(jnp.asarray(b), jnp.asarray(lb)))
stored_b = np.where((strand == 1)[:, None], stored_b, b).astype(np.uint8)

codes = np.concatenate([a, stored_b], 0)
cand = dict(i=np.arange(E), j=E + np.arange(E), li=la, lj=lb, pa=pa, pb=pb,
            strand=strand)
cand = {k: jnp.asarray(v, jnp.int32) for k, v in cand.items()}

kw = dict(k=K, backend="reference", band=17, max_steps=128)
exp = al.batch_extend(jnp.asarray(a), jnp.asarray(la), jnp.asarray(b),
                      jnp.asarray(lb), jnp.asarray(pa), jnp.asarray(pb), **kw)

mesh = make_test_mesh(__SHAPE__, __AXES__)
res, stats = align_bucket_shard_map(jnp.asarray(codes), cand, mesh=mesh, **kw)
for name, x, y in zip(exp._fields, exp, res):
    assert np.array_equal(np.asarray(x), np.asarray(y)), name

row_axes = infer_row_axes(mesh)
p = 1
for ax in row_axes:
    p *= mesh.shape[ax]
assert p == __P__, (row_axes, p)
n_pad = -(-codes.shape[0] // p) * p
bucket_pad = -(-E // p) * p
wm = words_align(n_pad=n_pad, row_width=L, bucket_pad=bucket_pad, p=p)
assert stats["exchange_words_align"] == wm, (dict(stats), wm)
hops = sum(mesh.shape[ax] - 1 for ax in row_axes)
assert stats["exchange_rounds_align"] == hops + 1, dict(stats)
print("OK", p, stats["exchange_words_align"])
"""


def _dist_code(shape, axes, p):
    return (
        _DIST_CODE
        .replace("__ROOT__", repr(_ROOT))
        .replace("__SHAPE__", repr(shape))
        .replace("__AXES__", repr(axes))
        .replace("__P__", repr(p))
    )


@pytest.mark.dist
def test_align_bucket_matches_local_on_2x2_mesh():
    """2×2 ("data", "model") mesh: the candidate axis splits over the one
    grid-row axis (P=2); scores/coords bit-identical to the local path and
    the measured words exactly equal to the analytic model."""
    out = run_with_devices(_dist_code((2, 2), ("data", "model"), 2),
                           n_devices=4)
    assert "OK 2" in out


@pytest.mark.dist
def test_align_bucket_matches_local_on_multipod_mesh():
    """Multipod (2,2,2) ("pod","data","model") mesh: the row split nests two
    axes (P=4) and the telescoped ring-gather accounting must still equal
    ``words_align`` exactly."""
    out = run_with_devices(
        _dist_code((2, 2, 2), ("pod", "data", "model"), 4), n_devices=8,
    )
    assert "OK 4" in out


@pytest.mark.dist
def test_assemble_shard_map_alignment_matches_gspmd():
    """Full-pipeline acceptance: ``distribution="shard_map"`` routes the
    alignment stage through ``align_bucket_shard_map`` and must reproduce
    the gspmd run bit-for-bit — R/S graphs, accepted-pair count, contig
    stats — while reporting live alignment exchange words that match
    ``words_align`` exactly (the gspmd run reports the same keys
    present-and-zero)."""
    run_with_devices(f"""
import sys
sys.path.insert(0, {_ROOT!r})
import numpy as np, jax
from repro.assembly.pipeline import PipelineConfig, assemble
from repro.assembly.simulate import simulate_genome, simulate_reads
from repro.core.spmat import ell_equal
from benchmarks.bench_comm_model import words_align

rng = np.random.default_rng(3)
g = simulate_genome(rng, 3000)
rs = simulate_reads(g, depth=8, mean_len=400, std_len=60, error_rate=0.02,
                    seed=4)
kw = dict(m_capacity=1 << 15, upper=48, read_capacity=64,
          overlap_capacity=32, r_capacity=24, band=17, max_steps=512,
          align_chunk=1024, xdrop=25, polish=False)
gs = assemble(rs.codes, rs.lengths, PipelineConfig(distribution="gspmd", **kw))
sm = assemble(rs.codes, rs.lengths,
              PipelineConfig(distribution="shard_map", **kw))

assert ell_equal(gs.r_graph, sm.r_graph)
assert ell_equal(gs.s_graph, sm.s_graph)
assert gs.stats["contigs"] == sm.stats["contigs"]
for key in ("n_aligned", "n_passed", "nnz_R", "nnz_S", "tr_iterations"):
    assert gs.stats[key] == sm.stats[key], key

assert gs.stats["align_distribution"] == "gspmd"
assert sm.stats["align_distribution"] == "shard_map"
assert gs.stats["exchange_words_align"] == 0  # present-and-zero
assert gs.stats["exchange_rounds_align"] == 0
p = len(jax.devices())
n_pad = -(-sm.stats["n_reads"] // p) * p
bucket_pad = -(-sm.stats["align_bucket"] // p) * p
wm = words_align(n_pad=n_pad, row_width=rs.codes.shape[1],
                 bucket_pad=bucket_pad, p=p)
assert sm.stats["exchange_words_align"] == wm, (
    sm.stats["exchange_words_align"], wm)
assert sm.stats["exchange_rounds_align"] == p
print("OK", sm.stats["exchange_words_align"])
""", n_devices=4)


# ---------------------------------------------------------------------------
# seeded determinism (two runs byte-identical; reference ≡ pallas)
# ---------------------------------------------------------------------------

# stats keys whose values are allowed to differ between byte-identical runs
# (memory sampling) or that *name* the path that ran (backend labels)
_MEM_KEYS = ("peak_hbm_bytes", "hbm_bytes_in_use", "hbm_source")
# labels naming the path that ran, plus counters measuring the path rather
# than the result (the host contig walk reports cc_iterations=0; the device
# pointer-doubling path reports the round count)
_PATH_KEYS = ("backend", "summa_backend", "tr_backend", "distribution",
              "cc_iterations", "op_impls")


def _stats_sans(stats, drop):
    return {k: v for k, v in stats.items() if k not in drop}


@pytest.fixture(scope="module")
def determinism_runs():
    from repro.assembly.pipeline import PipelineConfig, assemble
    from repro.assembly.simulate import simulate_genome, simulate_reads

    rng = np.random.default_rng(3)
    g = simulate_genome(rng, 3000)
    rs = simulate_reads(g, depth=8, mean_len=400, std_len=60,
                        error_rate=0.02, seed=4)

    def _cfg(backend):
        return PipelineConfig(
            m_capacity=1 << 15, upper=48, read_capacity=64,
            overlap_capacity=32, r_capacity=24, band=17, max_steps=512,
            align_chunk=1024, xdrop=25, backend=backend,
        )

    return (
        assemble(rs.codes, rs.lengths, _cfg("reference")),
        assemble(rs.codes, rs.lengths, _cfg("reference")),
        assemble(rs.codes, rs.lengths, _cfg("pallas")),
    )


def test_assemble_seeded_run_to_run_determinism(determinism_runs):
    """Two ``assemble()`` calls at a fixed seed must be byte-identical:
    every graph tensor, the contig/consensus tensors, and every stats entry
    except the memory-sampling gauges."""
    r1, r2, _ = determinism_runs
    for attr in ("r_graph", "s_graph"):
        m1, m2 = getattr(r1, attr), getattr(r2, attr)
        np.testing.assert_array_equal(np.asarray(m1.cols), np.asarray(m2.cols))
        np.testing.assert_array_equal(np.asarray(m1.vals), np.asarray(m2.vals))
    np.testing.assert_array_equal(np.asarray(r1.contained),
                                  np.asarray(r2.contained))
    assert _stats_sans(r1.stats, _MEM_KEYS) == _stats_sans(r2.stats, _MEM_KEYS)
    c1, c2 = r1.consensus, r2.consensus
    assert c1.n_contigs == c2.n_contigs
    for field in ("codes", "lengths", "states", "depth", "agree"):
        np.testing.assert_array_equal(
            np.asarray(getattr(c1, field)), np.asarray(getattr(c2, field)),
            err_msg=field,
        )
    for x, y in zip(r1.polished_contigs, r2.polished_contigs):
        assert x.reads == y.reads and x.length == y.length
        np.testing.assert_array_equal(np.asarray(x.codes), np.asarray(y.codes))


def test_assemble_seeded_backend_determinism(determinism_runs):
    """At the same fixed seed, ``backend="pallas"`` must agree with the
    reference run on the full numeric stats dict (only the path labels and
    memory gauges may differ) and on the polished contig bytes."""
    r1, _, r3 = determinism_runs
    assert r3.stats["backend"] == "pallas"
    drop = _MEM_KEYS + _PATH_KEYS
    assert _stats_sans(r1.stats, drop) == _stats_sans(r3.stats, drop)
    assert len(r1.polished_contigs) == len(r3.polished_contigs)
    for x, y in zip(r1.polished_contigs, r3.polished_contigs):
        assert x.reads == y.reads and x.length == y.length
        np.testing.assert_array_equal(np.asarray(x.codes), np.asarray(y.codes))
