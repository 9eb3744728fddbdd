"""Fig. 9 analogue: overlap detection, 1D outer-product algorithm vs the 2D
SpGEMM formulation, same inputs.

The 1D variant emulates diBELLA 1D's distributed-hash-table detection: group
k-mer instances by k-mer (the "owner bucket"), emit all read pairs per bucket
(a² per k-mer), then globally deduplicate — an outer-product SpGEMM.  The 2D
variant is our row-expansion SpGEMM on A·Aᵀ.  Also reports the model word
counts (a²m/P vs am/√P, paper §V-B).

``distributions=("local", "shard_map")`` adds the explicit-exchange ring
SUMMA rows (DESIGN.md §2.11): ``overlap[shard_map]/ring_<pr>x<pc>`` with the
measured per-``ppermute`` ``exchange_words_summa`` next to the analytic
``model_words_summa`` (``bench_comm_model.words_summa``) in the derived
field, plus the distributed x-drop row (§2.12):
``align[shard_map]/bucket<b>_P<p>`` with ``exchange_words_align`` vs
``model_words_align`` — ``scripts/check_smoke_comm.py`` asserts both pairs
match exactly."""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ._timing import timed


def _inputs(genome=10_000):
    from repro.assembly.counter import build_matrices, count_and_select
    from repro.assembly.kmers import extract_kmers
    from repro.assembly.simulate import simulate_genome, simulate_reads

    rng = np.random.default_rng(3)
    g = simulate_genome(rng, genome)
    rs = simulate_reads(g, depth=12, mean_len=900, std_len=120,
                        error_rate=0.03, seed=4)
    km = extract_kmers(jnp.asarray(rs.codes), jnp.asarray(rs.lengths), k=15)
    kc = count_and_select(km, k=15, lower=2, upper=24)
    a, at, _, _ = build_matrices(kc, n_reads=rs.n_reads, m_capacity=1 << 14,
                                 read_capacity=128, kmer_capacity=24)
    return a, at, kc, rs


def _outer_product_1d(at, n_reads, cap):
    """Per-k-mer bucket pair expansion (diBELLA-1D-like)."""
    from repro.core.semiring import overlap_semiring as OV
    from repro.core.spmat import from_coo

    m, u = at.cols.shape
    reads = at.cols  # (m, u) read ids per kmer
    pos = at.vals["pos"]
    valid = reads >= 0
    ii = jnp.broadcast_to(reads[:, :, None], (m, u, u)).reshape(-1)
    jj = jnp.broadcast_to(reads[:, None, :], (m, u, u)).reshape(-1)
    pi = jnp.broadcast_to(pos[:, :, None], (m, u, u)).reshape(-1)
    pj = jnp.broadcast_to(pos[:, None, :], (m, u, u)).reshape(-1)
    ok = (jnp.broadcast_to(valid[:, :, None] & valid[:, None, :],
                           (m, u, u)).reshape(-1) & (ii != jj))
    vals = {"cnt": jnp.ones_like(ii, jnp.int32),
            "apos": jnp.stack([pi, jnp.full_like(pi, -1)], -1),
            "bpos": jnp.stack([pj, jnp.full_like(pj, -1)], -1)}
    c, ovf = from_coo(ii, jj, vals, ok, n_rows=n_reads, n_cols=n_reads,
                      capacity=cap, semiring=OV)
    return c


def _ring_rows(a, at, n_reads, cap):
    """Time the explicit-exchange ring SUMMA path and cross-check words.

    Emits one ``overlap[shard_map]/ring_<pr>x<pc>`` row whose derived field
    carries the measured ``exchange_words_summa`` (counted per ``ppermute``
    at trace time) and the analytic ``model_words_summa`` from Table I —
    ``scripts/check_smoke_comm.py`` requires the two to agree.
    """
    from repro.assembly.counter import first_semiring
    from repro.core.semiring import overlap_semiring as OV
    from repro.core.summa import default_summa_mesh, overlap_spgemm_shard_map

    from .bench_comm_model import words_summa

    mesh = default_summa_mesh()
    pr = mesh.shape["data"]
    pc = mesh.shape["model"]

    def call():
        c, ovf, st = overlap_spgemm_shard_map(
            a, at, semiring=OV, operand_semiring=first_semiring,
            capacity=cap, mesh=mesh)
        return c, st

    t = timed(call, out_of=lambda r: r[0].cols)
    (c, st), t_ring = t.result, t.steady_us

    n_pad = -(-n_reads // pr) * pr
    m_rows = at.cols.shape[0]
    m_pad = -(-m_rows // pr) * pr
    # {"pos"} payload: 1 col word + 1 value word per slot.
    wm = words_summa(n_rows=n_pad, a_block_slots=a.capacity,
                     a_words_per_slot=2, m_rows=m_pad,
                     b_block_slots=at.capacity, b_words_per_slot=2,
                     pr=pr, pc=pc)
    derived = (f"exchange_words_summa={st['exchange_words_summa']}"
               f";model_words_summa={wm}"
               f";exchange_rounds_summa={st['exchange_rounds_summa']}"
               f";summa_algorithm={st['summa_algorithm']}"
               f";hbm_round_trips={st.get('spgemm_hbm_round_trips', 0)}"
               f";nnzC={int(c.nnz())}")
    return [(f"overlap[shard_map]/ring_{pr}x{pc}", t_ring, derived,
             t.compile_us, t.peak_hbm_bytes, t.hbm_source)]


def _align_rows(a, at, rs, cap, k=15):
    """Time the distributed x-drop extension and cross-check words.

    Rebuilds the pipeline's pv-valid candidate compaction from the local
    SpGEMM product, then routes the bucket through
    ``core.align_dist.align_bucket_shard_map`` on the default row mesh.
    Emits one ``align[shard_map]/bucket<b>_P<p>`` row whose derived field
    carries the measured ``exchange_words_align`` next to the analytic
    ``model_words_align`` (``bench_comm_model.words_align``) —
    ``scripts/check_smoke_comm.py`` requires the two to agree exactly."""
    from repro.core.align_dist import align_bucket_shard_map
    from repro.core.components_dist import default_row_mesh, infer_row_axes
    from repro.core.semiring import overlap_semiring as OV
    from repro.core.spgemm import spgemm
    from repro.core.spmat import next_pow2

    from .bench_comm_model import words_align

    n = rs.n_reads
    codes = jnp.asarray(rs.codes, jnp.uint8)
    lengths = jnp.asarray(rs.lengths, jnp.int32)
    c, _ = spgemm(a, at, semiring=OV, capacity=cap)

    # the pipeline's candidate compaction (assembly/pipeline.py Alignment)
    pair_i = jnp.broadcast_to(jnp.arange(n)[:, None], (n, cap)).reshape(-1)
    pair_j = c.cols.reshape(-1)
    cnt = c.vals["cnt"].reshape(-1)
    apos = c.vals["apos"][..., 0].reshape(-1)
    bpos = c.vals["bpos"][..., 0].reshape(-1)
    pv = (pair_j > pair_i) & (cnt >= 2)
    pa, ca = apos // 2, apos % 2
    pb, cb = bpos // 2, bpos % 2
    strand = jnp.where(pv, ca ^ cb, 0)
    li = lengths[jnp.where(pv, pair_i, 0)]
    lj = lengths[jnp.where(pv, pair_j, 0)]
    pb_or = jnp.where(strand == 1, lj - k - pb, pb)
    bucket = next_pow2(int(jnp.sum(pv)))
    idx = jnp.nonzero(pv, size=bucket, fill_value=0)[0]
    cand = {
        "i": pair_i[idx], "j": pair_j[idx], "li": li[idx], "lj": lj[idx],
        "pa": jnp.maximum(pa[idx], 0), "pb": jnp.maximum(pb_or[idx], 0),
        "strand": strand[idx],
    }

    mesh = default_row_mesh()
    p = 1
    for ax in infer_row_axes(mesh):
        p *= mesh.shape[ax]

    def call():
        return align_bucket_shard_map(
            codes, cand, k=k, mesh=mesh, backend="reference",
            band=33, max_steps=1024,
        )

    t = timed(call, out_of=lambda r: r[0].score)
    (res, st), t_align = t.result, t.steady_us

    n_pad = -(-n // p) * p
    bucket_pad = -(-bucket // p) * p
    wm = words_align(n_pad=n_pad, row_width=int(codes.shape[1]),
                     bucket_pad=bucket_pad, p=p)
    derived = (f"exchange_words_align={st['exchange_words_align']}"
               f";model_words_align={wm}"
               f";exchange_rounds_align={st['exchange_rounds_align']}"
               f";bucket={bucket}"
               f";n_scored={int(jnp.sum(res.score > 0))}")
    return [(f"align[shard_map]/bucket{bucket_pad}_P{p}", t_align, derived,
             t.compile_us, t.peak_hbm_bytes, t.hbm_source)]


def run(distributions=("local",), genome=10_000):
    from repro.core.semiring import overlap_semiring as OV
    from repro.core.spgemm import spgemm

    a, at, kc, rs = _inputs(genome)
    n = rs.n_reads

    rows = []
    if "shard_map" in distributions:
        rows += _ring_rows(a, at, n, 64)
        rows += _align_rows(a, at, rs, 64)
    if "local" not in distributions:
        return rows

    # repro: noqa[R001] — benchmark: jit built once per measurement.
    f2d = jax.jit(lambda: spgemm(a, at, semiring=OV, capacity=64))
    t2 = timed(f2d, out_of=lambda r: r[0].cols)
    (c2d, _), t_2d = t2.result, t2.steady_us

    # repro: noqa[R001] — benchmark: jit built once per measurement.
    f1d = jax.jit(lambda: _outer_product_1d(at, n, 64))
    t1 = timed(f1d, out_of=lambda r: r.cols)
    c1d, t_1d = t1.result, t1.steady_us

    # same candidate pairs?
    same = int(jnp.sum((c2d.cols >= 0) != (c1d.cols >= 0)))
    # model words at P=1024 (paper Table I)
    m_real = int(kc.m_reliable)
    am = float(a.nnz())
    p = 1024
    w1d = (am / m_real) * am / p if m_real else 0
    w2d = am / (p ** 0.5)
    rows += [
        ("overlap/2d_spgemm", t_2d, f"nnzC={int(c2d.nnz())}",
         t2.compile_us, t2.peak_hbm_bytes, t2.hbm_source),
        ("overlap/1d_outer_product", t_1d,
         f"pattern_mismatches={same};speedup_2d={t_1d / t_2d:.2f}x",
         t1.compile_us, t1.peak_hbm_bytes, t1.hbm_source),
        ("overlap/model_words_P1024", 0.0,
         f"W1D={w1d:.3e};W2D={w2d:.3e}", 0.0),
    ]
    return rows
