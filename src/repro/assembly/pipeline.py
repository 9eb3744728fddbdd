"""diBELLA 2D pipeline — the paper's Algorithm 1, end to end.

    reads → k-mer count/select → A, Aᵀ → C = A·Aᵀ (overlap semiring)
          → x-drop alignment on nnz(C) → prune by score → R
          → transitive reduction (Algorithm 2) → S → contigs
          → consensus (pileup polish, DESIGN.md §2.8)

Every stage is the JAX/TPU adaptation documented in DESIGN.md §2; stages are
individually jitted, and the overlap SpGEMM + transitive reduction can run
either locally or 2D-distributed over a mesh (SUMMA).  Per-stage wall-clock is
collected for the Fig. 5–8 style breakdown benchmark; with
``PipelineConfig.trace`` the same stage boundaries open :mod:`repro.obs`
spans, nesting the shard_map phase and dispatched-op spans the sub-stages
emit, and the resulting span tree is exportable as a Chrome trace
(``repro.obs.write_chrome_trace``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..core.backend import (
    recording_impls,
    resolve_backend,
    resolve_distribution,
)
from ..core.semiring import overlap_semiring
from ..core.spgemm import spgemm
from ..core.spmat import map_row_blocks, next_pow2
from ..core.summa import default_summa_mesh, overlap_spgemm_shard_map
from ..core.string_graph import build_overlap_graph, classify_overlaps, drop_contained
from ..core.transitive_reduction import (
    transitive_reduction,
    transitive_reduction_fused,
)
from ..obs import (
    Metrics,
    Tracer,
    counting_readbacks,
    readback,
    span,
    tracing,
    watermark,
)
from . import alignment as al
from .consensus import polish_contig_set
from .contig_gen import generate_contigs
from .contigs import contig_stats
from .counter import build_matrices, count_and_select
from .kmers import extract_kmers, revcomp


# 4-byte words the local overlap SpGEMM's merge sorts at once, at most: each
# of rows × K_A × K_B candidates carries its column key and one word per
# value plane through the row sorts.  2^27 words held 2^26 candidates when
# the sort carried a key and an index; at K_A·K_B = 8960 that is 4096-row
# blocks, for which XLA's v5e compile reserved 2.1 GB of temporaries (4.7 GB
# for 10,000 rows unblocked; an E. coli-length run unblocked would not fit
# 16 GB of HBM).
SPGEMM_SORT_WORDS = 1 << 27


@dataclasses.dataclass
class PipelineConfig:
    k: int = 15
    lower: int = 2  # reliable k-mer frequency window [lower, upper]
    upper: int = 8
    read_capacity: int = 128  # K_A: reliable k-mers kept per read
    m_capacity: int = 1 << 16  # static bound on reliable-unique k-mers
    overlap_capacity: int = 64  # K_C: candidate overlaps per read
    r_capacity: int = 48  # K_R: overlap-graph row capacity
    min_shared_kmers: int = 2
    # alignment
    xdrop: int = 20
    match: int = 1
    mismatch: int = -1
    gap: int = -1
    band: int = 65
    max_steps: int = 4096
    score_frac: float = 0.35  # accept if score ≥ frac · overlap span
    min_overlap: int = 100
    end_fuzz: int = 40
    # transitive reduction
    tr_fuzz: float = 150.0
    tr_max_iters: int = 8
    fused_tr: bool = True  # beyond-paper sampled square (DESIGN.md §2)
    align_chunk: int = 4096
    # consensus polishing of the contig tensor (DESIGN.md §2.8)
    polish: bool = True
    min_depth: int = 2  # pileup votes required before a column is re-called
    pileup_band: int = 512  # contig columns per pileup kernel block
    junction_radius: int = 12  # chain-junction refinement shift search radius
    # kernel backend for the hot ops (x-drop extension, min-plus squares):
    # "auto" = compiled Pallas on TPU, reference jnp elsewhere (DESIGN.md §2.5)
    backend: str = "auto"
    # distribution of the explicitly-exchanged stages (DESIGN.md §2.9-§2.11):
    # "gspmd" = auto-sharded, "shard_map" = (a) the overlap SpGEMM on the
    # explicit-exchange ring SUMMA (core/summa.py, 2D ("data", "model") mesh
    # built when `mesh` lacks a "model" axis), (b) the x-drop extension
    # block-split along the candidate-pair axis over the mesh's grid-row
    # axes (core/align_dist.py, §2.12) and (c) the contig chain stage's
    # branch cut + doubling + ring-bitonic ordering under one ppermute/psum
    # exchange region over `mesh` (a 1D device mesh is built when None)
    distribution: str = "gspmd"
    mesh: Any = None
    # ring-SUMMA stages fused per spgemm_ring_stages call (the fused Pallas
    # kernel's HBM round trips = ceil(√P / this))
    summa_stages_per_call: int = 4
    # collect a hierarchical span trace (stage → shard_map phase → op) on
    # AssemblyResult.trace; spans and host readbacks also forward to
    # jax.profiler.TraceAnnotation so device profiles carry the same names
    trace: bool = False


@dataclasses.dataclass
class AssemblyResult:
    r_graph: Any  # overlap matrix R (EllMatrix)
    s_graph: Any  # string matrix S (EllMatrix)
    contigs: list  # draft contigs (raw read concatenation)
    stats: Dict[str, Any]
    timings: Dict[str, float]
    contained: Any = None  # (n,) bool, reads dropped as contained
    consensus: Any = None  # ConsensusResult when cfg.polish (DESIGN.md §2.8)
    trace: Any = None  # obs.Tracer with the span tree when cfg.trace

    @functools.cached_property
    def polished_contigs(self) -> list:
        """Consensus-polished contigs (materialized once from the polished
        tensor); falls back to the draft when the polish stage was
        disabled."""
        return self.consensus.to_contigs() if self.consensus else self.contigs


# Key the persistent compile cache on op metadata.  By default JAX strips
# metadata from the key, so it reuses an executable cached from the same
# program under other op names (another version of this code, before a
# named scope was added), and a profile then reads that version's names.
# Locations keep one frame, so a key does not change with the caller's
# line: the alignment's per-job compile still finds its cached program.
# (Turning full tracebacks off instead drops most ops' names.)
_CACHE_KEY_FLAGS = {"jax_compilation_cache_include_metadata_in_key": True,
                    "jax_traceback_in_locations_limit": 1}


@contextlib.contextmanager
def _op_names_in_cache_key():
    was = {flag: getattr(jax.config, flag) for flag in _CACHE_KEY_FLAGS}
    for flag, value in _CACHE_KEY_FLAGS.items():
        jax.config.update(flag, value)
    try:
        yield
    finally:
        for flag, value in was.items():
            jax.config.update(flag, value)


@contextlib.contextmanager
def _tic(timings, key):
    """Stage timing as a thin wrapper over :func:`repro.obs.span` — the one
    timing code path.  The span device-syncs on whatever the body passes to
    ``sp.set_output`` (any pytree, dataclasses included), so the recorded
    wall-clock measures execution rather than async dispatch, and the stage
    appears in the active tracer's tree when tracing is on."""
    with span(key, kind="stage") as sp:
        yield sp
    timings[key] = timings.get(key, 0.0) + sp.duration_s


def assemble(codes, lengths, cfg: PipelineConfig = PipelineConfig()) -> AssemblyResult:
    # the whole run executes under a device-memory watermark (obs/memory.py)
    # so every AssemblyResult.stats carries the peak_hbm_bytes family —
    # HBM capacity is the genome-size ceiling, and the watermark is what the
    # bench trajectory and the regression gate track
    with watermark() as wm, recording_impls() as impls, \
            counting_readbacks() as reads, _op_names_in_cache_key():
        tracer = Tracer(annotate=True) if cfg.trace else None
        if tracer is None:
            res = _assemble(codes, lengths, cfg, tracer=None)
        else:
            with tracing(tracer):
                res = _assemble(codes, lengths, cfg, tracer=tracer)
    from ..obs import validated

    res.stats.update(validated({
        "peak_hbm_bytes": wm.peak_hbm_bytes,
        "hbm_bytes_in_use": wm.hbm_bytes_in_use,
        "hbm_source": wm.source,
        # what actually ran for every dispatched op (core/backend.py)
        "op_impls": {op: "+".join(sorted(v)) for op, v in impls.items()},
        # blocking device→host reads of the driver below (obs.readback)
        "host_readbacks": reads.n,
    }, context="assemble"))
    return res


def _assemble(codes, lengths, cfg: PipelineConfig, *, tracer) -> AssemblyResult:
    codes = jnp.asarray(codes, jnp.uint8)
    lengths = jnp.asarray(lengths, jnp.int32)
    n = codes.shape[0]
    backend = resolve_backend(cfg.backend)
    timings: Dict[str, float] = {}
    metrics = Metrics(context="assemble")
    metrics.emit("n_reads", int(n))
    metrics.emit("backend", backend)

    # --- CountKmer (paper: CountKmer) ---
    with _tic(timings, "CountKmer") as sp:
        kmers = extract_kmers(codes, lengths, k=cfg.k)
        kc = sp.set_output(
            count_and_select(kmers, k=cfg.k, lower=cfg.lower,
                             upper=cfg.upper)
        )
    m_reliable = int(readback(kc.m_reliable, "m_reliable"))
    metrics.emit_many({
        "m_reliable": m_reliable,
        "n_unique_kmers": int(readback(kc.n_unique, "n_unique_kmers")),
        "n_singletons": int(readback(kc.n_singleton, "n_singletons")),
    })
    assert m_reliable <= cfg.m_capacity, (
        f"m_capacity too small: {m_reliable} > {cfg.m_capacity}"
    )

    # --- CreateSpMat: A and Aᵀ ---
    with _tic(timings, "CreateSpMat") as sp:
        a, at, ovf_a, ovf_at = build_matrices(
            kc,
            n_reads=int(n),
            m_capacity=cfg.m_capacity,
            read_capacity=cfg.read_capacity,
            kmer_capacity=cfg.upper,
        )
        sp.set_output((a.cols, at.cols))
    metrics.emit("overflow_A", int(readback(ovf_a, "overflow_A")))
    metrics.emit("nnz_A", int(readback(a.nnz(), "nnz_A")))

    # --- SpGEMM: C = A·Aᵀ under the overlap semiring ---
    # distribution="shard_map" runs it on the explicit-exchange ring SUMMA
    # (zero GSPMD sub-stages, DESIGN.md §2.11) — bit-identical to the local
    # product, with the per-ppermute exchange words surfaced in stats.  The
    # summa exchange stats are present-and-zero on the gspmd path, same
    # contract as the contig-stage exchange keys below (seeded from
    # obs.schema's "summa_exchange" group after the branch).
    with _tic(timings, "SpGEMM") as sp:
        if resolve_distribution(cfg.distribution) == "shard_map":
            from .counter import first_semiring

            summa_mesh = cfg.mesh
            if (
                summa_mesh is None
                or "model" not in getattr(summa_mesh, "axis_names", ())
                or len(summa_mesh.axis_names) < 2
            ):
                summa_mesh = default_summa_mesh()
            c_mat, ovf_c, summa_stats = overlap_spgemm_shard_map(
                a, at, semiring=overlap_semiring,
                operand_semiring=first_semiring,
                capacity=cfg.overlap_capacity, mesh=summa_mesh,
                backend=backend,
                stages_per_call=cfg.summa_stages_per_call,
            )
            metrics.emit("overlap_distribution", "shard_map")
            metrics.emit_many(summa_stats)
        else:
            # the merge sorts rows·K_A·K_B candidates of a few words each;
            # past SPGEMM_SORT_WORDS it runs in row blocks (per-row
            # identical) so a bacterial genome's buffer fits in HBM
            planes = jax.tree.leaves(overlap_semiring.zero((1,)))
            words = 1 + sum(math.prod(p.shape[1:]) for p in planes)
            per_row = a.cols.shape[1] * at.cols.shape[1] * words
            row_chunk = 1 << (max(1, SPGEMM_SORT_WORDS // per_row)
                              .bit_length() - 1)
            c_mat, ovf_c = spgemm(
                a, at, semiring=overlap_semiring,
                capacity=cfg.overlap_capacity, row_chunk=row_chunk,
            )
            metrics.emit("overlap_distribution", "gspmd")
            metrics.emit("spgemm_row_chunk", min(row_chunk, int(n)))
        sp.set_output(c_mat.cols)
    metrics.seed_zero("summa_exchange")
    metrics.emit("overflow_C", int(readback(ovf_c, "overflow_C")))
    metrics.emit("nnz_C", int(readback(c_mat.nnz(), "nnz_C")))
    metrics.emit("c_density", metrics["nnz_C"] / max(1, int(n)))

    # --- Pairwise alignment on nnz(C) (upper triangle; each pair once) ---
    with _tic(timings, "Alignment") as sp:
        kq = cfg.overlap_capacity
        pair_i = jnp.broadcast_to(jnp.arange(n)[:, None], (n, kq)).reshape(-1)
        pair_j = c_mat.cols.reshape(-1)
        cnt = c_mat.vals["cnt"].reshape(-1)
        apos = c_mat.vals["apos"][..., 0].reshape(-1)
        bpos = c_mat.vals["bpos"][..., 0].reshape(-1)
        pv = (pair_j > pair_i) & (cnt >= cfg.min_shared_kmers)

        pa = apos // 2
        ca = apos % 2
        pb = bpos // 2
        cb = bpos % 2
        strand = jnp.where(pv, ca ^ cb, 0)
        li = lengths[jnp.where(pv, pair_i, 0)]
        lj = lengths[jnp.where(pv, pair_j, 0)]
        pb_or = jnp.where(strand == 1, lj - cfg.k - pb, pb)

        # Candidate compaction: C's ELL layout leaves most of the n × K_C
        # slots masked — instead of aligning every slot, gather the pv-valid
        # pairs into a bucket padded to the next power of two of the live
        # count, align only the bucket (row-chunked), and scatter results
        # back to slot order.
        e_total = int(pair_i.shape[0])
        n_live = int(readback(jnp.sum(pv), "n_live"))
        bucket = next_pow2(n_live)
        idx = jnp.nonzero(pv, size=bucket, fill_value=0)[0]
        live = jnp.arange(bucket) < n_live

        cand = {
            "i": pair_i[idx],
            "j": pair_j[idx],
            "li": li[idx],
            "lj": lj[idx],
            "pa": jnp.maximum(pa[idx], 0),
            "pb": jnp.maximum(pb_or[idx], 0),
            "strand": strand[idx],
        }

        # distribution="shard_map" redistributes the bucket over the mesh's
        # grid-row axes inside one explicit-exchange shard_map region
        # (core/align_dist.py, DESIGN.md §2.12) — bit-identical per-pair
        # results, with the gather/scatter words surfaced in stats.  The
        # align exchange stats are present-and-zero on the gspmd path
        # (seeded from obs.schema's "align_exchange" group after the
        # branch), same contract as the summa keys above.
        if resolve_distribution(cfg.distribution) == "shard_map":
            from ..core.align_dist import align_bucket_shard_map

            res_b, align_stats = align_bucket_shard_map(
                codes, cand, k=cfg.k, mesh=cfg.mesh, backend=backend,
                xdrop=cfg.xdrop, match=cfg.match, mismatch=cfg.mismatch,
                gap=cfg.gap, band=cfg.band, max_steps=cfg.max_steps,
            )
            metrics.emit("align_distribution", "shard_map")
            metrics.emit_many(align_stats)
        else:
            def _align_block(blk):
                with jax.named_scope("align_staging"):
                    ai = codes[blk["i"]]
                    bj = codes[blk["j"]]
                    bj = jnp.where((blk["strand"] == 1)[:, None],
                                   revcomp(bj, blk["lj"]), bj)
                out = al.batch_extend(
                    ai, blk["li"], bj, blk["lj"], blk["pa"], blk["pb"],
                    k=cfg.k, backend=backend, xdrop=cfg.xdrop,
                    match=cfg.match, mismatch=cfg.mismatch, gap=cfg.gap,
                    band=cfg.band, max_steps=cfg.max_steps,
                )
                return tuple(out), None

            res_b, _ = map_row_blocks(
                _align_block, cand, n_rows=bucket,
                row_chunk=min(cfg.align_chunk, bucket),
            )
            metrics.emit("align_distribution", "gspmd")

        # Scatter bucket results back to the (n · K_C,) slot layout; dead
        # slots (pv False) keep zeros and are masked out of ``passed`` below.
        safe_slot = jnp.where(live, idx, e_total)

        def _scatter(x):
            buf = jnp.zeros((e_total + 1,) + x.shape[1:], x.dtype)
            return buf.at[safe_slot].set(x)[:e_total]

        res = al.PairAlignment(*(_scatter(x) for x in res_b))
        sp.set_output(res.score)

    ospan = jnp.minimum(res.ei - res.bi, res.ej - res.bj)
    passed = (
        pv
        & (res.score >= cfg.score_frac * ospan)
        & (ospan >= cfg.min_overlap)
    )
    metrics.seed_zero("align_exchange")
    metrics.emit_many({
        "n_aligned": n_live,
        "align_candidates": e_total,
        "align_bucket": int(bucket),
        "n_passed": int(readback(jnp.sum(passed), "n_passed")),
    })

    # --- Build R: classify overlaps, drop contained ---
    with _tic(timings, "BuildR") as sp:
        cls = classify_overlaps(
            res.bi, res.ei, li, res.bj, res.ej, lj, strand,
            end_fuzz=cfg.end_fuzz,
        )
        r_mat, contained, ovf_r = build_overlap_graph(
            pair_i, pair_j, cls, passed, n_reads=int(n),
            capacity=cfg.r_capacity,
        )
        r_mat = drop_contained(r_mat, contained)
        sp.set_output(r_mat.cols)
    metrics.emit("overflow_R", int(readback(ovf_r, "overflow_R")))
    metrics.emit("nnz_R", int(readback(r_mat.nnz(), "nnz_R")))
    metrics.emit("r_density", metrics["nnz_R"] / max(1, int(n)))
    metrics.emit("n_contained",
                 int(readback(jnp.sum(contained), "n_contained")))

    # --- TrReduction: Algorithm 2 ---
    with _tic(timings, "TrReduction") as sp:
        tr = transitive_reduction_fused if cfg.fused_tr else transitive_reduction
        s_mat, tr_stats = tr(
            r_mat, fuzz=cfg.tr_fuzz, max_iters=cfg.tr_max_iters,
            backend=backend,
        )
        sp.set_output(s_mat.cols)
    metrics.emit("tr_iterations",
                 int(readback(tr_stats.iterations, "tr_iterations")))
    # the kernel path that actually ran: transitive_reduction_fused silently
    # downgrades backend="pallas" to the sampled ELL square above
    # TR_DENSE_MAX_ROWS, and benchmark rows must label the real path
    metrics.emit("tr_backend", tr_stats.backend)
    metrics.emit("tr_overflow",
                 int(readback(tr_stats.n_overflow, "tr_overflow")))
    metrics.emit("nnz_S", int(readback(s_mat.nnz(), "nnz_S")))
    metrics.emit("s_density", metrics["nnz_S"] / max(1, int(n)))

    # --- Contigs (backend-dispatched: host walk or device path, §2.7;
    # distribution-dispatched: gspmd or shard_map doubling, §2.9) ---
    with _tic(timings, "Contigs") as sp:
        cset = generate_contigs(
            s_mat, codes, lengths, contained, backend=backend,
            distribution=cfg.distribution, mesh=cfg.mesh,
        )
        contigs = cset.to_contigs()
        cs = contig_stats(contigs)
        sp.set_output(cset.codes)
    metrics.emit("contigs", dataclasses.asdict(cs))
    metrics.emit("n_branch_cut", cset.stats["n_branch_cut"])
    metrics.emit("cc_iterations", cset.stats["cc_iterations"])
    # what actually ran: "gspmd"/"shard_map" on the device path, "host" when
    # the backend resolved to the reference walk (the knob then has no
    # effect — surfaced rather than silently re-labelled)
    metrics.emit("distribution", cset.stats["distribution"])
    # exchange accounting is present-and-zero on paths without explicit
    # exchanges (gspmd / host), so distribution-axis benchmark rows compare
    # without key-existence checks (DESIGN.md §2.10); the key set is the
    # schema's "contig_exchange" group
    metrics.emit_many({
        key: val for key, val in cset.stats.items()
        if key.startswith("exchange_")
    })
    metrics.seed_zero("contig_exchange")

    # --- Consensus: pileup polishing of the contig tensor (§2.8) ---
    cres = None
    if cfg.polish:
        with _tic(timings, "Consensus") as sp:
            cres = polish_contig_set(
                cset, codes, lengths, backend=backend,
                min_depth=cfg.min_depth, band=cfg.pileup_band,
                junction_radius=cfg.junction_radius,
            )
            sp.set_output(cres.codes)
        metrics.emit_many({
            "consensus_depth_mean": cres.stats["consensus_depth_mean"],
            "identity_estimate": cres.stats["identity_estimate"],
            "qv_estimate": cres.stats["qv_estimate"],
            "consensus_changed": cres.stats["n_changed"],
            "n_junction_shifted": cres.stats["n_junction_shifted"],
        })

    return AssemblyResult(
        r_graph=r_mat, s_graph=s_mat, contigs=contigs, stats=metrics.as_dict(),
        timings=timings, contained=contained, consensus=cres, trace=tracer,
    )
