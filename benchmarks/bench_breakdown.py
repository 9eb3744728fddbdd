"""Fig. 5-8 analogue: per-stage runtime breakdown of the pipeline
(CountKmer / CreateSpMat / SpGEMM / Alignment / BuildR / TrReduction /
Contigs / Consensus), with a backend axis: the reference row set uses the jnp oracles
and the host contig walk, the pallas row set routes the hot ops (x-drop
extension, min-plus squares) through the Pallas kernels via the dispatch
layer (compiled on TPU, interpret elsewhere) and runs the device contig
path (DESIGN.md §2.7).

With ``--distribution shard_map`` (or ``both``) an extra pipeline run uses
the explicit-exchange contig doubling (§2.9) and emits a ``contig_comm``
row: measured per-device/per-round exchange volume next to the analytic
model from ``bench_comm_model.words_contig_doubling`` — plus an
``align_comm`` row for the distributed x-drop extension (§2.12): measured
``exchange_words_align`` next to ``bench_comm_model.words_align``.

Standalone: ``python -m benchmarks.bench_breakdown --backend pallas
--distribution both``.
"""

from __future__ import annotations

import numpy as np


def render_span_tree(tracer, max_depth: int = 4) -> str:
    """Render an ``obs.Tracer``'s span forest as an indented text tree.

    One line per span — ``name [attrs] ms`` — children indented under their
    parent, depth-capped at ``max_depth``.  This is the human-readable twin
    of the Chrome trace export (``obs.write_chrome_trace``): the breakdown
    benchmark prints it so a ``--trace`` run shows the stage → shard_map
    phase → op nesting without opening Perfetto."""
    lines = []

    def _fmt(sp, depth):
        if depth > max_depth:
            return
        attrs = {k: v for k, v in sp.attrs.items() if k != "kind"}
        att = (" [" + ", ".join(f"{k}={v}" for k, v in attrs.items()) + "]"
               if attrs else "")
        lines.append(f"{'  ' * depth}{sp.name}{att} {sp.duration_ms:.2f}ms")
        for child in sp.children:
            _fmt(child, depth + 1)

    for root in tracer.roots:
        _fmt(root, 0)
    return "\n".join(lines)


def run(backends=("reference", "pallas"), distributions=("gspmd",)):
    from repro.assembly.pipeline import PipelineConfig, assemble
    from repro.assembly.simulate import simulate_genome, simulate_reads

    rng = np.random.default_rng(9)
    g = simulate_genome(rng, 10_000)
    rs = simulate_reads(g, depth=12, mean_len=900, std_len=120,
                        error_rate=0.03, seed=10)
    rows = []
    for backend in backends:
        cfg = PipelineConfig(m_capacity=1 << 16, upper=48, read_capacity=128,
                             overlap_capacity=48, r_capacity=32, band=33,
                             max_steps=2048, align_chunk=8192, backend=backend)
        res = assemble(rs.codes, rs.lengths, cfg)
        total = sum(res.timings.values())
        live = res.stats["n_aligned"]
        cand = res.stats["align_candidates"]
        rows.extend(
            (f"breakdown[{backend}]/{k}", v * 1e6,
             f"frac={v / total:.3f};live_pairs={live}/{cand}")
            for k, v in res.timings.items()
        )
        rows.append(
            (f"breakdown[{backend}]/tr_stats",
             res.timings["TrReduction"] * 1e6,
             # tr_backend is the kernel path that actually ran — the fused
             # TR downgrades pallas→reference above TR_DENSE_MAX_ROWS, and
             # this row is where that must stay visible
             f"iters={res.stats['tr_iterations']};"
             f"tr_backend={res.stats['tr_backend']};"
             f"n_overflow={res.stats['tr_overflow']};"
             f"nnz_S={res.stats['nnz_S']}")
        )
        cs = res.stats["contigs"]
        rows.append(
            (f"breakdown[{backend}]/contig_stats",
             res.timings["Contigs"] * 1e6,
             f"n={cs['n_contigs']};n50={cs['n50']};l50={cs['l50']};"
             f"mean={cs['mean_length']:.0f};"
             f"branch_cut={res.stats['n_branch_cut']};"
             f"cc_iters={res.stats['cc_iterations']}")
        )
        rows.append(
            (f"breakdown[{backend}]/consensus_stats",
             res.timings["Consensus"] * 1e6,
             f"depth_mean={res.stats['consensus_depth_mean']:.2f};"
             f"identity_est={res.stats['identity_estimate']:.4f};"
             f"qv_est={res.stats['qv_estimate']:.1f};"
             f"changed={res.stats['consensus_changed']};"
             f"junction_shifts={res.stats['n_junction_shifted']}")
        )

    if "shard_map" in distributions:
        # §2.9 communication check: explicit-exchange contig doubling,
        # measured per-device exchange volume vs the analytic model
        import jax

        from .bench_comm_model import (
            words_align, words_chain_sort, words_contig_doubling,
            words_graph_cut,
        )

        cfg = PipelineConfig(m_capacity=1 << 16, upper=48, read_capacity=128,
                             overlap_capacity=48, r_capacity=32, band=33,
                             max_steps=2048, align_chunk=8192,
                             backend="pallas", distribution="shard_map")
        res = assemble(rs.codes, rs.lengths, cfg)
        p = len(jax.devices())
        n_states = 2 * res.stats["n_reads"]
        measured = res.stats["exchange_words"]
        rounds = res.stats["exchange_rounds"]
        dbl_rounds = res.stats["exchange_rounds_doubling"]
        model = words_contig_doubling(n_states, p, dbl_rounds)
        per_round = measured // max(rounds, 1)
        rows.append(
            (f"breakdown[pallas/shard_map]/contig_comm",
             res.timings["Contigs"] * 1e6,
             f"P={p};rounds={rounds};exchange_words={measured};"
             f"words_per_round={per_round};model_words={model};"
             f"model_words_logn={words_contig_doubling(n_states, p)};"
             f"exchange_words_cut={res.stats['exchange_words_cut']};"
             f"model_words_cut={words_graph_cut(n_states, p)};"
             f"exchange_words_sort={res.stats['exchange_words_sort']};"
             f"model_words_sort={words_chain_sort(n_states, p)}")
        )
        # §2.12 communication check: distributed x-drop extension, measured
        # per-device gather/scatter volume vs the analytic model (the
        # pipeline ran it on the default 1D row mesh over all devices)
        n_reads = res.stats["n_reads"]
        bucket = res.stats["align_bucket"]
        n_pad = -(-n_reads // p) * p
        bucket_pad = -(-bucket // p) * p
        wm_align = words_align(n_pad=n_pad, row_width=rs.codes.shape[1],
                               bucket_pad=bucket_pad, p=p)
        rows.append(
            (f"breakdown[pallas/shard_map]/align_comm",
             res.timings["Alignment"] * 1e6,
             f"P={p};bucket={bucket};"
             f"exchange_words_align={res.stats['exchange_words_align']};"
             f"model_words_align={wm_align};"
             f"exchange_rounds_align={res.stats['exchange_rounds_align']};"
             f"n_passed={res.stats['n_passed']}")
        )
    return rows


def main() -> None:
    """CLI entry point (CSV on stdout)."""
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--backend", default="both",
                   choices=["reference", "pallas", "both"])
    p.add_argument("--distribution", default="gspmd",
                   choices=["gspmd", "shard_map", "both"])
    p.add_argument("--trace", action="store_true",
                   help="run one traced pipeline and print its span tree "
                        "(stage -> phase -> kernel) to stderr")
    ns = p.parse_args()
    backends = (("reference", "pallas") if ns.backend == "both"
                else (ns.backend,))
    dists = (("gspmd", "shard_map") if ns.distribution == "both"
             else (ns.distribution,))
    print("name,us_per_call,derived")
    for name, us, derived in run(backends=backends, distributions=dists):
        print(f"{name},{us:.1f},{derived}", flush=True)
    if ns.trace:
        import sys

        from repro.assembly.pipeline import PipelineConfig, assemble
        from repro.assembly.simulate import simulate_genome, simulate_reads

        rng = np.random.default_rng(9)
        g = simulate_genome(rng, 10_000)
        rs = simulate_reads(g, depth=12, mean_len=900, std_len=120,
                            error_rate=0.03, seed=10)
        cfg = PipelineConfig(m_capacity=1 << 16, upper=48, read_capacity=128,
                             overlap_capacity=48, r_capacity=32, band=33,
                             max_steps=2048, align_chunk=8192,
                             backend="pallas", distribution="shard_map",
                             trace=True)
        res = assemble(rs.codes, rs.lengths, cfg)
        print(render_span_tree(res.trace), file=sys.stderr)


if __name__ == "__main__":
    main()
