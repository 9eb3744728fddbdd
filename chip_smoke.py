#!/usr/bin/env python3
"""Smoke run of the assembler on TPU chips, through ``assemble()``.

    python chip_smoke.py                 # one chip: parity + real-size phase
    python chip_smoke.py --chips 4       # four chips: shard_map vs gspmd

One chip runs two phases:

* **parity** — the ``examples/assemble_genome.py`` read profile (30 kb
  genome, depth 14, reads 1400 ± 250 bp, 5% error of which 60% indels,
  seeded) assembled with ``backend="pallas"`` (the compiled kernels) and
  ``backend="reference"`` on the chip; graphs, draft and polished contigs
  must be bit-identical.
* **real size** — the same read profile on a bacterial genome length,
  ``backend="pallas"``; polished identity against the simulated truth must
  not fall below the draft's, and the connected-components kernel
  (``cc_labels``, not on the ``assemble`` path) is checked against its
  oracle on the run's state graph.  The target is 4,641,652 bp (*E. coli*
  K-12 MG1655, NCBI NC_000913.3); the default is cut to 1,000,000 bp so
  that a cold run (every stage compiled, no persistent cache) ends within
  its 1200 s limit (ROADMAP 1.0b); ``--genome-bp`` sets it.

``--chips 4`` runs only ``distribution="shard_map"`` over four chips and its
one-device ``gspmd`` comparison on the same reads: bit-identical graphs and
contigs, exchange words equal to ``benchmarks/bench_comm_model.py``.

Every stage prints its wall time, the compile time spent inside it and the
device-memory watermark; every dispatched op prints the implementation that
ran.  Any failed check exits non-zero.  On any platform but TPU the script
exits non-zero before doing any work.  A passing run's last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

JAX's persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says; when that is unset, in ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
ECOLI_K12_BP = 4_641_652  # E. coli K-12 MG1655, NCBI NC_000913.3
REAL_BP = 1_000_000  # the cut the default run makes (ROADMAP 1.0b)
PARITY_BP = 30_000
FOUR_CHIP_BP = PARITY_BP  # shares the one-chip phase's compiled programs
# examples/assemble_genome.py read profile (CLR-like long reads)
PROFILE = dict(depth=14.0, mean_len=1400, std_len=250, error_rate=0.05,
               indel_frac=0.6)
STAGES = ("CountKmer", "CreateSpMat", "SpGEMM", "Alignment", "BuildR",
          "TrReduction", "Contigs", "Consensus")
# real-size quality check: contigs measured against the simulated truth, in
# a seeded order, until this many bases (the host edit-distance DP is slow)
IDENTITY_BASES = 300_000


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Collects XLA's backend-compile durations (persistent-cache misses)
    with the host clock at which each ended, so they can be attributed to
    the pipeline stage span that was open at the time.  Tracing and
    lowering are left out: their events nest, so sums would double count."""

    def __init__(self, report_over_s: float = 20.0):
        self.events = []
        self.report_over_s = report_over_s

    def __call__(self, event, duration_secs, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), float(duration_secs)))
            if duration_secs > self.report_over_s:  # progress on long runs
                log(f"[compile] {event.rsplit('/', 1)[-1]} "
                    f"{duration_secs:.6f} s fun={fun_name}")

    def within(self, t0: float, t1: float) -> float:
        return sum(d for t, d in self.events if t0 <= t <= t1)


def simulate(genome_bp: int, seed: int):
    """Genome + long reads of the smoke profile, seeded."""
    import numpy as np

    from repro.assembly.simulate import simulate_genome, simulate_reads

    genome = simulate_genome(np.random.default_rng(seed), genome_bp)
    return simulate_reads(genome, seed=seed + 1, **PROFILE)


def sized_config(reads, **overrides):
    """``PipelineConfig`` of the smoke profile with ``m_capacity`` sized
    from the data: the reliable k-mers are counted once with the
    pipeline's own CountKmer functions (same jitted shapes, so ``assemble``
    reuses the compile)."""
    import jax.numpy as jnp

    from repro.assembly.counter import count_and_select
    from repro.assembly.kmers import extract_kmers
    from repro.assembly.pipeline import PipelineConfig
    from repro.core.spmat import next_pow2

    upper = int(4 * PROFILE["depth"])
    cfg = PipelineConfig(
        upper=upper, read_capacity=160, overlap_capacity=64, r_capacity=40,
        band=65, max_steps=4096, xdrop=30, align_chunk=4096, trace=True,
        backend="pallas",
    )
    kmers = extract_kmers(jnp.asarray(reads.codes, jnp.uint8),
                          jnp.asarray(reads.lengths, jnp.int32), k=cfg.k)
    m_reliable = int(count_and_select(kmers, k=cfg.k, lower=cfg.lower,
                                      upper=upper).m_reliable)
    cfg.m_capacity = next_pow2(m_reliable)
    for key, val in overrides.items():
        setattr(cfg, key, val)
    log(f"[config] reads={reads.n_reads} width={reads.codes.shape[1]} "
        f"m_reliable={m_reliable} m_capacity={cfg.m_capacity} "
        f"read_capacity={cfg.read_capacity} "
        f"overlap_capacity={cfg.overlap_capacity} "
        f"r_capacity={cfg.r_capacity} band={cfg.band} "
        f"max_steps={cfg.max_steps}")
    return cfg


def run_assemble(label: str, reads, cfg, clock: CompileClock):
    """``assemble()`` with one line per stage (wall, compile inside it,
    watermark) and one for the implementations that ran."""
    from repro.assembly.pipeline import assemble

    t0 = time.perf_counter()
    res = assemble(reads.codes, reads.lengths, cfg)
    wall = time.perf_counter() - t0
    for name in STAGES:
        for sp in res.trace.find(name):
            if sp.attrs.get("kind") != "stage":
                continue
            log(f"[{label}] stage={name} wall_s={sp.duration_s:.6f} "
                f"compile_s={clock.within(sp.t0, sp.t1):.6f} "
                f"peak_hbm_bytes={sp.attrs.get('peak_hbm_bytes')} "
                f"hbm_source={sp.attrs.get('hbm_source')}")
    s = res.stats
    log(f"[{label}] total_wall_s={wall:.6f} "
        f"peak_hbm_bytes={s['peak_hbm_bytes']} hbm_source={s['hbm_source']}")
    log(f"[{label}] op_impls={json.dumps(s['op_impls'], sort_keys=True)} "
        f"tr_backend={s['tr_backend']} "
        f"summa_backend={s.get('summa_backend', '-')} "
        f"distribution={s['distribution']}")
    return res


def same_contigs(xs, ys) -> bool:
    import numpy as np

    return len(xs) == len(ys) and all(
        np.array_equal(np.asarray(a.codes), np.asarray(b.codes))
        and list(a.reads) == list(b.reads)
        for a, b in zip(xs, ys)
    )


def check_hbm(label: str, res, failures: list) -> None:
    """On a TPU the watermark must be the allocator's own."""
    import jax

    want = "device_stats" if jax.default_backend() == "tpu" else "live_buffers"
    if res.stats["hbm_source"] != want:
        failures.append(f"{label}: hbm_source={res.stats['hbm_source']}")


def check_compiled(label: str, res, ops, failures: list) -> None:
    """Each op in ``ops`` ran its Pallas kernel (compiled on a TPU)."""
    from repro.core.backend import default_impl

    want = default_impl("pallas")
    impls = res.stats["op_impls"]
    for op in ops:
        if impls.get(op) != want:
            failures.append(f"{label}: {op} ran {impls.get(op)!r}, "
                            f"not {want!r}")


def phase_parity(clock: CompileClock, seed: int,
                 genome_bp: int = PARITY_BP) -> list:
    """Compiled Pallas kernels vs the jnp oracles through ``assemble``."""
    from repro.core.spmat import ell_equal

    failures = []
    reads = simulate(genome_bp, seed)
    log(f"[parity] genome_bp={genome_bp} reads={reads.n_reads}")
    cfg = sized_config(reads)
    pal = run_assemble("parity/pallas", reads, cfg, clock)
    cfg.backend = "reference"
    ref = run_assemble("parity/reference", reads, cfg, clock)
    cfg.backend = "pallas"
    checks = {
        "r_graph": ell_equal(pal.r_graph, ref.r_graph),
        "s_graph": ell_equal(pal.s_graph, ref.s_graph),
        "contigs": same_contigs(pal.contigs, ref.contigs),
        "polished_contigs": same_contigs(pal.polished_contigs,
                                         ref.polished_contigs),
    }
    log("[parity] bit_identical " + " ".join(
        f"{k}={v}" for k, v in checks.items()))
    failures += [f"parity: {k} differs" for k, v in checks.items() if not v]
    if pal.stats["tr_backend"] != "pallas":
        failures.append(f"parity: tr_backend={pal.stats['tr_backend']} "
                        "(the dense minplus path did not run)")
    check_compiled("parity", pal, ("xdrop_extend", "consensus",
                                   "minplus_dense"), failures)
    check_hbm("parity", pal, failures)
    return failures


def phase_real_size(clock: CompileClock, seed: int, genome_bp: int) -> list:
    """One bacterial-genome assembly on the compiled kernels."""
    import numpy as np

    from repro.assembly.metrics import contig_identity_vs_truth
    from repro.core.backend import default_impl, recording_impls
    from repro.core.components import connected_components, expand_states
    from repro.kernels.cc import fused_path_fits

    failures = []
    if genome_bp != ECOLI_K12_BP:
        log(f"[real] genome cut from {ECOLI_K12_BP} to {genome_bp} bp")
    t0 = time.perf_counter()
    reads = simulate(genome_bp, seed)
    log(f"[real] genome_bp={genome_bp} reads={reads.n_reads} "
        f"simulate_s={time.perf_counter() - t0:.6f}")
    cfg = sized_config(reads)
    res = run_assemble("real", reads, cfg, clock)
    s = res.stats
    cs = s["contigs"]
    log(f"[real] n_contigs={cs['n_contigs']} n50={cs['n50']} "
        f"longest={cs['longest']} total_length={cs['total_length']} "
        f"overflow_A={s['overflow_A']} overflow_C={s['overflow_C']} "
        f"overflow_R={s['overflow_R']} tr_overflow={s['tr_overflow']} "
        f"n_aligned={s['n_aligned']} n_passed={s['n_passed']} "
        f"spgemm_row_chunk={s['spgemm_row_chunk']}")
    check_compiled("real", res, ("xdrop_extend", "consensus"), failures)
    check_hbm("real", res, failures)

    # cc_labels on this run's state graph, compiled kernel vs oracle
    graph = expand_states(res.s_graph)
    fused = fused_path_fits(graph.cols)
    with recording_impls() as impls:
        t0 = time.perf_counter()
        lp, ip = connected_components(graph, backend="pallas")
        lp.block_until_ready()
        t_pal = time.perf_counter() - t0
    lr, ir = connected_components(graph, backend="reference")
    same = bool(np.array_equal(np.asarray(lp), np.asarray(lr)))
    cc_impl = "+".join(sorted(impls.get("cc_labels", ())))
    log(f"[real] cc_labels impl={cc_impl} vmem_fused_path={fused} "
        f"n_states={graph.cols.shape[0]} slots={graph.cols.shape[1]} "
        f"iters_pallas={int(ip)} iters_reference={int(ir)} "
        f"wall_s={t_pal:.6f} labels_equal={same}")
    if not same:
        failures.append("real: cc_labels pallas labels differ from oracle")
    # cc_labels must not have fallen back to its oracle (VMEM budget)
    want = default_impl("pallas")
    if cc_impl != want:
        failures.append(f"real: cc_labels ran {cc_impl!r}, not {want!r}")

    # quality against the simulated truth, on a seeded sample of contigs
    order = np.random.default_rng(seed).permutation(len(res.contigs))
    band = max(64, int(8 * PROFILE["error_rate"] * PROFILE["mean_len"]))
    num_d = num_p = 0.0
    den = 0
    t0 = time.perf_counter()
    for i in order:
        draft, pol = res.contigs[i], res.polished_contigs[i]
        if len(draft.reads) < 2 or draft.length == 0 or pol.length == 0:
            continue
        num_d += contig_identity_vs_truth(draft, reads, band=band) * draft.length
        num_p += contig_identity_vs_truth(pol, reads, band=band) * draft.length
        den += draft.length
        if den >= IDENTITY_BASES:
            break
    draft_id = num_d / den if den else float("nan")
    pol_id = num_p / den if den else float("nan")
    log(f"[real] identity_vs_truth draft={draft_id:.6f} "
        f"polished={pol_id:.6f} bases_measured={den} "
        f"of={cs['total_length']} wall_s={time.perf_counter() - t0:.6f}")
    if not den:
        failures.append("real: no contig to measure identity on")
    elif not pol_id >= draft_id:
        failures.append(f"real: polished identity {pol_id} < draft "
                        f"{draft_id}")
    return failures


def phase_four_chips(clock: CompileClock, seed: int, genome_bp: int) -> list:
    """``distribution="shard_map"`` over four chips vs the one-device
    ``gspmd`` run on the same reads."""
    import jax

    from benchmarks.bench_comm_model import (
        words_align,
        words_chain_sort,
        words_graph_cut,
        words_summa,
    )
    from repro.core.components_dist import default_row_mesh
    from repro.core.spmat import ell_equal
    from repro.core.summa import default_summa_mesh

    failures = []
    p = len(jax.devices())
    spans = {
        "summa_mesh": default_summa_mesh().devices.size,
        "row_mesh": default_row_mesh().devices.size,
    }
    log(f"[4chip] devices={p} " + " ".join(
        f"{k}_devices={v}" for k, v in spans.items()))
    failures += [f"4chip: {k} spans {v} of {p} devices"
                 for k, v in spans.items() if v != p]
    reads = simulate(genome_bp, seed)
    log(f"[4chip] genome_bp={genome_bp} reads={reads.n_reads}")
    cfg = sized_config(reads)
    gs = run_assemble("4chip/gspmd", reads, cfg, clock)
    cfg.distribution = "shard_map"
    sm = run_assemble("4chip/shard_map", reads, cfg, clock)
    checks = {
        "r_graph": ell_equal(gs.r_graph, sm.r_graph),
        "s_graph": ell_equal(gs.s_graph, sm.s_graph),
        "contigs": same_contigs(gs.contigs, sm.contigs),
        "polished_contigs": same_contigs(gs.polished_contigs,
                                         sm.polished_contigs),
    }
    log("[4chip] bit_identical " + " ".join(
        f"{k}={v}" for k, v in checks.items()))
    failures += [f"4chip: {k} differs" for k, v in checks.items() if not v]

    s = sm.stats
    mesh = default_summa_mesh()
    pr, pc = mesh.shape["data"], mesh.shape["model"]
    n = s["n_reads"]
    models = {
        "exchange_words_summa": words_summa(
            n_rows=-(-n // pr) * pr, a_block_slots=cfg.read_capacity,
            a_words_per_slot=2, m_rows=-(-cfg.m_capacity // pr) * pr,
            b_block_slots=cfg.upper, b_words_per_slot=2, pr=pr, pc=pc),
        "exchange_words_align": words_align(
            n_pad=-(-n // p) * p, row_width=reads.codes.shape[1],
            bucket_pad=-(-s["align_bucket"] // p) * p, p=p),
        "exchange_words_sort": words_chain_sort(2 * n, p),
        "exchange_words_cut": words_graph_cut(2 * n, p),
    }
    for key, model in models.items():
        log(f"[4chip] {key}={s[key]} model={model}")
        if s[key] != model:
            failures.append(f"4chip: {key}={s[key]} != model {model}")
    check_hbm("4chip", sm, failures)
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--genome-bp", type=int, default=None,
                    help=f"real-size genome length (default {REAL_BP}, "
                         f"cut from {ECOLI_K12_BP}); with --chips 4 the "
                         f"shared genome length (default {FOUR_CHIP_BP})")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))
    sys.path.insert(0, os.path.join(REPO, "src"))
    sys.path.insert(0, REPO)

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")
    t0 = time.perf_counter()
    if args.chips == 4:
        failures = phase_four_chips(clock, args.seed,
                                    args.genome_bp or FOUR_CHIP_BP)
    else:
        failures = phase_parity(clock, args.seed)
        failures += phase_real_size(clock, args.seed,
                                    args.genome_bp or REAL_BP)
    log(f"[done] wall_s={time.perf_counter() - t0:.6f} "
        f"failures={len(failures)}")
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    faulthandler.enable()  # a fatal signal prints the Python stack
    sys.exit(main())
