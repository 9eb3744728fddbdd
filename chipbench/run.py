#!/usr/bin/env python3
"""Chip benchmark of the assembler: timed reads-to-polished-contigs jobs.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Everything is found by name: the cell in
``BENCHMARK.json``, its configuration in ``chipbench/configs/<config>.json``,
its traffic in ``chipbench/traffic/<traffic>.json``, its correctness limits
in ``chipbench/workloads/<cell>.json`` and each per-layer metric's reader in
``chipbench/metrics/<metric>.py``.

A run:

1. Set-up (``setup_s``): checks that JAX sees TPU chips (else exits 3 with no
   result), keeps the persistent compile cache where
   ``JAX_COMPILATION_CACHE_DIR`` says or else in ``<checkout>/.jax_cache``,
   simulates the cell's reads from ``--seed``, and runs one warm-up job.
2. Window: a closed loop of jobs, back to back on the same reads, until
   ``--seconds`` have passed; the job running then finishes and counts.  A
   job is ``assemble(codes, lengths, cfg)`` from host numpy reads to the
   polished contigs on the host.  ``--trace 1`` records the window with the
   JAX profiler and runs the pipeline with ``cfg.trace=True``.
3. After the window: the device's peak memory is read, then the last job's
   output is checked against the plain reference (``chipbench/check.py``).

The last stdout line is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
``checks`` last); the last stderr lines give each compared number beside
its limit.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# must run their Pallas kernels (consensus only where the job polishes)
KERNEL_OPS = ("xdrop_extend", "consensus")
KERNEL_IMPL = "pallas"  # the record of a compiled kernel call
OVERFLOWS = ("overflow_A", "overflow_C", "overflow_R", "tr_overflow")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(name: str) -> dict:
    """The cell's entry of BENCHMARK.json with its configuration, traffic
    and limits files loaded, and the per-layer metrics it reports."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]

    def reported(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {
        "cell": cell,
        "config": load_json(BENCH, "configs", cell["config"] + ".json"),
        "traffic": load_json(BENCH, "traffic", cell["traffic"] + ".json"),
        "limits": load_json(BENCH, "workloads", name + ".json"),
        "end_to_end": reported(bench["end_to_end"]),
        "per_layer": reported(bench["per_layer"]),
    }


class CompileClock:
    """XLA backend compiles (persistent-cache misses), each with the host
    clock at which it ended."""

    def __init__(self):
        self.events = []

    def __call__(self, event, duration_secs, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), float(duration_secs),
                                fun_name))
            if duration_secs > 10:  # progress through a cold set-up
                log(f"[compile] fun={fun_name} seconds={duration_secs:.6f}")

    def within(self, t0: float, t1: float) -> list:
        return [ev for ev in self.events if t0 <= ev[0] <= t1]

    def seconds(self, t0: float, t1: float) -> float:
        return sum(d for _, d, _ in self.within(t0, t1))


def open_chips(n: int):
    """The TPU devices, or exit 3: this benchmark never runs elsewhere.  A
    device kind with no row in ``peaks.json`` is an error too.  Every
    compile goes to the persistent cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``<checkout>/.jax_cache``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        log(f"chipbench: needs {n} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s). Nothing was run.")
        raise SystemExit(3)
    if devs[0].device_kind not in load_json(BENCH, "peaks.json")["devices"]:
        log(f"chipbench: no peaks for device kind {devs[0].device_kind!r} "
            "in chipbench/peaks.json. Nothing was run.")
        raise SystemExit(3)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devs


def pipeline_config(config: dict, traffic: dict, trace: bool):
    """The configuration's pipeline, with what the traffic's jobs ask for
    (``traffic["pipeline"]``, e.g. draft contigs only) on top."""
    from repro.assembly.pipeline import PipelineConfig

    return PipelineConfig(backend="pallas", trace=trace, **dict(
        config["pipeline"], **traffic.get("pipeline", {})))


def run_job(assemble, reads, cfg):
    """One job: host reads in, the contigs it returns (polished, or the
    draft where the job does not polish) materialised on the host."""
    t0 = time.perf_counter()
    res = assemble(reads.codes, reads.lengths, cfg)
    contigs = res.polished_contigs
    return res, contigs, time.perf_counter() - t0


def job_faults(stats: dict, cfg) -> list:
    """Overflowed capacities or a hot op that did not run its kernel."""
    bad = [f"{k}={stats[k]}" for k in OVERFLOWS if stats.get(k)]
    ops = KERNEL_OPS if cfg.polish else KERNEL_OPS[:1]
    bad += [f"{op} ran {stats['op_impls'].get(op)!r}" for op in ops
            if stats["op_impls"].get(op) != KERNEL_IMPL]
    return bad


def load_metric(name: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}",
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def measure(spec: dict, seed: int, seconds: float, trace: bool,
            warmup: bool = True) -> dict:
    """One whole run; returns the result object (see the module docstring).
    ``warmup=False`` skips the warm-up job, for a process that has already
    run this cell's shapes."""
    cell, config = spec["cell"], spec["config"]
    import jax

    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import check
    import sim
    import trace_reduce

    devs = open_chips(cell["chips"])
    from repro.assembly.pipeline import assemble

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    t_sim = time.perf_counter()
    reads = sim.simulate(config["reads"], seed)
    log(f"[setup] reads={reads.n_reads} width={reads.codes.shape[1]} "
        f"simulate_s={time.perf_counter() - t_sim:.6f}")
    cfg = pipeline_config(config, spec["traffic"], trace)
    warm_s = run_job(assemble, reads, cfg)[2] if warmup else 0.0
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    log(f"[setup] setup_s={setup_s:.6f} warmup_job_s={warm_s:.6f} "
        f"compiles={len(clock.within(0, t_window))} "
        f"compile_s={clock.seconds(0, t_window):.6f}")

    jobs = []
    prof_dir = os.path.join(BENCH, ".profile")
    if trace:
        shutil.rmtree(prof_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python frames would flood the trace
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
    t_win0 = time.perf_counter()
    while True:
        res, contigs, wall = run_job(assemble, reads, cfg)
        jobs.append({"wall_s": wall, "timings": dict(res.timings),
                     "faults": job_faults(res.stats, cfg),
                     "digest": check.digest(contigs)})
        if time.perf_counter() - t_win0 >= seconds:
            break
        del res, contigs
    t_win1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window_s = t_win1 - t_win0
    window_compiles = len(clock.within(t_win0, t_win1))
    for _, d, name in clock.within(t_win0, t_win1):
        log(f"[window] compile fun={name} seconds={d:.6f}")
    mem = devs[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell["chips"]])
    stats = res.stats
    log(f"[window] jobs={len(jobs)} window_s={window_s:.6f} "
        f"compiles={window_compiles} peak_hbm_bytes={peak} "
        f"bytes_limit={mem.get('bytes_limit')} tr_backend={stats['tr_backend']} "
        f"align_bucket={stats['align_bucket']} n_aligned={stats['n_aligned']} "
        f"n_passed={stats['n_passed']} nnz_R={stats['nnz_R']} "
        f"nnz_S={stats['nnz_S']} m_reliable={stats['m_reliable']}")
    log("[window] stage_s " + " ".join(
        f"{k}={sum(j['timings'].get(k, 0.0) for j in jobs) / len(jobs):.6f}"
        for k in trace_reduce.STAGES))
    log(f"[window] op_impls={json.dumps(stats['op_impls'], sort_keys=True)}")

    # --- after the window: quality and the comparison with the reference
    out = check.outputs(res, contigs)
    del res, contigs
    t_chk = time.perf_counter()
    quality = check.quality(out, reads, seed, spec["limits"])
    checks, detail = check.compare(out, reads, config, spec["limits"], seed,
                                   jobs)
    log(f"[check] seconds={time.perf_counter() - t_chk:.6f} " + " ".join(
        f"{k}={v}" for k, v in detail.items()))
    failed = checks["job_faults"][0]
    for j in jobs:
        if j["faults"]:
            log("[check] job fault: " + "; ".join(j["faults"]))
            break
    correct = all(v <= lim for v, lim in checks.values())

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"], "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(jobs), "failed": failed}
    if trace:
        red = trace_reduce.reduce_dir(prof_dir)
        log(f"[trace] busy_s={red['busy_s']:.6f} window_s={red['window_s']:.6f} "
            f"devices={red['n_devices']} top_ops=" + json.dumps(
                [[n, round(t, 6)] for n, t in red["device_ops"][:30]]))
        log("[trace] idle_gaps=" + json.dumps(
            [[n, round(t, 6)] for n, t in red["idle_gaps"][:15]]))
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = {"jobs": jobs, "trace": red, "window_compiles": window_compiles}
        metrics = {}
        for m in spec["per_layer"]:
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
    else:
        e2e = {"setup_s": setup_s,
               "job_s": window_s / len(jobs),
               "contig_identity": quality["contig_identity"],
               "peak_hbm_bytes": peak}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    result["metrics"] = metrics
    result["device"] = device
    result["detail"] = dict(detail, **quality)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    log("[quality] " + " ".join(f"{k}={v}" for k, v in quality.items()))
    for k, (v, lim) in checks.items():
        log(f"[check] {k}={v} limit={lim} {'ok' if v <= lim else 'FAIL'}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = cell_spec(args.workload)
    result = measure(spec, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
