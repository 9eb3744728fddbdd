"""Observability layer (src/repro/obs/): span tracing, the typed metric
schema, the dict-shape compatibility shim, and Chrome-trace export.

The schema-coverage tests parametrize over the emission paths (host walk,
gspmd device path, shard_map explicit exchange) and assert the contract the
scattered per-test key tuples used to check piecemeal: every emitted stats
key is registered in ``obs/schema.py`` with a kind-compatible value, and
every present-and-zero group key exists on every path."""

import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (
    Metrics,
    MetricsError,
    Tracer,
    schema,
    span,
    sync,
    to_chrome_trace,
    tracing,
    validated,
)


# ---------------------------------------------------------------------------
# spans + tracer
# ---------------------------------------------------------------------------


def test_span_nesting_builds_tree():
    tr = Tracer()
    with tracing(tr):
        with span("Stage", kind="stage"):
            with span("Phase", kind="phase", phase="ring_stage"):
                with span("op:xdrop_extend", kind="op"):
                    pass
            with span("Phase", kind="phase", phase="merge"):
                pass
        with span("Other", kind="stage"):
            pass
    assert [r.name for r in tr.roots] == ["Stage", "Other"]
    stage = tr.roots[0]
    assert [c.attrs["phase"] for c in stage.children] == ["ring_stage",
                                                          "merge"]
    assert stage.children[0].children[0].name == "op:xdrop_extend"
    assert all(sp.duration_s >= 0 for sp in tr.spans())
    assert len(tr.find("Phase")) == 2


def test_span_works_without_tracer():
    with span("lonely") as sp:
        sp.set_output(jnp.arange(4))
    assert sp.duration_s >= 0
    assert sp.t1 is not None


def test_tracing_restores_previous_tracer():
    outer, inner = Tracer(), Tracer()
    with tracing(outer):
        with tracing(inner):
            with span("in-inner"):
                pass
        with span("in-outer"):
            pass
    assert [r.name for r in inner.roots] == ["in-inner"]
    assert [r.name for r in outer.roots] == ["in-outer"]


def test_sync_descends_plain_dataclasses():
    @dataclasses.dataclass
    class Box:
        arr: object
        nested: object = None

    b = Box(arr=jnp.arange(8), nested=Box(arr=jnp.ones(3)))
    out = sync([b, {"k": jnp.zeros(2)}, 5, "s"])
    assert out[0] is b  # returns its argument


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_rejects_unregistered_key():
    m = Metrics(context="t")
    with pytest.raises(MetricsError, match="unregistered"):
        m.emit("definitely_not_a_metric", 1)


def test_metrics_rejects_wrong_kind():
    m = Metrics(context="t")
    with pytest.raises(MetricsError, match="counter"):
        m.emit("nnz_A", 1.5)  # counter must be integral
    with pytest.raises(MetricsError, match="counter"):
        m.emit("nnz_A", True)  # bools are not counters
    with pytest.raises(MetricsError, match="label"):
        m.emit("backend", 3)


def test_metrics_seed_zero_keeps_measured_values():
    m = Metrics(context="t")
    m.emit("exchange_words_summa", 42)
    m.seed_zero("summa_exchange")
    d = m.as_dict()
    assert d["exchange_words_summa"] == 42  # setdefault, not overwrite
    assert d["exchange_rounds_summa"] == 0
    assert set(schema.group_keys("summa_exchange")) <= set(d)


def test_validated_reports_missing_group_keys():
    with pytest.raises(MetricsError, match="present-and-zero"):
        validated({"exchange_words": 0}, context="t",
                  require_groups=("contig_exchange",))


def test_zero_groups_declared():
    assert set(schema.ZERO_GROUPS) == {
        "contig_exchange", "summa_exchange", "align_exchange",
    }
    assert len(schema.group_keys("contig_exchange")) == 7
    assert len(schema.group_keys("summa_exchange")) == 2
    assert len(schema.group_keys("align_exchange")) == 2


# ---------------------------------------------------------------------------
# schema coverage of the real emission paths (replaces the per-test key
# tuples that used to live in test_contigs / test_summa_dist)
# ---------------------------------------------------------------------------


def _string_graph(n=24):
    from repro.assembly.contig_gen import string_matrix_from_edges

    edges = []
    for i in range(n - 1):
        edges.append((i, i + 1, 0, 0, 30))
        edges.append((i + 1, i, 1, 1, 33))
    return string_matrix_from_edges(n, edges)


@pytest.mark.parametrize("backend,distribution,expect", [
    ("reference", "gspmd", "host"),
    ("pallas", "gspmd", "gspmd"),
    ("pallas", "shard_map", "shard_map"),
])
def test_contig_stats_schema_coverage(backend, distribution, expect):
    """Every ContigSet.stats key of every contig path is registered, kind-
    valid, and carries the full contig_exchange present-and-zero group."""
    from repro.assembly.contig_gen import generate_contigs

    n = 24
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 4, (n, 64)).astype(np.uint8)
    lengths = np.full(n, 64, np.int32)
    cset = generate_contigs(_string_graph(n), codes, lengths,
                            backend=backend, distribution=distribution)
    assert cset.stats["distribution"] == expect
    problems = schema.validate_stats(
        cset.stats, context=f"{backend}/{distribution}",
        require_groups=("contig_exchange",),
    )
    assert problems == []
    if expect != "shard_map":
        for key in schema.group_keys("contig_exchange"):
            assert cset.stats[key] == 0, key


def test_summa_stats_schema_coverage():
    """The ring-SUMMA stats dict (exchange_*_summa, spgemm_hbm_round_trips,
    summa_* labels) is fully registered and group-complete."""
    from repro.assembly.counter import first_semiring
    from repro.core.semiring import overlap_semiring
    from repro.core.spmat import from_coo
    from repro.core.summa import default_summa_mesh, overlap_spgemm_shard_map

    n, m = 12, 16
    rng = np.random.default_rng(1)
    rows = jnp.asarray(rng.integers(0, n, 40))
    cols = jnp.asarray(rng.integers(0, m, 40))
    vals = {"pos": jnp.asarray(rng.integers(0, 50, 40), jnp.int32)}
    ok = jnp.ones(40, bool)
    a, _ = from_coo(rows, cols, vals, ok, n_rows=n, n_cols=m, capacity=8,
                    semiring=first_semiring)
    at, _ = from_coo(cols, rows, vals, ok, n_rows=m, n_cols=n, capacity=8,
                     semiring=first_semiring)
    _, _, st = overlap_spgemm_shard_map(
        a, at, semiring=overlap_semiring, operand_semiring=first_semiring,
        capacity=16, mesh=default_summa_mesh(),
    )
    problems = schema.validate_stats(
        st, context="summa_ring", require_groups=("summa_exchange",)
    )
    assert problems == []
    assert "spgemm_hbm_round_trips" in st
    assert "spgemm_hbm_round_trips_reference" in st


def test_tr_stats_keys_registered():
    """The flattened TRStats surface (tr_iterations / tr_backend /
    tr_overflow) the pipeline emits is registered with correct kinds."""
    for key, value in (("tr_iterations", 3), ("tr_backend", "reference"),
                       ("tr_overflow", 0)):
        s = schema.spec(key)
        assert schema._kind_ok(s.kind, value), (key, s.kind)


def test_pipeline_stats_validate_and_trace_tree():
    """End-to-end: a tiny traced assemble's stats dict passes the registry
    with both zero groups required, and the span forest's roots are the
    Algorithm 1 stages in order."""
    from repro.assembly.pipeline import PipelineConfig, assemble
    from repro.assembly.simulate import simulate_genome, simulate_reads

    rng = np.random.default_rng(7)
    g = simulate_genome(rng, 1500)
    rs = simulate_reads(g, depth=6, mean_len=300, std_len=30, min_len=200,
                        seed=8)
    cfg = PipelineConfig(backend="reference", trace=True)
    res = assemble(rs.codes, rs.lengths, cfg)
    problems = schema.validate_stats(
        res.stats, context="assemble",
        require_groups=("contig_exchange", "summa_exchange"),
    )
    assert problems == []
    roots = [sp.name for sp in res.trace.roots]
    assert roots == ["CountKmer", "CreateSpMat", "SpGEMM", "Alignment",
                     "BuildR", "TrReduction", "Contigs", "Consensus"]
    # timings mirror the stage spans (one timing code path)
    for name in roots:
        (sp,) = res.trace.find(name)
        assert res.timings[name] == pytest.approx(sp.duration_s)


def test_untraced_assemble_has_no_tracer():
    from repro.assembly.pipeline import PipelineConfig, assemble
    from repro.assembly.simulate import simulate_genome, simulate_reads

    rng = np.random.default_rng(7)
    g = simulate_genome(rng, 1200)
    rs = simulate_reads(g, depth=5, mean_len=300, std_len=30, min_len=200,
                        seed=9)
    res = assemble(rs.codes, rs.lengths,
                   PipelineConfig(backend="reference", polish=False))
    assert res.trace is None


# ---------------------------------------------------------------------------
# host readbacks
# ---------------------------------------------------------------------------


def test_readback_counts_device_values_and_skips_host_values():
    from repro.obs import counting_readbacks, readback

    with counting_readbacks() as reads:
        assert int(readback(jnp.int32(3), "scalar")) == 3
        np.testing.assert_array_equal(readback(jnp.arange(4), "array"),
                                      np.arange(4))
        assert reads.n == 2
        assert int(readback(5, "host_int")) == 5
        np.testing.assert_array_equal(readback(np.arange(3), "host_array"),
                                      np.arange(3))
    assert reads.n == 2
    assert int(readback(jnp.int32(1), "uncounted")) == 1  # no counter open
    assert reads.n == 2


def test_readback_names_its_site_under_an_annotating_tracer(monkeypatch):
    import jax

    from repro.obs import readback

    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    readback(jnp.int32(1), "untraced")
    with tracing(Tracer(annotate=False, memory=False)):
        readback(jnp.int32(1), "not_annotated")
    with tracing(Tracer(annotate=True, memory=False)):
        readback(jnp.int32(1), "n_live")
        readback(7, "host_value")
    assert opened == ["readback:n_live"]


def test_assemble_counts_host_readbacks_traced_or_not():
    """Every read site of the pipeline driver is reached once per job,
    traced or not."""
    import inspect

    from repro.assembly import pipeline
    from repro.assembly.simulate import simulate_genome, simulate_reads

    rng = np.random.default_rng(7)
    g = simulate_genome(rng, 1200)
    rs = simulate_reads(g, depth=5, mean_len=300, std_len=30, min_len=200,
                        seed=9)
    counts = [
        pipeline.assemble(rs.codes, rs.lengths, pipeline.PipelineConfig(
            backend="reference", polish=False, trace=trace,
        )).stats["host_readbacks"]
        for trace in (False, True)
    ]
    sites = inspect.getsource(pipeline._assemble).count("readback(")
    assert counts == [sites, sites] and sites > 0


def test_assemble_keys_the_compile_cache_on_op_names(monkeypatch):
    """A profile is read by its op names, so assemble() never reuses an
    executable cached under other names; the settings are restored after."""
    import jax

    from repro.assembly import pipeline

    flags = ("jax_compilation_cache_include_metadata_in_key",
             "jax_traceback_in_locations_limit")
    seen = []

    def fake(codes, lengths, cfg, *, tracer):
        seen.append(tuple(getattr(jax.config, f) for f in flags))
        return pipeline.AssemblyResult(None, None, [], {}, {})

    monkeypatch.setattr(pipeline, "_assemble", fake)
    before = tuple(getattr(jax.config, f) for f in flags)
    for trace in (False, True):
        pipeline.assemble(np.zeros((2, 8), np.uint8), np.full(2, 8),
                          pipeline.PipelineConfig(trace=trace))
    assert seen == [(True, 1)] * 2
    assert tuple(getattr(jax.config, f) for f in flags) == before


# ---------------------------------------------------------------------------
# HBM watermark telemetry
# ---------------------------------------------------------------------------


def test_watermark_measures_allocations():
    from repro.obs import sample, watermark

    with watermark() as wm:
        x = jnp.ones((256, 256), jnp.float32)
        sync(x)
        sample()
    assert wm.source in ("device_stats", "live_buffers")
    assert wm.peak_hbm_bytes >= 256 * 256 * 4
    assert wm.hbm_bytes_in_use >= 0
    del x


def test_memory_sample_on_tpu_without_stats_raises(monkeypatch):
    """On a TPU the allocator watermark is the only acceptable source: a
    device that reports no ``memory_stats()`` raises instead of being
    relabelled with live-buffer bytes."""
    import jax

    from repro.obs import memory

    class _Dev:
        platform = "tpu"

        def memory_stats(self):
            return None

    monkeypatch.setattr(jax, "devices", lambda: [_Dev()])
    with pytest.raises(RuntimeError, match="memory_stats"):
        memory.sample()


def test_watermark_outer_absorbs_nested_samples():
    """An inner window's sample points fold into every open outer window,
    so an allocation freed before the outer exit still shows in its peak."""
    from repro.obs import sample, watermark

    with watermark() as outer:
        with watermark() as inner:
            x = jnp.ones((128, 128), jnp.float32)
            sync(x)
            sample()
            del x
    assert inner.peak_hbm_bytes >= 128 * 128 * 4
    assert outer.peak_hbm_bytes >= inner.peak_hbm_bytes
    assert outer.delta_bytes == (outer.exit.bytes_in_use
                                 - outer.enter.bytes_in_use)


def test_watermark_window_closes_on_error():
    from repro.obs import memory, watermark

    with pytest.raises(RuntimeError):
        with watermark():
            raise RuntimeError("boom")
    assert memory._open_watermarks() == []


def test_watermark_windows_are_thread_local():
    """A sample taken on another thread folds into that thread's windows
    only — concurrent pipelines never pollute each other's peaks."""
    import threading

    from repro.obs import memory, watermark

    with watermark() as wm:
        before = wm.peak_hbm_bytes
        t = threading.Thread(target=memory.sample)
        t.start()
        t.join()
        assert wm.peak_hbm_bytes == before


def test_span_survives_enter_sample_failure(monkeypatch):
    """A failing enter sample must not leak its watermark into the open
    registry (every later sample would fold into it forever) nor kill the
    span: the span records without memory attribution instead."""
    from repro.obs import memory

    def boom():
        raise RuntimeError("sampling failed")

    monkeypatch.setattr(memory, "sample", boom)
    tr = Tracer()
    with tracing(tr):
        with span("Stage", kind="stage") as sp:
            pass
    assert memory._open_watermarks() == []
    assert tr.roots == [sp]
    assert "peak_hbm_bytes" not in sp.attrs


def test_span_memory_attribution():
    """Spans under a memory-enabled tracer carry the HBM attrs the trace
    export and check_trace.py's stage assertion consume."""
    tr = Tracer()
    with tracing(tr):
        with span("Stage", kind="stage"):
            x = jnp.ones((64, 64), jnp.float32)
            sync(x)
    sp = tr.roots[0]
    for key in ("peak_hbm_bytes", "hbm_bytes_in_use", "hbm_delta_bytes",
                "hbm_source"):
        assert key in sp.attrs, key
    assert sp.attrs["peak_hbm_bytes"] >= sp.attrs["hbm_delta_bytes"]
    del x


def test_tracer_memory_opt_out():
    tr = Tracer(memory=False)
    with tracing(tr):
        with span("Stage", kind="stage"):
            pass
    assert "peak_hbm_bytes" not in tr.roots[0].attrs


def test_timed_returns_compile_split_and_watermark():
    import jax

    from benchmarks._timing import timed

    t = timed(jax.jit(lambda: jnp.ones((64, 64)) * 2),
              out_of=lambda r: r, reps=2)
    assert t.steady_us >= 0 and t.compile_us > 0
    assert t.peak_hbm_bytes >= 64 * 64 * 4
    assert t.hbm_source in ("device_stats", "live_buffers")


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------


def test_chrome_trace_export(tmp_path):
    from repro.obs import write_chrome_trace

    tr = Tracer()
    with tracing(tr):
        with span("Stage", kind="stage"):
            with span("Phase", kind="phase", phase="ring_stage", s=0):
                pass
    path = write_chrome_trace(tr, str(tmp_path / "t.json"))
    doc = json.loads(open(path).read())
    events = doc["traceEvents"]
    assert [e["name"] for e in events] == ["Stage", "Phase"]
    outer, inner = events
    assert outer["ph"] == "X" and inner["ph"] == "X"
    # nesting == ts/dur containment (Perfetto's stacking rule)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner["args"]["phase"] == "ring_stage"
    tree = doc["spanTree"]
    assert tree[0]["name"] == "Stage"
    assert tree[0]["children"][0]["attrs"]["phase"] == "ring_stage"
