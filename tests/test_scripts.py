"""CI gate scripts (``scripts/check_bench_regression.py``,
``scripts/check_trace.py`` and ``scripts/check_smoke_comm.py``) against
pass/fail fixtures.

The scripts are stdlib-only and loaded by file path (``scripts/`` is not a
package); the fixtures pin both directions of each gate — a clean run
exits 0 and each contract violation (gross slowdown, watermark growth,
dropped row, broken span nesting, missing memory attribution) produces a
targeted failure instead of a silent pass."""

import importlib.util
import json
import os

import pytest

_SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cbr():
    return _load_script("check_bench_regression")


@pytest.fixture(scope="module")
def ctr():
    return _load_script("check_trace")


# ---------------------------------------------------------------------------
# check_bench_regression
# ---------------------------------------------------------------------------


def _row(name, ms=10.0, peak=4 << 20, split=True, experiment=None):
    rec = {"name": name, "ms": ms, "peak_hbm_bytes": peak}
    if split:
        rec["compile_ms"] = 1.0
    if experiment is not None:
        rec["experiment"] = experiment
    return rec


def _write(path, rows):
    if str(path).endswith(".jsonl"):
        path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    else:
        path.write_text(json.dumps(rows))
    return str(path)


def test_cbr_clean_run_passes(tmp_path, cbr, capsys):
    fresh = _write(tmp_path / "fresh.json", [_row("a"), _row("b")])
    prev = _write(tmp_path / "traj.jsonl",
                  [_row("a", ms=9.0), _row("b", ms=11.0)])
    assert cbr.main([fresh, prev]) == 0
    assert "no gross perf/memory regression" in capsys.readouterr().out


def test_cbr_time_regression_fails(tmp_path, cbr, capsys):
    fresh = _write(tmp_path / "fresh.json", [_row("a", ms=100.0)])
    prev = _write(tmp_path / "prev.json", [_row("a", ms=10.0)])
    assert cbr.main([fresh, prev]) == 1
    assert "previous best 10.0 ms" in capsys.readouterr().out


def test_cbr_memory_regression_fails(tmp_path, cbr, capsys):
    fresh = _write(tmp_path / "fresh.json",
                   [_row("a", peak=40 << 20)])
    prev = _write(tmp_path / "prev.json", [_row("a", peak=4 << 20)])
    assert cbr.main([fresh, prev]) == 1
    assert "watermark grew" in capsys.readouterr().out


def test_cbr_small_pools_skip_memory_gate(tmp_path, cbr):
    # both sides under MIN_BYTES: allocator noise, not working-set growth
    fresh = _write(tmp_path / "fresh.json", [_row("a", peak=900_000)])
    prev = _write(tmp_path / "prev.json", [_row("a", peak=1_000)])
    assert cbr.main([fresh, prev]) == 0


def test_cbr_missing_memory_baseline_skips_gate(tmp_path, cbr):
    fresh = _write(tmp_path / "fresh.json", [_row("a", peak=1 << 30)])
    prev = _write(tmp_path / "prev.json",
                  [{"name": "a", "ms": 10.0, "compile_ms": 1.0}])
    assert cbr.main([fresh, prev]) == 0


def test_cbr_dropped_row_fails_coverage(tmp_path, cbr, capsys):
    fresh = _write(tmp_path / "fresh.json", [_row("a")])
    prev = _write(tmp_path / "prev.json", [_row("a"), _row("gone")])
    assert cbr.main([fresh, prev]) == 1
    assert "missing from fresh records" in capsys.readouterr().out


def test_cbr_out_of_scope_experiment_is_not_a_drop(tmp_path, cbr):
    # trajectory holds a full-size experiment the smoke run never executes:
    # out of scope, not a dropped benchmark
    fresh = _write(tmp_path / "fresh.json",
                   [_row("a", experiment="tr")])
    prev = _write(tmp_path / "traj.jsonl",
                  [_row("a", experiment="tr"),
                   _row("big/row", experiment="sparsity")])
    assert cbr.main([fresh, prev]) == 0


def test_cbr_pre_split_baseline_skipped_with_notice(tmp_path, cbr, capsys):
    fresh = _write(tmp_path / "fresh.json", [_row("a", ms=1000.0, peak=None)])
    prev = _write(tmp_path / "prev.json",
                  [{"name": "a", "ms": 1.0}])  # pre-split era
    assert cbr.main([fresh, prev]) == 0
    assert "skipped, not compared" in capsys.readouterr().out


def test_cbr_best_within_one_file_wins(tmp_path, cbr, capsys):
    # a .jsonl trajectory holds one row per code snapshot: the baseline is
    # the best ever recorded, not merely the most recent row (otherwise
    # each PR may regress 5x vs the previous PR — ratchet creep)
    fresh = _write(tmp_path / "fresh.json", [_row("a", ms=30.0)])
    prev = _write(tmp_path / "traj.jsonl",
                  [_row("a", ms=2.0), _row("a", ms=29.0)])
    assert cbr.main([fresh, prev]) == 1  # 30 > 5 x 2, not vs 29
    assert "previous best 2.0 ms" in capsys.readouterr().out


def test_cbr_within_file_smallest_watermark_wins(tmp_path, cbr, capsys):
    fresh = _write(tmp_path / "fresh.json", [_row("a", peak=40 << 20)])
    prev = _write(tmp_path / "traj.jsonl",
                  [_row("a", peak=4 << 20), _row("a", peak=39 << 20)])
    assert cbr.main([fresh, prev]) == 1
    assert "watermark grew" in capsys.readouterr().out


def test_cbr_later_pre_split_row_keeps_split_baseline(tmp_path, cbr, capsys):
    # a pre-split row appended after a split one must not displace it
    fresh = _write(tmp_path / "fresh.json", [_row("a", ms=100.0)])
    prev = _write(tmp_path / "traj.jsonl",
                  [_row("a", ms=10.0), {"name": "a", "ms": 0.5}])
    assert cbr.main([fresh, prev]) == 1
    assert "previous best 10.0 ms" in capsys.readouterr().out


def test_cbr_fresh_fingerprint_rows_excluded_from_baseline(
        tmp_path, cbr, capsys):
    # CI: the engine appends fresh rows to the trajectory before the gate
    # runs; rows stamped with the fresh run's fingerprint must not serve
    # as baseline or the ratio gates compare a measurement to itself
    fresh = _write(tmp_path / "fresh.json",
                   [dict(_row("a", ms=100.0), fingerprint="fpNEW")])
    prev = _write(tmp_path / "traj.jsonl",
                  [dict(_row("a", ms=10.0), fingerprint="fpOLD"),
                   dict(_row("a", ms=100.0), fingerprint="fpNEW")])
    assert cbr.main([fresh, prev]) == 1
    assert "previous best 10.0 ms" in capsys.readouterr().out
    # a store holding only the self-snapshot means the trajectory starts
    # here: zero shared rows, clean pass — not a silent self-comparison
    only_self = _write(tmp_path / "self.jsonl",
                       [dict(_row("a", ms=100.0), fingerprint="fpNEW")])
    assert cbr.main([fresh, only_self]) == 0
    assert "0 shared row(s)" in capsys.readouterr().out


def test_cbr_unlabelled_fresh_keeps_full_coverage(tmp_path, cbr, capsys):
    # legacy benchmarks/run.py output carries no experiment labels: every
    # labelled baseline row stays in scope, so a dropped row still fails
    # instead of being skipped as "out of scope"
    fresh = _write(tmp_path / "fresh.json", [_row("a")])
    prev = _write(tmp_path / "traj.jsonl",
                  [_row("a"), _row("gone", experiment="sparsity")])
    assert cbr.main([fresh, prev]) == 1
    assert "missing from fresh records" in capsys.readouterr().out


def test_cbr_best_previous_wins_across_baselines(tmp_path, cbr):
    fresh = _write(tmp_path / "fresh.json", [_row("a", ms=30.0)])
    slow = _write(tmp_path / "p1.json", [_row("a", ms=29.0)])
    fast = _write(tmp_path / "p2.jsonl", [_row("a", ms=2.0)])
    assert cbr.main([fresh, slow]) == 0
    assert cbr.main([fresh, slow, fast]) == 1  # 30 > 5 x 2


def test_cbr_usage_and_no_baseline(tmp_path, cbr, monkeypatch):
    assert cbr.main([]) == 2
    # no baselines anywhere: trajectory starts here
    monkeypatch.setattr(cbr, "_default_baselines", lambda fresh: [])
    fresh = _write(tmp_path / "fresh.json", [_row("a")])
    assert cbr.main([fresh]) == 0


def test_cbr_default_baselines_prefer_trajectory(tmp_path, cbr):
    root = str(tmp_path)
    assert cbr._default_baselines("fresh.json", root=root) == []
    _write(tmp_path / "BENCH_2.json", [_row("a", ms=1.0)])
    _write(tmp_path / "BENCH_10.json", [_row("a", ms=50.0)])
    found = cbr._default_baselines("fresh.json", root=root)
    assert [os.path.basename(p) for p in found] == \
        ["BENCH_10.json"]  # numeric, not lexicographic, latest
    # the fresh file itself never serves as its own baseline
    fresh = str(tmp_path / "BENCH_10.json")
    found = cbr._default_baselines(fresh, root=root)
    assert [os.path.basename(p) for p in found] == ["BENCH_2.json"]
    (tmp_path / "bench").mkdir()
    _write(tmp_path / "bench" / "trajectory.jsonl", [_row("a")])
    found = cbr._default_baselines("fresh.json", root=root)
    assert [os.path.basename(p) for p in found] == ["trajectory.jsonl"]


# ---------------------------------------------------------------------------
# check_trace
# ---------------------------------------------------------------------------


_MEM = {"peak_hbm_bytes": 1024, "hbm_bytes_in_use": 512,
        "hbm_source": "live_buffers"}


def _node(name, kind, children=(), **attrs):
    return {"name": name, "attrs": {"kind": kind, **attrs},
            "children": list(children)}


def _valid_tree(ctr):
    def stage(name, children=()):
        return _node(name, "stage", children, **_MEM)

    def phase(ph, children=()):
        return _node(ph, "phase", children, phase=ph)

    spgemm_children = [
        phase("skew"),
        phase("ring", [phase("ring_stage", [_node("op", "op")])]),
        phase("collect_merge"),
    ]
    contig_children = [phase("chain_stage",
                             [phase("cut"), phase("doubling"),
                              phase("sort")])]
    align_children = [phase("pair_exchange",
                            [phase("gather_reads"),
                             phase("extend", [_node("op", "op")]),
                             phase("scatter_scores")])]
    tree = []
    for name in ctr.STAGES:
        kids = ({"SpGEMM": spgemm_children,
                 "Contigs": contig_children,
                 "Alignment": align_children}.get(name, ()))
        tree.append(stage(name, kids))
    return tree


def test_ctr_valid_tree_passes(ctr):
    assert ctr.check(_valid_tree(ctr)) == []


def test_ctr_missing_stage_and_order(ctr):
    tree = _valid_tree(ctr)
    tree[0], tree[1] = tree[1], tree[0]
    assert any("out of Algorithm 1 order" in m for m in ctr.check(tree))
    assert any("missing stage root" in m for m in ctr.check(tree[1:]))


def test_ctr_missing_memory_attribution_fails(ctr):
    tree = _valid_tree(ctr)
    del tree[3]["attrs"]["peak_hbm_bytes"]  # Alignment
    msgs = ctr.check(tree)
    assert any("memory attribution" in m and "Alignment" in m for m in msgs)


def test_ctr_missing_ring_or_chain_phase_fails(ctr):
    tree = _valid_tree(ctr)
    spgemm = next(n for n in tree if n["name"] == "SpGEMM")
    spgemm["children"] = [c for c in spgemm["children"]
                          if c["name"] != "ring"]
    msgs = ctr.check(tree)
    assert any("ring_stage" in m for m in msgs)
    tree2 = _valid_tree(ctr)
    contigs = next(n for n in tree2 if n["name"] == "Contigs")
    contigs["children"] = []
    assert any("chain_stage" in m for m in ctr.check(tree2))


def test_ctr_missing_align_phase_fails(ctr):
    tree = _valid_tree(ctr)
    align = next(n for n in tree if n["name"] == "Alignment")
    align["children"] = []
    msgs = ctr.check(tree)
    for ph in ("pair_exchange", "gather_reads", "extend", "scatter_scores"):
        assert any(f"phase={ph!r}" in m and "Alignment" in m for m in msgs)


def test_ctr_main_exit_codes(tmp_path, ctr, capsys):
    good = tmp_path / "good.json"
    good.write_text(json.dumps({"traceEvents": [],
                                "spanTree": _valid_tree(ctr)}))
    assert ctr.main([str(good)]) == 0
    assert "span-tree structure ok" in capsys.readouterr().out
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": []}))
    assert ctr.main([str(bad)]) == 1
    assert ctr.main([]) == 2


# ---------------------------------------------------------------------------
# check_smoke_comm
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def csc():
    return _load_script("check_smoke_comm")


def _comm_row(op, shape, derived):
    return {"name": f"{op}[shard_map]/{shape}", "op": op,
            "backend": "shard_map", "shape": shape, "ms": 1.0,
            "derived": derived}


def _valid_artifact():
    return [
        _comm_row("contigs", "n256",
                  "exchange_words_sort=100;model_words_sort=100"),
        _comm_row("overlap", "ring_2x2",
                  "exchange_words_summa=200;model_words_summa=200"),
        _comm_row("align", "bucket512_P4",
                  "exchange_words_align=300;model_words_align=300"),
    ]


def test_csc_valid_artifact_passes(tmp_path, csc, capsys):
    path = _write(tmp_path / "bench.json", _valid_artifact())
    assert csc.main([path]) == 0
    out = capsys.readouterr().out
    assert "comm-model cross-check ok" in out
    assert "1 align" in out


def test_csc_missing_align_row_fails(tmp_path, csc, capsys):
    # a smoke artifact without the distributed-alignment row means the
    # distribution axis was silently dropped — CI must fail, not pass
    records = [r for r in _valid_artifact() if r["op"] != "align"]
    path = _write(tmp_path / "bench.json", records)
    assert csc.main([path]) == 1
    assert "no align[*/shard_map] rows found" in capsys.readouterr().out


def test_csc_align_word_mismatch_fails(tmp_path, csc, capsys):
    records = _valid_artifact()
    records[-1]["derived"] = \
        "exchange_words_align=300;model_words_align=600"
    path = _write(tmp_path / "bench.json", records)
    assert csc.main([path]) == 1
    assert "exchange_words_align=300" in capsys.readouterr().out


def test_csc_missing_align_fields_fails(tmp_path, csc, capsys):
    records = _valid_artifact()
    records[-1]["derived"] = "bucket=512"
    path = _write(tmp_path / "bench.json", records)
    assert csc.main([path]) == 1
    assert "missing exchange_words_align" in capsys.readouterr().out


def test_csc_degenerate_p1_rows_pass(tmp_path, csc):
    # P == 1: every exchange degenerates, both sides exactly 0
    records = [
        _comm_row("contigs", "n256",
                  "exchange_words_sort=0;model_words_sort=0"),
        _comm_row("overlap", "ring_1x1",
                  "exchange_words_summa=0;model_words_summa=0"),
        _comm_row("align", "bucket512_P1",
                  "exchange_words_align=0;model_words_align=0"),
    ]
    path = _write(tmp_path / "bench.json", records)
    assert csc.main([path]) == 0
