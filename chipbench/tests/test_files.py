"""The benchmark is found by name: every cell, configuration, traffic mix,
limits file and per-layer metric reader exists and agrees with
BENCHMARK.json; run.py refuses a host with no TPU."""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["chipbench"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51


@pytest.mark.parametrize("cell", BENCHMARK["workloads"],
                         ids=lambda c: c["name"])
def test_cell_files(cell):
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    assert cell["config"] in configs
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    cfg = json.load(open(os.path.join(ROOT, configs[cell["config"]]["file"])))
    assert cfg["name"] == cell["config"]
    assert set(configs[cell["config"]]["reduced"]) <= set(cfg["reduced"])
    for key in ("source", "reduced", "assumed", "derivation", "guarantees"):
        assert cfg[key], key
    # every PipelineConfig field the file sets is derived by a stated formula
    assert set(cfg["pipeline"]) <= set(cfg["derivation"])
    for key in ("lower", "upper", "read_capacity", "overlap_capacity",
                "r_capacity", "m_capacity", "band", "max_steps", "xdrop"):
        assert key in cfg["pipeline"], key
    assert os.path.exists(os.path.join(BENCH, "traffic",
                                       cell["traffic"] + ".json"))
    limits = json.load(open(os.path.join(BENCH, "workloads",
                                         cell["name"] + ".json")))
    assert "r_edge_errors" in limits and "control" in limits


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_declares_itself(metric):
    from run import load_metric

    mod = load_metric(metric["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        metric["unit"], metric["layer"], metric["moves"])
    stages = {"CountKmer": 1.0, "CreateSpMat": 1.0, "SpGEMM": 2.0,
              "Alignment": 3.0, "BuildR": 0.5, "TrReduction": 0.25,
              "Contigs": 0.25}
    ctx = {"jobs": [{"wall_s": 8.5, "timings": stages}] * 2,
           "trace": {"idle_share": 0.1}, "window_compiles": 0}
    value = mod.read(ctx)
    assert isinstance(value, (int, float)) and value >= 0
    assert metric["moves"] in {m["name"] for m in BENCHMARK["end_to_end"]}


@pytest.mark.parametrize("metric", BENCHMARK["end_to_end"]
                         + BENCHMARK["per_layer"], ids=lambda m: m["name"])
def test_metric_names_and_units(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCHMARK["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
