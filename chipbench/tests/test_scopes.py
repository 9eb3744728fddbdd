"""Device time per named scope on synthetic events and a synthetic XSpace."""

import json
import os

import pytest

import scopes
import trace_reduce
from conftest import ROOT
from run import load_metric

DEV = "/device:TPU:0"
STAGE_HOST = [("SpGEMM", 0, 1000), ("Alignment", 1000, 2000)]


def test_while_time_excludes_its_nested_ops():
    ops = [(0, 100, "other"), (10, 30, "spgemm_expand"),
           (40, 90, "merge_sorted_rows/sort"), (50, 60, "from_coo")]
    got = {row: ns for _, _, row, ns in scopes.exclusive(ops, (0, 1000))}
    assert got == {"other": 30, "spgemm_expand": 20,
                   "merge_sorted_rows/sort": 40, "from_coo": 10}


def test_ops_are_clipped_to_the_window():
    ops = [(-50, 50, "a"), (900, 1100, "b"), (1200, 1300, "c")]
    got = [(row, ns) for _, _, row, ns in scopes.exclusive(ops, (0, 1000))]
    assert sorted(got) == [("a", 50), ("b", 100)]


@pytest.mark.parametrize("tf_op, row", [
    ("jit(spgemm)/while/body/spgemm_expand/gather:", "spgemm_expand"),
    ("jit(spgemm)/while/body/closed_call/merge_sorted_rows/sort/"
     "jit(argsort)/sort:", "merge_sorted_rows/sort"),
    ("jit(f)/merge_sorted_rows/combine/cumsum:", "merge_sorted_rows/combine"),
    ("jit(f)/merge_sorted_rows/lt:", "merge_sorted_rows"),
    ("jit(f)/while/body/xdrop_kernel/xdrop_extend/xdrop_extend/"
     "pallas_call:", "xdrop_kernel/xdrop_extend"),
    ("jit(_transpose)/from_coo/sort:", "from_coo"),
    ("jit(f)/while/body/sort:", "other"),
    ("jit(f)/compact/sort:", "other"),
    (None, "other"),
])
def test_row_is_the_listed_scopes_on_the_path(tf_op, row):
    assert scopes.scope_of(tf_op) == row


def test_stage_by_midpoint_and_sum_equals_busy():
    devices = {DEV: [(0, 600, "other"), (100, 500, "spgemm_expand"),
                     (800, 1300, "merge_sorted_rows/compact"),
                     (1400, 1900, "xdrop_kernel/xdrop_extend"),
                     (1500, 1600, "align_staging")]}
    host = STAGE_HOST + [("readback:n_live", 1950, 1960),
                         ("readback:nnz_C", 990, 1010)]
    red = scopes.reduce_events(devices, host, n_jobs=2)
    # the compact op straddles the stage boundary: its midpoint (1050)
    # puts it in Alignment
    assert red["per_job"]["SpGEMM"] == pytest.approx(
        {"other": 100e-9, "spgemm_expand": 200e-9})
    assert red["per_job"]["Alignment"] == pytest.approx(
        {"merge_sorted_rows/compact": 250e-9,
         "xdrop_kernel/xdrop_extend": 200e-9, "align_staging": 50e-9})
    busy = trace_reduce.reduce_events(
        {DEV: [("x", s, e) for s, e, _ in devices[DEV]]}, host)["busy_s"]
    total = sum(t for rows in red["per_job"].values() for t in rows.values())
    assert red["busy_s"] == pytest.approx(busy)
    assert total * red["n_jobs"] == pytest.approx(busy)
    assert red["readbacks_per_job"] == 1.0


# --- a synthetic XSpace in the protobuf wire format -------------------------

def _varint(x):
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _xspace():
    stat_meta = [(7, "tf_op"), (8, "hlo_category")]
    ev_meta = [
        (1, "%while = ...", [(8, 5, "while")]),  # no tf_op
        (2, "%fusion.1 = ...", [(7, 5, "jit(f)/spgemm_expand/mul:")]),
        (3, "%sort.2 = ...", [(7, 7, 9)]),  # by reference to stat meta 9
    ]
    stat_meta.append((9, "jit(f)/merge_sorted_rows/sort/sort:"))
    plane = [(1, 0), (2, DEV)]
    plane += [(4, _msg((1, i), (2, _msg((1, i), (2, name), *[
        (5, _msg((1, sid), (kind, val))) for sid, kind, val in stats]))))
        for i, name, stats in ev_meta]
    plane += [(5, _msg((1, i), (2, _msg((1, i), (2, name)))))
              for i, name in stat_meta]
    events = [(1, 0, 1_000_000), (2, 100_000, 200_000), (3, 400_000, 300_000)]
    line = _msg((1, 3), (2, "XLA Ops"), (3, 5000),
                *[(4, _msg((1, m), (2, off), (3, dur)))
                  for m, off, dur in events])
    modules = _msg((1, 2), (2, "XLA Modules"), (3, 5000),
                   (4, _msg((1, 1), (2, 0), (3, 9_000_000))))
    plane += [(3, modules), (3, line)]
    host = _msg((1, 1), (2, "/host:CPU"))
    return _msg((1, _msg(*plane)), (1, host))


def test_device_ops_read_from_the_wire_format():
    ops = scopes.device_ops(_xspace())
    assert list(ops) == [DEV]
    assert ops[DEV] == [
        (5000.0, 6000.0, None),
        (5100.0, 5300.0, "jit(f)/spgemm_expand/mul:"),
        (5400.0, 5700.0, "jit(f)/merge_sorted_rows/sort/sort:"),
    ]


# --- the readers ------------------------------------------------------------

NEW = ["xdrop_kernel_s", "align_staging_s", "spgemm_expand_s",
       "spgemm_merge_s", "host_readbacks"]


@pytest.mark.parametrize("metric", NEW)
def test_reader_declares_its_benchmark_entry(metric):
    entry = {m["name"]: m for m in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["per_layer"]}[metric]
    mod = load_metric(metric)
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert "workloads" not in entry  # every cell reports it


def _reduction(per_job, readbacks=15.0):
    return {"per_job": per_job, "busy_s": 1.0, "readbacks_per_job": readbacks,
            "n_jobs": 1}


@pytest.mark.parametrize("metric, value", [
    ("xdrop_kernel_s", 3.0), ("align_staging_s", 2.0),
    ("spgemm_expand_s", 4.0), ("spgemm_merge_s", 6.0),
    ("host_readbacks", 15.0),
])
def test_readers_read_their_scope(monkeypatch, metric, value):
    red = _reduction({
        "SpGEMM": {"spgemm_expand": 4.0, "merge_sorted_rows/sort": 3.5,
                   "merge_sorted_rows/compact": 2.0, "merge_sorted_rows": 0.5,
                   "other": 0.1},
        "Alignment": {"xdrop_kernel/xdrop_extend": 3.0, "align_staging": 2.0,
                      "other": 0.4},
        "BuildR": {"from_coo": 1.0},
    })
    monkeypatch.setattr(scopes, "table", lambda ctx: red)
    assert load_metric(metric).read({"jobs": [{}]}) == pytest.approx(value)


@pytest.mark.parametrize("metric", NEW)
def test_readers_read_nothing_from_a_program_without_scopes(monkeypatch,
                                                            metric):
    red = _reduction({"SpGEMM": {"other": 15.0}, "Alignment": {"other": 22.0}},
                     readbacks=0.0)
    monkeypatch.setattr(scopes, "table", lambda ctx: red)
    assert load_metric(metric).read({"jobs": [{}]}) is None
    monkeypatch.setattr(scopes, "table", lambda ctx: None)
    assert load_metric(metric).read({"jobs": [{}]}) is None
