"""Profiler trace → device time per (stage, named scope), and the host
readbacks the program annotated.

Reads the same ``.xplane.pb`` as ``trace_reduce``.  Every op on a device
plane's ``XLA Ops`` line counts, ops nested inside a ``while`` included,
each with its exclusive time: its interval, clipped to the window, less the
ops nested in it.  So the times of all ops sum to the union of their
intervals, ``trace_reduce``'s ``busy_s``.

An op's scope path is the ``tf_op`` stat of its event metadata: the HLO
``op_name`` that ``jax.named_scope`` extends (on a v5e,
``jit(spgemm)/while/body/closed_call/merge_sorted_rows/sort/sort:``).  The
listed scopes on the path, outermost first, name the op's row
(``merge_sorted_rows/sort``); an op under none of them, or with no
``tf_op``, is ``other``.  Its stage is the stage span open at the op's
midpoint, as ``trace_reduce`` labels idle gaps.  Times are averaged over
the devices and divided by the window's jobs.

The host spans named ``readback:<site>`` (``repro.obs.readback`` under a
traced pipeline, one per blocking device→host read) are counted per job.

``jax.profiler.ProfileData`` does not expose event-metadata stats, so the
device planes are read here from the XSpace protobuf wire format
(``tsl/profiler/protobuf/xplane.proto``); host spans come from
``trace_reduce.collect``.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from collections import defaultdict

import trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))
PROFILE_DIR = os.path.join(BENCH, ".profile")  # where run.py records
# named scopes of the program, and the inner scopes each one splits into
SCOPES = ("spgemm_expand", "merge_sorted_rows", "align_staging",
          "xdrop_kernel", "from_coo", "xdrop_extend", "spgemm_ring_stages",
          "cc_labels", "pileup_vote", "minplus_dense")
INNER = {"merge_sorted_rows": ("sort", "combine", "compact")}
OTHER = "other"
READBACK = "readback:"
BETWEEN = "between stages"


# --- XSpace wire format -----------------------------------------------------

def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field, raw bytes for a fixed one."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _tf_op(meta, stat_names: dict):
    """The ``tf_op`` stat of an XEventMetadata, or None."""
    for f, stat in _fields(meta):
        if f != 5:  # XEventMetadata.stats
            continue
        st = dict(_fields(stat))
        if stat_names.get(st.get(1)) != "tf_op":
            continue
        if 5 in st:  # str_value
            return _text(st[5])
        if 7 in st:  # ref_value: the string is a stat metadata name
            return stat_names.get(st[7])
    return None


def device_ops(data: bytes) -> dict:
    """Per device plane, ``[(start_ns, end_ns, tf_op or None), ...]`` of the
    ops on its ``XLA Ops`` line (every line, where a plane has none), on
    the time base of ``jax.profiler.ProfileData``."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:  # XSpace.planes
            continue
        name, lines, metas, stat_names = "", [], {}, {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _text(v)
            elif pf == 3:
                lines.append(v)
            elif pf in (4, 5):  # event / stat metadata maps
                entry = dict(_fields(v))
                if 2 not in entry:
                    continue
                if pf == 4:
                    metas[entry.get(1, 0)] = entry[2]
                else:
                    sm = dict(_fields(entry[2]))
                    stat_names[sm.get(1, 0)] = _text(sm.get(2, b""))
        if not name.startswith("/device:"):
            continue
        parsed = []
        for ln in lines:
            lname, ts, evs = "", 0, []
            for lf, v in _fields(ln):
                if lf == 2:
                    lname = _text(v)
                elif lf == 3:
                    ts = v
                elif lf == 4:
                    evs.append(v)
            parsed.append((lname, ts, evs))
        chosen = [p for p in parsed if p[0] in trace_reduce.OP_LINES] or parsed
        paths, ops = {}, []
        for _, ts, evs in chosen:
            for ev in evs:
                mid = off = dur = 0
                for ef, v in _fields(ev):
                    if ef == 1:
                        mid = v
                    elif ef == 2:
                        off = v
                    elif ef == 3:
                        dur = v
                if mid not in paths:
                    meta = metas.get(mid)
                    paths[mid] = (None if meta is None
                                  else _tf_op(meta, stat_names))
                start = ts + off / 1e3
                ops.append((start, start + dur / 1e3, paths[mid]))
        if ops:
            out[name] = ops
    return out


# --- attribution ------------------------------------------------------------

def scope_of(tf_op) -> str:
    """The row of an op: the listed scopes on its ``op_name`` path,
    outermost first (``merge_sorted_rows/sort``), or ``other``."""
    if not tf_op:
        return OTHER
    parts = tf_op.rstrip(":").split("/")
    row = []
    for prev, part in zip([None] + parts, parts):
        if part in SCOPES:
            if not row or row[-1] != part:  # a kernel inside its own scope
                row.append(part)
        elif row and prev == row[-1] and part in INNER.get(prev, ()):
            row.append(part)
    return "/".join(row) or OTHER


def exclusive(ops, window):
    """``[(start, end, row, exclusive_ns)]`` of ops clipped to ``window``;
    an op starting inside another is nested in it (clipped to its end), so
    the exclusive times sum to the union of the intervals."""
    w0, w1 = window
    clipped = sorted((max(s, w0), -min(e, w1), row) for s, e, row in ops
                     if e > w0 and s < w1)
    out, stack = [], []  # stack entries: [start, end, row, nested_ns]

    def close(entry):
        s, e, row, nested = entry
        out.append((s, e, row, (e - s) - nested))

    for s, neg_e, row in clipped:
        e = -neg_e
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            e = min(e, stack[-1][1])
            stack[-1][3] += e - s
        stack.append([s, e, row, 0.0])
    while stack:
        close(stack.pop())
    return out


def reduce_events(devices: dict, host: list, n_jobs: int) -> dict:
    """Seconds per job by ``[stage][row]``, their total over the window
    (``busy_s``), and readback spans per job.  ``devices`` maps a plane to
    ``[(start_ns, end_ns, row)]``; ``host`` is ``trace_reduce``'s host
    spans.  The window is ``trace_reduce``'s: first to last stage span."""
    stage_spans = [h for h in host if h[0] in trace_reduce.STAGES]
    ref = stage_spans or [ev for evs in devices.values() for ev in evs]
    window = (min(s for _, s, _ in ref), max(e for _, _, e in ref))
    table = defaultdict(lambda: defaultdict(float))
    busy = 0.0
    for ops in devices.values():
        for s, e, row, ns in exclusive(ops, window):
            stage = (trace_reduce.innermost(stage_spans, (s + e) / 2,
                                            trace_reduce.STAGES) or BETWEEN)
            table[stage][row] += ns / len(devices)
            busy += ns / len(devices)
    readbacks = sum(1 for name, s, e in host if name.startswith(READBACK)
                    and window[0] <= (s + e) / 2 <= window[1])
    return {
        "per_job": {st: {row: ns / 1e9 / n_jobs for row, ns in rows.items()}
                    for st, rows in table.items()},
        "busy_s": busy / 1e9,
        "readbacks_per_job": readbacks / n_jobs,
        "n_jobs": n_jobs,
    }


def reduce_dir(profile_dir: str, n_jobs: int) -> dict:
    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    with open(files[-1], "rb") as f:
        ops = device_ops(f.read())
    devices = {plane: [(s, e, scope_of(tf)) for s, e, tf in evs]
               for plane, evs in ops.items()}
    _, host = trace_reduce.collect(trace_reduce.load(profile_dir))
    return reduce_events(devices, host, n_jobs)


def log_table(red: dict) -> None:
    rows = sorted(((st, row, t) for st, rs in red["per_job"].items()
                   for row, t in rs.items()), key=lambda x: -x[2])
    print(f"[scopes] n_jobs={red['n_jobs']} busy_s={red['busy_s']:.6f} "
          f"readbacks_per_job={red['readbacks_per_job']} per_job_s="
          + json.dumps([[st, row, round(t, 6)] for st, row, t in rows]),
          file=sys.stderr, flush=True)


_CACHE: dict = {}


def table(ctx: dict, profile_dir: str = PROFILE_DIR):
    """The reduction of the traced window behind ``ctx`` (one per profile
    and job count, logged once as a ``[scopes]`` line), or None where no
    profile was recorded."""
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    key = (max(files), os.path.getmtime(max(files)), len(ctx["jobs"]))
    if key not in _CACHE:
        _CACHE[key] = reduce_dir(profile_dir, len(ctx["jobs"]))
        log_table(_CACHE[key])
    return _CACHE[key]


def seconds(ctx: dict, stage: str, scope: str):
    """Mean device seconds per job under ``scope`` (its inner scopes
    included) in ``stage``; None where the trace has no op under ``scope``
    at all (a program without the scope)."""
    red = table(ctx)
    if red is None:
        return None

    def under(row):
        return row == scope or row.startswith(scope + "/")

    if not any(under(row) for rows in red["per_job"].values()
               for row in rows):
        return None
    return sum(t for row, t in red["per_job"].get(stage, {}).items()
               if under(row))


def readbacks(ctx: dict):
    """Mean ``readback:`` host spans per job; None where the trace has none
    (a program that does not annotate its reads)."""
    red = table(ctx)
    if red is None or not red["readbacks_per_job"]:
        return None
    return red["readbacks_per_job"]
