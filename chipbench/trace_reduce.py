"""Profiler trace → device busy and idle time, per-op device time, and idle
gaps labelled by what the host was doing.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes.  Device planes are
the ``/device:`` ones; on each, the ``XLA Ops`` line (every line, where a
plane has none) gives the operations that ran, named by their HLO
instruction (``%fusion.82``); per-op time counts top-level ops only, so an
op inside a loop body counts in the loop's time.  Busy time is the union of
their intervals, averaged over the devices; an idle gap is a stretch
between them, labelled by the innermost host span (``TraceAnnotation``)
open at its midpoint, under the stage span open then.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

STAGES = ("CountKmer", "CreateSpMat", "SpGEMM", "Alignment", "BuildR",
          "TrReduction", "Contigs", "Consensus")
OP_LINES = ("XLA Ops",)


def load(profile_dir: str):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return ProfileData.from_file(files[-1])


def collect(pd):
    """``(device_ops, host_spans)``: per device plane a list of
    ``(name, start_ns, end_ns)``; host spans likewise (Python-tracer frames,
    named ``$file:line``, left out)."""
    devices, host = {}, []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            ops = [ln for ln in lines if ln.name in OP_LINES] or lines
            # an op's event name is its HLO text; keep the instruction name
            evs = [(e.name.split(" = ")[0], e.start_ns,
                    e.start_ns + e.duration_ns)
                   for ln in ops for e in ln.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for ln in lines for e in ln.events
                     if not e.name.startswith("$") and e.duration_ns > 0]
    return devices, host


def union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def innermost(spans, t, names=None):
    best = None
    for name, s, e in spans:
        if s <= t <= e and (names is None or name in names):
            if best is None or e - s < best[2] - best[1]:
                best = (name, s, e)
    return best[0] if best else None


def reduce_events(devices: dict, host: list, window=None) -> dict:
    """Busy/idle seconds, top device ops and the longest labelled gaps.
    ``window`` is ``(start_ns, end_ns)``; by default the first to the last
    stage span (or device op, where there is no stage span)."""
    stage_spans = [h for h in host if h[0] in STAGES]
    if window is None:
        ref = stage_spans or [ev for evs in devices.values() for ev in evs]
        window = (min(s for _, s, _ in ref), max(e for _, _, e in ref))
    w0, w1 = window
    busy, per_op, gaps = 0.0, defaultdict(float), []
    for evs in devices.values():
        clipped = sorted((max(s, w0), min(e, w1), n) for n, s, e in evs
                         if e > w0 and s < w1)
        top_end = w0
        for s, e, n in clipped:  # an op nested in a loop counts in the loop
            if s >= top_end:
                per_op[n] += (e - s) / len(devices)
            top_end = max(top_end, e)
        merged = union([(s, e) for s, e, _ in clipped])
        busy += sum(e - s for s, e in merged) / len(devices)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    host_spans = [h for h in host if h[1] < w1 and h[2] > w0]
    by_label = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        stage = innermost(stage_spans, mid, STAGES) or "between stages"
        inner = innermost(host_spans, mid) or "no host span"
        label = stage if inner == stage else f"{stage}/{inner}"
        by_label[label] += (e - s) / len(devices)
    window_s = (w1 - w0) / 1e9
    return {
        "busy_s": busy / 1e9,
        "window_s": window_s,
        "idle_share": 1.0 - busy / (w1 - w0) if w1 > w0 else None,
        "device_ops": sorted(([n, t / 1e9] for n, t in per_op.items()),
                             key=lambda x: -x[1]),
        "idle_gaps": sorted(([n, t / 1e9] for n, t in by_label.items()),
                            key=lambda x: -x[1]),
        "n_devices": len(devices),
    }


def reduce_dir(profile_dir: str) -> dict:
    devices, host = collect(load(profile_dir))
    if not devices:
        raise RuntimeError(f"no device operations in the trace under "
                           f"{profile_dir}")
    return reduce_events(devices, host)
