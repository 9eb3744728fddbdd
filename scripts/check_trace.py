#!/usr/bin/env python
"""Assert the span-tree structure of an exported pipeline trace.

Reads the Chrome-trace JSON written by ``benchmarks/run.py --trace-dir``
(the ``spanTree`` side-channel key — explicit nesting, no timestamp
containment to re-derive) and checks the observability contract of
``docs/observability.md``:

* the root spans are the pipeline stages, in Algorithm 1 order —
  CountKmer → CreateSpMat → SpGEMM → Alignment → BuildR → TrReduction →
  Contigs → Consensus;
* the SpGEMM stage nests shard_map phase spans, including at least one
  ``phase="ring_stage"`` descendant (the explicit-exchange ring actually
  traced) and the skew/ring/collect phases around it;
* the Contigs stage nests the chain-stage phase spans (cut → doubling →
  sort under ``phase="chain_stage"``);
* the Alignment stage nests the distributed x-drop phase spans
  (``pair_exchange`` around the shard_map call; ``gather_reads`` →
  ``extend`` → ``scatter_scores`` inside it, DESIGN.md §2.12);
* every stage root span carries memory attribution — the
  ``peak_hbm_bytes`` / ``hbm_bytes_in_use`` / ``hbm_source`` attrs the
  tracer's per-span watermark (``repro.obs.memory``) attaches, so the
  exported trace answers "which stage holds the high-water mark", not
  just "which stage is slow".

Exits 1 with a per-check message when the structure is violated.  Run from
the repo root::

    python scripts/check_trace.py TRACE_DIR/assemble_trace.json
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

# Algorithm 1 stage order and per-stage phase contract: the single source
# shared with check_smoke_comm.py and analysis rule R003 (PR 10).
from repro.analysis.contracts import STAGE_PHASES, STAGES  # noqa: E402


def _walk(node, depth=0):
    yield node, depth
    for child in node.get("children", ()):
        yield from _walk(child, depth + 1)


def _descendants(node):
    for child in node.get("children", ()):
        yield from _walk(child)


def _phases(node):
    return {n["attrs"].get("phase") for n, _ in _descendants(node)
            if n["attrs"].get("kind") == "phase"}


def check(tree) -> list:
    """Return failure messages for one ``spanTree`` list; empty = clean."""
    failures = []
    roots = [n["name"] for n in tree]
    stage_pos = [roots.index(s) for s in STAGES if s in roots]
    missing = [s for s in STAGES if s not in roots]
    if missing:
        failures.append(f"missing stage root span(s): {', '.join(missing)}"
                        f" (roots: {roots})")
    if stage_pos != sorted(stage_pos):
        failures.append(f"stage roots out of Algorithm 1 order: {roots}")

    by_name = {n["name"]: n for n in tree}
    for stage, required in STAGE_PHASES.items():
        node = by_name.get(stage)
        if node is None:
            continue  # the missing root is already reported above
        phases = _phases(node)
        for ph in required:
            if ph in phases:
                continue
            if stage == "SpGEMM" and ph == "ring_stage":
                failures.append(
                    "SpGEMM stage has no phase='ring_stage' descendant — "
                    "the explicit-exchange ring was not traced "
                    f"(phases: {phases})")
            else:
                failures.append(
                    f"{stage} stage missing phase={ph!r} span")

    for root in tree:
        if root["name"] not in STAGES:
            continue
        attrs = root["attrs"]
        missing_mem = [k for k in ("peak_hbm_bytes", "hbm_bytes_in_use",
                                   "hbm_source") if k not in attrs]
        if missing_mem:
            failures.append(
                f"stage span {root['name']!r} lacks memory attribution "
                f"attr(s) {', '.join(missing_mem)} — the tracer watermark "
                "did not run for this span")
    return failures


def main(argv) -> int:
    """Check each trace path in ``argv``; 0 = structure holds everywhere."""
    if not argv:
        print("usage: check_trace.py TRACE.json [...]", file=sys.stderr)
        return 2
    failed = 0
    for path in argv:
        with open(path) as f:
            doc = json.load(f)
        tree = doc.get("spanTree")
        if not tree:
            print(f"{path}: no spanTree key — not a pipeline trace export")
            failed += 1
            continue
        failures = check(tree)
        for msg in failures:
            print(f"{path}: {msg}")
            failed += 1
        if not failures:
            n_spans = sum(1 for r in tree for _ in _walk(r))
            print(f"{path}: span-tree structure ok ({n_spans} spans, "
                  f"{len(tree)} roots)")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
