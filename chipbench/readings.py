#!/usr/bin/env python3
"""Readings that set a cell's correctness limits (not part of a run).

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6]

In one process, for each seed: the cell's reads, one timed-path job, and the
comparison of ``check.py``; one JSON line per seed on stdout.  For each
control seed the job runs the control instead: the cell's pipeline with the
overrides under ``control`` in ``chipbench/workloads/<cell>.json``, a
cheaper setting of the program that breaks one guarantee the configuration
states.  A limit lies between the largest reading of the sound seeds and
the smallest reading of the control.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sound = run.cell_spec(args.workload)
    control = copy.deepcopy(sound)
    control["config"]["pipeline"].update(sound["limits"]["control"])
    plan = [(sound, False, s) for s in args.seeds.split(",") if s] + \
        [(control, True, s) for s in args.control_seeds.split(",") if s]
    for spec, is_control, seed in plan:
        res = run.measure(spec, int(seed), 0.0, False, warmup=False)
        print(json.dumps({"seed": int(seed), "control": is_control,
                          "correct": res["correct"],
                          "detail": res["detail"],
                          "checks": res["checks"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
