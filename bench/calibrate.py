#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python bench/calibrate.py --workload granite-3-8b.chat \
        --seeds 101 102 103 --seconds 45

Runs the cell once per seed, in one process, exactly as ``bench/run.py``
does, and reads on each run's sample both the program's logit gaps and
the int8 control's (the reference computed from int8 operands, at the
same prompts and served tokens), the widest and the mean.  Each seed's
line gives both sides' ``correct`` as ``bench/run.py`` decides it, against
the limits now in ``bench/checks/<workload>.json``: the program has to
read true and the control false.  For each gap, the lower reading is the
largest program gap over the seeds, the upper the smallest control gap;
the last line is a JSON summary.  ``bench/checks/`` holds the limits set
from these readings.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    spec_ = importlib.util.spec_from_file_location("bench_run",
                                                   BENCH / "run.py")
    run = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(run)
    cell = run.spec.load_cell(args.workload)
    from harness import check
    rows = []
    for seed in args.seeds:
        res = run.run_cell(cell, seed=seed, seconds=args.seconds,
                           trace=False, control=True,
                           t_start=time.perf_counter())
        c = res["_compare"]
        _, control_correct = check.decide(cell.check, c, prefix="control_")
        row = {"seed": seed, "correct": res["correct"],
               "control_correct": control_correct,
               "program_gap": c["max_logit_gap"],
               "control_gap": c.get("control_max_logit_gap"),
               "program_mean_gap": c.get("mean_logit_gap"),
               "control_mean_gap": c.get("control_mean_logit_gap"),
               "compared_tokens": c["compared_tokens"],
               "requests": c["requests"],
               "longest_tokens": c.get("longest_tokens"),
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = [r["program_gap"] for r in rows if r["program_gap"] is not None]
    ctrl = [r["control_gap"] for r in rows if r["control_gap"] is not None]
    pm = [r["program_mean_gap"] for r in rows
          if r["program_mean_gap"] is not None]
    cm = [r["control_mean_gap"] for r in rows
          if r["control_mean_gap"] is not None]
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "program_correct": all(r["correct"] for r in rows),
                      "control_failed": not any(r["control_correct"]
                                                for r in rows),
                      "max_gap": {"lower": max(prog) if prog else None,
                                  "upper": min(ctrl) if ctrl else None},
                      "mean_gap": {"lower": max(pm) if pm else None,
                                   "upper": min(cm) if cm else None}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
