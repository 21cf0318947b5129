#!/usr/bin/env python3
"""Find the highest rate a Poisson cell's server sustains (its knee).

    python bench/sweep.py --workload granite-3-8b.chat \
        --rates 2.5 3.0 3.5 4.0 --seconds 40 --seed 5

Runs the cell once per rate, in one process, as ``bench/run.py`` does but
with the mix's ``rate_per_s`` replaced, and prints per rate: the
requests due and completed, how many due in the window were still
waiting for a slot when it closed, the mean number of busy slots per
step, and TTFT percentiles.  A rate is sustained while the queue at the
close stays near empty and TTFT does not climb with the rate; the cell's
mix file records the knee found and the rate set from it.
"""
from __future__ import annotations

import argparse
import copy
import importlib.util
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    spec_ = importlib.util.spec_from_file_location("bench_run",
                                                   BENCH / "run.py")
    run = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(run)
    from harness.stats import percentile
    base = run.spec.load_cell(args.workload)
    captured = {}
    from harness import serve

    def drive(*a, **k):
        w = orig_drive(*a, **k)
        captured["w"] = w
        return w
    orig_drive = serve.drive
    serve.drive = drive
    for rate in args.rates:
        cell = copy.deepcopy(base)
        cell.traffic["rate_per_s"] = rate
        res = run.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=False, t_start=time.perf_counter())
        w = captured["w"]
        close = w.seconds
        waiting = sum(1 for r in w.records if r.seq is None or
                      r.seq.admit_us / 1e6 - w.t0 > close)
        busy = [len(i.visible) for i in w.iters if i.end_s <= close]
        ttft = [(r.token_s[0] - r.arrival.due_s) if r.token_s
                else float("inf") for r in w.records]
        done = sum(1 for r in w.records
                   if r.seq is not None and r.seq.done)
        print(json.dumps({
            "rate_per_s": rate, "due": len(w.records), "completed": done,
            "waiting_at_close": waiting,
            "mean_busy_slots": sum(busy) / max(len(busy), 1),
            "steps_per_s": len(busy) / close,
            "ttft_p50_s": percentile(ttft, 50),
            "ttft_p90_s": percentile(ttft, 90),
            "correct": res["correct"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
