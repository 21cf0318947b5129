#!/usr/bin/env python3
"""Run a cell with the program's serve-loop spans on and read them.

    python bench/spans.py --workload granite-3-8b.chat --seed 7 \
        --seconds 51 --trace 1

Runs the cell once, as ``bench/run.py`` does, with a
``repro.obs.TraceRecorder(profile=True)`` on the batcher's clock given to
the batcher, the orchestrator (its journal's trace) and the engine for
the whole window; set-up records nothing.  ``--trace 1`` also profiles the
window's last seconds as ``bench/run.py`` does, and names each idle gap
of the device by the innermost program span open in its middle.

Prints to standard error, over the window: a self-time table per span
name (count, total, p50, p95, self time = duration less the union of the
children's), the longest ``engine.step`` split into its children, the p95
of ``serve.control`` and the p90 of ``req.queued`` (requests due in the
window; one never admitted ranks as infinitely late), and, traced, the
mean launch (``engine.dispatch`` start to the device starting
``serve_step``) and return (``serve_step`` ending on the device to the end
of ``engine.fetch``) of the decode step and their sum, which the
profiler's host-to-device clock offset leaves alone
(``harness.spans.launch_return``), with the named idle gaps; also the
recorder's cost per span on this host.  The last line of standard
output is ``bench/run.py``'s result object with these under ``"spans"``.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent


def span_cost_us(clock, n: int = 20000) -> float:
    """Host microseconds per span, open and close, profiler mirror on."""
    from repro.obs import TraceRecorder
    rec = TraceRecorder(clock, profile=True)
    t = time.perf_counter()
    for _ in range(n):
        with rec.span("serve.cost"):
            pass
    return (time.perf_counter() - t) / n * 1e6


def report(rec, window, captured) -> dict:
    from harness import spans
    from harness.stats import percentile
    lo = window.t0 * 1e6
    hi = (window.t0 + window.seconds) * 1e6
    table = spans.span_table(rec.spans, lo, hi)
    say = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    say(f"{'span':<20}{'count':>7}{'total ms':>14}{'p50 ms':>12}"
        f"{'p95 ms':>12}{'self ms':>14}")
    for name, r in sorted(table.items(), key=lambda kv: -kv[1]["self_ms"]):
        say(f"{name:<20}{r['count']:>7}{r['total_ms']:>14.3f}"
            f"{r['p50_ms']:>12.3f}{r['p95_ms']:>12.3f}{r['self_ms']:>14.3f}")
    queued = {s.args["req_id"]: s.duration_us / 1e3 for s in rec.spans
              if s.name == "req.queued"}
    out = {
        "control_p95_ms": percentile(
            spans.durations_ms(rec.spans, "serve.control", lo, hi), 95),
        "queue_wait_p90_ms": percentile(
            [queued.get(r.req_id, float("inf")) for r in window.records],
            90),
        "longest_engine_step": spans.longest(rec.spans, "engine.step",
                                             lo, hi),
        "iterations": len([i for i in window.iters
                           if i.end_s <= window.seconds]),
        "spans_in_window": sum(r["count"] for r in table.values()),
        "span_cost_us": span_cost_us(rec.clock),
        "table": table,
    }
    if "program" in captured:
        out.update(spans.launch_return(captured["program"],
                                       captured["events"]["modules"]))
    for k, v in out.items():
        if k != "table":
            say(f"{k}: {v}")
    return out


def run_with_spans(run, cell, *, seed: int, seconds: float, trace: bool,
                   **kw) -> dict:
    """``run.run_cell`` (``run`` is ``bench/run.py`` loaded as a module)
    with a recorder on for the window; the result gains ``"spans"``."""
    from harness import serve, spans
    from harness import trace as trace_mod
    from repro.obs import TraceRecorder

    captured = {}
    orig_drive, orig_load = serve.drive, trace_mod.load_xplane

    def drive(batcher, engine, *a, **k):
        rec = TraceRecorder(batcher.clock, profile=True)
        batcher.recorder = engine.recorder = rec
        batcher.orc.flight.trace = rec
        captured["rec"] = rec
        captured["window"] = orig_drive(batcher, engine, *a, **k)
        return captured["window"]

    def load_xplane(path, *a, **k):
        captured["events"] = orig_load(path, *a, **k)
        captured["program"] = spans.program_events(path)
        return spans.with_program_spans(captured["events"],
                                        captured["program"])

    serve.drive, trace_mod.load_xplane = drive, load_xplane
    try:
        res = run.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                           **kw)
    finally:
        serve.drive, trace_mod.load_xplane = orig_drive, orig_load
    res["spans"] = report(captured["rec"], captured["window"], captured)
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    spec_ = importlib.util.spec_from_file_location("bench_run",
                                                   BENCH / "run.py")
    run = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(run)
    cell = run.spec.load_cell(args.workload)
    try:
        res = run_with_spans(run, cell, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace))
    except run.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    res.pop("_compare")
    for name, s in res.get("breakdown", {}).get("idle_gaps", []):
        print(f"idle gap {s * 1e3:.3f} ms in {name}", file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
