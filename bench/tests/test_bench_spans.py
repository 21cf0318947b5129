"""The program's serve-loop spans as the benchmark reads them: from a
profiler trace beside the harness's own events, and in memory; the
queue-wait reader; ``bench/spans.py`` on the CPU."""
import gzip
import importlib.util
import json
import pathlib
from types import SimpleNamespace

import pytest

from harness import spans, spec, trace
from repro.obs.clock import ManualClock
from repro.obs.trace import PREFIXES, TraceRecorder

FIX = pathlib.Path(__file__).resolve().parent / "fixtures"


def _fixture():
    with gzip.open(FIX / "trace_granite_chat.json.gz", "rt") as f:
        return json.load(f)


def test_reduce_names_idle_gaps_by_program_spans():
    rec = _fixture()
    ev = rec["events"]
    plain = trace.reduce(ev)
    for k, v in rec["expect"].items():
        assert plain[k] == pytest.approx(v, rel=1e-9), k
    # A program span open across the middle of the longest gap names it;
    # the window, busy time and steps still come from bench: alone.
    host = sorted(ev["host"], key=lambda h: h[1])
    busy = trace.union_ns(
        [(o[1], o[1] + o[2]) for o in next(iter(ev["ops"].values()))
         if not trace.is_container(o[0])], host[0][1], 10 ** 30)
    gap = max(((b[0] - a[1]), a[1], b[0]) for a, b in zip(busy, busy[1:]))
    mid = (gap[1] + gap[2]) // 2
    named = trace.reduce(spans.with_program_spans(
        ev, [["engine.step", mid - 10_000, 20_000],
             ["engine.fetch", mid - 1_000, 2_000]]))
    for k in rec["expect"]:
        assert named[k] == plain[k], k
    assert named["idle_gaps"][0] == ["engine.fetch",
                                     pytest.approx(gap[0] / 1e9)]
    assert named["idle_gaps"][1:] == plain["idle_gaps"][1:]


def test_launch_and_return_match_partners_and_skip_the_edges():
    dev = "/device:TPU:0"
    modules = {dev: [["jit_serve_step(1)", 100, 50],    # no dispatch seen
                     ["jit_reset(2)", 205, 1],          # not a step
                     ["jit_serve_step(1)", 210, 50],
                     ["jit_serve_step(1)", 410, 40]]}   # no fetch seen
    program = [["engine.fetch", 120, 40],               # ends 160
               ["engine.dispatch", 200, 3],
               ["engine.fetch", 203, 60],               # ends 263
               ["engine.dispatch", 400, 4],
               ["engine.dispatch", 600, 4]]             # trace stops first
    got = spans.launch_return(program, modules)
    assert got["launches"] == 2 and got["returns"] == 2
    assert got["step_launch_ms"] == pytest.approx((10 + 10) / 2 / 1e6)
    assert got["step_return_ms"] == pytest.approx((10 + 3) / 2 / 1e6)
    assert got["launch_return_ms"] == pytest.approx(13 / 1e6)
    none = spans.launch_return([], {})
    assert none["step_launch_ms"] is None and none["step_return_ms"] is None


@pytest.mark.parametrize("skew", [-2, 0, 3])
def test_launch_and_return_under_clock_skew(skew):
    # The device's clock read ``skew`` ns off the host's: each module reads
    # as starting 1 - skew after its dispatch (before it, for skew > 1);
    # launch and return move by the skew in opposite directions, their sum
    # not, and no step is matched to its neighbour.
    dev = "/device:TPU:0"
    program, mods = [], []
    for t in (0, 100, 200, 300):
        program += [["engine.dispatch", t, 2], ["engine.fetch", t + 2, 54]]
        mods.append(["jit_serve_step(1)", t + 1 - skew, 50])
    got = spans.launch_return(program, {dev: mods})
    assert got["launches"] == got["returns"] == 4
    assert got["step_launch_ms"] == pytest.approx((1 - skew) / 1e6)
    assert got["step_return_ms"] == pytest.approx((5 + skew) / 1e6)
    assert got["launch_return_ms"] == pytest.approx(6 / 1e6)


def _tree():
    clock = ManualClock(tick_us=0.0)
    rec = TraceRecorder(clock)
    for dur in (10.0, 30.0):
        with rec.span("serve.control"):
            with rec.span("orc.step"):
                clock.advance(dur)
            with rec.span("serve.admit"):
                clock.advance(2.0)
            clock.advance(1.0)
    rec.record_span("req.queued", start_us=0.0, end_us=5.0, req_id=0)
    rec.record_span("req0", start_us=0.0, end_us=9.0)
    return rec


def test_span_table_self_time_and_longest():
    rec = _tree()
    t = spans.span_table(rec.spans, 0.0, 1e9)
    assert set(t) == {"serve.control", "orc.step", "serve.admit",
                      "req.queued"}                  # req0 is no layer
    c = t["serve.control"]
    assert c["count"] == 2
    assert c["total_ms"] == pytest.approx(46e-3)
    assert c["self_ms"] == pytest.approx(2e-3)
    assert c["p50_ms"] == pytest.approx(13e-3)
    assert c["p95_ms"] == pytest.approx(33e-3)
    assert t["orc.step"]["self_ms"] == pytest.approx(40e-3)
    assert spans.durations_ms(rec.spans, "serve.control", 12.0, 1e9) == \
        [pytest.approx(33e-3)]                       # the first is outside
    top = spans.longest(rec.spans, "serve.control", 0.0, 1e9)
    assert top["ms"] == pytest.approx(33e-3)
    assert top["children"] == [["orc.step", pytest.approx(30e-3)],
                               ["serve.admit", pytest.approx(2e-3)]]
    assert spans.longest(rec.spans, "engine.step", 0.0, 1e9) is None


def test_program_spans_reach_the_profilers_host_plane(tmp_path):
    import jax

    from repro.core.control_plane import ControlPlane
    from repro.orchestrator import Orchestrator, TenantSpec
    from repro.serve.batcher import (ContinuousBatcher,
                                     SimulatedDecodeEngine, serve_loop)
    from repro.serve.traffic import TenantTraffic, TrafficGenerator

    rec = TraceRecorder(ManualClock(), profile=True)
    traffic = TrafficGenerator([TenantTraffic(
        1, rate=0.5, prompt_mean=3, output_mean=3, prompt_max=6,
        output_max=6, vocab=100)], seed=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        orc = Orchestrator(ControlPlane(4, 64, num_logical=256), budget=8,
                           control_period=2, migrate=False, recorder=rec)
        orc.register(TenantSpec(1, "chat", qos="interactive", share=1.0))
        bat = ContinuousBatcher(orc, num_slots=2, page_tokens=8,
                                recorder=rec)
        serve_loop(bat, SimulatedDecodeEngine(2), traffic, steps=6)
    finally:
        jax.profiler.stop_trace()
    got = spans.program_events(trace.find_xplane(str(tmp_path)))
    names = {e[0] for e in got}
    want = {s.name for s in rec.spans if s.name.startswith(PREFIXES)}
    assert {"serve.control", "orc.step", "orc.refit", "cp.route_program",
            "cp.verify", "cp.journal", "serve.admit", "orc.request_lease",
            "req.queued", "serve.observe"} <= want
    assert names == want
    counts = {n: sum(1 for e in got if e[0] == n) for n in names}
    assert counts == {n: len(rec.find_all(n)) for n in names}
    (c,) = [e for e in got if e[0] == "serve.control"][:1]
    inner = [e for e in got if e[0] == "orc.step"][0]
    assert c[1] <= inner[1] and inner[1] + inner[2] <= c[1] + c[2]


def _records(waits):
    recs = []
    for i, w in enumerate(waits):
        seq = None if w is None else SimpleNamespace(
            arrive_us=1e6 * i, admit_us=1e6 * i + w * 1e3)
        recs.append(SimpleNamespace(
            seq=seq, submit_s=float(i), arrival=SimpleNamespace(due_s=i)))
    return recs


def test_queue_wait_reader():
    read = spec.metric_reader("queue_wait_p90_ms")
    w = SimpleNamespace(records=_records([float(k) for k in range(1, 11)]),
                        end_s=100.0)
    assert read(SimpleNamespace(window=w)) == pytest.approx(9.0)
    # never admitted ranks as infinitely late: the wait to the end of the
    # run (submitted at 8 s, run ended at 100 s) is the lower bound given
    w = SimpleNamespace(records=_records([1.0] * 8 + [None, None]),
                        end_s=100.0)
    assert read(SimpleNamespace(window=w)) == pytest.approx(92e3)
    assert read(SimpleNamespace(window=SimpleNamespace(
        records=[], end_s=1.0))) is None


def test_spans_tool_on_the_cpu(bench_run, tiny_cell):
    path = spec.BENCH / "spans.py"
    mod_spec = importlib.util.spec_from_file_location("bench_spans", path)
    tool = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(tool)
    res = tool.run_with_spans(bench_run, tiny_cell, seed=5, seconds=2.0,
                              trace=False, require_chip=False)
    assert res["correct"], res["_compare"]
    s = res["spans"]
    for name in ("serve.control", "orc.step", "serve.admit",
                 "orc.request_lease", "serve.step_inputs", "engine.step",
                 "engine.dispatch", "engine.fetch", "serve.observe",
                 "req.queued"):
        assert s["table"][name]["count"] > 0, name
    assert s["table"]["engine.step"]["count"] == s["iterations"]
    assert s["control_p95_ms"] > 0 and s["queue_wait_p90_ms"] >= 0
    assert [c[0] for c in s["longest_engine_step"]["children"]][-2:] == \
        ["engine.dispatch", "engine.fetch"]
    assert s["span_cost_us"] > 0
    # the harness's own objects are left as they were
    from harness import serve
    from harness import trace as trace_mod
    assert serve.drive.__module__ == "harness.serve"
    assert trace_mod.load_xplane.__module__ == "harness.trace"
