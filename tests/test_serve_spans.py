"""Serve-loop spans: what the batcher, orchestrator, control plane and
engine record into a ``TraceRecorder`` from inside the program.

* the span tree under a ``ManualClock``: ``serve.control`` >
  ``orc.step`` > ``orc.refit`` > ``cp.route_program`` > ``cp.verify``
  (a content's first time only) / ``cp.journal`` exactly every
  ``control_period`` ticks, admission and
  retirement spans, one ``req.queued`` per admission,
* ``engine.step`` > ``engine.reset`` / ``engine.dispatch`` /
  ``engine.fetch`` on a tiny jitted model,
* journal records stamped with the span open when they were made,
* ``recorder=None``: the same tokens and journal, nothing recorded,
* ``ContinuousBatcher.why`` over the recorded spans.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.control_plane import ControlPlane
from repro.obs.clock import ManualClock
from repro.obs.trace import (CAT_REQUEST, CP, ENGINE, ORC, PREFIXES, REQ,
                             SERVE, TraceRecorder)
from repro.orchestrator import Orchestrator, TenantSpec
from repro.serve.batcher import (ContinuousBatcher, SimulatedDecodeEngine,
                                 serve_loop)
from repro.serve.traffic import TenantTraffic, TrafficGenerator

PERIOD = 3


def _server(recorder, *, slots=4):
    clock = ManualClock(tick_us=1.0)
    rec = TraceRecorder(clock) if recorder else None
    orc = Orchestrator(ControlPlane(4, 64, num_logical=256), budget=8,
                       control_period=PERIOD, migrate=False, recorder=rec)
    orc.register(TenantSpec(1, "chat", qos="interactive", share=3.0))
    orc.register(TenantSpec(2, "crawl", qos="batch", share=1.0))
    bat = ContinuousBatcher(orc, num_slots=slots, page_tokens=8,
                            clock=clock, recorder=rec)
    return bat, rec


def _serve(bat, steps=20):
    traffic = TrafficGenerator([
        TenantTraffic(1, rate=0.6, prompt_mean=4, output_mean=4,
                      prompt_max=12, output_max=10, vocab=500),
        TenantTraffic(2, rate=0.4, prompt_mean=6, output_mean=5,
                      prompt_max=16, output_max=12, vocab=500)], seed=11)
    return serve_loop(bat, SimulatedDecodeEngine(bat.num_slots), traffic,
                      steps=steps)


def _kids(rec, span):
    return [s.name for s in rec.children(span)]


def test_span_tree_of_the_control_tick():
    bat, rec = _server(True)
    res = _serve(bat)
    assert res["completed"] == res["submitted"] > bat.num_slots
    assert all(s.name.startswith(PREFIXES) for s in rec.spans
               if not s.name.startswith("req"))
    assert all(s.end_us is not None for s in rec.spans)

    controls = rec.find_all(SERVE + "control")
    assert len(controls) == bat.step_count == res["steps"]
    for tick, c in enumerate(controls, start=1):
        kids = _kids(rec, c)
        refit = tick % PERIOD == 0
        assert kids == [ORC + "step"] + ([ORC + "refit_windows"] if refit
                                         else []) + [SERVE + "admit"]
        (orc_step,) = rec.children(c)[:1]
        assert (ORC + "refit" in _kids(rec, orc_step)) == refit
        for k in ("queue_depth", "slots_active", "in_flight", "admitted"):
            assert k in c.args

    # The orchestrator's constructor installs the first route program; in
    # the loop every program comes from the control period's refit.
    refits = rec.find_all(ORC + "refit")
    assert len(refits) == bat.step_count // PERIOD
    routes = rec.find_all(CP + "route_program")
    assert len(routes) == len(refits) + 1 and routes[0].parent_id is None
    # A program whose content this plane already verified is neither put on
    # the device nor verified again: its span says reused, with no verify.
    digests = [r.detail["digest"]
               for r in bat.orc.flight.records("route_program")]
    assert len(digests) == len(routes)
    for i, route in enumerate(routes):
        assert route.args["reused"] == (digests[i] in digests[:i])
    assert not routes[0].args["reused"]
    assert any(s.args["reused"] for s in routes)
    for r in refits:
        (route,) = [s for s in rec.children(r)
                    if s.name == CP + "route_program"]
        verify = [] if route.args["reused"] else [CP + "verify"]
        assert _kids(rec, route) == verify + [CP + "journal"]
        assert route.start_us <= rec.children(route)[0].start_us
        assert rec.children(route)[-1].end_us <= route.end_us <= r.end_us
    counts = bat.orc.cp.route_counts
    assert counts.verified == len(rec.find_all(CP + "verify"))
    assert counts.verified + counts.verify_skipped == len(routes)

    # One orc.request_lease per admission attempt, all inside serve.admit;
    # each attempt journals one admission verdict for its request.
    leases = rec.find_all(ORC + "request_lease")
    admits = {s.span_id for s in rec.find_all(SERVE + "admit")}
    assert leases and all(s.parent_id in admits for s in leases)
    verdicts = [r for r in bat.orc.flight.records("admission")
                if r.request_id is not None]
    assert len(verdicts) == len(leases)

    # One req.queued per admission, arrival to admission.
    queued = rec.find_all(REQ + "queued")
    assert len(queued) == len(bat.retired)
    by_req = {s.args["req_id"]: s for s in queued}
    for seq in bat.retired:
        s = by_req[seq.req.req_id]
        assert s.cat == CAT_REQUEST and s.parent_id is None
        assert s.args["tenant"] == seq.req.tenant_id
        assert s.duration_us == seq.admit_us - seq.arrive_us
    assert any(s.duration_us > 0 for s in queued)

    # One serve.retire per retirement, inside serve.observe.
    retires = rec.find_all(SERVE + "retire")
    assert len(retires) == len(bat.retired)
    observes = {s.span_id for s in rec.find_all(SERVE + "observe")}
    assert all(s.parent_id in observes for s in retires)
    assert len(rec.find_all(SERVE + "step_inputs")) == len(observes)


def test_journal_records_carry_the_open_span():
    bat, rec = _server(True)
    _serve(bat)
    name = {s.span_id: s.name for s in rec.spans}
    where = {}
    for r in bat.orc.flight.records():
        where.setdefault(r.kind, set()).add(name.get(r.span_id))
    assert where["cp_init"] == {None}
    assert where["route_program"] == {CP + "journal"}
    assert where["lease_grant"] == {ORC + "request_lease"}
    assert where["lease_release"] == {SERVE + "retire"}
    assert where["step_report"] == {ORC + "step"}
    # refits of the control period and of the batcher's queue depths
    assert where["refit"] >= {ORC + "refit", ORC + "refit_windows"}
    assert where["refit"] <= {None, ORC + "refit", ORC + "refit_windows"}


def test_no_recorder_same_tokens_same_journal_nothing_recorded():
    on, rec = _server(True)
    off, none = _server(False)
    _serve(on)
    _serve(off)
    assert none is None and off.recorder is None
    assert off.orc.flight.trace is None
    assert {s.req.req_id: s.out for s in off.retired} == \
        {s.req.req_id: s.out for s in on.retired}
    assert [r.kind for r in off.orc.flight.records()] == \
        [r.kind for r in on.orc.flight.records()]
    assert all(r.span_id is None for r in off.orc.flight.records())
    assert rec.spans and any(r.span_id is not None
                             for r in on.orc.flight.records())


@pytest.fixture(scope="module")
def tiny_engine():
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.config import RunConfig, ShapeConfig
    from repro.models import transformer
    from repro.serve.batcher import ModelDecodeEngine

    cfg = dataclasses.replace(configs.get_reduced("granite-3-8b"),
                              dtype="float32")
    run = RunConfig(model=cfg, shape=ShapeConfig("spans", 24, 2, "decode"),
                    kv_placement="local")
    params = transformer.init_params(cfg, jax.random.key(0))

    def make(recorder=None):
        return ModelDecodeEngine(run, params, batch=2, max_len=24,
                                 page_tokens=8, dtype=jnp.float32,
                                 recorder=recorder)
    return make


def test_engine_step_spans(tiny_engine):
    rec = TraceRecorder(ManualClock())
    eng, ref = tiny_engine(rec), tiny_engine()
    tokens = np.array([5, 7], np.int32)
    for reset in ([0, 1], [], [1]):
        assert np.array_equal(eng.step(tokens, reset),
                              ref.step(tokens, reset))
    steps = rec.find_all(ENGINE + "step")
    assert [_kids(rec, s) for s in steps] == [
        [ENGINE + "reset", ENGINE + "dispatch", ENGINE + "fetch"],
        [ENGINE + "dispatch", ENGINE + "fetch"],
        [ENGINE + "reset", ENGINE + "dispatch", ENGINE + "fetch"]]
    assert len(rec.spans) == 3 + 3 + 2 + 3
    assert all(s.parent_id is None for s in steps)


def test_why_returns_the_ticks_and_steps_a_request_lived_through(
        tiny_engine):
    bat, rec = _server(True, slots=2)
    eng = tiny_engine(rec)
    res = serve_loop(bat, eng, TrafficGenerator([
        TenantTraffic(1, rate=0.7, prompt_mean=3, output_mean=3,
                      prompt_max=6, output_max=6, vocab=100)], seed=3),
        steps=10)
    assert res["completed"] >= 3
    seq = bat.retired[-1]
    rid = seq.req.req_id
    got = bat.why(rid)
    assert got["request_id"] == rid and got["decisions"]
    names = [s["name"] for s in got["spans"]]
    assert names.count(REQ + "queued") == 1 and names.count(f"req{rid}") == 1
    (done,) = [s for s in got["spans"] if s["name"] == f"req{rid}"]
    lo, hi = done["start_us"], done["end_us"]
    want = [s.span_id for s in rec.spans
            if s.name in (SERVE + "control", ENGINE + "step")
            and s.end_us >= lo and s.start_us <= hi]
    ticks = [s for s in got["spans"]
             if s["name"] in (SERVE + "control", ENGINE + "step")]
    assert len(ticks) == len(want) and ticks
    assert {s["name"] for s in ticks} == {SERVE + "control",
                                          ENGINE + "step"}
    assert set(names) <= {REQ + "queued", f"req{rid}", SERVE + "control",
                          ENGINE + "step"}
    assert bat.why(10_000)["spans"] == []


def test_why_of_a_request_still_in_flight_runs_to_now():
    bat, rec = _server(True, slots=1)
    _serve(bat, steps=4)            # drains
    from repro.serve.traffic import make_request
    for i in (100, 101):
        bat.submit(make_request(i, 1, prompt_len=2, output_len=50, seed=1,
                                vocab=100))
    eng = SimulatedDecodeEngine(1)
    for _ in range(3):
        bat.control()
        tokens, resets = bat.step_inputs()
        bat.observe(eng.step(tokens, resets))
    got = bat.why(100)              # admitted, not retired
    names = [s["name"] for s in got["spans"]]
    assert names.count(REQ + "queued") == 1 and "req100" not in names
    assert names.count(SERVE + "control") >= 3
    assert bat.why(101)["spans"] == []      # still queued: nothing yet
    assert _server(False)[0].why(100)["spans"] == []
