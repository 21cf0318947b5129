"""repro.serve.batcher / traffic — the request-level serving front end.

Covers the continuous-batching acceptance contract:

* traffic: seeded determinism (per-(seed, tenant, step) streams make a
  tenant's arrivals independent of the mix), length bounds, arrival
  windows,
* fidelity: continuous-batched decode is bit-identical to a solo run of
  each request — on the simulated engine (which deliberately leaks state
  across slot reuse unless the batcher resets on admit) and on the real
  reduced-model jitted step,
* invariants, property-tested over random tenant mixes x queue depths:
  no slot double-assigned, every admitted sequence retires, request
  conservation (submitted == completed + shed + queued + active), every
  lease released at drain, peak concurrency >= slot occupancy,
* admission edges: oversized requests shed (never livelock the queue),
  attempt-bounded shedding, naive-vs-QoS flood isolation,
* the orchestrator hook: ``refit_windows`` steers bridge windows from
  serving queue depths.
"""
import numpy as np
import pytest

from repro.core.control_plane import ControlPlane
from repro.obs.clock import ManualClock
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import CAT_REQUEST, REQ, TraceRecorder
from repro.orchestrator import Orchestrator, TenantSpec
from repro.serve.batcher import (ContinuousBatcher, SimulatedDecodeEngine,
                                 serve_loop, solo_reference)
from repro.serve.traffic import (Request, TenantTraffic, TrafficGenerator,
                                 make_request)

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                        # pragma: no cover
    from hypofallback import given, settings, st


def mk_orc(num_nodes=4, pages_per_node=64, num_logical=None, specs=None,
           **kw):
    cp = ControlPlane(num_nodes, pages_per_node,
                      num_logical=num_logical or num_nodes * pages_per_node)
    orc = Orchestrator(cp, budget=8, control_period=2, migrate=False, **kw)
    for spec in specs or [TenantSpec(1, "chat", qos="interactive", share=4.0),
                          TenantSpec(2, "crawl", qos="batch", share=1.0)]:
        orc.register(spec)
    return orc


# ---------------------------------------------------------------------------
# traffic generator
# ---------------------------------------------------------------------------

def test_traffic_deterministic_and_mix_independent():
    mixes = [
        [TenantTraffic(1, rate=2.0, prompt_max=32, output_max=16)],
        [TenantTraffic(1, rate=2.0, prompt_max=32, output_max=16),
         TenantTraffic(2, rate=5.0)],
    ]
    seen = []
    for mix in mixes:
        gen = TrafficGenerator(mix, seed=11)
        seen.append([
            (r.req_id is not None, r.tenant_id, r.prompt, r.output_len)
            for s in range(6) for r in gen.arrivals(s) if r.tenant_id == 1])
    # tenant 1's stream is a pure function of (seed, tenant, step): adding
    # tenant 2 to the mix must not perturb it (the solo/flood runs of the
    # serve bench depend on this).
    assert seen[0] == seen[1]
    # and re-running the same mix reproduces byte-identical requests
    gen = TrafficGenerator(mixes[0], seed=11)
    again = [(True, r.tenant_id, r.prompt, r.output_len)
             for s in range(6) for r in gen.arrivals(s)]
    assert again == seen[0]


def test_traffic_bounds_and_windows():
    gen = TrafficGenerator([
        TenantTraffic(3, rate=4.0, prompt_mean=8, output_mean=4, tail=1.3,
                      prompt_max=24, output_max=12, start_step=2,
                      stop_step=5, vocab=100)], seed=5)
    reqs = [r for s in range(8) for r in gen.arrivals(s)]
    assert reqs, "expected arrivals from a rate-4 window"
    assert all(2 <= r.arrive_step < 5 for r in reqs)
    for r in reqs:
        assert 1 <= r.prompt_len <= 24
        assert 1 <= r.output_len <= 12
        assert all(1 <= t < 100 for t in r.prompt)
    ids = [r.req_id for r in reqs]
    assert ids == sorted(set(ids)), "request ids mint monotonically"
    assert gen.total_generated() == len(reqs)
    # num_pages: ceil(total / page_tokens)
    r = reqs[0]
    assert r.num_pages(8) == -(-(r.prompt_len + r.output_len) // 8)


def test_traffic_validation():
    with pytest.raises(ValueError):
        TenantTraffic(1, rate=-1.0)
    with pytest.raises(ValueError):
        TenantTraffic(1, rate=1.0, tail=1.0)
    with pytest.raises(ValueError):
        TrafficGenerator([TenantTraffic(1, rate=1.0),
                          TenantTraffic(1, rate=2.0)])


# ---------------------------------------------------------------------------
# fidelity on the simulated engine (state leaks unless slots reset)
# ---------------------------------------------------------------------------

def test_continuous_matches_solo_sim_engine():
    orc = mk_orc()
    bat = ContinuousBatcher(orc, num_slots=8, page_tokens=8)
    eng = SimulatedDecodeEngine(8)
    traffic = TrafficGenerator([
        TenantTraffic(1, rate=1.0, prompt_mean=6, output_mean=5,
                      prompt_max=20, output_max=16),
        TenantTraffic(2, rate=1.5, prompt_mean=10, output_mean=8,
                      prompt_max=32, output_max=24)], seed=3)
    res = serve_loop(bat, eng, traffic, steps=30, step_us=10.0)
    assert res["completed"] == res["submitted"] > 20
    # slot reuse must have happened for the reset mechanism to be exercised
    assert res["completed"] > bat.num_slots
    for seq in bat.retired:
        assert seq.out == solo_reference(
            SimulatedDecodeEngine(8), seq.req, slot=seq.slot)


def test_sim_engine_leaks_without_reset():
    """The oracle is only meaningful if a forgotten reset would fail."""
    eng = SimulatedDecodeEngine(4)
    req = make_request(0, 1, prompt_len=3, output_len=4, seed=9, vocab=500)
    first = solo_reference(eng, req, slot=2)      # leaves acc dirty
    # replay the same request on the same engine WITHOUT reset
    tokens = np.zeros((4,), np.int32)
    out, fed = [], 0
    while len(out) < req.output_len:
        tokens[2] = (req.prompt[fed] if fed < req.prompt_len
                     else out[fed - req.prompt_len])
        emitted = eng.step(tokens, [])            # no reset: stale acc
        if fed >= req.prompt_len - 1:
            out.append(int(emitted[2]))
        fed += 1
    assert out != first


def test_continuous_matches_solo_real_model():
    """Continuous batching is a pure scheduling change on the jitted model."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.config import RunConfig, ShapeConfig
    from repro.models import transformer
    from repro.serve.batcher import ModelDecodeEngine

    batch, max_len, pt = 4, 24, 8
    cfg = dataclasses.replace(configs.get_reduced("granite-3-8b"),
                              dtype="float32")
    shape = ShapeConfig("serve_test", max_len, batch, "decode")
    params = transformer.init_params(cfg, jax.random.key(0))
    run = RunConfig(model=cfg, shape=shape, kv_placement="local")
    reqs = [make_request(i, 1 + i % 2, prompt_len=2 + i, output_len=3 + i,
                         seed=7, vocab=cfg.vocab_size) for i in range(5)]

    orc = mk_orc()
    bat = ContinuousBatcher(orc, num_slots=batch, page_tokens=pt)
    eng = ModelDecodeEngine(run, params, batch=batch, max_len=max_len,
                            page_tokens=pt, dtype=jnp.float32)
    for r in reqs:
        bat.submit(r)
    guard = 0
    while bat.in_flight() and guard < 200:
        bat.control()
        if bat.active_count():
            tokens, resets = bat.step_inputs()
            bat.observe(eng.step(tokens, resets))
        guard += 1
    assert sum(bat.completed.values()) == len(reqs)
    assert any(s.req.req_id >= batch for s in bat.retired), \
        "expected slot reuse (the reset mechanism under test)"
    # one engine serves every solo reference: the slot reset makes the
    # previous occupant's KV invisible, which is itself the contract
    ref_eng = ModelDecodeEngine(run, params, batch=batch, max_len=max_len,
                                page_tokens=pt, dtype=jnp.float32)
    for seq in bat.retired:
        assert seq.out == solo_reference(ref_eng, seq.req, slot=seq.slot), \
            f"req {seq.req.req_id} diverged from its solo decode"


# ---------------------------------------------------------------------------
# batcher invariants, property-tested over random mixes x depths
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_batcher_invariants_random_mixes(seed):
    rng = np.random.default_rng(seed)
    num_tenants = int(rng.integers(1, 5))
    qos_pool = ["interactive", "batch", "best_effort"]
    specs = [TenantSpec(t + 1, f"t{t + 1}",
                        qos=qos_pool[int(rng.integers(0, 3))],
                        share=float(rng.uniform(0.5, 4.0)))
             for t in range(num_tenants)]
    num_slots = int(rng.integers(2, 17))
    policy = ["qos", "naive"][int(rng.integers(0, 2))]
    orc = mk_orc(specs=specs, max_tenants=8)
    bat = ContinuousBatcher(orc, num_slots=num_slots, page_tokens=8,
                            policy=policy)
    eng = SimulatedDecodeEngine(num_slots)
    mix = [TenantTraffic(s.tenant_id, rate=float(rng.uniform(0.2, 3.0)),
                         prompt_mean=int(rng.integers(2, 12)),
                         output_mean=int(rng.integers(2, 10)),
                         prompt_max=24, output_max=16, vocab=1000)
           for s in specs]
    traffic = TrafficGenerator(mix, seed=seed)
    steps = int(rng.integers(5, 25))
    submitted_reqs = []
    admitted_ids = set()
    for step in range(steps):
        for req in traffic.arrivals(step):
            submitted_reqs.append(req)
            bat.submit(req)
        for seq in bat.control():
            assert seq.req.req_id not in admitted_ids, \
                "sequence admitted twice"
            admitted_ids.add(seq.req.req_id)
        # invariant: no slot double-assigned, slot map consistent
        live = [s for s in bat.slots if s is not None]
        assert len({s.slot for s in live}) == len(live)
        assert set(range(num_slots)) == \
            {s.slot for s in live} | set(bat.free)
        # conservation: submitted == completed + shed + queued + active
        acc = bat.accounting()
        for t in acc["submitted"]:
            assert acc["submitted"][t] == (
                acc["completed"].get(t, 0) + acc["shed"].get(t, 0)
                + acc["queued"].get(t, 0) + acc["active"].get(t, 0))
        assert bat.peak_in_flight >= bat.in_flight()
        if bat.active_count():
            tokens, resets = bat.step_inputs()
            bat.observe(eng.step(tokens, resets))
    # drain: every admitted sequence retires, every lease releases
    guard = 0
    while bat.in_flight() and guard < 3000:
        bat.control()
        if bat.active_count():
            tokens, resets = bat.step_inputs()
            bat.observe(eng.step(tokens, resets))
        guard += 1
    assert bat.in_flight() == 0, f"did not drain: {bat.describe()}"
    assert {s.req.req_id for s in bat.retired} >= admitted_ids
    assert len(orc.leases) == 0, "retirement must release every lease"
    assert len(bat.free) == num_slots
    acc = bat.accounting()
    assert sum(acc["submitted"].values()) == len(submitted_reqs)
    for t in acc["submitted"]:
        assert acc["submitted"][t] == (acc["completed"].get(t, 0)
                                       + acc["shed"].get(t, 0))
    # every retired sequence decoded exactly its requested output length
    for seq in bat.retired:
        assert len(seq.out) == seq.req.output_len


# ---------------------------------------------------------------------------
# admission edges
# ---------------------------------------------------------------------------

def test_oversized_request_sheds_not_livelocks():
    # pool: 4 nodes x 4 pages = 16 slots; a 40-page request can never fit
    orc = mk_orc(num_nodes=4, pages_per_node=4, num_logical=64)
    bat = ContinuousBatcher(orc, num_slots=4, page_tokens=8)
    whale = make_request(0, 2, prompt_len=300, output_len=20, vocab=100)
    assert whale.num_pages(8) == 40
    assert bat.submit(whale) == "shed"
    assert bat.queue_depth() == 0
    assert bat.shed[2]["terminal"] == 1
    # a feasible request still serves normally afterwards
    ok = make_request(1, 1, prompt_len=4, output_len=3, vocab=100)
    assert bat.submit(ok) == "queued"
    eng = SimulatedDecodeEngine(4)
    guard = 0
    while bat.in_flight() and guard < 100:
        bat.control()
        if bat.active_count():
            tokens, resets = bat.step_inputs()
            bat.observe(eng.step(tokens, resets))
        guard += 1
    assert bat.completed.get(1) == 1


def test_quota_bound_tenant_sheds_at_submit():
    specs = [TenantSpec(1, "small", qos="interactive", page_quota=2)]
    orc = mk_orc(specs=specs)
    bat = ContinuousBatcher(orc, num_slots=4, page_tokens=8)
    big = make_request(0, 1, prompt_len=30, output_len=10, vocab=100)
    assert big.num_pages(8) == 5 > 2
    assert bat.submit(big) == "shed"
    assert bat.shed[1]["terminal"] == 1


def test_attempt_bounded_shedding():
    # one tenant whose single seated lease pins the whole pool forever
    specs = [TenantSpec(1, "hog", qos="batch"),
             TenantSpec(2, "late", qos="interactive")]
    orc = mk_orc(num_nodes=2, pages_per_node=2, num_logical=4, specs=specs)
    dec, hog = orc.request_lease(1, 4, term=0, auto_renew=True)
    assert dec.admitted
    bat = ContinuousBatcher(orc, num_slots=2, page_tokens=8,
                            max_admit_attempts=3)
    late = make_request(0, 2, prompt_len=4, output_len=3, vocab=100)
    assert bat.submit(late) == "queued"   # 2 pages fit the pool in principle
    for _ in range(8):
        bat.control()
    assert bat.queue_depth() == 0, "attempt bound must evict the request"
    assert bat.shed[2]["attempts"] == 1


def test_qos_isolates_interactive_from_flood():
    """QoS slot windows bound interactive latency; naive FIFO does not."""
    def run(policy):
        orc = mk_orc(num_nodes=8, pages_per_node=256, num_logical=2048)
        registry = MetricsRegistry()
        bat = ContinuousBatcher(orc, num_slots=8, page_tokens=16,
                                policy=policy, registry=registry)
        mix = [TenantTraffic(1, rate=0.5, prompt_mean=4, output_mean=4,
                             prompt_max=12, output_max=10, stop_step=20,
                             vocab=1000),
               TenantTraffic(2, rate=15.0, prompt_mean=10, output_mean=8,
                             prompt_max=32, output_max=24, start_step=2,
                             stop_step=8, vocab=1000)]
        serve_loop(bat, SimulatedDecodeEngine(8),
                   TrafficGenerator(mix, seed=4), steps=20, step_us=100.0)
        return registry.family_quantiles(
            "serve_request_latency_us")["interactive"]["p99"]

    qos_p99, naive_p99 = run("qos"), run("naive")
    assert qos_p99 < naive_p99, (
        f"QoS admission (p99 {qos_p99}us) must beat naive FIFO "
        f"({naive_p99}us) under a batch flood")


# ---------------------------------------------------------------------------
# obs + orchestrator integration
# ---------------------------------------------------------------------------

def test_latency_histograms_and_request_spans():
    orc = mk_orc()
    clock = ManualClock(tick_us=0.0)
    recorder = TraceRecorder(clock=clock)
    registry = MetricsRegistry()
    bat = ContinuousBatcher(orc, num_slots=4, page_tokens=8,
                            registry=registry, clock=clock,
                            recorder=recorder)
    traffic = TrafficGenerator([
        TenantTraffic(1, rate=0.8, prompt_mean=4, output_mean=3,
                      prompt_max=12, output_max=8, vocab=500),
        TenantTraffic(2, rate=0.8, prompt_mean=4, output_mean=3,
                      prompt_max=12, output_max=8, vocab=500)], seed=2)
    res = serve_loop(bat, SimulatedDecodeEngine(4), traffic, steps=15,
                     step_us=50.0)
    lat = registry.family_quantiles("serve_request_latency_us")
    assert set(lat) == {"interactive", "batch"}
    for qos, q in lat.items():
        assert q["count"] > 0
        assert 0 < q["p50"] <= q["p99"]
    assert res["latency_us"].keys() == lat.keys()
    # one req<id> span per retirement and one req.queued per admission
    # (CAT_REQUEST both), wall-clock consistent
    queued = recorder.find_all(name=REQ + "queued")
    assert len(queued) == res["completed"]
    spans = [s for s in recorder.find_all(cat=CAT_REQUEST)
             if s.name != REQ + "queued"]
    assert len(spans) == res["completed"]
    for s in spans:
        # a 1-prompt/1-output request can legally retire in its arrival
        # step (zero modeled latency); anything longer takes clock time
        assert s.duration_us >= 0
        assert s.args["qos"] in ("interactive", "batch")
        assert s.args["output_len"] > 0
    # goodput denominated in the modeled clock
    assert res["goodput_tokens_per_s"] > 0
    # ttft <= full latency, per class
    ttft = registry.family_quantiles("serve_ttft_us")
    for qos in lat:
        assert ttft[qos]["p50"] <= lat[qos]["p50"] + 1e-9


def test_refit_windows_from_queue_depths():
    orc = mk_orc()
    # datapath telemetry would say "idle"; queue depths say tenant 2 is
    # flooded — the serving-layer refit must open tenant 2's window.
    sched = orc.refit_windows({1: 1.0, 2: float(orc.budget * 3)})
    assert sched.windows[2] > sched.windows[1] >= 1
    assert sum(sched.windows.values()) <= orc.budget
    # interactive still composes first regardless of window size
    assert sched.order[0] == 1


def test_lease_renewal_rides_control_period():
    """In-flight sequences outlive their lease term via auto-renew."""
    orc = mk_orc()
    bat = ContinuousBatcher(orc, num_slots=2, page_tokens=8, lease_term=2)
    req = make_request(0, 1, prompt_len=6, output_len=12, vocab=100)
    bat.submit(req)
    eng = SimulatedDecodeEngine(2)
    renewals = 0
    guard = 0
    while bat.in_flight() and guard < 100:
        bat.control()
        renewals += len(orc.leases) and any(
            l.auto_renew for l in orc.leases.values())
        if bat.active_count():
            tokens, resets = bat.step_inputs()
            bat.observe(eng.step(tokens, resets))
        guard += 1
    # residency (6 + 12 - 1 = 17 steps) >> term 2: renewal must have fired
    assert bat.completed.get(1) == 1
    assert len(orc.leases) == 0
    assert req.prompt_len + req.output_len - 1 > 2 * orc.default_term \
        or renewals > 0
