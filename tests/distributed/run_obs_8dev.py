"""Observability-plane validation on 8 virtual CPU devices.

Run as a subprocess by tests/test_distributed.py (auto-collected).  Proves
the tracing/metrics/calibration plane against the *real* 8-way mem ring:

* span <-> counter reconciliation is bit-exact: a fenced span annotated
  from the real datapath's in-band telemetry carries identical counts to
  one annotated from the ref oracle, for every program variant — uni /
  bi / pruned / load-balanced / hierarchical / group-masked — and the
  metrics registry's counter families agree with both,
* with a ManualClock, tracing the real datapath twice produces
  byte-identical Chrome-trace JSON (determinism survives actual jax
  dispatch, not just synthetic spans),
* phase attribution sees the real compiled programs: the unfused
  engine's ``obs:wire_req`` op count scales with pipeline depth while
  the fused engine's stays flat (the measured cause of the depth>1
  wall-clock regression),
* the calibrator closes the loop on real measurements: RLS-fitted
  constants predict the measured pull latencies with lower error than
  the static datasheet prior, and the fitted chunk overhead steers
  ``select_channels``.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import functools  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import bridge, perfmodel, ref, steering  # noqa: E402
from repro.core.control_plane import ControlPlane  # noqa: E402
from repro.core.memport import MemPortTable  # noqa: E402
from repro.core.topology import Topology  # noqa: E402
from repro.obs import (ManualClock, MetricsRegistry,  # noqa: E402
                       TraceRecorder, phase_op_counts)
from repro.telemetry import TelemetryAggregator  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

N, PPN, PAGE = 8, 8, 16
TENANT_NAMES = {0: "t0", 1: "t1", 2: "t2", 3: "t3"}


def variants(topo):
    hier = steering.hierarchical_program(topo)
    mask = np.asarray(hier.rank_epoch) >= 0
    r8 = np.arange(N)
    mask[0, :] = topo.pair_intra(r8, (r8 + 1) % N)
    bi = steering.bidirectional_program(N)
    return [
        ("uni", steering.unidirectional_program(N)),
        ("bi", bi),
        ("pruned", steering.pruned_program(bi, [1, 2, 6])),
        ("load_balanced", steering.load_balanced_program(
            N, np.asarray([6, 3, 2, 0, 0, 1, 4], float))),
        ("hierarchical", hier),
        ("masked", steering.masked_ranks_program(hier, mask)),
    ]


def span_reconciliation_checks():
    """Real-telemetry span args == oracle-telemetry span args, bit-exact,
    and the registry's counter families agree with both."""
    mesh8 = make_mesh((8,), ("data",))
    rng = np.random.default_rng(41)
    pool = jnp.asarray(rng.normal(size=(N * PPN, PAGE)).astype(np.float32))
    table = MemPortTable.striped(48, N, PPN)
    want = jnp.asarray(rng.integers(-1, 48, size=(N, 7)).astype(np.int32))
    lane = jnp.asarray(rng.integers(0, 4, size=(N, 7)).astype(np.int32))
    ab = jnp.asarray(rng.integers(1, 4, size=(N,)).astype(np.int32))
    topo = Topology.boards(2, 4)
    page_bytes = PAGE * 4

    rec = TraceRecorder(ManualClock(), process_name="obs-8dev")
    pull = jax.jit(functools.partial(
        bridge.pull_pages, mesh=mesh8, budget=3, topology=topo,
        collect_telemetry=True))
    for name, prog in variants(topo):
        with rec.span(f"transfer:{name}", variant=name,
                      budget=3) as sp:
            out, telem = pull(pool, want, table, program=prog,
                              active_budget=ab, tenant_ids=lane)
            rec.fence((out, telem))
        rec.annotate_telemetry(sp, telem, page_bytes=page_bytes,
                               tenant_names=TENANT_NAMES)

        exp = ref.expected_transfer_telemetry(
            np.asarray(want), table, prog, num_nodes=N, budget=3,
            topology=topo, active_budget=np.asarray(ab),
            tenant_ids=np.asarray(lane))
        with rec.span(f"oracle:{name}", variant=name) as sp_exp:
            pass
        rec.annotate_telemetry(sp_exp, exp, page_bytes=page_bytes,
                               tenant_names=TENANT_NAMES)
        counters = {k: v for k, v in sp.args.items()
                    if k not in ("variant", "budget")}
        counters_exp = {k: v for k, v in sp_exp.args.items()
                        if k != "variant"}
        assert counters == counters_exp, (
            f"{name}: span counters diverge from oracle\n"
            f"real:   {counters}\noracle: {counters_exp}")
        assert counters["pages_served"] > 0, f"{name}: nothing served"

        reg = MetricsRegistry()
        reg.observe_telemetry(telem, page_bytes=page_bytes)
        snap = reg.snapshot()["counters"]
        assert snap["bridge_pages_served_total"] == \
            counters["pages_served"], name
        assert snap['bridge_wire_pages_total{direction="cw"}'] == \
            counters["wire_pages_cw"], name
        assert snap['bridge_wire_pages_total{direction="ccw"}'] == \
            counters["wire_pages_ccw"], name
        assert snap["bridge_wire_bytes_total"] == \
            counters["wire_bytes"], name
        tenant_total = sum(
            v for k, v in snap.items()
            if k.startswith("bridge_tenant_pages_total"))
        assert tenant_total == sum(counters["tenant_pages"].values())
        print(f"ok: span/registry/oracle reconcile bit-exact [{name}]")
    return rec


def deterministic_trace_checks():
    """Two traced runs of the real datapath serialize byte-identically."""
    mesh8 = make_mesh((8,), ("data",))
    rng = np.random.default_rng(17)
    pool = jnp.asarray(rng.normal(size=(N * PPN, PAGE)).astype(np.float32))
    table = MemPortTable.striped(48, N, PPN)
    want = jnp.asarray(rng.integers(-1, 48, size=(N, 6)).astype(np.int32))

    def traced_run() -> str:
        rec = TraceRecorder(ManualClock(start_us=10.0, tick_us=3.0),
                            process_name="obs-deterministic")
        pull = jax.jit(functools.partial(
            bridge.pull_pages, mesh=mesh8, budget=3,
            collect_telemetry=True))
        with rec.span("transfer:deterministic", pages=6) as sp:
            out, telem = pull(pool, want, table)
            rec.fence((out, telem))
        rec.annotate_telemetry(sp, telem, page_bytes=PAGE * 4)
        return rec.to_json(indent=1)

    a, b = traced_run(), traced_run()
    assert a == b, "ManualClock trace not byte-identical across runs"
    assert '"ts": 10.0' in a
    print("ok: ManualClock trace byte-identical across two real-ring runs")


def phase_attribution_checks():
    """Compiled-HLO phase op counts: unfused scales with depth, fused
    does not — the structural cause of the pipeline wall-clock regression."""
    mesh8 = make_mesh((8,), ("data",))
    rng = np.random.default_rng(23)
    pool = jnp.asarray(rng.normal(size=(N * PPN, PAGE)).astype(np.float32))
    table = MemPortTable.striped(N * PPN, N, PPN)
    want = jnp.asarray(
        rng.integers(0, N * PPN, size=(N, 16)).astype(np.int32))
    counts = {}
    for fused in (False, True):
        for c in (1, 4):
            text = jax.jit(
                lambda p, w, t, _c=c, _f=fused: bridge.pull_pages(
                    p, w, t, mesh=mesh8, budget=8, channels=_c,
                    fused=_f)).lower(pool, want, table) \
                .compile().as_text()
            counts[(fused, c)] = phase_op_counts(text)
    for key, ops in counts.items():
        assert {"wire_req", "gather", "wire_data", "commit"} <= ops.keys(), (
            key, ops)
    assert counts[(False, 4)]["wire_req"] > counts[(False, 1)]["wire_req"], \
        "unfused steering collectives should scale with channels"
    assert counts[(True, 4)]["wire_req"] == counts[(True, 1)]["wire_req"], \
        "fused engine should issue one request all_gather at any depth"
    print(f"ok: phase op counts attribute the depth regression "
          f"(unfused wire_req {counts[(False, 1)]['wire_req']} -> "
          f"{counts[(False, 4)]['wire_req']}, fused flat at "
          f"{counts[(True, 1)]['wire_req']})")


def calibration_loop_checks():
    """Fit the perfmodel on real measured pulls; fitted must beat static,
    and the fitted chunk overhead must steer select_channels."""
    mesh8 = make_mesh((8,), ("data",))
    rng = np.random.default_rng(29)
    pool = jnp.asarray(rng.normal(size=(N * PPN, 64)).astype(np.float32))
    table = MemPortTable.striped(N * PPN, N, PPN)
    bi = steering.bidirectional_program(N)
    page_bytes = 64 * 4
    samples = []
    for c in (1, 2, 4):
        for cols in (8, 16):
            want = jnp.asarray(rng.integers(
                0, N * PPN, size=(N, cols)).astype(np.int32))
            pull = jax.jit(
                lambda p, w, t, _c=c: bridge.pull_pages(
                    p, w, t, mesh=mesh8, budget=8, channels=_c,
                    fused=False))
            jax.block_until_ready(pull(pool, want, table))
            reps = 3
            t0 = time.perf_counter()
            for _ in range(reps):
                r = pull(pool, want, table)
            jax.block_until_ready(r)
            us = (time.perf_counter() - t0) / reps * 1e6
            rounds = steering.num_rounds(cols, 8)
            feats = perfmodel.route_features(
                bi, page_bytes, 8, rounds=rounds, channels=c)
            samples.append((feats, us))

    cal = perfmodel.Calibrator()
    for _ in range(4):
        for feats, us in samples:
            cal.observe(feats, us)
    assert cal.fitted
    static_err = float(np.mean(
        [abs(cal.static_predict_us(f) - m) / m for f, m in samples]))
    fitted_err = float(np.mean(
        [abs(cal.predict_us(f) - m) / m for f, m in samples]))
    assert fitted_err < static_err, (
        f"fitted {fitted_err:.3f} not below static {static_err:.3f}")
    # dispatch dominates this backend: the fitted chunk overhead must be
    # real money, and the calibrated depth pick must not exceed static's
    assert cal.chunk_overhead_us > 0
    cp = ControlPlane(num_nodes=N, pages_per_node=PPN,
                      num_logical=N * PPN)
    agg = TelemetryAggregator(N, page_bytes=4096)
    agg.update(ref.expected_transfer_telemetry(
        np.asarray(rng.integers(0, N * PPN, size=(N, 8)), np.int32),
        table, bi, num_nodes=N, budget=8))
    pick_static = cp.select_channels(8, 4096, telemetry=agg)
    pick_cal = cp.select_channels(8, 4096, telemetry=agg, calibrator=cal)
    assert pick_cal <= pick_static
    print(f"ok: calibrator on real ring: err {static_err:.3f} -> "
          f"{fitted_err:.3f} ({cal.samples} obs), chunk "
          f"{cal.chunk_overhead_us:.0f}us, pick {pick_static} -> "
          f"{pick_cal}")


def main():
    assert jax.device_count() >= 8, "need 8 virtual devices"
    span_reconciliation_checks()
    deterministic_trace_checks()
    phase_attribution_checks()
    calibration_loop_checks()
    print("ALL OK")


if __name__ == "__main__":
    main()
