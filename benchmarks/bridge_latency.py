"""Paper §3 latency reproduction: the 134-cycle / 800 ns round trip.

Reports the stage-by-stage pipeline budget (design partition), checks it
sums to the published total, and measures the *software* path length of our
bridge datapath (translation -> steering -> epochs) in ops/epochs per pull,
which is the TPU-side analogue of the cycle count.

Also compares route-program schedule variants (unidirectional /
bidirectional / pruned / load_balanced): circuit epochs, wired slots, bytes
per round and the analytical round latency from ``repro.core.perfmodel``.
The ``load_balanced`` variant closes the software-defined loop: a skewed
traffic scenario runs through the bridge with ``collect_telemetry=True``
(on a real 8-way mem ring when 8 devices exist, through the telemetry
oracle otherwise), the measured distance loads compile a load-balanced
program, and its predicted round latency under the *measured* loads is
recorded against the static bidirectional split's.

The ``pipeline`` section sweeps the pipelined multi-channel round engine
(``channels``): modeled round latency per depth, real-datapath wall-clock
per depth on an 8-device ring when one exists (fused and unfused engines
both, plus a normalized ``model_vs_measured_error`` record), and the
control plane's telemetry-driven depth pick at a wire-bound and a
latency-bound page size.

The ``fused`` section times the fused Pallas datapath against the unfused
ppermute-chain escape hatch at the wire-bound (256 KiB) and latency-bound
(4 KiB) page sizes and counts copies/collectives in both lowered HLO
programs; it is also written standalone to ``BENCH_fused_compare.json``
(the CI comparison artifact).

The ``tenancy`` section co-locates an interactive decode tenant with a
batch-pull noisy neighbour through ``repro.orchestrator``: the same offered
load is priced solo, under naive FIFO sharing, and under the orchestrator's
weighted-fair QoS windows — the acceptance bar keeps the interactive
tenant's completion latency within 1.5x of its solo run while naive
sharing degrades with the backlog depth.

The ``calibration`` section closes the observability loop (``repro.obs``):
every measured scenario runs inside a fenced trace span (the whole run's
span tree is written to ``BENCH_trace.json``, openable in Perfetto), each
timed pull contributes a ``(route-feature, measured us)`` sample, and a
``repro.core.perfmodel.Calibrator`` RLS fit of the analytic model's
constants is compared against the static datasheet prior per scenario —
``validate_bench.py`` gates fitted <= static.  ``pipeline`` additionally
records a per-depth ``phase_breakdown`` from ``obs:<phase>`` named-scope
op counts in the compiled HLO, attributing the unfused depth>1 wall-clock
regression to steering-collective dispatch.

Emits CSV rows: name,us_per_call,derived — and writes the same data
machine-readably to ``BENCH_bridge.json`` at the repo root so the perf
trajectory is tracked across PRs (schema checked by
``benchmarks/validate_bench.py`` in CI; ``--quick`` trims timing reps for
the smoke job).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
from benchmarks import hlo_analysis  # noqa: E402

from repro.core import bridge, perfmodel, ref, steering
from repro.core.control_plane import ControlPlane
from repro.core.memport import MemPortTable
from repro.core.topology import Topology
from repro.launch.mesh import make_mesh
from repro.obs import TraceRecorder, phase_op_counts
from repro.orchestrator import Orchestrator, TenantSpec
from repro.telemetry import TelemetryAggregator

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / "BENCH_bridge.json"
# Standalone fused-vs-unfused comparison record (CI uploads it next to
# BENCH_bridge.json so the fused-datapath claim is a first-class artifact).
FUSED_JSON = BENCH_JSON.with_name("BENCH_fused_compare.json")
# Chrome-trace/Perfetto span record of every measured scenario in this run
# (CI uploads it; open at https://ui.perfetto.dev).
TRACE_JSON = BENCH_JSON.with_name("BENCH_trace.json")
# Postmortem archive of the sentinel drill's orchestrator (flight journal +
# metrics + state; ``repro.obs.replay()`` re-executes the journal).
BUNDLE_ZIP = BENCH_JSON.with_name("BENCH_debug_bundle.zip")

# Online-calibration fit: RLS passes over the measured-scenario samples
# (deterministic order, so the fitted constants are reproducible given the
# same wall-clock samples).
CAL_EPOCHS = 4

# Route-program comparison geometry: an 8-node mem ring moving 256 KiB pages
# in rounds of 8; "pruned" keeps the three distances a blocked/affinity
# placement typically exercises.
ROUTE_NODES = 8
ROUTE_PAGE_BYTES = 1 << 18
ROUTE_BUDGET = 8

# Skewed-traffic scenario: every requester hammers its three nearest
# clockwise neighbours 6:3:2 (hotspot locality) — the shape that makes the
# static min(d, N-d) split pile every live circuit onto one direction.
SKEW_PAGES = {1: 6, 2: 3, 3: 2}

# Hierarchical fabrics compared flat-vs-two-tier: the real 8-endpoint ring
# (2 boards x 4) plus simulated rack-scale 16 and 32 endpoint fabrics.
HIER_FABRICS = {"8": (2, 4), "16": (4, 4), "32": (4, 8)}

# Pipelined round-engine depth sweep (the channels knob): modeled round
# latency per depth, wall-clock on the real 8-ring when available, and the
# control plane's telemetry-driven pick at a wire-bound (256 KiB) and a
# latency-bound (4 KiB) page size.
PIPELINE_CHANNELS = (1, 2, 4, 8)
SMALL_PAGE_BYTES = 4096

# Fused-vs-unfused epoch comparison geometry: the wire-bound (256 KiB) and
# latency-bound (4 KiB) page sizes of the control plane's two regimes.
FUSED_PAGE_SIZES = {"256KiB": 1 << 18, "4KiB": SMALL_PAGE_BYTES}
# Intra-board-heavy traffic: pages pulled from each board mate at local
# ring delta 1/2/3+ (hotspot locality *within* the board).
INTRA_PAGES = {1: 6, 2: 3, 3: 2}

# Multi-tenant co-location scenario: a latency-sensitive interactive decode
# tenant (6 near-neighbour pages per node per step, 3:1 budget share) next
# to a batch-pull noisy neighbour with a deep striped backlog.
TENANCY_INTERACTIVE_PAGES = {1: 3, 2: 3}   # per node, by ring distance
TENANCY_BATCH_BACKLOG = 40                 # pages per node, striped homes


def route_variants() -> dict[str, steering.RouteProgram]:
    bi = steering.bidirectional_program(ROUTE_NODES)
    return {
        "unidirectional": steering.unidirectional_program(ROUTE_NODES),
        "bidirectional": bi,
        "pruned": steering.pruned_program(bi, [1, 2, 6]),
    }


def measure_sw_pull_us(reps: int = 50) -> float:
    """One-page pull latency through the loopback bridge (jitted)."""
    table = MemPortTable.striped(16, 4, 4)
    pool = jnp.asarray(np.random.default_rng(0).normal(
        size=(16, 256)).astype(np.float32))
    want = jnp.asarray([[3]], jnp.int32)
    pull = jax.jit(lambda p, w, t: bridge.pull_pages(
        p, w, t, mesh=None, budget=1, table_nodes=4))
    jax.block_until_ready(pull(pool, want, table))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        r = pull(pool, want, table)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e6


def skewed_traffic_scenario(recorder: TraceRecorder | None = None,
                            samples: list | None = None,
                            quick: bool = False) -> tuple:
    """Measure a skewed matrix, recompile, compare predicted latencies.

    Returns ``(measured, program, aggregator, control_plane)``: the
    ``measured`` section of BENCH_bridge.json — per-distance measured pages
    per round, the static-bidirectional vs load-balanced predicted round
    latency under those loads, and how the telemetry was captured (real
    8-device ring or oracle counters) — plus the telemetry-compiled
    load-balanced program and the aggregator / control plane that compiled
    it (``pipeline_sweep`` reuses them for the measured channels pick).

    When running on the real ring the pull is also wall-clock timed inside
    a fenced trace span (annotated with the exact bridge counters) and a
    ``(features, measured_us)`` calibration sample is appended to
    ``samples`` — the feature vector prices the *actual* moved bytes, not
    the scenario's nominal 256 KiB page, so the fit sees what ran.
    """
    n, ppn = ROUTE_NODES, 16
    cp = ControlPlane(num_nodes=n, pages_per_node=ppn, num_logical=n * ppn)
    cp.allocate(n * ppn, policy="striped")   # page p -> home p % n
    table = cp.table()
    # Node i requests SKEW_PAGES[d] pages homed at (i + d) % n.
    want_rows = []
    for i in range(n):
        row = []
        for d, count in SKEW_PAGES.items():
            h = (i + d) % n
            row += [h + n * k for k in range(count)]   # striped: home = id % n
        want_rows.append(row)
    want = np.asarray(want_rows, np.int32)
    rounds = steering.num_rounds(want.shape[1], ROUTE_BUDGET)

    source = "oracle"
    measured_pull_us = None
    if jax.device_count() >= n:
        source = f"{n}-device ring"
        mesh = make_mesh((n,), ("data",))
        pool = jnp.zeros((n * ppn, 4), jnp.float32)
        rec = recorder if recorder is not None else TraceRecorder()
        reps = 2 if quick else 5
        pull = jax.jit(lambda p, w, t: bridge.pull_pages(
            p, w, t, mesh=mesh, budget=ROUTE_BUDGET,
            collect_telemetry=True))
        wj = jnp.asarray(want)
        jax.block_until_ready(pull(pool, wj, table))   # compile
        t0 = time.perf_counter()
        with rec.span("transfer:skewed", scenario="skewed",
                      rounds=rounds, reps=reps) as sp:
            for _ in range(reps):
                r = pull(pool, wj, table)
            rec.fence(r)
        measured_pull_us = (time.perf_counter() - t0) / reps * 1e6
        _, telem = r
        rec.annotate_telemetry(sp, telem, page_bytes=pool.shape[1] * 4)
        if samples is not None:
            samples.append({
                "scenario": "skewed", "name": "skewed_pull",
                "features": [round(float(x), 6) for x in
                             perfmodel.route_features(
                                 steering.bidirectional_program(n),
                                 pool.shape[1] * 4, ROUTE_BUDGET,
                                 rounds=rounds)],
                "measured_us": round(measured_pull_us, 1)})
    else:
        telem = ref.expected_transfer_telemetry(
            want, table, steering.bidirectional_program(n), num_nodes=n,
            budget=ROUTE_BUDGET)

    agg = TelemetryAggregator(n, page_bytes=ROUTE_PAGE_BYTES)
    agg.update(telem)
    lb = cp.route_program(telemetry=agg)
    lb.validate()
    # Measured pages per slot per requester-round: what one bridge round
    # actually moves under this matrix.
    slot_pages = agg.distance_pages() / (n * rounds)
    bi = steering.bidirectional_program(n)
    lat_bi = perfmodel.predict_round_latency_us(
        bi, ROUTE_PAGE_BYTES, ROUTE_BUDGET, slot_pages=slot_pages)
    lat_lb = perfmodel.predict_round_latency_us(
        lb, ROUTE_PAGE_BYTES, ROUTE_BUDGET, slot_pages=slot_pages)
    out = {
        "source": source,
        "skew_pages": {str(d): c for d, c in SKEW_PAGES.items()},
        "distance_pages_per_round": [round(float(x), 3) for x in slot_pages],
        "spilled": int(np.asarray(telem.spilled).sum()),
        "pruned": int(np.asarray(telem.pruned).sum()),
        "static_bidirectional_us": round(lat_bi, 2),
        "load_balanced_us": round(lat_lb, 2),
    }
    if measured_pull_us is not None:
        out["measured_pull_us"] = round(measured_pull_us, 1)
    return out, lb, agg, cp


def _phase_breakdown(phase_ops: dict, measured: dict,
                     measured_unfused: dict) -> dict:
    """Attribute the pipeline-depth wall-clock to datapath phases.

    The unfused engine's op count inside the ``obs:`` scopes scales with
    ``2*(N-1)*channels`` (every extra channel re-runs the whole
    request/data ppermute ladder per chunk), while the fused engine keeps
    one request all_gather and a fixed payload exchange at any depth.  A
    linear fit of measured wall-clock against scoped op count across the
    unfused sweep yields the per-op dispatch cost on this backend; each
    phase's attributed share is ``us_per_op * its op count``.  This is the
    measured explanation of the depth>1 slowdown first recorded in the
    pipelined-engine PR: dispatch grows with depth, and on an emulated
    synchronous ring no overlap exists to pay for it.
    """
    depths = sorted(phase_ops["unfused"], key=int)
    totals = {c: sum(phase_ops["unfused"][c].values()) for c in depths}
    xs = np.array([totals[c] for c in depths], float)
    ys = np.array([measured_unfused[c] for c in depths], float)
    if len(depths) > 1 and float(np.ptp(xs)) > 0:
        us_per_op, base_us = (float(v) for v in np.polyfit(xs, ys, 1))
    else:
        us_per_op, base_us = 0.0, float(ys.mean()) if len(depths) else 0.0
    out: dict = {"unfused": {}, "fused": {}}
    for c in depths:
        ops = phase_ops["unfused"][c]
        out["unfused"][c] = {
            "total_us": measured_unfused[c],
            "phase_ops": ops,
            "total_ops": totals[c],
            "attributed_us": {ph: round(us_per_op * k, 1)
                              for ph, k in sorted(ops.items())},
        }
    for c in sorted(phase_ops["fused"], key=int):
        ops = phase_ops["fused"][c]
        out["fused"][c] = {
            "total_us": measured[c],
            "phase_ops": ops,
            "total_ops": sum(ops.values()),
        }
    out["dispatch_us_per_op"] = round(us_per_op, 2)
    out["dispatch_base_us"] = round(base_us, 1)
    out["finding"] = (
        "unfused wall-clock grows with depth because every extra channel "
        "adds another 2*(N-1) steering collectives per round (the "
        "wire_req/wire_data op counts scale with channels) and the "
        "emulated host ring pays per-op dispatch with nothing "
        "overlapping; the fused engine's phase op counts stay flat, so "
        "does its wall-clock. The modeled overlap win needs a real wire; "
        "here the calibrated per-chunk overhead keeps select_channels "
        "serial.")
    return out


def pipeline_sweep(agg: TelemetryAggregator, cp: ControlPlane,
                   quick: bool = False,
                   recorder: TraceRecorder | None = None,
                   samples: list | None = None) -> dict:
    """Pipeline-depth sweep: the pipelined multi-channel round engine.

    Models one bridge round at every depth in PIPELINE_CHANNELS (worst-case
    budget loads on the bidirectional schedule — the overlap term hides
    min(wire, RTT) behind max(wire, RTT) with 1/channels exposed), times the
    real jitted datapath per depth on an 8-device ring when one exists, and
    records the control plane's telemetry pick at a wire-bound and a
    latency-bound page size.  Acceptance (validate_bench.py): every
    channels > 1 modeled round latency <= the serial engine's.  The
    wall-clock numbers are informational only: the host-CPU ring emulates
    ppermute synchronously (nothing can overlap) and pays per-op dispatch
    for the smaller chunked gathers, so the overlap win exists only where
    the wire is real (the model's regime).

    The ``phase_breakdown`` record makes that attribution evidence, not
    narrative: every depth's compiled program is counted per
    ``obs:<phase>`` named scope (``repro.obs.phase_op_counts``), and a
    linear dispatch fit ``measured_us ~ base + us_per_op * phase_ops``
    over the unfused sweep prices each phase's share of the measured
    wall-clock.  Each timed loop also runs inside a fenced trace span and
    appends a calibration sample (features x measured wall-clock) to
    ``samples``.
    """
    bi = steering.bidirectional_program(ROUTE_NODES)
    model = {str(c): round(perfmodel.predict_round_latency_us(
        bi, ROUTE_PAGE_BYTES, ROUTE_BUDGET, channels=c), 2)
        for c in PIPELINE_CHANNELS}
    out: dict = {
        "source": "model",
        "model_round_us": model,
        "selected_channels": {
            "wire_bound_256KiB": cp.select_channels(
                ROUTE_BUDGET, ROUTE_PAGE_BYTES, telemetry=agg),
            "latency_bound_4KiB": cp.select_channels(
                ROUTE_BUDGET, SMALL_PAGE_BYTES, telemetry=agg),
        },
    }
    n, ppn = ROUTE_NODES, 16
    if jax.device_count() >= n:
        out["source"] = f"{n}-device ring"
        mesh = make_mesh((n,), ("data",))
        rng = np.random.default_rng(3)
        pool = jnp.asarray(rng.normal(size=(n * ppn, 64)).astype(np.float32))
        table = MemPortTable.striped(n * ppn, n, ppn)
        want = jnp.asarray(
            rng.integers(0, n * ppn, size=(n, 16)).astype(np.int32))
        reps = 3 if quick else 30
        rounds = steering.num_rounds(want.shape[1], ROUTE_BUDGET)
        page_bytes = pool.shape[1] * 4
        rec = recorder if recorder is not None else TraceRecorder()
        measured: dict = {}
        measured_unfused: dict = {}
        phase_ops: dict = {"fused": {}, "unfused": {}}
        for c in PIPELINE_CHANNELS:
            for fused, acc in ((True, measured),
                               (False, measured_unfused)):
                key = "fused" if fused else "unfused"
                pull = jax.jit(
                    lambda p, w, t, _c=c, _f=fused: bridge.pull_pages(
                        p, w, t, mesh=mesh, budget=ROUTE_BUDGET,
                        channels=_c, fused=_f))
                compiled = pull.lower(pool, want, table).compile()
                phase_ops[key][str(c)] = phase_op_counts(
                    compiled.as_text())
                jax.block_until_ready(compiled(pool, want, table))
                t0 = time.perf_counter()
                with rec.span(f"transfer:pipeline_{key}_c{c}",
                              scenario="pipeline", engine=key,
                              channels=c, reps=reps):
                    for _ in range(reps):
                        r = compiled(pool, want, table)
                    rec.fence(r)
                acc[str(c)] = round(
                    (time.perf_counter() - t0) / reps * 1e6, 1)
                if samples is not None:
                    samples.append({
                        "scenario": "pipeline",
                        "name": f"pipeline_{key}_c{c}",
                        "features": [round(float(x), 6) for x in
                                     perfmodel.route_features(
                                         bi, page_bytes, ROUTE_BUDGET,
                                         rounds=rounds, channels=c)],
                        "measured_us": acc[str(c)]})
        out["measured_us_per_call"] = measured
        out["measured_unfused_us_per_call"] = measured_unfused
        out["phase_breakdown"] = _phase_breakdown(
            phase_ops, measured, measured_unfused)
        # Model-vs-measured shape error: both sweeps normalized to their
        # serial (channels=1) point, so the record tracks whether deeper
        # pipelines *scale* the way the model says they should — the PR 4
        # regression (measured wall-clock growing with depth while the
        # model predicts a mild win) shows up here as a large error, and
        # validate_bench.py bands the fused sweep itself.
        err = {str(c): round(abs(
            measured[str(c)] / measured["1"]
            - model[str(c)] / model["1"]), 3) for c in PIPELINE_CHANNELS}
        err["mean"] = round(sum(err.values()) / len(err), 3)
        out["model_vs_measured_error"] = err
    return out


def fused_section(quick: bool = False,
                  recorder: TraceRecorder | None = None,
                  samples: list | None = None) -> dict:
    """Fused vs unfused epoch wall-clock + lowered-datapath op counts.

    Times one jitted ``pull_pages`` epoch (2 rounds of budget 8) on the
    real 8-device ring with the fused Pallas datapath on and off, at the
    wire-bound (256 KiB) and latency-bound (4 KiB) page sizes.  Acceptance
    (validate_bench.py): fused beats unfused at **both** sizes — the fused
    engine collapses each round's 2*(N-1)*channels steering collectives
    to at most N (one request all_gather plus the payload exchange: an
    all_to_all on TPU, a ppermute hop per slot off-TPU) and drops the
    per-slot mask->gather->commit chain, so its win must not depend on
    the wire-bound regime.

    Methodology: the emulated ring timeshares one host (CI runs on a
    single core), so back-to-back config sweeps drift by double-digit
    percentages and whichever engine runs first in a fixed rotation eats a
    positional penalty (allocator/cache state left by the previous cycle).
    Each page size therefore times the two engines as interleaved pairs
    with the order flipped every repetition (ABBA) and records the
    per-engine **median** — ambient drift and the positional bias cancel
    instead of deciding the gate.  The ``hlo`` block counts intermediate
    ``copy`` ops and collectives in both lowered programs
    (benchmarks/hlo_analysis.py), making the dispatch-overhead claim
    inspectable rather than inferred.
    """
    n, ppn = ROUTE_NODES, 16
    out: dict = {"source": "model-only", "page_sweep": {}}
    if jax.device_count() < n:
        return out
    out["source"] = f"{n}-device ring"
    mesh = make_mesh((n,), ("data",))
    rng = np.random.default_rng(11)
    table = MemPortTable.striped(n * ppn, n, ppn)
    want = jnp.asarray(
        rng.integers(0, n * ppn, size=(n, 16)).astype(np.int32))
    reps = 10 if quick else 24
    rec = recorder if recorder is not None else TraceRecorder()
    rounds = steering.num_rounds(want.shape[1], ROUTE_BUDGET)
    bi = steering.bidirectional_program(n)
    for label, page_bytes in FUSED_PAGE_SIZES.items():
        pool = jnp.asarray(rng.normal(
            size=(n * ppn, page_bytes // 4)).astype(np.float32))
        entry: dict = {"page_bytes": page_bytes}
        pulls, times = {}, {}
        for fused in (True, False):
            pulls[fused] = jax.jit(
                lambda p, w, t, _f=fused: bridge.pull_pages(
                    p, w, t, mesh=mesh, budget=ROUTE_BUDGET, fused=_f))
            jax.block_until_ready(pulls[fused](pool, want, table))
            times[fused] = []
        with rec.span(f"transfer:fused_{label}", scenario="fused",
                      page_bytes=page_bytes, reps=reps) as sp:
            for rep in range(reps):
                order = (True, False) if rep % 2 == 0 else (False, True)
                for fused in order:
                    t0 = time.perf_counter()
                    jax.block_until_ready(
                        pulls[fused](pool, want, table))
                    times[fused].append(time.perf_counter() - t0)
        entry["fused_us"] = round(
            float(np.median(times[True])) * 1e6, 1)
        entry["unfused_us"] = round(
            float(np.median(times[False])) * 1e6, 1)
        entry["speedup"] = round(entry["unfused_us"]
                                 / max(entry["fused_us"], 1e-9), 2)
        rec.annotate(sp, fused_us=entry["fused_us"],
                     unfused_us=entry["unfused_us"])
        out["page_sweep"][label] = entry
        if samples is not None:
            # The only samples with non-trivial wire bytes: they make
            # the calibrator's us/MiB payload term identifiable.
            feats = [round(float(x), 6) for x in perfmodel.route_features(
                bi, page_bytes, ROUTE_BUDGET, rounds=rounds)]
            for engine in ("fused", "unfused"):
                samples.append({
                    "scenario": "fused",
                    "name": f"fused_{label}_{engine}",
                    "features": feats,
                    "measured_us": entry[f"{engine}_us"]})
    # Lowered-HLO structure at the latency-bound size (where dispatch
    # and copy overhead, not wire bytes, decide the epoch time).
    pool = jnp.asarray(rng.normal(
        size=(n * ppn, SMALL_PAGE_BYTES // 4)).astype(np.float32))
    hlo = {}
    for fused, key in ((True, "fused"), (False, "unfused")):
        text = jax.jit(lambda p, w, t, _f=fused: bridge.pull_pages(
            p, w, t, mesh=mesh, budget=ROUTE_BUDGET, fused=_f)).lower(
                pool, want, table).compile().as_text()
        hlo[f"{key}_copies"] = hlo_analysis.count_ops(text, "copy")
        hlo[f"{key}_collectives"] = sum(
            hlo_analysis.count_ops(text, c)
            for c in hlo_analysis.COLLECTIVES)
    out["hlo"] = hlo
    return out


def _measure_composition(want, lane, table, program, n: int,
                         active_budget, recorder=None, label: str = "",
                         samples: list | None = None,
                         reps: int = 3) -> object:
    """Telemetry for one composed request matrix (real ring or oracle).

    On the real ring the composition is jitted, wall-clock timed inside a
    fenced trace span annotated with the per-tenant bridge counters, and
    (when ``samples`` is given) appended as a calibration sample.
    """
    if jax.device_count() >= n:
        ppn = 16
        mesh = make_mesh((n,), ("data",))
        pool = jnp.zeros((n * ppn, 4), jnp.float32)
        rec = recorder if recorder is not None else TraceRecorder()
        pull = jax.jit(lambda p, w, t, ab, tid: bridge.pull_pages(
            p, w, t, mesh=mesh, budget=ROUTE_BUDGET, program=program,
            active_budget=ab, collect_telemetry=True, tenant_ids=tid))
        args = (pool, jnp.asarray(want), table,
                jnp.asarray(active_budget), jnp.asarray(lane))
        jax.block_until_ready(pull(*args))   # compile
        t0 = time.perf_counter()
        with rec.span(f"transfer:tenancy_{label or 'composition'}",
                      scenario="tenancy", composition=label,
                      reps=reps) as sp:
            for _ in range(reps):
                r = pull(*args)
            rec.fence(r)
        dt_us = (time.perf_counter() - t0) / reps * 1e6
        _, telem = r
        rec.annotate_telemetry(
            sp, telem, page_bytes=pool.shape[1] * 4,
            tenant_names={0: "interactive", 1: "batch"})
        if samples is not None:
            rounds = steering.num_rounds(want.shape[1], ROUTE_BUDGET)
            samples.append({
                "scenario": "tenancy",
                "name": f"tenancy_{label or 'composition'}",
                "features": [round(float(x), 6) for x in
                             perfmodel.route_features(
                                 program, pool.shape[1] * 4, ROUTE_BUDGET,
                                 rounds=rounds)],
                "measured_us": round(dt_us, 1)})
        return telem
    return ref.expected_transfer_telemetry(
        want, table, program, num_nodes=n, budget=ROUTE_BUDGET,
        active_budget=active_budget, tenant_ids=lane)


def _interactive_completion_us(telem, program, n: int, last_idx: int,
                               total_len: int) -> float:
    """Completion latency of the interactive tenant's last request.

    A composition of ``total_len`` requests is served in
    ``num_rounds(total_len, budget)`` rounds of ``ROUTE_BUDGET`` lanes; the
    request at index ``last_idx`` retires when round
    ``ceil((last_idx + 1) / budget)`` completes, each round priced by the
    perfmodel under the composition's *measured* per-slot loads.
    """
    agg = TelemetryAggregator(n, page_bytes=ROUTE_PAGE_BYTES)
    agg.update(telem)
    rounds_total = steering.num_rounds(total_len, ROUTE_BUDGET)
    slot_pages = agg.distance_pages() / (n * rounds_total)
    round_us = perfmodel.predict_round_latency_us(
        program, ROUTE_PAGE_BYTES, ROUTE_BUDGET, slot_pages=slot_pages)
    return steering.num_rounds(last_idx + 1, ROUTE_BUDGET) * round_us


def tenancy_scenario(recorder: TraceRecorder | None = None,
                     samples: list | None = None) -> dict:
    """Interactive decode tenant vs a batch-pull noisy neighbour.

    Three compositions of the same offered load, measured (real 8-ring or
    oracle) and priced by the perfmodel under the measured loads:

    * **solo** — the interactive tenant alone: its 6 pages/node complete in
      one bridge round (the baseline its SLO is written against);
    * **naive FIFO** — no orchestration: the batch tenant's 40-page backlog
      is already queued ahead, so the interactive requests retire only when
      the last round of the combined 46-page list drains (degradation grows
      unboundedly with the backlog);
    * **QoS** — the orchestrator's weighted-fair schedule (3:1 shares)
      clips the batch tenant to its window and composes the interactive
      window first: the interactive pages again complete in round one,
      sharing it with only the batch window's pages.

    Acceptance (validate_bench.py): ``interactive_qos_us`` within 1.5x of
    ``interactive_solo_us`` while the naive ratio is strictly worse.
    """
    n, ppn = ROUTE_NODES, 16
    topo = Topology.boards(2, 4)
    cp = ControlPlane(num_nodes=n, pages_per_node=ppn, num_logical=n * ppn,
                      topology=topo)
    orc = Orchestrator(cp, budget=ROUTE_BUDGET, page_bytes=ROUTE_PAGE_BYTES,
                       control_period=1, migrate=False)
    orc.register(TenantSpec(0, "interactive", qos="interactive", share=3.0,
                            slo_round_us=1e5))
    orc.register(TenantSpec(1, "batch", qos="batch", share=1.0))
    inter_pages = sum(TENANCY_INTERACTIVE_PAGES.values())
    _, li = orc.request_lease(0, n * inter_pages)
    _, lb = orc.request_lease(1, n * (ppn - inter_pages) - n,
                              policy="striped")
    assert li is not None and lb is not None
    program = orc.route_program()

    # Interactive backlog: per node, pages homed at its near neighbours
    # (affinity placement put tenant 0's pages on board 0; re-key the
    # request lists off the actual table so distances are as designed).
    home = np.asarray(cp.table().home)
    inter_rows: list[list[int]] = []
    for i in range(n):
        row = []
        for d, count in TENANCY_INTERACTIVE_PAGES.items():
            h = (i + d) % n
            ids = [int(p) for p in li.region.page_ids if home[p] == h]
            row += ids[:count]
            # fabric may have spilled pages off the exact neighbour: fall
            # back to any of the tenant's pages to keep the load constant
        row += [int(p) for p in li.region.page_ids
                if int(p) not in row][: inter_pages - len(row)]
        inter_rows.append(row[:inter_pages])
    # Batch readers scan the whole leased region: each node's backlog
    # cycles over the lease's pages (pull is read-only, so repeated ids
    # across nodes are fine — it is a striped hot scan).
    bids = np.asarray(lb.region.page_ids, np.int64)
    batch_rows = [[int(bids[(i * 7 + k) % len(bids)])
                   for k in range(TENANCY_BATCH_BACKLOG)] for i in range(n)]

    source = ("oracle" if jax.device_count() < n else f"{n}-device ring")
    table = orc.table()

    # 1. solo: the interactive tenant alone, full budget.
    want_solo = np.full((n, inter_pages), -1, np.int32)
    for i, row in enumerate(inter_rows):
        want_solo[i, : len(row)] = row
    lane_solo = np.zeros_like(want_solo)
    telem_solo = _measure_composition(want_solo, lane_solo, table, program,
                                      n, np.full((n,), ROUTE_BUDGET,
                                                 np.int32),
                                      recorder=recorder, label="solo",
                                      samples=samples)
    solo_us = _interactive_completion_us(telem_solo, program, n,
                                         inter_pages - 1, inter_pages)

    # 2. naive FIFO: batch backlog queued ahead, no windows.
    naive_len = TENANCY_BATCH_BACKLOG + inter_pages
    want_naive = np.full((n, naive_len), -1, np.int32)
    lane_naive = np.zeros((n, naive_len), np.int32)
    for i in range(n):
        want_naive[i, :TENANCY_BATCH_BACKLOG] = batch_rows[i]
        lane_naive[i, :TENANCY_BATCH_BACKLOG] = 1
        want_naive[i, TENANCY_BATCH_BACKLOG:] = inter_rows[i]
    telem_naive = _measure_composition(want_naive, lane_naive, table,
                                       program, n,
                                       np.full((n,), ROUTE_BUDGET, np.int32),
                                       recorder=recorder, label="naive_fifo",
                                       samples=samples)
    naive_us = _interactive_completion_us(telem_naive, program, n,
                                          naive_len - 1, naive_len)

    # 3. QoS: the orchestrator's weighted-fair windows (interactive first).
    backlogs = {0: inter_rows, 1: batch_rows}
    want_qos, lane_qos, _ = orc.compose_requests(backlogs)
    telem_qos = _measure_composition(want_qos, lane_qos, table, program, n,
                                     orc.active_budget(),
                                     recorder=recorder, label="qos",
                                     samples=samples)
    windows = dict(orc.schedule.windows)
    qos_us = _interactive_completion_us(telem_qos, program, n,
                                        windows[0] - 1,
                                        want_qos.shape[1])
    orc.step(telem_qos)   # close the loop: measured demand re-fits windows

    served = np.asarray(telem_qos.tenant_served).sum(0)
    spilled = np.asarray(telem_qos.tenant_spilled).sum(0)
    return {
        "source": source,
        "interactive_pages": inter_pages,
        "batch_backlog_pages": TENANCY_BATCH_BACKLOG,
        "windows": {"interactive": windows[0], "batch": windows[1]},
        "refit_windows": {"interactive": orc.schedule.windows[0],
                          "batch": orc.schedule.windows[1]},
        "interactive_solo_us": round(solo_us, 2),
        "interactive_naive_us": round(naive_us, 2),
        "interactive_qos_us": round(qos_us, 2),
        "qos_isolation_ratio": round(qos_us / solo_us, 3),
        "naive_degradation_ratio": round(naive_us / solo_us, 3),
        "tenant_served": {"interactive": int(served[0]),
                          "batch": int(served[1])},
        "tenant_spilled": {"interactive": int(spilled[0]),
                           "batch": int(spilled[1])},
    }


def hierarchical_scenario(num_boards: int, board_size: int,
                          recorder: TraceRecorder | None = None) -> dict:
    """Flat-vs-hierarchical round latency under intra-board-heavy traffic.

    Builds the fabric, drives an intra-heavy request matrix (each endpoint
    pulls INTRA_PAGES from its board mates by local ring delta), measures
    the per-distance / per-tier loads — through the real datapath with
    ``collect_telemetry`` when enough devices exist, through the telemetry
    oracle otherwise (the simulated 16/32-endpoint racks) — and models one
    round under the measured loads for the topology-blind flat
    bidirectional schedule vs the two-tier hierarchical schedule.
    """
    topo = Topology.boards(num_boards, board_size)
    n, g = topo.num_nodes, board_size
    ppn = 16
    cp = ControlPlane(num_nodes=n, pages_per_node=ppn, num_logical=n * ppn,
                      topology=topo)
    cp.allocate(n * ppn, policy="striped")   # page p -> home p % n
    table = cp.table()
    want_rows = []
    for i in range(n):
        row, l_i, base = [], i % g, (i // g) * g
        for dl, count in INTRA_PAGES.items():
            if dl >= g:
                continue
            h = base + (l_i + dl) % g
            row += [h + n * k for k in range(count)]
        want_rows.append(row)
    want = np.asarray(want_rows, np.int32)
    rounds = steering.num_rounds(want.shape[1], ROUTE_BUDGET)

    source = "oracle"
    bi = steering.bidirectional_program(n)
    if jax.device_count() >= n:
        source = f"{n}-device ring"
        mesh = make_mesh((n,), ("data",))
        pool = jnp.zeros((n * ppn, 4), jnp.float32)
        rec = recorder if recorder is not None else TraceRecorder()
        with rec.span(f"transfer:hierarchical_{num_boards}x{board_size}",
                      scenario="hierarchical", boards=num_boards,
                      board_size=board_size) as sp:
            _, telem = bridge.pull_pages(
                pool, jnp.asarray(want), table, mesh=mesh,
                budget=ROUTE_BUDGET, topology=topo,
                collect_telemetry=True)
            rec.fence(telem)
        rec.annotate_telemetry(sp, telem, page_bytes=pool.shape[1] * 4)
    else:
        telem = ref.expected_transfer_telemetry(
            want, table, bi, num_nodes=n, budget=ROUTE_BUDGET, topology=topo)

    agg = TelemetryAggregator(n, page_bytes=ROUTE_PAGE_BYTES)
    agg.update(telem)
    slot_pages = agg.distance_pages() / (n * rounds)
    slot_intra = agg.distance_intra_pages() / (n * rounds)
    live = agg.live_distances()
    hier = cp.route_program(telemetry=agg)
    steering.validate_hierarchical(hier, topo)
    flat = steering.pruned_program(bi, live)
    kw = dict(slot_pages=slot_pages, topology=topo,
              slot_intra_pages=slot_intra)
    lat_flat = perfmodel.predict_round_latency_us(
        flat, ROUTE_PAGE_BYTES, ROUTE_BUDGET, **kw)
    lat_hier = perfmodel.predict_round_latency_us(
        hier, ROUTE_PAGE_BYTES, ROUTE_BUDGET, **kw)
    stats_h = perfmodel.hierarchical_route_stats(hier, topo)
    stats_f = perfmodel.hierarchical_route_stats(flat, topo)
    return {
        "source": source,
        "num_boards": num_boards,
        "board_size": board_size,
        "intra_pages": {str(d): c for d, c in INTRA_PAGES.items() if d < g},
        "bytes_per_round": perfmodel.predict_round_bytes(
            hier, ROUTE_PAGE_BYTES, ROUTE_BUDGET, slot_pages=slot_pages),
        "board_hops_flat": stats_f["board_hops"],
        "board_hops_hier": stats_h["board_hops"],
        "flat_bidirectional_us": round(lat_flat, 2),
        "hierarchical_us": round(lat_hier, 2),
    }


def calibration_section(samples: list, cp: ControlPlane,
                        agg: TelemetryAggregator) -> dict:
    """Fit the online perfmodel calibrator on the measured-scenario samples.

    Every wall-clock sample collected by the skewed / pipeline / tenancy
    scenarios is a ``(route-feature vector, measured us)`` pair; CAL_EPOCHS
    deterministic RLS passes fit the linearized analytic model's constants
    (per-tier hop RTTs, payload us/MiB, per-chunk and per-transfer
    overhead) to what this backend actually ran.  The record compares the
    static-prior prediction against the fitted one per sample and per
    scenario — ``validate_bench.py`` gates fitted <= static, i.e. the
    measure->fit->steer loop must beat the datasheet constants on its own
    training regime before anyone trusts it to steer.  The fitted
    calibrator then re-runs the control plane's pipeline-depth pick so the
    steering consequence (dispatch-dominated backend -> stay serial) is
    recorded next to the constants that caused it.
    """
    out: dict = {"source": "model-only",
                 "feature_names": list(perfmodel.FEATURE_NAMES),
                 "epochs": CAL_EPOCHS}
    if not samples:
        return out
    out["source"] = f"{ROUTE_NODES}-device ring"
    cal = perfmodel.Calibrator()
    for _ in range(CAL_EPOCHS):
        for s in samples:
            cal.observe(s["features"], s["measured_us"])
    rows_out = []
    per_scen: dict[str, list[tuple[float, float]]] = {}
    for s in samples:
        m = float(s["measured_us"])
        static_us = cal.static_predict_us(s["features"])
        fitted_us = cal.predict_us(s["features"])
        se = abs(static_us - m) / max(m, 1e-9)
        fe = abs(fitted_us - m) / max(m, 1e-9)
        rows_out.append({**s, "static_us": round(static_us, 1),
                         "fitted_us": round(fitted_us, 1),
                         "static_err": round(se, 4),
                         "fitted_err": round(fe, 4)})
        per_scen.setdefault(s["scenario"], []).append((se, fe))
    err = {scen: {"static": round(sum(e[0] for e in v) / len(v), 4),
                  "fitted": round(sum(e[1] for e in v) / len(v), 4)}
           for scen, v in sorted(per_scen.items())}
    flat = [e for v in per_scen.values() for e in v]
    err["overall"] = {
        "static": round(sum(e[0] for e in flat) / len(flat), 4),
        "fitted": round(sum(e[1] for e in flat) / len(flat), 4)}
    out["constants"] = cal.constants()
    out["samples"] = rows_out
    out["model_vs_measured_error"] = err
    out["selected_channels"] = {
        "static": {
            "wire_bound_256KiB": cp.select_channels(
                ROUTE_BUDGET, ROUTE_PAGE_BYTES, telemetry=agg),
            "latency_bound_4KiB": cp.select_channels(
                ROUTE_BUDGET, SMALL_PAGE_BYTES, telemetry=agg)},
        "calibrated": {
            "wire_bound_256KiB": cp.select_channels(
                ROUTE_BUDGET, ROUTE_PAGE_BYTES, telemetry=agg,
                calibrator=cal),
            "latency_bound_4KiB": cp.select_channels(
                ROUTE_BUDGET, SMALL_PAGE_BYTES, telemetry=agg,
                calibrator=cal)},
    }
    return out


def alerts_section() -> dict:
    """Sentinel drill: zero false positives clean, catches a 2x injection.

    Drives an orchestrated 8-ring through a clean phase whose measured
    round latencies are exactly the calibrator's own prediction (residuals
    ~0, ratios ~1 — any alert here is a false positive), then injects a
    sustained 2x latency regression and counts the samples until the
    sentinel's windowed-median detector fires.  ``validate_bench.py``
    gates clean_alerts == 0, regression_alerts >= 1 and detection within
    one window.  The orchestrator's debug bundle (flight journal +
    metrics + state) lands in ``BENCH_debug_bundle.zip``.
    """
    cp = ControlPlane(num_nodes=ROUTE_NODES, pages_per_node=16,
                      num_logical=ROUTE_NODES * 16)
    orc = Orchestrator(cp, budget=ROUTE_BUDGET, page_bytes=ROUTE_PAGE_BYTES,
                       control_period=4, migrate=False)
    orc.register(TenantSpec(0, "drill", qos="interactive"))
    orc.request_lease(0, ROUTE_NODES * 4)
    window = orc.sentinel.window
    clean_rounds = window + 8
    for _ in range(clean_rounds):
        feats = perfmodel.route_features(
            orc.route_program(), orc.page_bytes, orc.budget,
            channels=orc.channels)
        orc.step(measured_round_us=orc.calibrator.predict_us(feats))
    clean_alerts = len(orc.sentinel.alerts)
    detect_samples = 0
    for i in range(2 * window):
        feats = perfmodel.route_features(
            orc.route_program(), orc.page_bytes, orc.budget,
            channels=orc.channels)
        orc.step(measured_round_us=2.0 * orc.calibrator.predict_us(feats))
        if len(orc.sentinel.alerts) > clean_alerts:
            detect_samples = i + 1
            break
    orc.dump_debug_bundle(str(BUNDLE_ZIP))
    return {
        "source": f"{ROUTE_NODES}-node orchestrated drill",
        "window": window,
        "clean_rounds": clean_rounds,
        "clean_alerts": clean_alerts,
        "regression_alerts": len(orc.sentinel.alerts) - clean_alerts,
        "detect_samples": detect_samples,
        "alert_kinds": sorted({a.kind for a in orc.sentinel.alerts}),
    }


def rows(quick: bool = False) -> list[str]:
    out = []
    total = sum(perfmodel.RTT_PIPELINE_CYCLES.values())
    for stage, cyc in perfmodel.RTT_PIPELINE_CYCLES.items():
        ns = cyc / perfmodel.PAPER_HW.clock_mhz * 1e3
        out.append(f"rtt_stage_{stage.split('(')[0].strip().replace(' ', '_')},"
                   f"0,{cyc}cyc={ns:.0f}ns")
    out.append(f"rtt_total,0,{total}cyc={total/perfmodel.PAPER_HW.clock_mhz*1e3:.0f}ns"
               f" (paper: 134cyc=800ns)")

    us = measure_sw_pull_us(reps=5 if quick else 50)
    out.append(f"bridge_sw_pull_1page,{us:.1f},loopback_jitted")

    # modelled TPU pull-mode page latency (1 hop, 256 KiB page)
    lat_us = (2 * perfmodel.TPU_HW.ici_hop_latency_us
              + (1 << 18) / (perfmodel.TPU_HW.ici_link_gbps * 1e9) * 1e6)
    out.append(f"bridge_tpu_page_rtt_model,0,{lat_us:.1f}us_per_256KiB_page")
    bw = perfmodel.tpu_remote_page_bandwidth_gbps(1 << 18)
    out.append(f"bridge_tpu_pull_bandwidth_model,0,{bw:.1f}GB/s_per_pair")

    # route-program schedule variants (the software-defined circuit plane)
    bench: dict[str, dict] = {"sw_pull_1page_us": round(us, 2),
                              "num_nodes": ROUTE_NODES,
                              "page_bytes": ROUTE_PAGE_BYTES,
                              "budget": ROUTE_BUDGET, "variants": {}}
    # Every measured scenario below runs inside this recorder's fenced
    # spans (written to BENCH_trace.json) and feeds the calibration
    # samples the online perfmodel fit consumes at the end.
    recorder = TraceRecorder(process_name="bench:bridge_latency")
    cal_samples: list[dict] = []
    # the measured closed loop: skew -> telemetry -> load-balanced program
    measured, lb_prog, skew_agg, skew_cp = skewed_traffic_scenario(
        recorder=recorder, samples=cal_samples, quick=quick)
    variants = dict(route_variants())
    variants["load_balanced"] = lb_prog
    for name, prog in variants.items():
        stats = perfmodel.route_epoch_stats(prog)
        model_us = perfmodel.predict_round_latency_us(
            prog, ROUTE_PAGE_BYTES, ROUTE_BUDGET)
        model_us_nobuf = perfmodel.predict_round_latency_us(
            prog, ROUTE_PAGE_BYTES, ROUTE_BUDGET, edge_buffer=False)
        bytes_per_round = stats["live_slots"] * ROUTE_BUDGET * ROUTE_PAGE_BYTES
        out.append(
            f"bridge_route_{name},0,epochs={stats['num_epochs']}"
            f" slots={stats['live_slots']} hops={stats['total_hops']}"
            f" round_model={model_us:.0f}us")
        bench["variants"][name] = {
            "epochs": stats["num_epochs"],
            "live_slots": stats["live_slots"],
            "total_hops": stats["total_hops"],
            "bytes_per_round": bytes_per_round,
            "model_round_us": round(model_us, 2),
            "model_round_us_bufferless": round(model_us_nobuf, 2),
        }
    bench["measured"] = measured
    out.append(
        f"bridge_route_measured,0,source={measured['source']}"
        f" static_bi={measured['static_bidirectional_us']}us"
        f" load_balanced={measured['load_balanced_us']}us")
    # pipelined multi-channel round engine: depth sweep + control-plane pick
    pipe = pipeline_sweep(skew_agg, skew_cp, quick=quick,
                          recorder=recorder, samples=cal_samples)
    bench["pipeline"] = pipe
    sweep = " ".join(f"c{c}={pipe['model_round_us'][str(c)]}us"
                     for c in PIPELINE_CHANNELS)
    out.append(
        f"bridge_pipeline_sweep,0,source={pipe['source']} {sweep}"
        f" picks={pipe['selected_channels']}")
    # fused vs unfused epoch wall-clock (the Pallas datapath claim)
    fus = fused_section(quick=quick, recorder=recorder,
                        samples=cal_samples)
    bench["fused"] = fus
    FUSED_JSON.write_text(json.dumps(fus, indent=2) + "\n")
    if fus["page_sweep"]:
        cmp_str = " ".join(
            f"{label}:{e['fused_us']}us_vs_{e['unfused_us']}us"
            f"(x{e['speedup']})" for label, e in fus["page_sweep"].items())
        out.append(f"bridge_fused_epoch,0,source={fus['source']} {cmp_str}")
    else:
        out.append(f"bridge_fused_epoch,0,source={fus['source']}")
    # flat ring vs board + rack fabric (8 real endpoints, 16/32 simulated)
    bench["hierarchical"] = {}
    for label, (boards, size) in HIER_FABRICS.items():
        h = hierarchical_scenario(boards, size, recorder=recorder)
        bench["hierarchical"][label] = h
        out.append(
            f"bridge_hier_{label},0,{boards}x{size} source={h['source']}"
            f" flat_bi={h['flat_bidirectional_us']}us"
            f" hier={h['hierarchical_us']}us")
    # multi-tenant co-location: QoS windows vs naive FIFO sharing
    ten = tenancy_scenario(recorder=recorder, samples=cal_samples)
    bench["tenancy"] = ten
    out.append(
        f"bridge_tenancy,0,source={ten['source']}"
        f" solo={ten['interactive_solo_us']}us"
        f" qos={ten['interactive_qos_us']}us"
        f" (x{ten['qos_isolation_ratio']})"
        f" naive={ten['interactive_naive_us']}us"
        f" (x{ten['naive_degradation_ratio']})")
    # online calibration: fit the perfmodel constants to what actually ran
    cal = calibration_section(cal_samples, skew_cp, skew_agg)
    bench["calibration"] = cal
    if "model_vs_measured_error" in cal:
        e = cal["model_vs_measured_error"]["overall"]
        out.append(
            f"bridge_calibration,0,source={cal['source']}"
            f" samples={len(cal['samples'])}"
            f" err_static={e['static']} err_fitted={e['fitted']}"
            f" picks={cal['selected_channels']['calibrated']}")
    else:
        out.append(f"bridge_calibration,0,source={cal['source']}")
    # sentinel drill: clean run stays silent, injected 2x regression caught
    al = alerts_section()
    bench["alerts"] = al
    out.append(
        f"bridge_alerts,0,source={al['source']}"
        f" clean={al['clean_alerts']} regression={al['regression_alerts']}"
        f" detect_samples={al['detect_samples']}/{al['window']}"
        f" kinds={','.join(al['alert_kinds'])}")
    out.append(f"bridge_debug_bundle,0,{BUNDLE_ZIP.name}")
    BENCH_JSON.write_text(json.dumps(bench, indent=2) + "\n")
    out.append(f"bridge_route_json,0,{BENCH_JSON.name}")
    recorder.write(str(TRACE_JSON))
    out.append(f"bridge_trace,0,{TRACE_JSON.name}"
               f" spans={len(recorder.spans)} (https://ui.perfetto.dev)")
    return out


def run(quick: bool = False) -> list[str]:
    return rows(quick=quick)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer timing reps (CI smoke job)")
    for r in run(quick=ap.parse_args().quick):
        print(r)
