"""repro.obs — tracing and metrics plane over the in-band telemetry.

Three layers, host-side only (nothing here runs under jit):

- ``clock``: injectable monotonic clocks (wall for production, manual for
  deterministic tests).
- ``trace``: ``TraceRecorder`` keeps a tree of host spans and exports
  Chrome-trace/Perfetto JSON.  The serve loop records into it from
  inside the program (``serve.control`` > ``orc.step`` > ``orc.refit`` >
  ``cp.route_program``, ``engine.step`` > ``engine.dispatch`` /
  ``engine.fetch``, ``req.queued``, ...) when its batcher, orchestrator
  and engine are given ``recorder=``; ``recorder=None``, the default,
  is the off switch.  ``profile=True`` mirrors each span into the
  ``jax.profiler`` trace, on the device's clock.  Fenced spans
  decorated with ``BridgeTelemetry`` counters time datapath calls.
- ``metrics``: counter/gauge/log-bucketed-histogram registry with
  per-tenant / per-QoS / per-tier families fed by ``TelemetryAggregator``
  and spans, plus an SLO burn-rate monitor.
- ``flight``: the decision plane — ``FlightRecorder`` journals every
  control-plane action as a typed ``DecisionRecord`` (JSONL in/out) and
  ``replay()`` re-executes a journal bit-identically against a fresh
  control plane; ``why(request_id)`` walks the causal chain behind one
  serving request.
- ``detect``: the ``Sentinel`` — online latency-shift / calibration-drift
  / SLO-burn / telemetry-conservation detectors emitting ``Alert``
  records into the journal and ``obs_alerts_total`` counters.

The measured span latencies feed ``repro.core.perfmodel.Calibrator`` so
control-plane decisions run on fitted, not guessed, constants.
"""

from repro.obs.clock import Clock, ManualClock, MonotonicClock
from repro.obs.detect import Alert, Sentinel
from repro.obs.flight import (
    DecisionRecord,
    FlightRecorder,
    JournalError,
    JournalTruncatedError,
    ReplayDivergenceError,
    ReplayResult,
    program_digest,
    replay,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    SLOMonitor,
)
from repro.obs.trace import Span, TraceRecorder, phase_op_counts

__all__ = [
    "Alert",
    "Clock",
    "DecisionRecord",
    "FlightRecorder",
    "JournalError",
    "JournalTruncatedError",
    "ManualClock",
    "MonotonicClock",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "ReplayDivergenceError",
    "ReplayResult",
    "SLOMonitor",
    "Sentinel",
    "Span",
    "TraceRecorder",
    "phase_op_counts",
    "program_digest",
    "replay",
]
