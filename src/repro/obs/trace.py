"""Host-side spans: the serve loop, the control plane, datapath calls.

A :class:`TraceRecorder` keeps a tree of wall-clock spans in memory.
Spans nest (the recorder keeps an open stack), carry free-form args, and
export as Chrome-trace JSON (``{"traceEvents": [...]}``, ``ph="X"``
complete events) for https://ui.perfetto.dev or ``chrome://tracing``.
The clock is injectable (:mod:`repro.obs.clock`): the serve loop passes
its batcher's clock, so spans line up with the loop's own timestamps, and
with a ``ManualClock`` a trace is reproducible byte for byte.

**The serve loop.**  ``ContinuousBatcher``, ``Orchestrator`` and
``ModelDecodeEngine`` take ``recorder=``; the control plane records into
the recorder of its flight journal (``FlightRecorder.trace``, which the
orchestrator sets).  Names are ``<layer>.<phase>``, the layers being the
prefixes below::

    serve.control                  one ContinuousBatcher.control tick,
                                   args queue_depth, slots_active, ...
      orc.step
        orc.refit                  the control-period block
          cp.route_program
            cp.verify              the static program check
            cp.journal             program_to_dict / program_digest
      orc.refit_windows
      serve.admit
        orc.request_lease          one per admission attempt
    serve.step_inputs
    engine.step
      engine.reset                 only when slots reset
      engine.dispatch              the jitted serve step, issued
      engine.fetch                 its tokens copied to the host
    serve.observe
      serve.retire                 one per retired sequence
    req.queued                     arrival to admission, one per request
    req<id>                        arrival to retirement

Journal records made inside a span carry its id (``span_id``).

**Off is free.**  ``recorder=None`` (the default everywhere) turns the
serve-loop spans off: each site then costs an attribute check and the
shared no-op context :data:`NULL_SPAN` (:func:`maybe_span`).

**The profiler's clock.**  ``TraceRecorder(profile=True)`` also opens a
``jax.profiler.TraceAnnotation`` of the same name for every span, so
while ``jax.profiler`` runs, each span lands on the trace's host plane,
on the clock of the device's ``XLA Ops``/``XLA Modules`` lines.  A span
recorded after the fact (:meth:`TraceRecorder.record_span`) can only mark
the moment it was recorded there: its duration is in the recorder.

Spans never fence by themselves: ``fence=`` blocks on a pytree of
async-dispatched results before the span closes, for callers that time
device work from the host (``benchmarks/bridge_latency.py``).
:func:`phase_op_counts` attributes the work *inside* one jitted call by
its ``jax.named_scope("obs:…")`` phases, from HLO text.
"""

from __future__ import annotations

import json
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from repro.obs.clock import Clock, MonotonicClock

#: Span categories.  Free-form: conventions, not an enum the recorder
#: enforces.
CAT_TRANSFER = "transfer"   # one pull/push transaction (all rounds)
CAT_ROUND = "round"         # one bridge round
CAT_CHUNK = "chunk"         # one channel chunk within a round
CAT_PHASE = "phase"         # wire_req / gather / wire_data / commit
CAT_COMPILE = "compile"     # trace/lower/compile of a jitted cell
CAT_CONTROL = "control"     # serve.*, orc.*, cp.*: the host's serve tick
CAT_STEP = "step"           # engine.*: one decode step, host side
CAT_REQUEST = "request"     # req.*: one serving request's lifecycle

#: The serve-loop span layers (module docstring); a span is named
#: ``<prefix><phase>``.
SERVE = "serve."            # ContinuousBatcher
ORC = "orc."                # Orchestrator
CP = "cp."                  # ControlPlane
ENGINE = "engine."          # ModelDecodeEngine
REQ = "req."                # one request
PREFIXES = (SERVE, ORC, CP, ENGINE, REQ)

#: What an instrumented site enters when its recorder is None.
NULL_SPAN = nullcontext()


def maybe_span(recorder: Optional["TraceRecorder"], name: str,
               cat: str = CAT_CONTROL):
    """``recorder.span(name, cat)``, or :data:`NULL_SPAN` (which yields
    None) where ``recorder`` is None."""
    return NULL_SPAN if recorder is None else recorder.span(name, cat)


@dataclass
class Span:
    """One closed-interval trace span (microsecond timestamps)."""

    span_id: int
    parent_id: Optional[int]
    name: str
    cat: str
    start_us: float
    end_us: Optional[float] = None
    args: Dict[str, Any] = field(default_factory=dict)

    @property
    def duration_us(self) -> float:
        return 0.0 if self.end_us is None else self.end_us - self.start_us


def _jsonable(v):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.ndarray):
        return v.tolist()
    return v


def _args(attrs: Dict[str, Any]) -> Dict[str, Any]:
    return {k: _jsonable(v) for k, v in attrs.items()} if attrs else {}


class TraceRecorder:
    """Collects a span tree and exports Chrome-trace/Perfetto JSON.

    ``profile=True`` mirrors every span into the ``jax.profiler`` trace
    as a ``TraceAnnotation`` of the same name (module docstring)."""

    def __init__(self, clock: Optional[Clock] = None, *, pid: int = 0,
                 process_name: str = "repro-bridge", profile: bool = False):
        self.clock = clock if clock is not None else MonotonicClock()
        self.pid = pid
        self.process_name = process_name
        self.spans: List[Span] = []
        self._stack: List[Span] = []
        self._next_id = 0
        self._annotation = None
        if profile:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation

    # ---------------------------------------------------------------- spans
    @contextmanager
    def span(self, name: str, cat: str = CAT_TRANSFER, *, fence=None,
             **attrs) -> Iterator[Span]:
        """Open a span around a block; ``fence=`` pytrees are blocked on
        before the span closes so async-dispatched device work is inside."""
        ann = None
        if self._annotation is not None:
            ann = self._annotation(name)
            ann.__enter__()
        s = Span(span_id=self._next_id,
                 parent_id=self._stack[-1].span_id if self._stack else None,
                 name=name, cat=cat, start_us=self.clock.now_us(),
                 args=_args(attrs))
        self._next_id += 1
        self.spans.append(s)
        self._stack.append(s)
        try:
            yield s
        finally:
            if fence is not None:
                self.fence(fence)
            self._stack.pop()
            s.end_us = self.clock.now_us()
            if ann is not None:
                ann.__exit__(None, None, None)

    def record_span(self, name: str, cat: str = CAT_REQUEST, *,
                    start_us: float, end_us: float, **attrs) -> Span:
        """Append a closed span with explicit timestamps.

        For lifecycle spans whose start predates the call — e.g. a serving
        request recorded at retirement, whose arrival timestamp was taken
        steps ago — where the context-manager protocol cannot apply.  The
        span is top-level (no parent inferred from the open stack).  With
        ``profile=True`` the profiler gets a zero-length event of the
        name, at the time of the call.
        """
        if self._annotation is not None:
            with self._annotation(name):
                pass
        s = Span(span_id=self._next_id, parent_id=None, name=name, cat=cat,
                 start_us=float(start_us), end_us=float(end_us),
                 args=_args(attrs))
        self._next_id += 1
        self.spans.append(s)
        return s

    @staticmethod
    def fence(tree) -> None:
        """Block until every array in ``tree`` is ready (async barrier)."""
        import jax

        jax.block_until_ready(tree)

    def annotate(self, span: Span, **attrs) -> None:
        span.args.update(_args(attrs))

    def annotate_telemetry(self, span: Span, telem, *, page_bytes: int = 0,
                           tenant_names: Optional[Dict[int, str]] = None
                           ) -> None:
        """Decorate ``span`` with the BridgeTelemetry counters it fenced.

        ``telem`` leaves may carry a leading requester axis (the N-device
        path returns [N, ...]); counts are summed over it so the span
        describes the whole transaction.  All values are exact integers —
        tests reconcile them bit-exactly against the oracle.
        """
        a = lambda x: np.asarray(x)  # noqa: E731
        served = int(a(telem.served_total()).sum())
        loop = int(a(telem.loopback_served).sum())
        cw, ccw = telem.wire_pages()
        cw, ccw = int(a(cw).sum()), int(a(ccw).sum())
        intra, inter = telem.tier_pages()
        tier_hops = a(telem.tier_hops).reshape(-1, 2).sum(0)
        args: Dict[str, Any] = {
            "pages_served": served,
            "pages_loopback": loop,
            "pages_spilled": int(a(telem.spilled).sum()),
            "pages_pruned": int(a(telem.pruned).sum()),
            "wire_pages_cw": cw,
            "wire_pages_ccw": ccw,
            "pages_intra_board": int(a(intra).sum()),
            "pages_inter_board": int(a(inter).sum()),
            "board_hop_pages": int(tier_hops[0]),
            "rack_hop_pages": int(tier_hops[1]),
        }
        if page_bytes:
            args["bytes_served"] = served * page_bytes
            args["wire_bytes"] = (cw + ccw) * page_bytes
        tser = a(telem.tenant_served).reshape(-1, telem.max_tenants).sum(0)
        tspill = a(telem.tenant_spilled).reshape(-1, telem.max_tenants).sum(0)
        names = tenant_names or {}
        args["tenant_pages"] = {
            str(names.get(t, t)): int(tser[t])
            for t in range(telem.max_tenants) if tser[t] or tspill[t]}
        span.args.update(args)

    # -------------------------------------------------------------- queries
    def find(self, name: str) -> Optional[Span]:
        """Most recent span with this name (None if absent)."""
        for s in reversed(self.spans):
            if s.name == name:
                return s
        return None

    def find_all(self, name: Optional[str] = None,
                 cat: Optional[str] = None) -> List[Span]:
        return [s for s in self.spans
                if (name is None or s.name == name)
                and (cat is None or s.cat == cat)]

    def children(self, span: Span) -> List[Span]:
        return [s for s in self.spans if s.parent_id == span.span_id]

    def clear(self) -> None:
        self.spans = []
        self._stack = []
        self._next_id = 0

    # --------------------------------------------------------------- export
    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome-trace dict: ``M`` metadata + one ``X`` event per span."""
        events: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
            "args": {"name": self.process_name},
        }]
        for s in self.spans:
            args = dict(s.args, span_id=s.span_id, parent_id=s.parent_id)
            if s.end_us is None:
                # Auto-close still-open spans at export time so they show
                # up in the trace (flagged, not silently dropped).  The
                # span itself stays open — export must not mutate it.
                dur = max(self.clock.now_us() - s.start_us, 0.0)
                args["unclosed"] = True
            else:
                dur = s.duration_us
            events.append({
                "name": s.name, "cat": s.cat, "ph": "X",
                "ts": round(s.start_us, 3),
                "dur": round(dur, 3),
                "pid": self.pid, "tid": 0,
                "args": args,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"recorder": self.process_name}}

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_chrome_trace(), sort_keys=True,
                          indent=indent)

    def write(self, path: str, indent: Optional[int] = 1) -> None:
        with open(path, "w") as f:
            f.write(self.to_json(indent=indent))
            f.write("\n")


def phase_op_counts(hlo_text: str) -> Dict[str, int]:
    """Count HLO instructions per ``obs:<phase>`` named scope.

    The datapath wraps its phases in ``jax.named_scope("obs:wire_req")``
    etc.; after lowering, each HLO instruction's metadata ``op_name``
    carries the scope path.  Counting instructions per phase shows where
    a program variant or pipeline depth pays its dispatch cost — the
    in-jit complement of host-side spans (XLA may rewrite ``:`` to ``_``
    in scope names, so both spellings are matched).

    Thin wrapper over the shared HLO parser's
    :func:`repro.analysis.hlo.scope_op_counts` — the jaxpr auditor's
    collective budgets count the same ops this reports.
    """
    from repro.analysis.hlo import scope_op_counts

    return scope_op_counts(hlo_text, prefix="obs")
