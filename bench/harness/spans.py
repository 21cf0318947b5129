"""The program's serve-loop spans, read beside the harness's own.

A ``repro.obs.TraceRecorder`` handed to the batcher, the orchestrator and
the engine records the serve loop from inside the program (the tree is in
``repro.obs.trace``).  Its spans are read two ways:

* in memory, for the whole window, on the batcher's clock (the harness's
  ``WallClock``: ``perf_counter`` microseconds): :func:`span_table`,
  :func:`durations_ms`, :func:`longest`;
* with ``profile=True``, as host events of the profiler's trace, on the
  device's clock: :func:`program_events` reads them from the
  ``.xplane.pb``, :func:`with_program_spans` lets ``trace.reduce`` name
  each idle gap of the device by the innermost program span open in its
  middle, and :func:`launch_return` times the decode step's launch and
  return against the device's ``serve_step`` modules.

Events are ``[name, start_ns, duration_ns]``, as in ``harness.trace``.
"""
from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Sequence

from repro.obs.trace import ENGINE, PREFIXES

from harness.stats import percentile
from harness.trace import STEP_MODULE

DISPATCH = ENGINE + "dispatch"
FETCH = ENGINE + "fetch"


def program_events(path: str) -> List[list]:
    """The program's spans among the host events of one trace file."""
    from jax.profiler import ProfileData
    out: List[list] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        out.append([e.name, e.start_ns, e.duration_ns])
    return out


def with_program_spans(events: Dict[str, Any], program: List[list]
                       ) -> Dict[str, Any]:
    """``events`` whose host list also holds the program's spans.

    ``trace.reduce`` takes its window, busy time and steps from the
    ``bench:`` events alone, so only the names of the idle gaps change."""
    return dict(events, host=list(events["host"]) + list(program))


def _mean(v: Sequence[float]) -> Optional[float]:
    return sum(v) / len(v) if v else None


def _nearest(xs: List[float], t: float, reach: float) -> Optional[float]:
    """The element of sorted ``xs`` nearest to ``t``, if within ``reach``."""
    i = bisect.bisect_left(xs, t)
    near = min(xs[max(i - 1, 0):i + 1], key=lambda x: abs(x - t),
               default=None)
    return near if near is not None and abs(near - t) <= reach else None


def launch_return(program: List[list], modules: Dict[str, List[list]]
                  ) -> Dict[str, Optional[float]]:
    """Mean milliseconds from an ``engine.dispatch`` starting to the
    device starting the ``serve_step`` it issued (``step_launch_ms``), and
    from that ``serve_step`` ending on the device to the end of the
    ``engine.fetch`` that waited on it (``step_return_ms``); their sum per
    step (``launch_return_ms``) is the host's dispatch-to-tokens time less
    the device's.  Partners are matched as the nearest within half a step:
    the profiler aligns the device's clock with the host's only to about
    a millisecond, so a module can read as starting before the dispatch
    that issued it, which skews launch and return by the same amount in
    opposite directions and leaves their sum.  A step whose partner lies
    beyond the edge of the trace is skipped.  First device only, as
    ``trace.reduce`` reads the steps."""
    mods = sorted((m[1], m[1] + m[2])
                  for m in (modules[min(modules)] if modules else [])
                  if STEP_MODULE in m[0])
    starts = [s for s, _ in mods]
    gaps = sorted(b - a for a, b in zip(starts, starts[1:]))
    reach = gaps[len(gaps) // 2] / 2 if gaps else float("inf")
    fetch_ends = sorted(e[1] + e[2] for e in program if e[0] == FETCH)
    end_of = dict(mods)
    launch, ret, both = [], [], []
    for t in sorted(e[1] for e in program if e[0] == DISPATCH):
        s = _nearest(starts, t, reach)
        if s is None:
            continue
        launch.append(s - t)
        f = _nearest(fetch_ends, end_of[s], reach)
        if f is not None:
            both.append(s - t + f - end_of[s])
    for _, end in mods:
        f = _nearest(fetch_ends, end, reach)
        if f is not None:
            ret.append(f - end)
    ms = lambda v: None if v is None else v / 1e6  # noqa: E731
    return {"step_launch_ms": ms(_mean(launch)),
            "step_return_ms": ms(_mean(ret)),
            "launch_return_ms": ms(_mean(both)),
            "launches": len(launch), "returns": len(ret)}


def _inside(spans, lo_us: float, hi_us: float):
    return [s for s in spans if s.end_us is not None
            and lo_us <= s.start_us and s.end_us <= hi_us]


def durations_ms(spans, name: str, lo_us: float, hi_us: float
                 ) -> List[float]:
    """Durations of the spans named ``name`` inside ``[lo_us, hi_us]``."""
    return [s.duration_us / 1e3 for s in _inside(spans, lo_us, hi_us)
            if s.name == name]


def _covered(intervals: List[Sequence[float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def span_table(spans, lo_us: float, hi_us: float
               ) -> Dict[str, Dict[str, float]]:
    """Per program span name inside ``[lo_us, hi_us]``: count, total,
    p50, p95 and self time (a span's duration less the union of its
    children's), in milliseconds."""
    kids: Dict[int, List[Sequence[float]]] = {}
    for s in spans:
        if s.parent_id is not None and s.end_us is not None:
            kids.setdefault(s.parent_id, []).append((s.start_us, s.end_us))
    rows: Dict[str, List[List[float]]] = {}
    for s in _inside(spans, lo_us, hi_us):
        if s.name.startswith(PREFIXES):
            own = s.duration_us - _covered(kids.get(s.span_id, []))
            rows.setdefault(s.name, []).append([s.duration_us, own])
    table = {}
    for name, v in rows.items():
        d = [x[0] / 1e3 for x in v]
        table[name] = {"count": len(v), "total_ms": sum(d),
                       "p50_ms": percentile(d, 50),
                       "p95_ms": percentile(d, 95),
                       "self_ms": sum(x[1] for x in v) / 1e3}
    return table


def longest(spans, name: str, lo_us: float, hi_us: float
            ) -> Optional[Dict[str, Any]]:
    """The longest span named ``name`` inside the window, with the
    durations of its children, in milliseconds."""
    inside = [s for s in _inside(spans, lo_us, hi_us) if s.name == name]
    if not inside:
        return None
    top = max(inside, key=lambda s: s.duration_us)
    return {"name": name, "at_us": top.start_us,
            "ms": top.duration_us / 1e3,
            "children": [[c.name, c.duration_us / 1e3] for c in spans
                         if c.parent_id == top.span_id]}
