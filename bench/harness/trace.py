"""From a profiler trace to per-layer numbers.

:func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into plain events; :func:`reduce` turns those events into the traced
window, the device's busy time, per-step program and KV-path times, the
heaviest device operations and the longest idle gaps, each gap named by
the harness span (``bench:<call>``) open on the host in its middle.

Events are ``[name, start_ns, duration_ns]``.  On a TPU the ``XLA Ops``
line names each event by its HLO text (``%fusion.12 = ...``); the name
kept is the instruction's (``fusion.12``, ``bridge_gather.7``).  The KV
path is the ``bridge_*`` kernels alone: the TPU trace of jax 0.9 carries
no framework scope, so the datapath's plain XLA ops (the scan's slices and
copies of the stacked page pools) cannot be told from the model's and are
not counted in it.  Control-flow ops (``while``, ``conditional``,
``call``) contain the ops of their bodies and are left out of busy time
and of the heaviest operations.
"""
from __future__ import annotations

import glob
import re
from typing import Any, Dict, List, Optional, Sequence

HOST_PREFIX = "bench:"
STEP_MODULE = "serve_step"
KV_KERNEL_PREFIX = "bridge_"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


_HLO_NAME = re.compile(r"^%([^ ]+) = ")
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def load_xplane(path: str, device_prefix: str = "/device:TPU:"
                ) -> Dict[str, Any]:
    """``{"ops": {device: [...]}, "modules": {device: [...]},
    "host": [...]}`` from one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[list]] = {}
    modules: Dict[str, List[list]] = {}
    host: List[list] = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(device_prefix) and name[len(device_prefix):] \
                .isdigit():
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[name] = [[op_name(e.name), e.start_ns,
                                  e.duration_ns] for e in line.events]
                elif line.name == "XLA Modules":
                    modules[name] = [[e.name, e.start_ns, e.duration_ns]
                                     for e in line.events]
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, e.start_ns, e.duration_ns])
    return {"ops": ops, "modules": modules, "host": host}


def union_ns(intervals: Sequence[Sequence[float]], lo: float, hi: float
             ) -> List[List[float]]:
    """Merged ``[start, end]`` intervals clipped to ``[lo, hi]``."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def base_name(op: str) -> str:
    """``fusion.123`` -> ``fusion``; ``bridge_gather.7`` -> ``bridge_gather``."""
    return re.sub(r"[.:]\d+$", "", op)


def is_container(name: str) -> bool:
    return base_name(name).lower() in CONTAINERS


def is_kv_op(name: str) -> bool:
    return name.startswith(KV_KERNEL_PREFIX)


def _span_at(host: List[list], t: float) -> str:
    """The innermost harness span open at ``t`` (the latest started)."""
    best: Optional[list] = None
    for h in host:
        if h[1] <= t <= h[1] + h[2] and (best is None or h[1] > best[1]):
            best = h
    return best[0] if best is not None else "host:outside_harness_spans"


def reduce(events: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """Per-layer numbers of the traced window, or None where the trace
    holds no complete loop iteration or no device operation."""
    host = sorted(events["host"], key=lambda h: h[1])
    starts = [h[1] for h in host if h[0] == HOST_PREFIX + "submit"]
    ends = [h[1] + h[2] for h in host if h[0] == HOST_PREFIX + "observe"]
    if not starts or not ends:
        return None
    lo, hi = starts[0], ends[-1]
    # The device's trace can begin later or end earlier than the host's
    # (a profiler that arms late): the window is then the loop iterations
    # the device trace covers, from the one whose step is the first
    # recorded to the one whose step is the last.
    steps = sorted(m[1:3] for ms in events["modules"].values() for m in ms
                   if STEP_MODULE in m[0] and lo <= m[1] <= hi)
    if steps:
        lo = max([s for s in starts if s <= steps[0][0]], default=lo)
        hi = min([e for e in ends if e >= sum(steps[-1])], default=hi)
    if hi <= lo:
        return None
    steps_host = sum(1 for h in host if h[0] == HOST_PREFIX + "engine_step"
                     and lo <= h[1] and h[1] + h[2] <= hi)
    per_dev = []
    for dev, ops in sorted(events["ops"].items()):
        inside = [o for o in ops if o[1] < hi and o[1] + o[2] > lo
                  and not is_container(o[0])]
        if not inside:
            continue
        busy = union_ns([(o[1], o[1] + o[2]) for o in inside], lo, hi)
        mods = [m for m in events["modules"].get(dev, [])
                if STEP_MODULE in m[0] and lo <= m[1] and m[1] + m[2] <= hi]
        kv = union_ns([(o[1], o[1] + o[2]) for o in inside
                       if is_kv_op(o[0])], lo, hi)
        per_dev.append({"dev": dev, "ops": inside, "busy": busy,
                        "modules": mods, "kv_ns": sum(e - s for s, e in kv)})
    if not per_dev:
        return None
    window_ns = hi - lo
    busy_ns = sum(sum(e - s for s, e in d["busy"]) for d in per_dev) \
        / len(per_dev)
    d0 = per_dev[0]
    n_mod = len(d0["modules"])
    totals: Dict[str, float] = {}
    for o in d0["ops"]:
        k = base_name(o[0])
        totals[k] = totals.get(k, 0.0) + o[2]
    ops_top = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    prev = lo
    for s, e in d0["busy"] + [[hi, hi]]:
        if s > prev:
            gaps.append((s - prev, (s + prev) / 2))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[0])
    idle_top = [[_span_at(host, mid), g / 1e9] for g, mid in gaps[:top]]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "steps": n_mod,
        "host_steps": steps_host,
        "step_s": (sum(m[2] for m in d0["modules"]) / n_mod / 1e9
                   if n_mod else None),
        "kv_path_s": (d0["kv_ns"] / n_mod / 1e9 if n_mod else None),
        "device_ops": [[k, v / 1e9] for k, v in ops_top],
        "idle_gaps": idle_top,
    }
