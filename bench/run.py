#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload granite-3-8b.chat --seed 7 \
        --seconds 45 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``).  The run makes
the weights on the device from ``--seed``, builds the bridge-backed server,
warms up every program the window uses (all of this is ``setup_s``), then
offers the mix open loop for ``--seconds`` of wall time.  Afterwards it
reads the device's peak memory, frees the server and compares a sample of
the served requests with the configuration's plain reference
(``correct``).  ``--trace 1`` profiles a few seconds of the window and
reports the per-layer metrics instead of the end-to-end ones.

It runs in one process, starts none, and exits non-zero without printing
a result where JAX finds no TPU or fewer chips than the cell asks for.
The last line of standard output is one JSON object; the numbers compared
for ``correct`` are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Any, Callable, Dict, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from harness import spec  # noqa: E402

# The persistent compilation cache lives at a fixed path in the checkout:
# the path is part of every entry's key.
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
# Traced runs profile the last seconds of the window; the profiler stops at
# its close, so writing the trace stalls no request that is due in it.
TRACE_SECONDS = 2.0
# Requests due in the window are followed to their first token for at
# most this long after it closes.
FOLLOW_LIMIT_S = 120.0


class NoChip(SystemExit):
    pass


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); no result")
        if len(devs) < chips:
            raise NoChip(f"bench: the cell asks for {chips} chips, JAX "
                         f"sees {len(devs)}; no result")
    return devs[:chips]


def resolve(cell: spec.Cell) -> Dict[str, Any]:
    """The configuration file plus what the harness derives from it.
    Whatever the program cannot build is refused here, before any weight
    is made, by a ``plan.PlanError`` that names the file's key."""
    from harness import plan, serve
    from repro.models import transformer
    cfg = dict(cell.config)
    arch = cell.reference().arch(cfg)
    cfg["glu"] = arch["glu"]
    layers = plan.layer_plan(cfg)
    model = serve.program_config(cfg)
    cfg["padded_vocab"] = model.padded_vocab
    want = {"rms": "rmsnorm", "layer": "layernorm"}[arch["norm"]]
    if model.norm != want or model.glu != arch["glu"]:
        raise ValueError(f"{cfg['deployment']['registry']}: the program's "
                         f"blocks differ from the reference's {arch}")
    check_plan(model, layers)
    cfg["_program_params"] = transformer.abstract_params(model)
    return cfg


def check_plan(model, layers) -> None:
    """The program's layers, one by one, are the plan's."""
    from repro.config import FULL_ATTN, GLOBAL_ATTN, SWA_ATTN

    from harness import plan
    if len(model.layers) != len(layers):
        raise ValueError(f"the program has {len(model.layers)} layers, the "
                         f"plan {len(layers)}")
    for i, (kind, want) in enumerate(zip(model.layers, layers)):
        a, f = want.attn, want.ffn
        if a.kind == plan.WINDOW:
            attn_ok = kind == SWA_ATTN and model.window_size == a.window
        else:
            attn_ok = kind in (FULL_ATTN, GLOBAL_ATTN)
        attn_ok = attn_ok and (model.num_heads, model.num_kv_heads,
                               model.head_dim) == (a.heads, a.kv_heads,
                                                   a.head_dim)
        if f.kind == plan.ROUTED:
            ffn_ok = model.is_moe and (
                model.num_experts, model.experts_per_token, model.d_ff) == (
                f.experts_held, f.experts_per_token, f.width)
        else:
            ffn_ok = not model.is_moe and model.d_ff == f.width
        if not (attn_ok and ffn_ok):
            raise ValueError(
                f"layer {i}: the program builds {kind} attention "
                f"(window {model.window_size}) and "
                f"{'routed' if model.is_moe else 'dense'} d_ff {model.d_ff}; "
                f"the plan says {want}")


def check_layout(cfg: Dict[str, Any]) -> None:
    """The harness's weight tree must be what the program's step reads."""
    import jax
    from harness import weights
    ours = weights.abstract_weights(cfg)
    theirs = cfg["_program_params"]
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), ours)
    exp = jax.tree.map(lambda a: (a.shape, str(a.dtype)), theirs)
    if got != exp:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"ours {got}\nprogram {exp}")


class Tracer:
    """Starts and stops the profiler at loop-iteration boundaries."""

    def __init__(self, start_s: float, stop_s: float, directory: str):
        self.start_s, self.stop_s = start_s, stop_s
        self.dir = directory
        self.state = "before"
        self.iter_range = [None, None]

    def __call__(self, t: float, n: int) -> None:
        import jax
        if self.state == "before" and t >= self.start_s:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.state, self.iter_range[0] = "on", n
        elif self.state == "on" and t >= self.stop_s:
            jax.profiler.stop_trace()
            self.state, self.iter_range[1] = "done", n

    def finish(self, n: int) -> None:
        import jax
        if self.state == "on":
            jax.profiler.stop_trace()
            self.state, self.iter_range[1] = "done", n


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             require_chip: bool = True,
             engine_hook: Optional[Callable[[Any], None]] = None,
             control: bool = False,
             t_start: Optional[float] = None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object.

    ``require_chip=False`` skips the look for a chip (the CPU tests);
    ``engine_hook`` is called with the engine before the window (the tests
    break the timed path there); ``control=True`` also reads the int8
    control's gaps on the same sample (``bench/calibrate.py``).
    """
    t_start = T_START if t_start is None else t_start
    if require_chip:
        enable_cache()
    devs = devices(cell.chips, require_chip)
    import jax
    from harness import check, roofline, serve, traffic, weights

    kind = devs[0].device_kind
    peaks = roofline.peaks(kind) if require_chip else None
    cfg = resolve(cell)
    check_layout(cfg)
    dep = cfg["deployment"]
    params = weights.make_weights(cfg, seed)
    jax.block_until_ready(params)
    batcher, engine, ids = serve.build_server(cfg, cell.traffic, params, seed)
    serve.warm_up(engine)
    arrivals = traffic.generate(cell.traffic, seed=seed, seconds=seconds,
                                vocab=int(cfg["vocab_size"]),
                                max_len=int(dep["max_len"]))
    if engine_hook is not None:
        engine_hook(engine)
    setup_s = time.perf_counter() - t_start
    say(f"bench: {cell.name} seed {seed}: set-up {setup_s:.3f} s on "
        f"{kind} x{len(devs)}; {len(arrivals)} requests for a "
        f"{seconds:g} s window")

    tracer = None
    if trace:
        tracer = Tracer(seconds - min(TRACE_SECONDS, seconds / 2), seconds,
                        str(TRACE_DIR))
    follow = cell.traffic.get("follow", "none")
    window = serve.drive(batcher, engine, arrivals, ids, seconds=seconds,
                         follow=follow,
                         follow_limit_s=FOLLOW_LIMIT_S, on_iteration=tracer)
    if tracer is not None:
        tracer.finish(len(window.iters))
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)
    say(f"bench: window {window.end_s:.3f} s, {len(window.iters)} steps, "
        f"{len(window.records)} requests due, {window.compiles} compiles "
        f"in the window; peak device memory {mem_peak} bytes")

    # Free the server before the reference runs: the peak is read.
    engine.state = None
    engine.params = None
    del engine
    gc.collect()
    ref = cell.reference()
    cmp = check.compare(ref, cfg, weights.neutral(params), window.records,
                        seed=seed, requests=int(cell.check["requests"]),
                        control=control)
    del params
    gc.collect()

    traced = None
    if tracer is not None and tracer.state == "done":
        from harness import trace as trace_mod
        ev = trace_mod.load_xplane(trace_mod.find_xplane(str(TRACE_DIR)))
        traced = trace_mod.reduce(ev)
        if traced is not None:
            i0, i1 = tracer.iter_range
            traced["iters"] = window.iters[i0:i1]
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    ctx = SimpleNamespace(cfg=cfg, mix=cell.traffic, window=window,
                          seconds=seconds, setup_s=setup_s, trace=traced,
                          peaks=peaks, memory_peak_bytes=mem_peak,
                          roofline=roofline)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks, correct = check.decide(cell.check, cmp)
    shed = sum(1 for r in window.records if r.shed)
    lost = sum(1 for r in window.records if not r.shed and not r.token_s) \
        if follow != "none" else 0
    device = {"platform": devs[0].platform, "kind": kind,
              "device_kind": kind, "count": len(devs),
              "memory_peak_bytes": mem_peak}
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": len(window.records),
        "failed": shed + lost,
        "metrics": metrics,
        "device": device,
    }
    if trace and traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["checks"] = checks
    result["_compare"] = cmp
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = spec.load_cell(args.workload)
    try:
        res = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace))
    except NoChip as e:
        say(str(e))
        return 3
    res.pop("_compare")
    for name, c in res["checks"].items():
        side = "at least" if name == "compared_tokens" else "at most"
        say(f"check {name} {c['value']} limit {c['limit']} ({side})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
