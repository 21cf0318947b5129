#!/usr/bin/env python3
"""On-chip smoke test of the bridge-backed serving path.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # four TPU chips of one host

One chip: granite-3-8b at its published widths, cut to 16 of its 40
layers, serves a few dozen seeded two-tenant requests through the same
path as ``python -m repro.launch.serve --traffic --kv bridge_pull``
(ContinuousBatcher + ModelDecodeEngine + Orchestrator) with 8 slots,
``max_len`` 2048 and 16-token KV pages.  Before that it checks one bridge
pull/push round bit-exact against ``repro.core.ref`` and one decode step's
logits under ``bridge_pull`` against the ``local`` placement.

Four chips (``--chips 4``): only the parts that exist across chips — a
pull/push round over a 4-node ``data`` mesh with a bidirectional route
program (the fused engine's ``all_to_all`` exchange), checked bit-exact
against the oracle, and the granite decode step with its ``bridge_pull``
KV pool sharded over the chips, checked against ``local`` on the same
chips.

Progress goes to stdout; the last stdout line is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The script
exits non-zero, printing no result, when JAX finds no TPU or any phase
fails.  It runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import pathlib
import re
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

MODEL, LAYERS = "granite-3-8b", 16
SLOTS, MAX_LEN, PAGE_TOKENS = 8, 2048, 16
# Decode steps fed before the compared step: three KV pages per sequence
# are flushed to the pool, so the step pulls them through the bridge.
LOGIT_STEPS = 48
# bf16 tolerance on one decode step's logits, relative to the largest
# |logit| of the local placement.  Correct paths differ by bf16 rounding
# of the attention output (0.0026 on a 2-layer CPU model); a bridge that
# served the wrong KV history differs by 0.6 there.
LOGIT_REL_TOL = 0.05
# Traffic: arrivals for TRAFFIC_STEPS steps at TRAFFIC_RATE per tenant per
# step, with prompt/output caps that keep the run to a few hundred steps.
TRAFFIC_STEPS, TRAFFIC_RATE = 40, 0.4
PROMPT_MAX, OUTPUT_MAX = 256, 64
TIMED_STEPS = 20
BRIDGE_KERNELS = {"bridge_gather", "bridge_stream_attention",
                  "bridge_scatter"}


def say(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def custom_call_kernels(hlo: str) -> set:
    """Names of the Pallas kernels compiled into ``hlo``."""
    return {m for line in hlo.splitlines() if "tpu_custom_call" in line
            for m in re.findall(r"%(bridge_[a-z_]+)", line)}


def granite_cut():
    from repro import configs
    full = configs.get_config(MODEL)
    cfg = dataclasses.replace(full, num_layers=LAYERS)
    say(f"model: {cfg.name} cut to {cfg.num_layers}/{full.num_layers} "
        f"layers at published widths (d_model {cfg.d_model}, heads "
        f"{cfg.num_heads}, kv heads {cfg.num_kv_heads}, head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.dtype}); {cfg.param_count() / 1e9:.2f} B params")
    return cfg


def run_config(cfg, kv: str):
    from repro.config import RunConfig, ShapeConfig
    return RunConfig(model=cfg, shape=ShapeConfig("chip_smoke", MAX_LEN,
                                                  SLOTS, "decode"),
                     kv_placement=kv)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def bridge_round(mesh, seed: int) -> None:
    """One pull and one push round of granite KV pages, bit-exact against
    the oracle.  ``mesh=None`` is the one-chip loopback path (4 logical
    nodes); a mesh runs the fused engine across its ``data`` axis."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import bridge, ref, steering
    from repro.core.memport import FREE, MemPortTable

    nodes = 4
    ppn, reqs, budget = 32, 24, 8
    page = (PAGE_TOKENS, 8, 128)          # granite: 16 tokens x 8 kv x 128
    num_logical = nodes * ppn
    rng = np.random.default_rng(seed)
    table = MemPortTable.striped(num_logical, nodes, ppn)
    program = steering.bidirectional_program(nodes)
    k_pool, k_pay = jax.random.split(jax.random.key(seed))
    pool = jax.random.normal(k_pool, (nodes * ppn,) + page, jnp.bfloat16)
    want = np.where(rng.random((nodes, reqs)) < 0.15, FREE,
                    rng.integers(0, num_logical, (nodes, reqs)))
    dest = rng.permutation(num_logical)[: nodes * reqs].reshape(nodes, reqs)
    dest = np.where(rng.random((nodes, reqs)) < 0.15, FREE, dest)
    payload = jax.random.normal(k_pay, (nodes, reqs) + page, jnp.bfloat16)
    want, dest = (jnp.asarray(x, jnp.int32) for x in (want, dest))
    if mesh is not None:
        def shard(x):
            spec = P("data", *([None] * (x.ndim - 1)))
            return jax.device_put(x, NamedSharding(mesh, spec))
        pool, want, dest, payload = map(shard, (pool, want, dest, payload))

    kw = dict(mesh=mesh, budget=budget,
              table_nodes=0 if mesh is not None else nodes)
    pull = jax.jit(functools.partial(bridge.pull_pages, **kw))
    push = jax.jit(functools.partial(bridge.push_pages, **kw))
    pull_c = pull.lower(pool, want, table, program=program).compile()
    push_c = push.lower(pool, dest, payload, table,
                        program=program).compile()
    hlo = pull_c.as_text() + push_c.as_text()
    kernels = custom_call_kernels(hlo)
    say(f"bridge round: kernels compiled in: {sorted(kernels)}")
    require(bool(kernels), "no bridge kernel in the compiled round")
    if mesh is not None:
        require("all-to-all" in pull_c.as_text(),
                "the fused pull round holds no all-to-all exchange")
        require({"bridge_pull_commit", "bridge_push_commit"} <= kernels,
                "the fused round's commit kernels are missing")

    got = np.asarray(pull_c(pool, want, table, program=program))
    exp = np.asarray(ref.pull_pages_ref(pool, want, table,
                                        pages_per_node=ppn, program=program))
    require(np.array_equal(got.view(np.uint16), exp.view(np.uint16)),
            "pulled pages differ from the oracle")
    got = np.asarray(push_c(pool, dest, payload, table, program=program))
    exp = np.asarray(ref.push_pages_ref(pool, dest, payload, table,
                                        pages_per_node=ppn, program=program))
    require(np.array_equal(got.view(np.uint16), exp.view(np.uint16)),
            "pushed pool differs from the oracle")
    live = int((np.asarray(want) >= 0).sum())
    say(f"bridge round: {live} pages pulled and "
        f"{int((np.asarray(dest) >= 0).sum())} pushed over {nodes} nodes "
        f"({'mesh ' + str(dict(mesh.shape)) if mesh is not None else 'loopback'}"
        f"): bit-exact against core/ref.py")


def init_params(cfg, mesh, seed: int):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import transformer
    out = None if mesh is None else NamedSharding(mesh, P())
    t0 = time.perf_counter()
    params = jax.jit(functools.partial(transformer.init_params, cfg),
                     out_shardings=out)(jax.random.key(seed))
    jax.block_until_ready(params)
    say(f"params: random from seed {seed} in "
        f"{time.perf_counter() - t0:.1f} s")
    return params


def decode_logits(cfg, params, kv: str, tokens, mesh):
    """Logits of the last of ``tokens`` ([steps, SLOTS]) under ``kv``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import transformer
    from repro.parallel.sharding import make_rules
    from repro.serve import step as serve_step

    run = run_config(cfg, kv)
    ops = serve_step.make_cache_ops(run, mesh, MAX_LEN,
                                    page_tokens=PAGE_TOKENS,
                                    dtype=jnp.dtype(cfg.dtype))
    def step(params, state, tokens):
        return transformer.decode_step(cfg, params, state, tokens, ops)

    state = serve_step.init_serve_state(run, SLOTS, ops)
    if mesh is None:
        jstep = jax.jit(step, donate_argnums=(1,))
    else:
        rules = make_rules(run.sharding, mesh, global_batch=SLOTS,
                           head_dim=cfg.head_dim, kv_heads=cfg.num_kv_heads,
                           num_heads=cfg.num_heads)
        st_sh = serve_step.decode_state_shardings(
            run, mesh, rules, jax.eval_shape(lambda: state))
        state = jax.device_put(state, st_sh)
        rep = NamedSharding(mesh, P())
        jstep = jax.jit(step, in_shardings=(rep, st_sh, rep),
                        out_shardings=(rep, st_sh), donate_argnums=(1,))
    t0 = time.perf_counter()
    for tok in tokens:
        logits, state = jstep(params, state, jnp.asarray(tok))
    out = np.asarray(logits, np.float32)
    say(f"decode {kv}: {len(tokens)} steps in "
        f"{time.perf_counter() - t0:.1f} s (first includes compile)")
    return out


def logits_check(cfg, params, mesh, seed: int) -> None:
    import numpy as np
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (LOGIT_STEPS, SLOTS)).astype(np.int32)
    local = decode_logits(cfg, params, "local", tokens, mesh)
    pulled = decode_logits(cfg, params, "bridge_pull", tokens, mesh)
    require(bool(np.isfinite(pulled).all()), "non-finite bridge logits")
    scale = float(np.abs(local).max())
    rel = float(np.abs(pulled - local).max()) / scale
    agree = float((pulled.argmax(-1) == local.argmax(-1)).mean())
    say(f"logits check: step {LOGIT_STEPS} ({LOGIT_STEPS // PAGE_TOKENS} "
        f"pages/seq pulled), max|bridge_pull - local| / max|local| = "
        f"{rel:.6f} (limit {LOGIT_REL_TOL}), max|local| = {scale:.4f}, "
        f"argmax agreement {agree:.3f}")
    require(rel <= LOGIT_REL_TOL, "bridge_pull logits do not match local")


def serve_traffic(cfg, params, seed: int) -> None:
    """The request-level serving path, timed."""
    import jax
    import numpy as np

    from repro.launch.serve import build_traffic_server, make_traffic
    from repro.serve.batcher import serve_loop

    run = run_config(cfg, "bridge_pull")
    batcher, engine, orc = build_traffic_server(
        run, params, slots=SLOTS, max_len=MAX_LEN, page_tokens=PAGE_TOKENS,
        seed=seed)
    t0 = time.perf_counter()
    compiled = engine.lower().compile()
    say(f"serve step: compiled in {time.perf_counter() - t0:.1f} s")
    kernels = custom_call_kernels(compiled.as_text())
    say(f"serve step: bridge kernels compiled in: {sorted(kernels)}")
    require(BRIDGE_KERNELS <= kernels,
            f"serve step lacks bridge kernels {BRIDGE_KERNELS - kernels}")

    traffic = make_traffic(cfg, prompt_max=PROMPT_MAX,
                           output_max=OUTPUT_MAX, rate=TRAFFIC_RATE,
                           seed=seed)
    t0 = time.perf_counter()
    res = serve_loop(batcher, engine, traffic, steps=TRAFFIC_STEPS)
    wall = time.perf_counter() - t0
    say(batcher.describe())
    say(f"traffic: {res['completed']}/{res['submitted']} requests completed "
        f"({res['shed']} shed), {res['tokens']} tokens in {res['steps']} "
        f"decode steps, {wall:.1f} s wall (first step includes the "
        f"cached compile)")
    require(res["submitted"] >= 12, "too few requests offered")
    require(res["completed"] == res["submitted"] and res["shed"] == 0,
            "not every request retired")

    tokens = np.zeros((SLOTS,), np.int32)
    engine.step(tokens)
    jax.block_until_ready(engine.state)
    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        engine.step(tokens)
        jax.block_until_ready(engine.state)
    ms = (time.perf_counter() - t0) / TIMED_STEPS * 1e3
    say(f"serve step: {ms:.2f} ms/step steady state ({TIMED_STEPS} steps, "
        f"batch {SLOTS}, block_until_ready)")


def report_memory(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        say(f"memory: {d} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache = enable_compile_cache()

    import jax
    devices = jax.devices()
    require(devices[0].platform == "tpu",
            f"JAX found no TPU (platform {devices[0].platform!r})")
    require(len(devices) >= args.chips,
            f"{args.chips} chips asked, JAX sees {len(devices)}")
    devices = devices[: args.chips]
    say(f"device: {devices[0].device_kind} x{len(devices)} "
        f"(jax {jax.__version__}); compile cache {cache}")

    cfg = granite_cut()
    if args.chips == 1:
        bridge_round(None, args.seed)
        params = init_params(cfg, None, args.seed)
        logits_check(cfg, params, None, args.seed)
        serve_traffic(cfg, params, args.seed)
    else:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((args.chips,), ("data",), devices=devices)
        bridge_round(mesh, args.seed)
        params = init_params(cfg, mesh, args.seed)
        logits_check(cfg, params, mesh, args.seed)
    report_memory(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
