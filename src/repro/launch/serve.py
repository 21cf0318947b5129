"""Serving launcher: batched greedy decode with a selectable KV placement.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b --reduced \
      --kv bridge_pull --batch 4 --steps 32

``--traffic`` switches from one fixed batch to request-level serving: a
seeded Poisson arrival stream (two tenants, interactive + batch QoS)
drives the continuous batcher over the same jitted decode step — slots
admit from per-tenant queues as sequences retire, KV pages lease from an
orchestrated pool, and the run reports per-QoS p50/p99 round latencies:

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b \
      --reduced --traffic --batch 8 --traffic-steps 24
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.clock import MonotonicClock

from repro import configs
from repro.config import RunConfig, ShapeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import step as serve_step_mod


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--kv", default="local",
                    choices=["local", "ring", "bridge_pull", "bridge_push"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-tokens", type=int, default=16)
    ap.add_argument("--telemetry", action="store_true",
                    help="collect in-band bridge counters (bridge_* "
                         "placements) and print the aggregate")
    ap.add_argument("--channels", type=int, default=1,
                    help="pipelined bridge round-engine depth (1=serial)")
    ap.add_argument("--no-fused", action="store_true",
                    help="escape hatch: run the unfused ppermute-chain "
                         "bridge engines instead of the fused Pallas "
                         "datapath (bit-exact either way)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="serve the batch as K tenants (sequence b belongs "
                         "to tenant b %% K); with --telemetry the bridge "
                         "counters attribute traffic per tenant")
    ap.add_argument("--metrics", action="store_true",
                    help="trace every decode step as a fenced span, print "
                         "the metrics registry snapshot (per-step latency "
                         "p50/p99, bridge counter families) and, with "
                         "--trace-out, write the Perfetto trace JSON")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="with --metrics: write the Chrome-trace/Perfetto "
                         "JSON of the decode loop to PATH")
    ap.add_argument("--traffic", action="store_true",
                    help="request-level serving: continuous batching over "
                         "a seeded two-tenant Poisson arrival stream "
                         "(--batch sets the decode slot count)")
    ap.add_argument("--traffic-steps", type=int, default=32,
                    help="arrival steps to offer load for (the loop then "
                         "drains in-flight sequences)")
    ap.add_argument("--traffic-rate", type=float, default=0.5,
                    help="expected arrivals per step per tenant")
    ap.add_argument("--traffic-seed", type=int, default=0)
    ap.add_argument("--policy", default="qos", choices=["qos", "naive"],
                    help="slot admission: QoS-aware weighted-fair windows "
                         "or a single global FIFO (the noisy-neighbour "
                         "baseline)")
    ap.add_argument("--debug-bundle", default=None, metavar="PATH",
                    help="with --traffic: write a postmortem zip (flight "
                         "journal, Perfetto trace, metrics text, "
                         "describe()) to PATH after the run")
    args = ap.parse_args()

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    shape = ShapeConfig("cli", args.max_len, args.batch, "decode")
    from repro.config import BridgeConfig
    run = RunConfig(model=cfg, shape=shape, kv_placement=args.kv,
                    bridge=BridgeConfig(channels=args.channels,
                                        fused=not args.no_fused))

    from repro.models import transformer
    params = transformer.init_params(cfg, jax.random.key(0))
    if args.traffic:
        _traffic_mode(run, cfg, params, args)
        return
    collect = args.telemetry and args.kv in ("bridge_pull", "bridge_push")
    if args.tenants < 1:
        ap.error("--tenants must be >= 1")
    tenant_of_seq = (np.arange(args.batch) % args.tenants
                     if args.tenants > 1 else None)
    cache_ops = serve_step_mod.make_cache_ops(
        run, mesh=None, max_len=args.max_len, page_tokens=args.page_tokens,
        collect_telemetry=collect, tenant_of_seq=tenant_of_seq,
        max_tenants=args.tenants if args.tenants > 1 else 0,
        dtype=jnp.dtype(cfg.dtype))
    enc_out = None
    if cfg.cross_attention:
        enc_out = jnp.asarray(np.random.default_rng(0).normal(
            size=(args.batch, 16, cfg.d_model)), jnp.dtype(cfg.dtype))
    state = serve_step_mod.init_serve_state(run, args.batch, cache_ops,
                                            enc_out=enc_out)
    step = jax.jit(serve_step_mod.build_serve_step(run, cache_ops),
                   donate_argnums=(1,))

    # --metrics wraps every decode step in a fenced span: the per-step
    # fence changes the loop's async-dispatch overlap, so it is opt-in —
    # the untraced path stays exactly as before.
    recorder = registry = None
    if args.metrics:
        from repro.obs import MetricsRegistry, TraceRecorder
        recorder = TraceRecorder(process_name=f"serve:{args.arch}")
        registry = MetricsRegistry()

    tokens = jnp.ones((args.batch,), jnp.int32)
    wall = MonotonicClock()
    t0 = wall.now_us()
    emitted = []
    for i in range(args.steps):
        if recorder is not None:
            with recorder.span("decode_step", "round", step=i) as sp:
                tokens, state = step(params, state, tokens)
                recorder.fence(tokens)
            registry.observe_span(sp)
        else:
            tokens, state = step(params, state, tokens)
        emitted.append(np.asarray(tokens))
    dt = (wall.now_us() - t0) / 1e6
    print(f"arch={cfg.name} kv={args.kv} batch={args.batch} "
          f"steps={args.steps}")
    print(f"tokens/s={args.batch*args.steps/dt:.1f} "
          f"({dt/args.steps*1e3:.1f} ms/step)")
    print("sample:", np.stack(emitted, 1)[0][:16])
    if collect:
        from repro.core.control_plane import ControlPlane
        from repro.telemetry import TelemetryAggregator
        telem = serve_step_mod.collect_state_telemetry(state)
        if telem is not None:
            agg = TelemetryAggregator(telem.num_nodes,
                                      max_tenants=telem.max_tenants)
            agg.update(telem)
            print(agg.describe())
            if args.tenants > 1:
                served = np.asarray(telem.tenant_served).sum(0)
                spilled = np.asarray(telem.tenant_spilled).sum(0)
                for t in range(args.tenants):
                    print(f"tenant {t}: served={int(served[t])} pages "
                          f"spilled={int(spilled[t])}")
            # The closed loop's pipeline-depth pick from measured occupancy
            # (what --channels should be next run).
            cp = ControlPlane(telem.num_nodes, 1, 1)
            page_bytes = (args.page_tokens * cfg.num_kv_heads * cfg.head_dim
                          * jnp.dtype(cfg.dtype).itemsize)
            pick = cp.select_channels(run.bridge.epoch_budget, page_bytes,
                                      telemetry=agg)
            print(f"control plane channels pick: {pick} "
                  f"(running with {args.channels})")
            if registry is not None:
                registry.observe_telemetry(telem)
                registry.observe_aggregator(agg)
    if registry is not None:
        print("metrics:")
        for line in registry.to_text().splitlines():
            print(" ", line)
        if args.trace_out:
            recorder.write(args.trace_out)
            print(f"trace: {args.trace_out} "
                  f"({len(recorder.spans)} spans; open at "
                  f"https://ui.perfetto.dev)")


def build_traffic_server(run, params, *, slots: int, max_len: int,
                         page_tokens: int, seed: int, policy: str = "qos"):
    """The request-level server: ``(batcher, engine, orchestrator)``.

    A two-tenant orchestrated pool (interactive ``chat`` at share 3, batch
    ``crawl`` at share 1) leases KV pages to a :class:`ContinuousBatcher`
    whose slots decode through a :class:`ModelDecodeEngine` running
    ``run``'s KV placement.
    """
    from repro.core.control_plane import ControlPlane
    from repro.orchestrator import Orchestrator, TenantSpec
    from repro.serve.batcher import ContinuousBatcher, ModelDecodeEngine

    pages_per_seq = -(-max_len // page_tokens)
    # Pool sized for the slot count (plus headroom so admission, not raw
    # capacity, is the governing control).
    cp = ControlPlane(4, slots * pages_per_seq,
                      num_logical=4 * slots * pages_per_seq, seed=seed)
    orc = Orchestrator(cp, budget=run.bridge.epoch_budget,
                       control_period=4, migrate=False)
    orc.register(TenantSpec(1, "chat", qos="interactive", share=3.0))
    orc.register(TenantSpec(2, "crawl", qos="batch", share=1.0))
    batcher = ContinuousBatcher(orc, num_slots=slots,
                                page_tokens=page_tokens, policy=policy)
    engine = ModelDecodeEngine(run, params, batch=slots, max_len=max_len,
                               mesh=None, page_tokens=page_tokens,
                               dtype=jnp.dtype(run.model.dtype))
    return batcher, engine, orc


def make_traffic(cfg, *, prompt_max: int, output_max: int, rate: float,
                 seed: int):
    """Seeded Poisson arrivals of the two tenants: ``chat`` sends requests
    a quarter of the length caps on average, ``crawl`` half of them."""
    from repro.serve.traffic import TenantTraffic, TrafficGenerator
    return TrafficGenerator([
        TenantTraffic(1, rate=rate, prompt_mean=prompt_max // 4 or 1,
                      output_mean=output_max // 4 or 1,
                      prompt_max=prompt_max, output_max=output_max,
                      vocab=cfg.vocab_size),
        TenantTraffic(2, rate=rate, prompt_mean=prompt_max // 2 or 1,
                      output_mean=output_max // 2 or 1,
                      prompt_max=prompt_max, output_max=output_max,
                      vocab=cfg.vocab_size),
    ], seed=seed)


def _traffic_mode(run, cfg, params, args) -> None:
    """Request-level serving over the real jitted decode step."""
    from repro.serve.batcher import serve_loop

    slots = args.batch
    batcher, engine, orc = build_traffic_server(
        run, params, slots=slots, max_len=args.max_len,
        page_tokens=args.page_tokens, seed=args.traffic_seed,
        policy=args.policy)
    # Lengths cap: a sequence's prompt + output must fit max_len.
    pmax = max(args.max_len // 2, 2)
    traffic = make_traffic(cfg, prompt_max=pmax,
                           output_max=max(args.max_len - pmax, 1),
                           rate=args.traffic_rate, seed=args.traffic_seed)

    wall = MonotonicClock()
    t0 = wall.now_us()
    result = serve_loop(batcher, engine, traffic, steps=args.traffic_steps)
    dt = (wall.now_us() - t0) / 1e6
    print(f"arch={cfg.name} kv={args.kv} slots={slots} "
          f"policy={args.policy}")
    print(batcher.describe())
    print(f"{result['completed']}/{result['submitted']} requests, "
          f"{result['tokens']} tokens in {result['steps']} decode steps "
          f"({dt:.1f}s wall, {result['tokens']/dt:.1f} tokens/s)")
    for qos, lat in batcher.registry.family_quantiles(
            "serve_request_steps").items():
        print(f"  {qos}: {lat['count']} requests, round latency p50="
              f"{lat['p50']:.0f} p99={lat['p99']:.0f} steps")
    print(orc.admission.describe())
    if args.debug_bundle:
        path = orc.dump_debug_bundle(args.debug_bundle,
                                     trace=batcher.recorder)
        print(f"debug bundle: {path} "
              f"({len(orc.flight)} decision records)")


if __name__ == "__main__":
    main()
