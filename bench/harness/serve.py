"""Drive the served path for a measured window, open loop, wall clock.

The server is put together as ``repro.launch.serve.build_traffic_server``
puts it together (control plane, orchestrator, tenants, KV-page leases,
``ContinuousBatcher`` over ``ModelDecodeEngine``), with the tenants of the
cell's mix and the deployment of its configuration.

One loop iteration is ``submit -> control -> step_inputs -> engine.step ->
observe``, each call wrapped in a ``jax.profiler.TraceAnnotation``
(``bench:<call>``) so that a traced run can say what the host was doing
in each idle gap of the device.  Requests are submitted when they are due
by the wall clock, whatever the server is doing, and are timed from that
due time; every output token is stamped when the step that made it
returns.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from harness.traffic import Arrival


@dataclass
class ReqRecord:
    """What the harness saw of one request."""

    arrival: Arrival
    req_id: int
    submit_s: Optional[float] = None
    shed: bool = False
    token_s: List[float] = field(default_factory=list)
    seq: Any = None                       # the batcher's SeqState
    slot_reused: bool = False


@dataclass
class IterRecord:
    """One loop iteration: host times and the step's visible lengths."""

    start_s: float
    step_start_s: float
    step_end_s: float
    end_s: float
    visible: List[int]


@dataclass
class WindowResult:
    seconds: float
    records: List[ReqRecord]
    iters: List[IterRecord]
    end_s: float                          # when the loop stopped
    t0: float = 0.0                       # perf_counter at the start
    compiles: int = 0


class WallClock:
    """The batcher's clock: microseconds of ``time.perf_counter``."""

    def now_us(self) -> float:
        return time.perf_counter() * 1e6


def program_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` with the file's sizes as run, built
    from the file's per-layer plan (``harness.plan``).  Raises
    ``plan.PlanError``, naming the key, for a structure the program
    cannot build."""
    from repro import configs
    from repro.config import FULL_ATTN, SWA_ATTN

    from harness import plan as pl
    layers = pl.layer_plan(cfg)
    base = configs.get_config(cfg["deployment"]["registry"])
    heads = pl.uniform(layers, lambda x: x.attn.heads,
                       "num_attention_heads", "head count")
    kv = pl.uniform(layers, lambda x: x.attn.kv_heads,
                    "num_key_value_heads", "KV head count")
    window = pl.uniform([x for x in layers if x.attn.kind == pl.WINDOW],
                        lambda x: x.attn.window, "sliding_window", "window")
    kinds = tuple(SWA_ATTN if x.attn.kind == pl.WINDOW else FULL_ATTN
                  for x in layers)
    mixed = ("mlp_layer_types" if "mlp_layer_types" in cfg
             else "num_dense_layers")
    ffn = pl.uniform(layers, lambda x: x.ffn, mixed,
                     "feed-forward block (dense or routed)")
    moe = {"num_experts": 0, "experts_per_token": 0}
    if ffn.kind == pl.ROUTED:
        _routable(cfg, ffn)
        moe = {"family": "moe", "num_experts": ffn.experts_held,
               "experts_per_token": ffn.experts_per_token}
    return dataclasses.replace(
        base, num_layers=len(layers), d_model=int(cfg["hidden_size"]),
        num_heads=heads, num_kv_heads=kv,
        head_dim=pl.uniform(layers, lambda x: x.attn.head_dim, "head_dim",
                            "head size"),
        d_ff=ffn.width, layer_pattern=pl.period(kinds),
        window_size=window or 0, **moe,
        vocab_size=int(cfg["vocab_size"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"])


def _routable(cfg: Dict[str, Any], ffn) -> None:
    """Refuse a routed block that ``repro.models.moe`` cannot build: it
    holds every expert its router scores, renormalises softmax gates and
    has no shared expert."""
    from harness.plan import PlanError
    if ffn.router_width != ffn.experts_held:
        raise PlanError("num_experts", f"the router scores "
                        f"{ffn.router_width} experts and {ffn.experts_held} "
                        f"are held here; the program's expert layer holds "
                        f"every expert it routes to")
    if ffn.shared_width:
        key = ("shared_expert_intermediate_size"
               if "shared_expert_intermediate_size" in cfg
               else "num_shared_experts")
        raise PlanError(key, "the program's expert layer has no shared "
                        "expert")
    if not ffn.norm_topk_prob:
        raise PlanError("norm_topk_prob", "the program always renormalises "
                        "the chosen gates")
    if ffn.score_func != "softmax":
        raise PlanError("score_func", f"the program routes by softmax, not "
                        f"{ffn.score_func!r}")
    if ffn.route_scale != 1.0:
        raise PlanError("route_scale", "the program does not scale the "
                        "routed output")


def build_server(cfg: Dict[str, Any], mix: Dict[str, Any], params,
                 seed: int):
    """``(batcher, engine, tenant ids by name)`` for one run."""
    import jax.numpy as jnp

    from repro.config import BridgeConfig, RunConfig, ShapeConfig
    from repro.core.control_plane import ControlPlane
    from repro.orchestrator import Orchestrator, TenantSpec
    from repro.serve.batcher import ContinuousBatcher, ModelDecodeEngine

    dep = cfg["deployment"]
    slots, max_len = int(dep["slots"]), int(dep["max_len"])
    page_tokens = int(dep["page_tokens"])
    model = program_config(cfg)
    run = RunConfig(model=model,
                    shape=ShapeConfig("bench", max_len, slots, "decode"),
                    kv_placement=dep["kv_placement"],
                    bridge=BridgeConfig(fused=bool(dep["fused"])))
    pages_per_seq = -(-max_len // page_tokens)
    nodes = int(dep["control_nodes"])
    cp = ControlPlane(nodes, slots * pages_per_seq,
                      num_logical=nodes * slots * pages_per_seq,
                      seed=abs(int(seed)) % 2 ** 31)
    orc = Orchestrator(cp, budget=run.bridge.epoch_budget,
                       control_period=4, migrate=False)
    ids = {}
    for i, t in enumerate(mix["tenants"]):
        ids[t["name"]] = i + 1
        orc.register(TenantSpec(i + 1, t["name"], qos=t["qos"],
                                share=float(t["share"])))
    batcher = ContinuousBatcher(orc, num_slots=slots,
                                page_tokens=page_tokens,
                                policy="qos",
                                clock=WallClock())
    engine = ModelDecodeEngine(run, params, batch=slots, max_len=max_len,
                               mesh=None, page_tokens=page_tokens,
                               dtype=jnp.dtype(model.dtype))
    return batcher, engine, ids


def warm_up(engine) -> None:
    """Compile and run every program the window will use: the serve step
    and the slot reset for each number of slots admitted at once."""
    import jax
    tokens = np.zeros((engine.num_slots,), np.int32)
    for k in range(1, engine.num_slots + 1):
        engine.step(tokens, list(range(k)))
    engine.step(tokens, [])
    jax.block_until_ready(engine.state)


class _CompileCounter:
    """Counts backend compilations while active."""

    def __init__(self):
        self.count = 0
        self.active = False
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


_COUNTER: Optional[_CompileCounter] = None


def drive(batcher, engine, arrivals: List[Arrival], tenant_ids: Dict[str, int],
          *, seconds: float, follow: str, follow_limit_s: float,
          on_iteration: Optional[Callable[[float, int], None]] = None
          ) -> WindowResult:
    """Run the window: offer ``arrivals`` at their due times for
    ``seconds``; then keep stepping, with no new arrivals and for at most
    ``follow_limit_s`` more seconds, until every request due in the window
    has its first token (``follow="first_token"``); ``follow="none"``
    stops at the close."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.serve.traffic import Request

    global _COUNTER
    if _COUNTER is None:
        _COUNTER = _CompileCounter()
    due = [a for a in arrivals if a.due_s < seconds]
    records = [ReqRecord(arrival=a, req_id=i) for i, a in enumerate(due)]
    by_req = {r.req_id: r for r in records}
    used_slots = set()
    iters: List[IterRecord] = []
    nxt = 0
    waiting_first = 0                    # submitted, no first token yet
    _COUNTER.count, _COUNTER.active = 0, True
    t0 = time.perf_counter()
    clock = time.perf_counter
    while True:
        start = clock() - t0
        if on_iteration is not None:
            on_iteration(start, len(iters))
            start = clock() - t0
        if start >= seconds:
            pending = (len(records) - nxt) + waiting_first
            if follow == "none" or pending == 0 or \
                    start >= seconds + follow_limit_s:
                break
        with TraceAnnotation("bench:submit"):
            while nxt < len(records) and records[nxt].arrival.due_s <= start:
                r = records[nxt]
                a = r.arrival
                req = Request(req_id=r.req_id,
                              tenant_id=tenant_ids[a.tenant],
                              arrive_step=batcher.step_count,
                              prompt=a.prompt, output_len=a.output_len)
                r.submit_s = clock() - t0
                if batcher.submit(req) == "shed":
                    r.shed = True
                else:
                    waiting_first += 1
                nxt += 1
        with TraceAnnotation("bench:control"):
            admitted = batcher.control()
        for seq in admitted:
            r = by_req[seq.req.req_id]
            r.seq = seq
            r.slot_reused = seq.slot in used_slots
            used_slots.add(seq.slot)
        if batcher.active_count() == 0:
            if nxt < len(records):
                wait = records[nxt].arrival.due_s - (clock() - t0)
                if wait > 0:
                    time.sleep(min(wait, 0.05))
            elif start >= seconds:
                break
            else:
                time.sleep(min(seconds - start, 0.05))
            continue
        with TraceAnnotation("bench:step_inputs"):
            tokens, resets = batcher.step_inputs()
        live = [s for s in batcher.slots if s is not None]
        before = [(s, len(s.out)) for s in live]
        visible = [s.fed + 1 for s in live]
        step_start = clock() - t0
        with TraceAnnotation("bench:engine_step"):
            out = engine.step(tokens, resets)
        step_end = clock() - t0
        with TraceAnnotation("bench:observe"):
            batcher.observe(out)
        for s, n in before:
            if len(s.out) > n:
                r = by_req[s.req.req_id]
                if not r.token_s:
                    waiting_first -= 1
                r.token_s.append(step_end)
        iters.append(IterRecord(start, step_start, step_end, clock() - t0,
                                visible))
    end = clock() - t0
    _COUNTER.active = False
    jax.block_until_ready(engine.state)
    return WindowResult(seconds=seconds, records=records, iters=iters,
                        end_s=end, t0=t0, compiles=_COUNTER.count)
