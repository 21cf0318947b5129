import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces:
  * proof the distribution config is coherent (compile succeeds),
  * ``memory_analysis()``  — bytes per device (fits-in-HBM evidence),
  * ``cost_analysis()``    — XLA's flop/byte counts (per-while-body-once),
  * trip-count-corrected FLOPs / HBM bytes / collective bytes from the
    HLO-text analyzer (benchmarks/hlo_analysis.py),
  * the derived three-term roofline (compute / memory / collective seconds).

Results are cached as JSON under results/dryrun/ — one file per cell — so
the full sweep is resumable and the roofline table is assembled offline.

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--kv bridge_pull]
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs  # noqa: E402
from repro.config import (SHAPES, BridgeConfig, RunConfig,  # noqa: E402
                          ShardingConfig)
from repro.data.pipeline import make_batch_specs  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.obs.trace import CAT_COMPILE, TraceRecorder  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.parallel.sharding import make_rules  # noqa: E402
from repro.serve import step as serve_step_mod  # noqa: E402
from repro.train import step as train_step_mod  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))
from benchmarks import hlo_analysis  # noqa: E402

RESULTS = REPO / "results" / "dryrun"

# TPU v5e-class constants (per assignment)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link (per direction)

PAGE_TOKENS = 512


def cell_skip_reason(arch: str, shape_name: str) -> str | None:
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return "skip(full-attn): 500k decode needs bounded per-token state"
    if shape.is_decode and cfg.num_layers == 0:
        return "skip(encoder-only)"
    return None


def default_kv_placement(arch: str) -> str:
    cfg = configs.get_config(arch)
    kinds = set(cfg.layers)
    if kinds <= {"rglru", "mlstm", "slstm", "swa"}:
        return "local"       # bounded state everywhere: ring/recurrent
    return "bridge_pull"     # paper-faithful baseline


def build_cell(arch: str, shape_name: str, *, multi_pod: bool,
               kv_placement: str | None = None,
               bridge_budget: int = 8, edge_buffer: bool = True,
               bridge_channels: int = 1, bridge_fused: bool = True,
               microbatch: int = 1, replicate_kv_inner: bool = False,
               scan_decode: bool = True):
    """Returns (lowered, meta) for one cell."""
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    kv = kv_placement or default_kv_placement(arch)
    run = RunConfig(
        model=cfg, shape=shape,
        bridge=BridgeConfig(epoch_budget=bridge_budget,
                            edge_buffer=edge_buffer,
                            channels=bridge_channels,
                            fused=bridge_fused),
        kv_placement=kv, microbatch=microbatch, scan_layers=scan_decode)
    rules = make_rules(run.sharding, mesh, seq_len=shape.seq_len,
                       global_batch=shape.global_batch,
                       head_dim=0 if replicate_kv_inner else cfg.head_dim,
                       kv_heads=cfg.num_kv_heads,
                       num_heads=cfg.num_heads)
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "kv_placement": kv if shape.is_decode else None,
            "mode": shape.mode}

    params_abs = transformer.abstract_params(cfg)
    p_shard = jax.tree.map(
        lambda a: NamedSharding(mesh, rules.spec(*a)),
        transformer.params_logical_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(i, str) or i is None for i in x))

    if shape.mode == "train":
        state_abs = train_step_mod.abstract_train_state(run)
        s_shard = train_step_mod.train_state_shardings(run, mesh, rules)
        batch_abs = make_batch_specs(cfg, shape)
        b_shard = train_step_mod.batch_shardings(run, mesh, rules)
        step = train_step_mod.build_train_step(run, mesh, rules)
        lowered = jax.jit(
            step, in_shardings=(s_shard, b_shard),
            donate_argnums=(0,)).lower(state_abs, batch_abs)
        return lowered, meta

    if shape.mode == "prefill":
        batch_abs = make_batch_specs(cfg, shape)
        batch_abs.pop("labels")
        b_shard = train_step_mod.batch_shardings(run, mesh, rules)
        b_shard.pop("labels")

        def prefill(params, batch):
            logits, _ = transformer.forward(cfg, params, batch, run.remat)
            # serving prefill emits only the last position's logits
            return logits[:, -1, :]

        lowered = jax.jit(
            prefill, in_shardings=(p_shard, b_shard)).lower(
                params_abs, batch_abs)
        return lowered, meta

    # decode
    b = shape.global_batch
    cache_ops = serve_step_mod.make_cache_ops(
        run, mesh, max_len=shape.seq_len, page_tokens=PAGE_TOKENS)
    enc_len = 3000 if cfg.cross_attention else 0
    state_abs = serve_step_mod.abstract_serve_state(run, b, cache_ops,
                                                    enc_len=enc_len)
    s_shard = serve_step_mod.decode_state_shardings(run, mesh, rules,
                                                    state_abs)
    step = serve_step_mod.build_serve_step(run, cache_ops)
    tok_abs = jax.ShapeDtypeStruct((b,), jnp.int32)
    tok_shard = NamedSharding(mesh, P())
    lowered = jax.jit(
        step, in_shardings=(p_shard, s_shard, tok_shard),
        donate_argnums=(1,)).lower(params_abs, state_abs, tok_abs)
    return lowered, meta


def roofline_terms(stats: hlo_analysis.HloStats, num_chips: int,
                   cfg, shape) -> dict:
    """Three-term roofline from the trip-count-corrected HLO stats.

    The compiled module is the SPMD *partitioned* program, so the analyzer's
    FLOPs/bytes are already **per device**; each term divides by one chip's
    peak.  collective_s conservatively assumes one ICI link per transfer.
    """
    compute_s = stats.flops / PEAK_FLOPS
    memory_s = stats.hbm_bytes / HBM_BW
    collective_s = stats.collective_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    n_params = cfg.active_param_count()
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_params * tokens
    elif shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_params * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2 * n_params * tokens
    model_flops_per_device = model_flops / num_chips
    return {
        **terms,
        "dominant": dom,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops_per_device / stats.flops
                               if stats.flops else 0.0),
        "roofline_fraction": (terms["compute_s"] / max(sum(terms.values()),
                                                       1e-30)),
        "step_time_bound_s": max(terms.values()),
    }


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             kv_placement: str | None = None, tag: str = "",
             bridge_budget: int = 8, edge_buffer: bool = True,
             bridge_channels: int = 1, bridge_fused: bool = True,
             microbatch: int = 1, replicate_kv_inner: bool = False,
             scan_decode: bool = True, force: bool = False,
             recorder: TraceRecorder | None = None) -> dict:
    RESULTS.mkdir(parents=True, exist_ok=True)
    mesh_tag = "2pod" if multi_pod else "1pod"
    kv_tag = f"_{kv_placement}" if kv_placement else ""
    name = f"{arch}_{shape_name}_{mesh_tag}{kv_tag}{('_' + tag) if tag else ''}"
    out_path = RESULTS / f"{name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    record: dict = {"cell": name}
    skip = cell_skip_reason(arch, shape_name)
    if skip:
        record.update({"status": skip})
        out_path.write_text(json.dumps(record, indent=1))
        return record

    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    num_chips = 512 if multi_pod else 256
    # Phase timing rides the shared observability clock (monotonic
    # perf_counter, injectable for tests) as proper spans instead of ad-hoc
    # ``time.time()`` deltas; lower_s/compile_s stay in the record for
    # compatibility and the spans land in the cell's trace.
    rec = recorder if recorder is not None else TraceRecorder(
        process_name=f"dryrun:{name}")
    try:
        with rec.span(f"cell:{name}", CAT_COMPILE, cell=name):
            with rec.span("lower", CAT_COMPILE, cell=name) as sp_lower:
                lowered, meta = build_cell(
                    arch, shape_name, multi_pod=multi_pod,
                    kv_placement=kv_placement,
                    bridge_budget=bridge_budget,
                    edge_buffer=edge_buffer,
                    bridge_channels=bridge_channels,
                    bridge_fused=bridge_fused,
                    microbatch=microbatch,
                    replicate_kv_inner=replicate_kv_inner,
                    scan_decode=scan_decode)
            with rec.span("compile", CAT_COMPILE, cell=name) as sp_compile:
                compiled = lowered.compile()
        t_lower = sp_lower.duration_us / 1e6
        t_compile = sp_compile.duration_us / 1e6
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        stats = hlo_analysis.analyze_compiled(compiled)
        record.update(meta)
        record.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
                # buffer-assignment peak of one SPMD partition = HBM high
                # water mark per chip (the fits-in-16GiB evidence)
                "peak_bytes_per_device": getattr(
                    mem, "peak_memory_in_bytes", 0),
            },
            "xla_cost": {"flops": cost.get("flops", 0.0),
                         "bytes_accessed": cost.get("bytes accessed", 0.0)},
            "hlo": stats.as_dict(),
            "roofline": roofline_terms(stats, num_chips, cfg, shape),
            "num_chips": num_chips,
        })
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug report
        record.update({"status": "FAIL",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-3000:]})
    out_path.write_text(json.dumps(record, indent=1))
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--kv", default=None,
                    choices=[None, "local", "ring", "bridge_pull",
                             "bridge_push"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--budget", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--replicate-kv-inner", action="store_true")
    ap.add_argument("--no-scan-decode", action="store_true",
                    help="unroll decode layers (no pool slice/copy)")
    ap.add_argument("--no-edge-buffer", action="store_true")
    ap.add_argument("--channels", type=int, default=1,
                    help="pipelined bridge round-engine depth (1=serial)")
    ap.add_argument("--no-fused", action="store_true",
                    help="unfused ppermute-chain bridge engines (escape "
                         "hatch; fused Pallas datapath is the default)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = configs.lm_archs() if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape_name, multi_pod=mp,
                               kv_placement=args.kv, tag=args.tag,
                               bridge_budget=args.budget,
                               edge_buffer=not args.no_edge_buffer,
                               bridge_channels=args.channels,
                               bridge_fused=not args.no_fused,
                               microbatch=args.microbatch,
                               replicate_kv_inner=args.replicate_kv_inner,
                               scan_decode=not args.no_scan_decode,
                               force=args.force)
                status = rec.get("status", "?")
                dom = rec.get("roofline", {}).get("dominant", "")
                peak = rec.get("memory", {}).get("peak_bytes_per_device", 0)
                print(f"{rec['cell']:<60s} {status:<12s} "
                      f"{dom:<14s} peak/dev={peak/2**30:.2f}GiB"
                      if status == "ok" else
                      f"{rec['cell']:<60s} {status}",
                      flush=True)
                if status == "FAIL":
                    failures += 1
                    print(rec.get("error", ""), flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
