"""Peaks of the chip and the least work a decode step needs.

The operations and bytes are computed from the configuration's shapes and
the step's visible lengths, never from how the program happens to do the
work (its rounds, pages or grid steps): a later implementation that does
less reads a higher share of the same roofline.  The shapes are the
configuration's per-layer plan (``harness.plan``): a routed layer counts
the experts a token reaches, not every expert held, and a sliding-window
layer at most its window.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from harness import plan

# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
# "TPU v5e" (system architecture): 197 TFLOP/s bf16, 819 GB/s HBM, 16 GiB.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30,
                    "source": "Google Cloud documentation, TPU v5e"},
}

BF16 = 2


def peaks(device_kind: str) -> Dict[str, Any]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def _ffn_mults(cfg: Dict[str, Any]) -> int:
    """Matrices of one feed-forward block (gate, up, down or up, down)."""
    return 3 if cfg["glu"] else 2


def _layer_params(cfg: Dict[str, Any], layer, experts: float) -> float:
    """Weights of one layer that ``experts`` routed experts bring in."""
    d = int(cfg["hidden_size"])
    a, f = layer.attn, layer.ffn
    n = d * a.heads * a.head_dim * 2 + d * a.kv_heads * a.head_dim * 2
    m = _ffn_mults(cfg)
    if f.kind == plan.DENSE:
        return n + d * f.width * m
    return (n + d * f.router_width + d * f.shared_width * m
            + experts * d * f.width * m)


def least_experts(layer) -> int:
    """Routed experts held here that a token reaches at least: all of its
    ``k`` where every expert the router scores is held."""
    f = layer.ffn
    return max(f.experts_per_token - (f.router_width - f.experts_held), 0)


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights one token multiplies by: every layer's projections (of a
    routed layer the router, the shared expert and the experts it
    reaches) and the LM head (the embedding lookup reads one row and is
    counted apart)."""
    layers = plan.layer_plan(cfg)
    v, d = int(cfg["vocab_size"]), int(cfg["hidden_size"])
    return int(sum(_layer_params(cfg, x, least_experts(x)) for x in layers)
               + v * d)


def weight_bytes(cfg: Dict[str, Any], experts: Optional[int] = None) -> int:
    """Bytes of weights a step reads at least once: the projections, the
    norms and the head.  Of a routed layer, the experts the step's tokens
    were routed to: ``experts`` distinct ones a layer where the caller
    counted them, else the least a token reaches (:func:`least_experts`)."""
    layers = plan.layer_plan(cfg)
    v, d = int(cfg["vocab_size"]), int(cfg["hidden_size"])
    norms = (2 * len(layers) + 1) * d
    total = sum(_layer_params(cfg, x, least_experts(x) if experts is None
                              else experts) for x in layers)
    return int(total + v * d + norms) * BF16


def _kv_bytes(layer) -> int:
    return 2 * layer.attn.kv_heads * layer.attn.head_dim * BF16


def kv_token_bytes(cfg: Dict[str, Any]) -> int:
    """KV bytes of one token over the layers whose KV the bridge carries
    (full attention; keys and values, bf16)."""
    return sum(_kv_bytes(x) for x in plan.layer_plan(cfg)
               if x.attn.kind == plan.FULL)


def _attn_flops(layer, visible: int) -> int:
    return 2 * 2 * layer.attn.heads * layer.attn.head_dim * visible


def attention_flops(cfg: Dict[str, Any], visible: int) -> int:
    """Scores and weighted values of one query over ``visible`` tokens in
    the full-attention layers."""
    return sum(_attn_flops(x, visible) for x in plan.layer_plan(cfg)
               if x.attn.kind == plan.FULL)


def kv_work(cfg: Dict[str, Any], visible: Sequence[int]):
    """(flops, bytes) of the bridge's KV path of one step: each active
    sequence reads its visible keys and values of every full-attention
    layer once and writes its new token's."""
    n = len(visible)
    tok = sum(int(x) for x in visible)
    flops = sum(attention_flops(cfg, int(x)) for x in visible)
    nbytes = (tok + n) * kv_token_bytes(cfg)
    return flops, nbytes


def window_work(cfg: Dict[str, Any], visible: Sequence[int]):
    """(flops, bytes) of the sliding-window layers of one step: each reads
    the last ``min(visible, window)`` tokens' keys and values once and
    writes its new token's."""
    flops = nbytes = 0
    for x in plan.layer_plan(cfg):
        if x.attn.kind != plan.WINDOW:
            continue
        seen = [min(int(v), x.attn.window) for v in visible]
        flops += sum(_attn_flops(x, s) for s in seen)
        nbytes += (sum(seen) + len(seen)) * _kv_bytes(x)
    return flops, nbytes


def step_work(cfg: Dict[str, Any], visible: Sequence[int],
              experts: Optional[int] = None):
    """(flops, bytes) of one whole decode step for the active sequences
    with these visible lengths (``experts``: as :func:`weight_bytes`)."""
    d = int(cfg["hidden_size"])
    n = len(visible)
    kf, kb = kv_work(cfg, visible)
    wf, wb = window_work(cfg, visible)
    flops = 2 * matmul_params(cfg) * n + kf + wf
    nbytes = weight_bytes(cfg, experts) + n * d * BF16 + kb + wb
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: Dict[str, Any]):
    """The least time at the chip's peaks, and which peak bounds it."""
    tf = flops / peak["flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
