"""Peaks of the chip and the least work a decode step needs.

The operations and bytes are computed from the configuration's shapes and
the step's visible lengths, never from how the program happens to do the
work (its rounds, pages or grid steps): a later implementation that does
less reads a higher share of the same roofline.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

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


def _dims(cfg: Dict[str, Any]):
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return (d, h, int(cfg["num_key_value_heads"]),
            int(cfg.get("head_dim", d // h)), int(cfg["intermediate_size"]),
            int(cfg["vocab_size"]), int(cfg["num_hidden_layers"]),
            bool(cfg["glu"]))


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights one token multiplies by: every layer's projections and the
    LM head (the embedding lookup reads one row and is counted apart)."""
    d, h, kv, hd, ff, v, L, glu = _dims(cfg)
    attn = d * h * hd * 2 + d * kv * hd * 2
    mlp = d * ff * (3 if glu else 2)
    return L * (attn + mlp) + v * d


def weight_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of weights a step reads at least once: the projections, the
    norms and the head."""
    d, h, kv, hd, ff, v, L, glu = _dims(cfg)
    norms = (2 * L + 1) * d
    return (matmul_params(cfg) + norms) * BF16


def kv_token_bytes(cfg: Dict[str, Any]) -> int:
    """KV bytes of one token over all layers (keys and values, bf16)."""
    d, h, kv, hd, ff, v, L, glu = _dims(cfg)
    return L * 2 * kv * hd * BF16


def attention_flops(cfg: Dict[str, Any], visible: int) -> int:
    """Scores and weighted values of one query over ``visible`` tokens."""
    d, h, kv, hd, ff, v, L, glu = _dims(cfg)
    return L * 2 * 2 * h * hd * visible


def kv_work(cfg: Dict[str, Any], visible: Sequence[int]):
    """(flops, bytes) of the KV path of one step: each active sequence
    reads its visible keys and values once and writes its new token's."""
    n = len(visible)
    tok = sum(int(x) for x in visible)
    flops = sum(attention_flops(cfg, int(x)) for x in visible)
    nbytes = (tok + n) * kv_token_bytes(cfg)
    return flops, nbytes


def step_work(cfg: Dict[str, Any], visible: Sequence[int]):
    """(flops, bytes) of one whole decode step for the active sequences
    with these visible lengths."""
    d = int(cfg["hidden_size"])
    n = len(visible)
    kf, kb = kv_work(cfg, visible)
    flops = 2 * matmul_params(cfg) * n + kf
    nbytes = weight_bytes(cfg) + n * d * BF16 + kb
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: Dict[str, Any]):
    """The least time at the chip's peaks, and which peak bounds it."""
    tf = flops / peak["flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
