"""Plain reference of a dense decoder with grouped-query attention.

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``: full causal
attention over whole sequences, no cache, no pages, no batching tricks,
nothing imported from the program.  It follows the published
descriptions of the configurations that name it (``deployment.reference``
in ``bench/configs/<name>.json``), with the values the file states as run:

* granite (IBM Granite 3.0): pre-RMSNorm blocks, SwiGLU MLP, RoPE
  (rotate-half), tied embeddings; the multipliers as the file gives them.
* starcoder2 (BigCode StarCoder2): pre-LayerNorm blocks, a plain MLP with
  tanh-approximated GELU, RoPE (rotate-half); biases and the sliding
  window as the file gives them (no biases; the window is never reached).

``quant="int8"`` is the control: every linear layer computed from int8
operands (weights per output channel, activations per token, symmetric),
the next precision below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST

# What each model family's blocks are, from its published description.
ARCH = {
    "granite": {"norm": "rms", "glu": True, "act": "silu"},
    "starcoder2": {"norm": "layer", "glu": False, "act": "gelu_tanh"},
}


def arch(cfg: Dict[str, Any]) -> Dict[str, Any]:
    a = ARCH[cfg["model_type"]]
    if cfg.get("use_bias") or cfg.get("attention_bias") or \
            cfg.get("mlp_bias"):
        raise ValueError("biases are not modelled by this reference")
    return a


def _eps(cfg):
    return float(cfg.get("rms_norm_eps", cfg.get("norm_epsilon", 1e-5)))


def _norm(kind, x, scale, eps):
    if kind == "rms":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + eps) * scale
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale


def _act(kind, x):
    if kind == "silu":
        return x / (1.0 + jnp.exp(-x))
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _q8(a, axis):
    """Symmetric int8 round trip of ``a`` with one scale per slice along
    ``axis`` (the reduction axis of the product it feeds)."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(a / s), -127, 127) * s


def _linear(x, w, quant):
    """x [..., i] @ w [i, o] in float32, or from int8 operands."""
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def _rope(x, pos, theta):
    """Rotate-half RoPE.  x: [S, H, hd]; pos: [S]."""
    hd = x.shape[-1]
    half = hd // 2
    inv = theta ** (-np.arange(0, half, dtype=np.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale):
    """Causal GQA over one sequence.  q [S,H,hd]; k, v [S,kv,hd]."""
    s, h, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(s, kv, h // kv, hd)
    sc = jnp.einsum("skgd,tkd->kgst", qg, k, precision=HI) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HI)
    return o.reshape(s, h * hd)


def _layer(cfg_items, quant, x, w, layer):
    """One block over x [n, S, d]; ``w`` holds the stacked bf16 leaves."""
    cfg = dict(cfg_items)
    a = arch(cfg)
    lw = {k: v[layer].astype(jnp.float32) for k, v in w.items()}
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // h)
    eps = _eps(cfg)
    scale = float(cfg.get("attention_multiplier", hd ** -0.5))
    res = float(cfg.get("residual_multiplier", 1.0))
    theta = float(cfg["rope_theta"])
    n, s, _ = x.shape
    pos = jnp.arange(s)

    def attend(xs):
        hh = _norm(a["norm"], xs, lw["norm1"], eps)
        q = _linear(hh, lw["wq"], quant).reshape(s, h, hd)
        k = _linear(hh, lw["wk"], quant).reshape(s, kv, hd)
        v = _linear(hh, lw["wv"], quant).reshape(s, kv, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        return _linear(_attention(q, k, v, scale), lw["wo"], quant)

    x = x + res * jax.lax.map(attend, x)
    hh = _norm(a["norm"], x, lw["norm2"], eps)
    up = _linear(hh, lw["w_in"], quant)
    if a["glu"]:
        up = _act(a["act"], _linear(hh, lw["w_gate"], quant)) * up
    else:
        up = _act(a["act"], up)
    return x + res * _linear(up, lw["w_out"], quant)


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_items, quant):
    return jax.jit(functools.partial(_layer, cfg_items, quant))


def _embed(cfg_items, tokens, table):
    cfg = dict(cfg_items)
    mult = float(cfg.get("embedding_multiplier", 1.0))
    return table[tokens].astype(jnp.float32) * mult


@functools.lru_cache(maxsize=None)
def _embed_fn(cfg_items):
    return jax.jit(functools.partial(_embed, cfg_items))


def _head(cfg_items, quant, x, norm, table):
    """Final norm and logits of one sequence: x [S, d] -> [S, V]."""
    cfg = dict(cfg_items)
    a = arch(cfg)
    v = cfg["vocab_size"]
    hh = _norm(a["norm"], x, norm.astype(jnp.float32), _eps(cfg))
    w = table[:v].astype(jnp.float32).T
    logits = _linear(hh, w, quant)
    return logits / float(cfg.get("logits_scaling", 1.0))


@functools.lru_cache(maxsize=None)
def _head_fn(cfg_items, quant):
    return jax.jit(functools.partial(_head, cfg_items, quant))


def _gaps(ref_logits, targets, ctrl_logits):
    """Per position: how far the served token's reference logit lies below
    the reference's best, and the same for the control's first choice; in
    standard deviations of that position's reference logits, so that
    limits read alike whatever the scale of the logits."""
    best = jnp.max(ref_logits, -1)
    sd = jnp.std(ref_logits, -1)
    safe = jnp.maximum(targets, 0)
    served = jnp.take_along_axis(ref_logits, safe[:, None], -1)[:, 0]
    gap = jnp.where(targets >= 0, (best - served) / sd, 0.0)
    if ctrl_logits is None:
        return gap, None
    pick = jnp.argmax(ctrl_logits, -1)
    ctrl = jnp.take_along_axis(ref_logits, pick[:, None], -1)[:, 0]
    return gap, jnp.where(targets >= 0, (best - ctrl) / sd, 0.0)


_gaps_jit = jax.jit(_gaps)


def _hidden(cfg_items, quant, weights, tokens):
    x = _embed_fn(cfg_items)(tokens, weights["embed"])
    stacked = {k: v for k, v in weights.items()
               if k not in ("embed", "lm_head", "out_norm")}
    fn = _layer_fn(cfg_items, quant)
    for layer in range(dict(cfg_items)["num_hidden_layers"]):
        x = fn(x, stacked, jnp.int32(layer))
    return x


def logit_gaps(cfg: Dict[str, Any], weights: Dict[str, Any],
               tokens: np.ndarray, targets: np.ndarray,
               control: Optional[str] = None):
    """Reference forward over ``tokens`` [n, S] (prompt, then the served
    tokens but the last); ``targets`` [n, S] is the served token due at
    each position, -1 where none is.

    Returns ``gap`` [n, S] (reference best minus the served token's
    reference logit, in standard deviations of the position's reference
    logits) and, with ``control="int8"``, ``control_gap`` [n, S]
    (the same for the token the int8 control puts first), both 0 where
    no token is due.
    """
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    tokens = jnp.asarray(tokens, jnp.int32)
    table = weights.get("lm_head", weights["embed"])
    ref = _hidden(items, None, weights, tokens)
    ctl = (_hidden(items, control, weights, tokens)
           if control is not None else None)
    head = _head_fn(items, None)
    head_c = _head_fn(items, control) if control is not None else None
    gaps, cgaps = [], []
    for i in range(tokens.shape[0]):
        t = jnp.asarray(targets[i], jnp.int32)
        lr = head(ref[i], weights["out_norm"], table)
        lc = (head_c(ctl[i], weights["out_norm"], table)
              if ctl is not None else None)
        g, c = _gaps_jit(lr, t, lc)
        gaps.append(np.asarray(g))
        if c is not None:
            cgaps.append(np.asarray(c))
    return np.stack(gaps), (np.stack(cgaps) if cgaps else None)
