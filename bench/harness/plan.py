"""A configuration's structure, layer by layer, read from its file.

Everything that differs between the layers of a model lives in its file as
data: which layers attend over a sliding window (``layer_types``,
``sliding_window``), how many heads each has, and whether its feed-forward
block is dense or routed over experts (``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``norm_topk_prob``, a
shared expert, leading dense layers).  :func:`layer_plan` turns that into
one :class:`Layer` per layer; the harness builds the program, the weights
and the least work of a step from the plan, never from a family name.

In the file, ``num_experts`` is the number of experts held on this chip
(the ``model-configs`` guide's cut of one chip's share); the router's
width is the published count (``published.num_experts``) where the file
states one.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

FULL = "full"
WINDOW = "window"
DENSE = "dense"
ROUTED = "routed"

# ``layer_types`` values as the published configurations spell them.
_ATTN_TYPES = {"full_attention": FULL, "sliding_attention": WINDOW}
# ``mlp_layer_types`` values.
_MLP_TYPES = {"dense": DENSE, "sparse": ROUTED}


@dataclass(frozen=True)
class Attention:
    kind: str                        # FULL or WINDOW
    window: int                      # tokens a query sees (WINDOW only)
    heads: int
    kv_heads: int
    head_dim: int


@dataclass(frozen=True)
class FeedForward:
    kind: str                        # DENSE or ROUTED
    width: int                       # the dense width, or one expert's
    experts_held: int = 0            # experts on this chip
    router_width: int = 0            # experts the router scores
    experts_per_token: int = 0
    norm_topk_prob: bool = True      # chosen gates renormalised to sum 1
    score_func: str = "softmax"
    route_scale: float = 1.0
    shared_width: int = 0            # a shared expert's width, 0 if none


@dataclass(frozen=True)
class Layer:
    attn: Attention
    ffn: FeedForward


class PlanError(ValueError):
    """The file's structure cannot be read or built; names the key."""

    def __init__(self, key: str, why: str):
        super().__init__(f"{key}: {why}")
        self.key = key


def _per_layer(cfg: Dict[str, Any], key: str, n: int, default=None):
    """``cfg[key]`` for each of ``n`` layers: a list of ``n`` or one value
    for all."""
    v = cfg.get(key, default)
    if isinstance(v, list):
        if len(v) != n:
            raise PlanError(key, f"{len(v)} entries for {n} layers")
        return list(v)
    return [v] * n


def _attention(cfg: Dict[str, Any], n: int):
    d = int(cfg["hidden_size"])
    heads = _per_layer(cfg, "num_attention_heads", n)
    kv = _per_layer(cfg, "num_key_value_heads", n)
    hd = cfg.get("head_dim")
    types = _per_layer(cfg, "layer_types", n, "full_attention")
    out = []
    for i in range(n):
        if types[i] not in _ATTN_TYPES:
            raise PlanError("layer_types", f"layer {i}: unknown kind "
                            f"{types[i]!r}; known {sorted(_ATTN_TYPES)}")
        kind = _ATTN_TYPES[types[i]]
        window = 0
        if kind == WINDOW:
            if not cfg.get("sliding_window"):
                raise PlanError("sliding_window", f"layer {i} is "
                                f"sliding_attention and no window is set")
            window = int(cfg["sliding_window"])
        h = int(heads[i])
        out.append(Attention(kind, window, h, int(kv[i]),
                             int(hd) if hd else d // h))
    return out


def _mlp_kinds(cfg: Dict[str, Any], n: int):
    if "mlp_layer_types" in cfg:
        kinds = _per_layer(cfg, "mlp_layer_types", n)
        for i, k in enumerate(kinds):
            if k not in _MLP_TYPES:
                raise PlanError("mlp_layer_types", f"layer {i}: unknown "
                                f"kind {k!r}; known {sorted(_MLP_TYPES)}")
        return [_MLP_TYPES[k] for k in kinds]
    lead = int(cfg.get("num_dense_layers", 0))
    return [DENSE] * min(lead, n) + [ROUTED] * max(n - lead, 0)


def _routed(cfg: Dict[str, Any]) -> FeedForward:
    held = int(cfg["num_experts"])
    published = cfg.get("published") or {}
    width = int(cfg.get("moe_intermediate_size", cfg["intermediate_size"]))
    if "norm_topk_prob" in cfg:
        norm = bool(cfg["norm_topk_prob"])
    elif "route_norm" in cfg:
        norm = bool(cfg["route_norm"])
    else:
        raise PlanError("norm_topk_prob", "a routed configuration states "
                        "whether the chosen gates are renormalised")
    if "shared_expert_intermediate_size" in cfg:
        shared = int(cfg["shared_expert_intermediate_size"])
    else:
        shared = int(cfg.get("num_shared_experts", 0)) * width
    return FeedForward(
        kind=ROUTED, width=width, experts_held=held,
        router_width=int(published.get("num_experts") or held),
        experts_per_token=int(cfg["num_experts_per_tok"]),
        norm_topk_prob=norm,
        score_func=str(cfg.get("score_func", "softmax")),
        route_scale=float(cfg.get("route_scale", 1.0)),
        shared_width=shared)


def layer_plan(cfg: Dict[str, Any]) -> Tuple[Layer, ...]:
    """One :class:`Layer` per layer of the configuration file ``cfg``."""
    n = int(cfg["num_hidden_layers"])
    attn = _attention(cfg, n)
    dense = FeedForward(DENSE, int(cfg["intermediate_size"]))
    if not cfg.get("num_experts"):
        return tuple(Layer(a, dense) for a in attn)
    routed = _routed(cfg)
    return tuple(Layer(a, routed if k == ROUTED else dense)
                 for a, k in zip(attn, _mlp_kinds(cfg, n)))


def period(kinds: Tuple[Any, ...]) -> Tuple[Any, ...]:
    """The shortest prefix of ``kinds`` that, repeated and cut to length,
    gives ``kinds``."""
    for p in range(1, len(kinds) + 1):
        if all(kinds[i] == kinds[i % p] for i in range(len(kinds))):
            return tuple(kinds[:p])
    return tuple(kinds)


def uniform(plan: Tuple[Layer, ...], get, key: str, what: str) -> Any:
    """The one value ``get(layer)`` takes over ``plan``; a
    :class:`PlanError` naming ``key`` where the layers differ."""
    values = sorted({get(layer) for layer in plan}, key=repr)
    if len(values) > 1:
        raise PlanError(key, f"the program builds one {what} for every "
                        f"layer; the file gives {values}")
    return values[0] if values else None
