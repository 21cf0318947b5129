"""Served weights, made on the device from the seed in one jitted call.

The weights are random: speed and agreement with the reference need no
trained values.  Every matrix is normal with standard deviation
``fan_in ** -0.5`` and every norm scale ``1 + 0.1 * normal``, so a norm
that is skipped or swapped shows in the logits.  The embedding's standard
deviation is ``1 / hidden``: the program scales it by ``sqrt(hidden)``,
and at ``hidden ** -0.5`` the token's own embedding would dominate the
residual stream, so that with tied embeddings every position's first
choice would be its input token whatever the context.  Kept small, the
logits depend on the attention over the context, which is what the check
has to see.  An untied head has ``hidden ** -0.5``.

:func:`make_weights` returns the tree in the layout the program's decode
step reads (``repro.models.transformer.abstract_params``);
:func:`neutral` gives the reference the same arrays under plain names.
The reference calls the same compiled function, so both see identical
values.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import numpy as np

# Fixed stream ids: a leaf's values do not depend on which other leaves
# exist.
_LEAF_IDS = {"embed": 1, "lm_head": 2, "out_norm": 3, "norm1": 4,
             "norm2": 5, "wq": 6, "wk": 7, "wv": 8, "wo": 9, "w_in": 10,
             "w_gate": 11, "w_out": 12}


def seed_words(seed: int) -> np.ndarray:
    """``seed`` (any integer) as four 30-bit words for ``fold_in``."""
    s = int(seed)
    neg = 1 if s < 0 else 0
    s = abs(s)
    return np.array([(s >> (30 * i)) & (2 ** 30 - 1) for i in range(3)]
                    + [neg], np.uint32)


def sizes_of(cfg: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    """The hashable sizes of a configuration file, for the jit's key."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return (("d", d), ("heads", h), ("kv", int(cfg["num_key_value_heads"])),
            ("hd", int(cfg.get("head_dim", d // h))),
            ("ff", int(cfg["intermediate_size"])),
            ("vocab", int(cfg["vocab_size"])),
            ("padded_vocab", int(cfg["padded_vocab"])),
            ("layers", int(cfg["num_hidden_layers"])),
            ("glu", bool(cfg["glu"])),
            ("tied", bool(cfg["tie_word_embeddings"])))


def leaf_specs(s: Dict[str, Any]) -> Dict[str, Tuple[tuple, str, float]]:
    """name -> (shape, kind, std); ``kind`` is ``normal`` or ``norm``."""
    d, h, kv, hd, ff = s["d"], s["heads"], s["kv"], s["hd"], s["ff"]
    L, V = s["layers"], s["vocab"]
    spec = {
        "embed": ((V, d), "normal", 1.0 / d),
        "out_norm": ((d,), "norm", 0.0),
        "norm1": ((L, d), "norm", 0.0),
        "norm2": ((L, d), "norm", 0.0),
        "wq": ((L, d, h * hd), "normal", d ** -0.5),
        "wk": ((L, d, kv * hd), "normal", d ** -0.5),
        "wv": ((L, d, kv * hd), "normal", d ** -0.5),
        "wo": ((L, h * hd, d), "normal", (h * hd) ** -0.5),
        "w_in": ((L, d, ff), "normal", d ** -0.5),
        "w_out": ((L, ff, d), "normal", ff ** -0.5),
    }
    if s["glu"]:
        spec["w_gate"] = ((L, d, ff), "normal", d ** -0.5)
    if not s["tied"]:
        spec["lm_head"] = ((V, d), "normal", d ** -0.5)
    return spec


def _leaf(key, shape, kind, std, stacked):
    import jax
    import jax.numpy as jnp

    def slab(k, shp):
        z = jax.random.normal(k, shp, jnp.float32)
        if kind == "norm":
            return (1.0 + 0.1 * z).astype(jnp.bfloat16)
        return (z * std).astype(jnp.bfloat16)

    if not stacked:
        return slab(key, shape)
    # One layer at a time: the float32 draw of a whole stack never lives.
    keys = jax.random.split(key, shape[0])
    return jax.lax.map(lambda k: slab(k, shape[1:]), keys)


def _make(sizes, words):
    import jax
    import jax.numpy as jnp

    s = dict(sizes)
    key = jax.random.key(0)
    for i in range(4):
        key = jax.random.fold_in(key, words[i])
    w = {name: _leaf(jax.random.fold_in(key, _LEAF_IDS[name]), shape, kind,
                     std, stacked=name not in ("embed", "lm_head",
                                               "out_norm"))
         for name, (shape, kind, std) in leaf_specs(s).items()}
    pad = s["padded_vocab"] - s["vocab"]

    def rows(a):
        return jnp.pad(a, ((0, pad), (0, 0))) if pad else a

    ffn = {"wi": w["w_in"], "wo": w["w_out"]}
    if s["glu"]:
        ffn["wg"] = w["w_gate"]
    tree = {"embed": rows(w["embed"]), "out_norm": w["out_norm"],
            "periods": {"pos0": {
                "norm1": w["norm1"], "norm2": w["norm2"], "ffn": ffn,
                "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")}}}}
    if not s["tied"]:
        tree["lm_head"] = rows(w["lm_head"])
    return tree


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax
    return jax.jit(_make, static_argnums=0)


def make_weights(cfg: Dict[str, Any], seed: int):
    """The served weights of ``cfg`` for ``seed``: one jitted call."""
    return _jitted()(sizes_of(cfg), seed_words(seed))


def abstract_weights(cfg: Dict[str, Any]):
    import jax
    return jax.eval_shape(functools.partial(_make, sizes_of(cfg)),
                          seed_words(0))


def neutral(tree) -> Dict[str, Any]:
    """The reference's view of :func:`make_weights`'s tree (no copies;
    the embedding and head keep their padding rows)."""
    p = tree["periods"]["pos0"]
    out = {"embed": tree["embed"], "out_norm": tree["out_norm"],
           "norm1": p["norm1"], "norm2": p["norm2"],
           "w_in": p["ffn"]["wi"], "w_out": p["ffn"]["wo"]}
    out.update(p["attn"])
    if "wg" in p["ffn"]:
        out["w_gate"] = p["ffn"]["wg"]
    if "lm_head" in tree:
        out["lm_head"] = tree["lm_head"]
    return out
