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

The tree is walked from the program's own (``resolve`` puts it in the
configuration as ``_program_params``), so a routed block (``moe``), a
period of several layer kinds (``periods/pos{i}``) or an unscanned
``tail`` needs no code here; a leaf the harness has no stream id for is
an error.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import numpy as np

# Fixed stream ids, by the leaf's name in the program's tree (a block's
# leaves by their path inside the block): a leaf's values do not depend on
# which other leaves exist.
_LEAF_IDS = {"embed": 1, "lm_head": 2, "out_norm": 3, "norm1": 4,
             "norm2": 5, "attn.wq": 6, "attn.wk": 7, "attn.wv": 8,
             "attn.wo": 9, "ffn.wi": 10, "ffn.wg": 11, "ffn.wo": 12,
             "moe.router": 13, "moe.wi": 14, "moe.wg": 15, "moe.wo": 16}
# The reference's names for a block's leaves.
_PLAIN = {"norm1": "norm1", "norm2": "norm2", "attn.wq": "wq",
          "attn.wk": "wk", "attn.wv": "wv", "attn.wo": "wo",
          "ffn.wi": "w_in", "ffn.wg": "w_gate", "ffn.wo": "w_out",
          "moe.router": "router", "moe.wi": "expert_in",
          "moe.wg": "expert_gate", "moe.wo": "expert_out"}
_NORMS = ("norm1", "norm2", "out_norm")


def seed_words(seed: int) -> np.ndarray:
    """``seed`` (any integer) as four 30-bit words for ``fold_in``."""
    s = int(seed)
    neg = 1 if s < 0 else 0
    s = abs(s)
    return np.array([(s >> (30 * i)) & (2 ** 30 - 1) for i in range(3)]
                    + [neg], np.uint32)


def _leaf_name(path: Tuple[str, ...]) -> str:
    """``periods/pos0/attn/wq`` -> ``attn.wq``; ``embed`` -> ``embed``."""
    inner = path[2:] if path[0] in ("periods", "tail") else path
    name = ".".join(inner)
    if name not in _LEAF_IDS:
        raise ValueError(f"the program's weight {'/'.join(path)} has no "
                         f"stream id in the harness")
    return name


def leaf_specs(cfg: Dict[str, Any]) -> Tuple[tuple, ...]:
    """How each leaf of the program's weight tree is drawn, hashable for
    the jit's key: ``(path, shape, kind, std, stream id, layers, draw)``.

    ``kind`` is ``normal`` or ``norm``; ``std`` is ``fan_in ** -0.5``
    along the contraction axis (the embedding ``1 / hidden``).  ``layers``
    is ``None`` for the embedding, head and final norm; otherwise the
    layer numbers the leaf holds, one per row of a stacked leaf
    (``periods/pos{i}``: layers ``i, i + period, ...``) or one for an
    unstacked ``tail`` leaf.  ``draw`` is ``(total layers, rows drawn
    for the embedding and head)``.
    """
    import jax
    tree = cfg["_program_params"]
    total = int(cfg["num_hidden_layers"])
    vocab = int(cfg["vocab_size"])
    hidden = int(cfg["hidden_size"])
    periods = tree.get("periods", {})
    n_per = len(periods)
    reps = (jax.tree.leaves(periods)[0].shape[0] if periods else 0)
    out = []
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        path = tuple(k.key for k in kp)
        name = _leaf_name(path)
        shape = tuple(int(x) for x in leaf.shape)
        if path[0] == "periods":
            i = int(path[1][len("pos"):])
            layers = tuple(i + r * n_per for r in range(reps))
        elif path[0] == "tail":
            layers = (reps * n_per + int(path[1][len("layer"):]),)
        else:
            layers = None
        if name in _NORMS:
            kind, std = "norm", 0.0
        elif name == "embed":
            kind, std = "normal", 1.0 / hidden
        elif name == "lm_head":
            kind, std = "normal", hidden ** -0.5
        else:
            kind, std = "normal", shape[-2] ** -0.5
        out.append((path, shape, kind, std, _LEAF_IDS[name], layers,
                    (total, vocab)))
    return tuple(out)


def _slab(key, shape, kind, std):
    import jax
    import jax.numpy as jnp
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return (1.0 + 0.1 * z).astype(jnp.bfloat16)
    return (z * std).astype(jnp.bfloat16)


def _leaf(key, spec):
    import jax
    import jax.numpy as jnp
    path, shape, kind, std, _, layers, (total, vocab) = spec
    if layers is None:
        if path[0] in ("embed", "lm_head"):
            # drawn at the vocabulary's size; the padding rows are zero
            a = _slab(key, (vocab,) + shape[1:], kind, std)
            pad = shape[0] - vocab
            return jnp.pad(a, ((0, pad), (0, 0))) if pad else a
        return _slab(key, shape, kind, std)
    # Layer l's slab is drawn from the l-th of ``total`` keys, whichever
    # stack holds it; one layer at a time, so the float32 draw of a whole
    # stack never lives.
    keys = jax.random.split(key, total)
    if path[0] == "tail":
        return _slab(keys[layers[0]], shape, kind, std)
    if layers != tuple(range(total)):
        keys = keys[np.asarray(layers)]
    return jax.lax.map(lambda k: _slab(k, shape[1:], kind, std), keys)


def _make(specs, words):
    import jax
    key = jax.random.key(0)
    for i in range(4):
        key = jax.random.fold_in(key, words[i])
    tree: Dict[str, Any] = {}
    for spec in specs:
        node = tree
        for k in spec[0][:-1]:
            node = node.setdefault(k, {})
        node[spec[0][-1]] = _leaf(jax.random.fold_in(key, spec[4]), spec)
    return tree


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax
    return jax.jit(_make, static_argnums=0)


def make_weights(cfg: Dict[str, Any], seed: int):
    """The served weights of ``cfg`` (as :func:`bench.run.resolve` gives
    it) for ``seed``: one jitted call."""
    return _jitted()(leaf_specs(cfg), seed_words(seed))


def abstract_weights(cfg: Dict[str, Any]):
    import jax
    return jax.eval_shape(functools.partial(_make, leaf_specs(cfg)),
                          seed_words(0))


def _plain(block: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for k, v in block.items():
        if isinstance(v, dict):
            out.update({_PLAIN[f"{k}.{kk}"]: vv for kk, vv in v.items()})
        else:
            out[_PLAIN[k]] = v
    return out


def neutral(tree) -> Dict[str, Any]:
    """The reference's view of :func:`make_weights`'s tree, under plain
    names and without copies (the embedding and head keep their padding
    rows).

    Where one stack holds every layer (``periods/pos0`` and no tail), a
    block's leaves are given flat, stacked by layer.  Otherwise
    ``layers`` lists, in layer order, each layer's ``(leaves, row)``: the
    leaves of the stack that holds it and its row there (``None`` for an
    unstacked tail layer).
    """
    out = {k: tree[k] for k in ("embed", "out_norm", "lm_head")
           if k in tree}
    periods, tail = tree.get("periods", {}), tree.get("tail", {})
    if len(periods) == 1 and not tail:
        out.update(_plain(periods["pos0"]))
        return out
    stacks = [_plain(periods[f"pos{i}"]) for i in range(len(periods))]
    reps = len(next(iter(stacks[0].values()))) if stacks else 0
    layers = [(stacks[i], r) for r in range(reps)
              for i in range(len(stacks))]
    layers += [(_plain(tail[f"layer{i}"]), None) for i in range(len(tail))]
    out["layers"] = tuple(layers)
    return out
