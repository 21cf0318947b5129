"""A configuration with routed experts and sliding-window and full layers
runs through ``run_cell`` end to end on the CPU, with no edit to the
harness: 4 layers (3 on a 16-token window, 1 full), 8 experts top-2 in
each (``fixtures/tiny-moe-window.json``), served by the bridge-backed
batcher and compared with a plain float32 reference that this file
supplies.  The run reads ``correct``; a planted fault in the served
weights, or a reference that ignores the window, reads not correct.

Only the mean gap is held to a limit (``fixtures/tiny-moe-window.check.json``,
0.05).  The program routes from bfloat16 activations, the reference from
float32, so a token whose k-th and (k+1)-th router scores nearly tie can
take another expert on each side, and the sequence's later positions
differ too.  Over seeds 11-18 (CPU) the program's mean gap read
0.0024-0.0215 and its widest gap 0.23-1.90; with every expert chosen
(k = 8 of 8: no discrete choice) the mean gap read 0.00015-0.00033, as
the dense fixture does.  The planted faults read 0.27-0.44 (expert 0's
down projection zeroed) and 1.16-1.52 (window ignored); their widest gaps
(1.93-6.40) overlap the program's, so the widest gap is not compared."""
import functools

import numpy as np
import pytest

from harness import spec

FIX = spec.BENCH / "tests" / "fixtures"
_D = spec.load_module(spec.BENCH / "reference" / "dense_gqa.py",
                      "bench_reference_dense_gqa")


class MoeWindowReference:
    """Plain reference of a decoder whose layers are full or sliding-window
    GQA attention, each followed by a routed SwiGLU block: pre-RMSNorm,
    rotate-half RoPE, softmax router over every expert, the top
    ``num_experts_per_tok`` gates renormalised to sum 1.  Every expert is
    computed for every token (no capacity, nothing dropped) and weighted
    by its gate, 0 where not chosen.  A query at position ``s`` sees keys
    ``t <= s`` and, on a window layer, ``s - t < sliding_window``.

    Float32 at ``HIGHEST``; ``control="int8"``: every linear layer from
    int8 operands.  ``ignore_window=True`` is a planted fault: every layer
    attends over the whole causal context.
    """

    def __init__(self, ignore_window: bool = False):
        self.ignore_window = ignore_window

    @staticmethod
    def arch(cfg):
        return {"norm": "rms", "glu": True, "act": "silu"}

    def logit_gaps(self, cfg, weights, tokens, targets, control=None):
        import jax.numpy as jnp
        items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str, bool))))
        tokens = jnp.asarray(tokens, jnp.int32)
        table = weights.get("lm_head", weights["embed"])
        ref = self._hidden(cfg, items, None, weights, tokens)
        ctl = (self._hidden(cfg, items, control, weights, tokens)
               if control is not None else None)
        gaps, cgaps = [], []
        for i in range(tokens.shape[0]):
            t = jnp.asarray(targets[i], jnp.int32)
            lr = _head_fn(items, None)(ref[i], weights["out_norm"], table)
            lc = (_head_fn(items, control)(ctl[i], weights["out_norm"],
                                           table)
                  if ctl is not None else None)
            g, c = _D._gaps_jit(lr, t, lc)
            gaps.append(np.asarray(g))
            if c is not None:
                cgaps.append(np.asarray(c))
        return np.stack(gaps), (np.stack(cgaps) if cgaps else None)

    def _hidden(self, cfg, items, quant, weights, tokens):
        import jax.numpy as jnp
        x = _D._embed_fn(items)(tokens, weights["embed"])
        for i, kind in enumerate(cfg["layer_types"]):
            window = 0
            if kind == "sliding_attention" and not self.ignore_window:
                window = int(cfg["sliding_window"])
            if "layers" in weights:
                leaves, row = weights["layers"][i]
            else:
                leaves = {k: v for k, v in weights.items()
                          if k not in ("embed", "lm_head", "out_norm")}
                row = i
            fn = _layer_fn(items, quant, window, row is None)
            x = fn(x, leaves) if row is None else fn(x, leaves,
                                                     jnp.int32(row))
        return x


def _layer(cfg_items, quant, window, x, w, row=None):
    """One block over x [n, S, d]."""
    import jax
    import jax.numpy as jnp
    cfg = dict(cfg_items)
    HI = _D.HI
    experts = ("expert_in", "expert_gate", "expert_out")
    lw = {k: (v if row is None else v[row]) for k, v in w.items()}
    lw = {k: v if k in experts else v.astype(jnp.float32)
          for k, v in lw.items()}
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = float(cfg["rms_norm_eps"])
    scale = float(cfg["attention_multiplier"])
    n, s, d = x.shape
    pos = jnp.arange(s)
    seen = pos[:, None] >= pos[None, :]
    if window:
        seen = seen & (pos[:, None] - pos[None, :] < window)

    def attend(xs):
        hh = _D._norm("rms", xs, lw["norm1"], eps)
        q = _D._linear(hh, lw["wq"], quant).reshape(s, h, hd)
        k = _D._linear(hh, lw["wk"], quant).reshape(s, kv, hd)
        v = _D._linear(hh, lw["wv"], quant).reshape(s, kv, hd)
        q = _D._rope(q, pos, float(cfg["rope_theta"]))
        k = _D._rope(k, pos, float(cfg["rope_theta"]))
        qg = q.reshape(s, kv, h // kv, hd)
        sc = jnp.einsum("skgd,tkd->kgst", qg, k, precision=HI) * scale
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HI)
        return _D._linear(o.reshape(s, h * hd), lw["wo"], quant)

    x = x + jax.lax.map(attend, x)
    hh = _D._norm("rms", x, lw["norm2"], eps).reshape(n * s, d)
    probs = jax.nn.softmax(_D._linear(hh, lw["router"], quant), axis=-1)
    top, idx = jax.lax.top_k(probs, int(cfg["num_experts_per_tok"]))
    top = top / jnp.sum(top, -1, keepdims=True)
    e = lw["router"].shape[1]
    gates = jnp.sum(jax.nn.one_hot(idx, e) * top[..., None], axis=1)

    def expert(acc, args):
        g, wi, wg, wo = (a.astype(jnp.float32) for a in args)
        up = _D._act("silu", _D._linear(hh, wg, quant)) * \
            _D._linear(hh, wi, quant)
        return acc + g[:, None] * _D._linear(up, wo, quant), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(hh),
                          (gates.T, lw["expert_in"], lw["expert_gate"],
                           lw["expert_out"]))
    return x + out.reshape(n, s, d)


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_items, quant, window, unstacked):
    import jax
    return jax.jit(functools.partial(_layer, cfg_items, quant, window))


def _head(cfg_items, quant, x, norm, table):
    """Final norm and logits of one sequence: x [S, d] -> [S, V]."""
    import jax.numpy as jnp
    cfg = dict(cfg_items)
    hh = _D._norm("rms", x, norm.astype(jnp.float32),
                  float(cfg["rms_norm_eps"]))
    w = table[: cfg["vocab_size"]].astype(jnp.float32).T
    return _D._linear(hh, w, quant)


@functools.lru_cache(maxsize=None)
def _head_fn(cfg_items, quant):
    import jax
    return jax.jit(functools.partial(_head, cfg_items, quant))


def moe_window_cell(reference=None):
    cell = spec.Cell(
        name="tiny-moe-window.chat", chips=1,
        config=spec.load_json(FIX / "tiny-moe-window.json"),
        traffic=spec.load_json(FIX / "tiny-mix.json"),
        check=spec.load_json(FIX / "tiny-moe-window.check.json"))
    ref = reference or MoeWindowReference()
    cell.reference = lambda: ref
    return cell


def expert_zeroed(engine):
    """Expert 0's down projection is zero in every layer of the served
    tree (the reference keeps the weights made from the seed)."""
    import jax
    params = jax.tree.map(lambda a: a, engine.params)
    for block in params["periods"].values():
        block["moe"]["wo"] = block["moe"]["wo"].at[:, 0].set(0)
    engine.params = params


def _run(bench_run, cell, **kw):
    return bench_run.run_cell(cell, seed=kw.pop("seed", 11), seconds=8.0,
                              trace=False, require_chip=False, **kw)


def test_routed_window_cell_runs_and_reads_correct(bench_run):
    cell = moe_window_cell()
    res = _run(bench_run, cell)
    c = res["_compare"]
    assert res["correct"], c
    assert c["compared_tokens"] >= cell.check["compared_tokens_min"]
    # the window was reached: some compared request is longer than it
    assert c["longest_tokens"] > cell.config["sliding_window"]


@pytest.mark.parametrize("fault", ["expert_zeroed", "window_ignored"])
def test_planted_fault_reads_not_correct(bench_run, fault):
    if fault == "expert_zeroed":
        cell = moe_window_cell()
        res = _run(bench_run, cell, engine_hook=expert_zeroed)
    else:
        cell = moe_window_cell(MoeWindowReference(ignore_window=True))
        res = _run(bench_run, cell)
    assert not res["correct"], res["_compare"]
    assert res["checks"]["mean_logit_gap"]["value"] > \
        cell.check["mean_logit_gap"]["limit"]
