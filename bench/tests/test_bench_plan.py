"""The harness reads a configuration's structure from its file, layer by
layer (``harness.plan``), and what it reads on the dense configurations
is what it read before the plan: the same ``ModelConfig``, the same
weights bit for bit, the same least work.  A structure the program cannot
build is refused before any weight is made, naming the file's key."""
import copy
import dataclasses

import numpy as np
import pytest

from harness import plan, roofline, serve, spec, weights

FIX = spec.BENCH / "tests" / "fixtures"


def _config(name):
    return spec.load_json(spec.BENCH / "configs" / f"{name}.json")


def _tiny_starcoder2():
    """tiny-granite's widths with starcoder2's blocks, an untied head and
    a vocabulary that needs padding rows."""
    c = spec.load_json(FIX / "tiny-granite.json")
    c.update(model_type="starcoder2", vocab_size=8000,
             tie_word_embeddings=False, norm_epsilon=1e-6,
             sliding_window=4096)
    for k in ("rms_norm_eps", "attention_multiplier"):
        c.pop(k)
    c["deployment"] = dict(c["deployment"], registry="starcoder2-7b")
    return c


def _cell(config, name="plan-test"):
    return spec.Cell(name=name, chips=1, config=config, traffic={},
                     check={})


# ---- the harness before the plan, frozen ---------------------------------

_OLD_IDS = {"embed": 1, "lm_head": 2, "out_norm": 3, "norm1": 4,
            "norm2": 5, "wq": 6, "wk": 7, "wv": 8, "wo": 9, "w_in": 10,
            "w_gate": 11, "w_out": 12}


def _old_program_config(cfg):
    from repro import configs
    base = configs.get_config(cfg["deployment"]["registry"])
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return dataclasses.replace(
        base, num_layers=int(cfg["num_hidden_layers"]), d_model=d,
        num_heads=h, num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim", d // h)),
        d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"])


def _old_sizes_of(cfg):
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


def _old_leaf_specs(s):
    d, h, kv, hd, ff = s["d"], s["heads"], s["kv"], s["hd"], s["ff"]
    L, V = s["layers"], s["vocab"]
    spec_ = {
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
        spec_["w_gate"] = ((L, d, ff), "normal", d ** -0.5)
    if not s["tied"]:
        spec_["lm_head"] = ((V, d), "normal", d ** -0.5)
    return spec_


def _old_leaf(key, shape, kind, std, stacked):
    import jax
    import jax.numpy as jnp

    def slab(k, shp):
        z = jax.random.normal(k, shp, jnp.float32)
        if kind == "norm":
            return (1.0 + 0.1 * z).astype(jnp.bfloat16)
        return (z * std).astype(jnp.bfloat16)

    if not stacked:
        return slab(key, shape)
    keys = jax.random.split(key, shape[0])
    return jax.lax.map(lambda k: slab(k, shape[1:]), keys)


def _old_make(sizes, words):
    import jax
    import jax.numpy as jnp

    s = dict(sizes)
    key = jax.random.key(0)
    for i in range(4):
        key = jax.random.fold_in(key, words[i])
    w = {name: _old_leaf(jax.random.fold_in(key, _OLD_IDS[name]), shape,
                         kind, std, stacked=name not in ("embed", "lm_head",
                                                         "out_norm"))
         for name, (shape, kind, std) in _old_leaf_specs(s).items()}
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


def _old_make_weights(cfg, seed):
    import jax
    return jax.jit(_old_make, static_argnums=0)(_old_sizes_of(cfg),
                                                weights.seed_words(seed))


# ---- invariance on the dense configurations -------------------------------

@pytest.mark.parametrize("name", ["granite-3-8b", "starcoder2-7b"])
def test_resolve_builds_the_same_model_config(bench_run, name):
    cfg = _config(name)
    assert serve.program_config(cfg) == _old_program_config(cfg)
    resolved = bench_run.resolve(_cell(cfg))
    assert resolved["padded_vocab"] == _old_program_config(cfg).padded_vocab


@pytest.mark.parametrize("seed", [5, -2 ** 33 - 17])
@pytest.mark.parametrize("which", ["tiny-granite", "tiny-starcoder2"])
def test_dense_weights_are_bit_identical(bench_run, which, seed):
    import jax
    config = (spec.load_json(FIX / "tiny-granite.json")
              if which == "tiny-granite" else _tiny_starcoder2())
    cfg = bench_run.resolve(_cell(config))
    new = weights.make_weights(cfg, seed)
    old = _old_make_weights(cfg, seed)
    assert jax.tree.structure(new) == jax.tree.structure(old)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a).view(np.uint16),
                              np.asarray(b).view(np.uint16))
    plain = weights.neutral(new)
    assert "layers" not in plain
    assert plain["wq"] is new["periods"]["pos0"]["attn"]["wq"]
    assert ("lm_head" in plain) == (which == "tiny-starcoder2")


# name -> (matmul_params, weight_bytes, kv_token_bytes, step_work at
# visible 100, 200, ..., 800), as the harness read them before the plan
PINS = {
    "granite-3-8b": (3_389_009_920, 6_778_290_176, 65_536,
                     (55_167_877_120, 7_014_809_600)),
    "starcoder2-7b": (3_699_376_128, 7_399_056_384, 32_768,
                      (60_251_701_248, 7_517_357_056)),
}


@pytest.mark.parametrize("what", ["matmul_params", "weight_bytes",
                                  "kv_token_bytes", "step_work"])
@pytest.mark.parametrize("name", sorted(PINS))
def test_roofline_pins(name, what):
    cfg = dict(_config(name), glu=name == "granite-3-8b")
    got = {"matmul_params": roofline.matmul_params,
           "weight_bytes": roofline.weight_bytes,
           "kv_token_bytes": roofline.kv_token_bytes,
           "step_work": lambda c: roofline.step_work(
               c, list(range(100, 900, 100)))}[what](cfg)
    want = PINS[name][("matmul_params", "weight_bytes", "kv_token_bytes",
                       "step_work").index(what)]
    assert got == want


# ---- the plan itself --------------------------------------------------------

def _mellum_like(layers=4, **over):
    """Mellum2-12B-A2.5B-Instruct's structure at toy widths: windows on 3
    of every 4 layers, every layer routed, 8 experts top-2."""
    c = spec.load_json(FIX / "tiny-moe-window.json")
    c["num_hidden_layers"] = layers
    c["layer_types"] = (["sliding_attention"] * 3 + ["full_attention"]) \
        * (layers // 4) + ["sliding_attention"] * (layers % 4)
    c.update(over)
    return c


def test_starcoder2_window_is_never_a_window_layer():
    layers = plan.layer_plan(_config("starcoder2-7b"))
    assert len(layers) == 16
    assert {x.attn.kind for x in layers} == {plan.FULL}
    assert {x.ffn for x in layers} == {plan.FeedForward(plan.DENSE, 18432)}


def test_routed_window_plan_and_its_program():
    c = _mellum_like(layers=6)
    layers = plan.layer_plan(c)
    assert [x.attn.kind for x in layers] == [plan.WINDOW] * 3 + \
        [plan.FULL] + [plan.WINDOW] * 2
    assert {x.attn.window for x in layers if x.attn.kind == plan.WINDOW} \
        == {16}
    f = layers[0].ffn
    assert (f.kind, f.experts_held, f.router_width, f.experts_per_token,
            f.width, f.norm_topk_prob, f.shared_width) == \
        (plan.ROUTED, 8, 8, 2, 96, True, 0)
    model = serve.program_config(c)
    assert model.layer_pattern == ("swa", "swa", "swa", "full")
    assert model.layers == ("swa",) * 3 + ("full",) + ("swa",) * 2
    assert (model.window_size, model.num_experts, model.experts_per_token,
            model.d_ff, model.is_moe) == (16, 8, 2, 96, True)


def test_leading_dense_layers_and_shared_expert_are_read():
    c = _mellum_like(layers=4, num_dense_layers=1, num_shared_experts=1,
                     intermediate_size=384)
    layers = plan.layer_plan(c)
    assert [x.ffn.kind for x in layers] == [plan.DENSE] + [plan.ROUTED] * 3
    assert layers[0].ffn.width == 384
    assert layers[1].ffn.shared_width == 96
    c = _mellum_like(layers=4, mlp_layer_types=["dense", "sparse",
                                                "sparse", "dense"])
    assert [x.ffn.kind for x in plan.layer_plan(c)] == \
        [plan.DENSE, plan.ROUTED, plan.ROUTED, plan.DENSE]


def test_period_is_the_shortest_tiling():
    assert plan.period(("a",) * 5) == ("a",)
    assert plan.period(("s", "s", "s", "f") * 7) == ("s", "s", "s", "f")
    assert plan.period(("s", "s", "s", "f", "s", "s")) == \
        ("s", "s", "s", "f")
    assert plan.period(("s", "f", "f")) == ("s", "f", "f")


@pytest.mark.parametrize("over,key", [
    ({"num_shared_experts": 1}, "num_shared_experts"),
    ({"shared_expert_intermediate_size": 192},
     "shared_expert_intermediate_size"),
    ({"num_dense_layers": 1}, "num_dense_layers"),
    ({"mlp_layer_types": ["dense", "sparse", "sparse", "sparse"]},
     "mlp_layer_types"),
    ({"published": {"num_experts": 64}}, "num_experts"),
    ({"num_attention_heads": [4, 4, 4, 2]}, "num_attention_heads"),
    ({"num_key_value_heads": [2, 2, 1, 2]}, "num_key_value_heads"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"score_func": "sigmoid"}, "score_func"),
    ({"route_scale": 2.5}, "route_scale"),
    ({"layer_types": ["sliding_attention", "chunked_attention",
                      "sliding_attention", "full_attention"]},
     "layer_types"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_resolve_refuses_what_the_program_cannot_build(
        bench_run, monkeypatch, over, key):
    def never(*a, **k):
        raise AssertionError("weights made for a configuration the "
                             "program cannot build")
    monkeypatch.setattr(weights, "make_weights", never)
    cell = _cell(_mellum_like(layers=4, **copy.deepcopy(over)))
    cell.reference = lambda: _REF_ARCH
    with pytest.raises(plan.PlanError) as e:
        bench_run.run_cell(cell, seed=1, seconds=1.0, trace=False,
                           require_chip=False)
    assert e.value.key == key
    assert key in str(e.value)


class _REF_ARCH:
    @staticmethod
    def arch(cfg):
        return {"norm": "rms", "glu": True, "act": "silu"}


# ---- least work of routed and window layers --------------------------------

def _rf(**over):
    return dict(_mellum_like(layers=4, **over), glu=True)


def test_routed_flops_scale_with_k_not_with_experts_held():
    base = roofline.matmul_params(_rf())
    more = roofline.matmul_params(_rf(num_experts=64))
    d, w = 128, 96
    # the router scores every expert held; the experts a token reaches
    # stay k
    assert more - base == 4 * d * (64 - 8)
    k4 = roofline.matmul_params(_rf(num_experts_per_tok=4))
    assert k4 - base == 4 * (4 - 2) * 3 * d * w


def test_routed_weight_bytes_take_the_experts_counted():
    d, w = 128, 96
    least = roofline.weight_bytes(_rf())
    assert roofline.weight_bytes(_rf(), experts=2) == least
    assert roofline.weight_bytes(_rf(), experts=8) - least == \
        4 * 6 * 3 * d * w * 2
    flops, nbytes = roofline.step_work(_rf(), [10, 20], experts=5)
    assert nbytes - roofline.step_work(_rf(), [10, 20])[1] == \
        4 * 3 * 3 * d * w * 2
    # a cut router: experts not held here are reached by no one here
    cut = _rf(published={"num_experts": 12})
    assert roofline.least_experts(plan.layer_plan(cut)[0]) == 0


def test_window_layers_are_capped_at_the_window():
    c = _rf()
    kv_tok = 2 * 2 * 32 * 2                    # k, v x 2 heads x 32 x bf16
    assert roofline.kv_token_bytes(c) == 1 * kv_tok
    wf, wb = roofline.window_work(c, [5, 100])
    assert wb == 3 * ((5 + 16) + 2) * kv_tok
    assert wf == 3 * 4 * 4 * 32 * (5 + 16)
    f1, b1 = roofline.step_work(c, [100])
    f2, b2 = roofline.step_work(c, [1000])
    # beyond the window only the full layer reads more
    assert b2 - b1 == 900 * kv_tok
    assert f2 - f1 == 900 * 4 * 4 * 32


def test_kv_work_of_a_mixed_plan_counts_full_layers_only():
    c = _rf()
    flops, nbytes = roofline.kv_work(c, [100, 50])
    assert nbytes == (150 + 2) * 2 * 2 * 32 * 2
    assert flops == 4 * 4 * 32 * 150
    all_full = dict(c)
    all_full.pop("layer_types")
    assert roofline.kv_work(all_full, [100, 50]) == (4 * flops, 4 * nbytes)


def test_mixed_weights_follow_the_program_tree(bench_run):
    import jax
    cell = _cell(_mellum_like(layers=6))
    cell.reference = lambda: _REF_ARCH
    cfg = bench_run.resolve(cell)
    bench_run.check_layout(cfg)
    w = weights.make_weights(cfg, 3)
    assert set(w["periods"]) == {"pos0", "pos1", "pos2", "pos3"}
    assert set(w["tail"]) == {"layer0", "layer1"}
    assert w["periods"]["pos0"]["moe"]["wi"].shape == (1, 8, 128, 96)
    plain = weights.neutral(w)
    assert len(plain["layers"]) == 6
    leaves, row = plain["layers"][3]
    assert row == 0 and leaves["expert_out"] is \
        w["periods"]["pos3"]["moe"]["wo"]
    leaves, row = plain["layers"][5]
    assert row is None and leaves["router"] is \
        w["tail"]["layer1"]["moe"]["router"]
    # a layer's values depend on its number, not on where it is stacked
    four = bench_run.resolve(_cell_with_ref(_mellum_like(layers=4)))
    w4 = weights.make_weights(four, 3)
    for p in ("pos0", "pos3"):
        for a, b in zip(jax.tree.leaves(w["periods"][p]),
                        jax.tree.leaves(w4["periods"][p])):
            assert np.array_equal(np.asarray(a).view(np.uint16),
                                  np.asarray(b).view(np.uint16))
    # the padding rows of the embedding and the head are zero
    assert not np.asarray(w["lm_head"][8000:]).any()
    assert w["lm_head"].shape[0] == 8192


def _cell_with_ref(config):
    cell = _cell(config)
    cell.reference = lambda: _REF_ARCH
    return cell
