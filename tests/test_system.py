"""End-to-end behaviour tests for the framework.

* config registry: all 10 assigned archs load; analytic parameter counts
  match the published model sizes (the config-fidelity check);
* training integration: a reduced model trains for 12 steps end-to-end
  (data pipeline -> train step -> checkpoint -> resume) and the resumed
  run is bit-identical;
* serving integration: greedy decode agrees across all three KV placements
  (local / bridge_pull / bridge_push) on a model with mixed SWA+full layers.
"""
import dataclasses
import pathlib
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.config import SHAPES, OptimConfig, RunConfig, ShapeConfig
from repro.data.pipeline import SyntheticLM
from repro.models import transformer
from repro.serve import step as serve_step_mod
from repro.train import step as train_step_mod

# published sizes (B params): total, active
PUBLISHED = {
    "internvl2-2b": (1.9, 1.9),          # LM backbone of the 2B VLM
    "granite-moe-1b-a400m": (1.3, 0.4),
    "phi3_5-moe-42b-a6_6b": (41.9, 6.6),
    "recurrentgemma-9b": (8.5, 8.5),
    "seamless-m4t-medium": (0.6, 0.6),   # decoder+encoder backbone
    "h2o-danube-3-4b": (4.0, 4.0),
    "gemma3-12b": (11.8, 11.8),
    "granite-3-8b": (8.2, 8.2),
    "starcoder2-7b": (7.4, 7.4),
    "xlstm-125m": (0.09, 0.09),
}


def test_registry_has_all_assigned_archs():
    assert len(configs.lm_archs()) == 10
    assert len(SHAPES) == 4


@pytest.mark.parametrize("arch", configs.lm_archs())
def test_param_counts_match_published(arch):
    cfg = configs.get_config(arch)
    total, active = PUBLISHED[arch]
    assert cfg.param_count() / 1e9 == pytest.approx(total, rel=0.15)
    assert cfg.active_param_count() / 1e9 == pytest.approx(active, rel=0.15)


def test_train_checkpoint_resume_bitwise():
    cfg = dataclasses.replace(configs.get_reduced("granite-3-8b"),
                              dtype="float32")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", 32, 2, "train"),
                    optim=OptimConfig(lr=1e-3, warmup_steps=2,
                                      total_steps=12))
    step = jax.jit(train_step_mod.build_train_step(run), donate_argnums=(0,))
    data = SyntheticLM(cfg, 2, 32)

    def run_steps(state, lo, hi):
        for i in range(lo, hi):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
            state, metrics = step(state, batch)
        return state, metrics

    state = train_step_mod.make_train_state(run, jax.random.key(0))
    state, _ = run_steps(state, 0, 6)
    with tempfile.TemporaryDirectory() as d:
        ckpt = CheckpointManager(d)
        ckpt.save(6, state, extra={"step": 6})
        # continue directly
        direct, m_direct = run_steps(state, 6, 12)
        # resume from checkpoint and continue identically
        template = train_step_mod.make_train_state(run, jax.random.key(0))
        resumed, extra = ckpt.restore(template)
        resumed = jax.tree.map(jnp.asarray, resumed)
        resumed, m_resumed = run_steps(resumed, int(extra["step"]), 12)
    assert float(m_direct["loss"]) == pytest.approx(
        float(m_resumed["loss"]), abs=1e-6)
    for a, b in zip(jax.tree.leaves(direct.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["gemma3-12b", "granite-moe-1b-a400m"])
def test_serve_placements_agree(arch):
    """Mixed SWA+global layers (gemma3) and MoE (granite-moe)."""
    cfg = dataclasses.replace(configs.get_reduced(arch), dtype="float32")
    shape = ShapeConfig("s", 32, 2, "decode")
    params = transformer.init_params(cfg, jax.random.key(0))
    outs = {}
    for kv in ("local", "bridge_pull", "bridge_push"):
        run = RunConfig(model=cfg, shape=shape, kv_placement=kv)
        ops_ = serve_step_mod.make_cache_ops(run, mesh=None, max_len=32,
                                             page_tokens=8,
                                             dtype=jnp.float32)
        state = serve_step_mod.init_serve_state(run, 2, ops_)
        step = jax.jit(serve_step_mod.build_serve_step(run, ops_),
                       donate_argnums=(1,))
        tokens = jnp.asarray([3, 5], jnp.int32)
        seq = []
        for _ in range(12):
            tokens, state = step(params, state, tokens)
            seq.append(np.asarray(tokens))
        outs[kv] = np.stack(seq)
    np.testing.assert_array_equal(outs["local"], outs["bridge_pull"])
    np.testing.assert_array_equal(outs["local"], outs["bridge_push"])


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_dir(monkeypatch, tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes
    to the fixed directory inside the checkout."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
            got = compile_cache.enable_compile_cache()
            assert got == str(compile_cache.REPO_CACHE_DIR)
            assert compile_cache.REPO_CACHE_DIR.parent == \
                pathlib.Path(__file__).resolve().parents[1]
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_long_context_skip_policy():
    """The DESIGN.md §5 applicability matrix is what the code enforces."""
    expect_run = {"recurrentgemma-9b", "h2o-danube-3-4b", "gemma3-12b",
                  "xlstm-125m"}
    for arch in configs.lm_archs():
        cfg = configs.get_config(arch)
        assert cfg.supports_long_context == (arch in expect_run), arch
