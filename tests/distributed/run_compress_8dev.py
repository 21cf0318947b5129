"""Compressed-DP training validation on 8 virtual devices.

Checks: (1) the int8 ring all-reduce matches jnp mean-reduce within
quantization error; (2) a compressed train step tracks the uncompressed one
(error feedback bounds the drift); (3) the HLO contains s8 collective
traffic (the compression is real, not decorative).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import bridge  # noqa: E402
from repro.config import OptimConfig, RunConfig, ShapeConfig  # noqa: E402
from repro.data.pipeline import SyntheticLM  # noqa: E402
from repro.optim import compress as C  # noqa: E402
from repro.train import step as train_step_mod  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def test_ring_allreduce(mesh):
    n = 4
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 1001)).astype(np.float32)

    def body(xl):
        return C.compressed_ring_allreduce(xl[0], "data", n)[None]

    f = bridge.shard_map(body, mesh, in_specs=P("data", None),
                         out_specs=P("data", None), mem_axis="data")
    got = np.asarray(f(jnp.asarray(x)))
    want = x.mean(axis=0)
    for i in range(n):
        np.testing.assert_allclose(got[i], want, atol=2e-2)
    # all replicas agree bitwise
    for i in range(1, n):
        np.testing.assert_array_equal(got[i], got[0])
    print("ok: int8 ring all-reduce")


def test_compressed_training(mesh):
    cfg = dataclasses.replace(configs.get_reduced("granite-3-8b"),
                              dtype="float32")
    shape = ShapeConfig("t", 32, 8, "train")
    base = RunConfig(model=cfg, shape=shape,
                     optim=OptimConfig(lr=1e-3, warmup_steps=2,
                                       total_steps=10))
    comp = dataclasses.replace(
        base, optim=dataclasses.replace(base.optim, compress_grads=True))

    data = SyntheticLM(cfg, 8, 32)
    state_p = train_step_mod.make_train_state(base, jax.random.key(0))
    state_c = train_step_mod.make_train_state(comp, jax.random.key(0),
                                              compress=True, dp_size=4)
    from repro.parallel.sharding import make_rules
    rules = make_rules(base.sharding, mesh, global_batch=8)
    step_p = jax.jit(train_step_mod.build_train_step(base, mesh, rules))
    step_c = jax.jit(train_step_mod.build_train_step(comp, mesh, rules))

    lowered = jax.jit(
        train_step_mod.build_train_step(comp, mesh, rules)).lower(
        state_c, {k: jnp.asarray(v) for k, v in data.batch_at(0).items()})
    hlo = lowered.compile().as_text()
    assert "s8[" in hlo and "collective-permute" in hlo, \
        "int8 wire traffic missing from compressed step"
    print("ok: s8 collective-permute traffic present in HLO")

    losses_p, losses_c = [], []
    for i in range(8):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        state_p, mp = step_p(state_p, batch)
        state_c, mc = step_c(state_c, batch)
        losses_p.append(float(mp["loss"]))
        losses_c.append(float(mc["loss"]))
    print("plain:", [round(x, 4) for x in losses_p])
    print("compressed:", [round(x, 4) for x in losses_c])
    assert losses_c[-1] < losses_c[0], "compressed training diverged"
    assert abs(losses_c[-1] - losses_p[-1]) < 0.15, \
        "compressed training drifted too far from fp32 baseline"
    print("ok: compressed step tracks fp32 baseline")


def main():
    assert jax.device_count() == 8
    mesh = make_mesh((4, 2), ("data", "model"))
    test_ring_allreduce(mesh)
    test_compressed_training(mesh)
    print("ALL OK")


if __name__ == "__main__":
    main()
