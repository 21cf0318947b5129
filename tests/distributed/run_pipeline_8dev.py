"""Pipeline-parallel validation on 8 virtual devices (4 stages x 2 data).

Checks: (1) the GPipe schedule over ppermute circuits reproduces the
sequential stack bit-for-bit; (2) it is differentiable end-to-end (grads
match the sequential reference); (3) the HLO contains the stage-to-stage
collective-permute route.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.parallel import pipeline  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

S, M, MB, D = 4, 6, 3, 16  # stages, microbatches, microbatch size, width


def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def sequential(params, x):
    for i in range(S):
        x = stage_fn(jax.tree.map(lambda a, i=i: a[i], params), x)
    return x


def main():
    assert jax.device_count() == 8
    mesh = make_mesh((4, 2), ("stage", "data"))
    rng = np.random.default_rng(0)
    params = {
        "w": jnp.asarray(rng.normal(size=(S, D, D)).astype(np.float32)) * 0.3,
        "b": jnp.asarray(rng.normal(size=(S, D)).astype(np.float32)) * 0.1,
    }
    x = jnp.asarray(rng.normal(size=(M * MB, D)).astype(np.float32))
    x_mb = pipeline.split_microbatches(x, M)

    run = jax.jit(lambda p, xm: pipeline.pipeline_apply(
        stage_fn, p, xm, mesh=mesh, stage_axis="stage"))
    got = pipeline.merge_microbatches(run(params, x_mb))
    exp = sequential(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-5)
    print("ok: pipeline == sequential")

    def loss_pipe(p):
        return (pipeline.merge_microbatches(pipeline.pipeline_apply(
            stage_fn, p, x_mb, mesh=mesh, stage_axis="stage")) ** 2).sum()

    def loss_seq(p):
        return (sequential(p, x) ** 2).sum()

    g1 = jax.grad(loss_pipe)(params)
    g2 = jax.grad(loss_seq)(params)
    np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g2["w"]),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(g1["b"]), np.asarray(g2["b"]),
                               atol=2e-4)
    print("ok: pipeline backward == sequential backward")

    hlo = jax.jit(lambda p, xm: pipeline.pipeline_apply(
        stage_fn, p, xm, mesh=mesh, stage_axis="stage")).lower(
        params, x_mb).compile().as_text()
    assert "collective-permute" in hlo
    print("ok: stage route is a collective-permute circuit")
    print("ALL OK")


if __name__ == "__main__":
    main()
