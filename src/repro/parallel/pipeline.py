"""GPipe-style pipeline parallelism over a mesh axis.

The pipeline is one more circuit on the pod fabric: stage s holds its
layer block's parameters; activations travel stage -> stage+1 over a static
``ppermute`` route (the same circuit-epoch primitive as the bridge), and
microbatches fill the pipe GPipe-fashion: at tick t, stage s processes
microbatch t - s, for M + S - 1 ticks.

Differentiable end-to-end: the schedule is plain traced JAX (scan over
ticks inside a partial-manual shard_map over the stage axis), so jax.grad
drives the backward pipe in reverse automatically.

Usage (see tests/distributed/run_pipeline_8dev.py):

    y = pipeline_apply(stage_fn, params_staged, x_mb, mesh=mesh,
                       stage_axis="stage")

  * ``stage_fn(stage_params, x) -> x`` applies ONE stage's layers;
  * ``params_staged`` leaves have a leading [num_stages] dim (sharded over
    the stage axis);
  * ``x_mb``: [num_micro, mb, ...] microbatched input (replicated);
  * returns [num_micro, mb, ...] pipeline output (replicated).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import bridge


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   params_staged: Any, x_mb: jax.Array, *, mesh: Mesh,
                   stage_axis: str = "stage") -> jax.Array:
    """Run the GPipe schedule; see module docstring."""
    s = mesh.shape[stage_axis]
    m = x_mb.shape[0]
    fwd = [(j, (j + 1) % s) for j in range(s)]

    def body(params_local, x_local):
        # params_local: [1, ...] leaves (this stage); x_local: [M, mb, ...]
        my = jax.lax.axis_index(stage_axis)
        p_mine = jax.tree.map(lambda a: a[0], params_local)
        ticks = m + s - 1

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t; others use the incoming buffer
            mb_idx = jnp.clip(t, 0, m - 1)
            inject = jnp.where((my == 0) & (t < m), 1.0, 0.0)
            x_in = inject * x_local[mb_idx] + (1.0 - inject) * buf
            y = stage_fn(p_mine, x_in)
            # last stage banks finished microbatch t - (S - 1)
            done_idx = jnp.clip(t - (s - 1), 0, m - 1)
            bank = (my == s - 1) & (t - (s - 1) >= 0)
            cur = outs[done_idx]
            outs = outs.at[done_idx].set(jnp.where(bank, y, cur))
            # circuit epoch: activations advance one stage
            buf_next = jax.lax.ppermute(y, stage_axis, perm=fwd)
            return (buf_next, outs), None

        buf0 = bridge._pvary(jnp.zeros(x_local.shape[1:], x_local.dtype),
                            stage_axis)
        outs0 = bridge._pvary(jnp.zeros_like(x_local), stage_axis)
        (_, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(ticks))
        # replicate the last stage's banked outputs to every stage
        outs = jax.lax.psum(
            jnp.where(my == s - 1, outs, jnp.zeros_like(outs)), stage_axis)
        return outs

    staged_spec = jax.tree.map(
        lambda _: P(stage_axis), params_staged,
        is_leaf=lambda x: hasattr(x, "shape"))
    return bridge.shard_map(
        body, mesh,
        in_specs=(staged_spec, P()), out_specs=P(),
        mem_axis=stage_axis,
    )(params_staged, x_mb)


def split_microbatches(x: jax.Array, num_micro: int) -> jax.Array:
    b = x.shape[0]
    assert b % num_micro == 0, (b, num_micro)
    return x.reshape(num_micro, b // num_micro, *x.shape[1:])


def merge_microbatches(x_mb: jax.Array) -> jax.Array:
    return x_mb.reshape(-1, *x_mb.shape[2:])
