"""Disaggregated-optimizer-state (zero_bridge) validation on 8 devices."""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import zero_bridge  # noqa: E402
from repro.core.control_plane import ControlPlane  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main():
    assert jax.device_count() == 8
    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    tree = {
        "w1": jnp.asarray(rng.normal(size=(40, 30)).astype(np.float32)),
        "b1": jnp.asarray(rng.normal(size=(30,)).astype(np.float32)),
        "nested": {"w2": jnp.asarray(
            rng.normal(size=(30, 17)).astype(np.float32))},
    }
    n = 4
    packer = zero_bridge.TreePacker.plan(tree, page_elems=64)
    per_node = -(-packer.num_pages // n)
    cp = ControlPlane(n, per_node + 4, packer.num_pages)

    store = zero_bridge.create_store(tree, mesh=mesh, mem_axis="data",
                                     page_elems=64, budget=4, cp=cp)
    got = zero_bridge.pull_tree(store, mesh=mesh)
    for k in ("w1", "b1"):
        np.testing.assert_allclose(got[k], tree[k], atol=1e-6, err_msg=k)
    np.testing.assert_allclose(got["nested"]["w2"], tree["nested"]["w2"],
                               atol=1e-6)
    print("ok: store/pull roundtrip")

    # update-in-pool cycle: pull, mutate, push, re-pull
    tree2 = jax.tree.map(lambda x: x * 2 + 1, got)
    store = zero_bridge.push_tree(store, tree2, mesh=mesh)
    got2 = zero_bridge.pull_tree(store, mesh=mesh)
    np.testing.assert_allclose(got2["w1"], tree["w1"] * 2 + 1, atol=1e-6)
    print("ok: update cycle")

    # elastic remap after node failure, restore from checkpoint image
    store = zero_bridge.rehome_after_failure(store, cp, failed_node=1,
                                             restore_tree=tree2, mesh=mesh)
    got3 = zero_bridge.pull_tree(store, mesh=mesh)
    np.testing.assert_allclose(got3["nested"]["w2"],
                               tree["nested"]["w2"] * 2 + 1, atol=1e-6)
    assert not np.any(np.asarray(store.table.home) == 1)
    print("ok: elastic remap restore")

    # pipelined round engine: a channels=4 store round-trips bit-exactly
    # (push and pull both run the multi-channel datapath)
    import dataclasses
    store4 = dataclasses.replace(store, channels=4)
    store4 = zero_bridge.push_tree(store4, tree2, mesh=mesh)
    got4 = zero_bridge.pull_tree(store4, mesh=mesh)
    for a, b in zip(jax.tree.leaves(got4), jax.tree.leaves(got3)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("ok: channels=4 store roundtrip bit-exact")

    print("ALL OK")


if __name__ == "__main__":
    main()
