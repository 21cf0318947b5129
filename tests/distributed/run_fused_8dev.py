"""Fused Pallas datapath parity on 8 virtual CPU devices.

The fused serve/gather/commit engines (one kernel pair + one collective
pair per round, see repro.kernels.bridge_gather) must serve exactly what
the numpy oracles say on a real N-device mesh, across the six steering
program variants x channel depths {1, 2, 4} x multi-tenant lanes — the
N-device face of the loopback-path contract in tests/test_fused_bridge.py
(which additionally fuzzes fused-vs-unfused over random ragged fabrics).

Program variants are runtime inputs, so the whole variant sweep reuses one
trace per (channels, engine) shape — the compile budget stays inside the
tier-1 subprocess timeout; fused-vs-unfused cross-checks are spot checks
here for the same reason.

Run as a subprocess by tests/test_distributed.py (auto-collected).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import bridge, ref, steering  # noqa: E402
from repro.core.memport import MemPortTable  # noqa: E402
from repro.core.topology import Topology  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

TELEM_FIELDS = ("slot_served", "loopback_served", "spilled", "pruned",
                "traffic", "epoch_cw", "epoch_ccw", "slot_intra",
                "tier_hops", "tenant_served", "tenant_spilled",
                "tenant_pruned")


def check_equal(name, got, exp):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp),
                                  err_msg=name)
    print(f"ok: {name}")


def check_telem(name, got, exp):
    for field in TELEM_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(exp, field)),
            err_msg=f"{name}: {field}")
    print(f"ok: {name} telemetry")


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = make_mesh((8,), ("data",))
    n, ppn, page = 8, 8, 4
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.normal(size=(n * ppn, page)).astype(np.float32))
    table = MemPortTable.striped(48, n, ppn)
    want = jnp.asarray(rng.integers(-1, 48, size=(n, 6)), jnp.int32)
    dest = jnp.asarray(rng.permutation(48).reshape(n, 6), jnp.int32)
    payload = jnp.asarray(rng.normal(size=(n, 6, page)), jnp.float32)
    tenants = jnp.asarray(rng.integers(0, 3, size=(n, 6)), jnp.int32)
    topo = Topology.boards(2, 4)

    # The six program variants of the steering suite (None = default full
    # bidirectional coverage).
    variants = {
        "uni": steering.unidirectional_program(n),
        "bi": steering.bidirectional_program(n),
        "pruned": steering.pruned_program(
            steering.bidirectional_program(n), [1, 2, 7]),
        "lb": steering.load_balanced_program(
            n, [1.0 + (d % 3) for d in range(1, n)]),
        "hier": steering.hierarchical_program(topo),
        "masked": steering.masked_ranks_program(
            steering.bidirectional_program(n),
            np.tile(np.array([1, 1, 0, 1, 1, 1, 0, 1], bool), (n - 1, 1))),
        "default": None,
    }

    with jax.set_mesh(mesh):
        # fused vs the numpy page oracles: six variants x channels {1,2,4}
        # (one trace per channels — programs swap as runtime inputs)
        for name, prog in variants.items():
            for ch in (1, 2, 4):
                got = bridge.pull_pages(pool, want, table, mesh=mesh,
                                        budget=3, channels=ch, program=prog,
                                        fused=True)
                exp = ref.pull_pages_ref(pool, want, table,
                                         pages_per_node=ppn, program=prog)
                check_equal(f"pull {name} ch={ch} fused vs oracle", got, exp)
                got = bridge.push_pages(pool, dest, payload, table,
                                        mesh=mesh, budget=3, channels=ch,
                                        program=prog, fused=True)
                exp = ref.push_pages_ref(pool, dest, payload, table,
                                         pages_per_node=ppn, program=prog)
                check_equal(f"push {name} ch={ch} fused vs oracle", got, exp)

        # fused telemetry vs the counter oracle, throttled + 3 tenant lanes
        # (again one trace across all variants)
        for name, prog in variants.items():
            tp = topo if name == "hier" else None
            _, telem = bridge.pull_pages(
                pool, want, table, mesh=mesh, budget=3, channels=2,
                program=prog, topology=tp, fused=True,
                collect_telemetry=True, tenant_ids=tenants, max_tenants=4,
                active_budget=jnp.int32(2))
            exp = ref.expected_transfer_telemetry(
                want, table, prog, num_nodes=n, budget=3, active_budget=2,
                topology=tp, tenant_ids=tenants, max_tenants=4)
            check_telem(f"pull {name} fused vs counter oracle", telem, exp)

        # fused vs unfused spot check: pages + telemetry bit-exact under
        # throttle + tenants at the deepest channel count (the loopback
        # property suite fuzzes this across random fabrics; this pins the
        # real-collective engines against each other once per datapath)
        kw = dict(mesh=mesh, budget=3, channels=4, collect_telemetry=True,
                  tenant_ids=tenants, max_tenants=4,
                  active_budget=jnp.int32(2))
        of, tf = bridge.pull_pages(pool, want, table, fused=True, **kw)
        ou, tu = bridge.pull_pages(pool, want, table, fused=False, **kw)
        check_equal("pull ch=4 fused==unfused", of, ou)
        check_telem("pull ch=4 fused==unfused", tf, tu)
        pf, ptf = bridge.push_pages(pool, dest, payload, table, fused=True,
                                    **kw)
        pu, ptu = bridge.push_pages(pool, dest, payload, table, fused=False,
                                    **kw)
        check_equal("push ch=4 fused==unfused", pf, pu)
        check_telem("push ch=4 fused==unfused", ptf, ptu)

        # edge_buffer=False has no fused engine: the knob must fall back
        # to the serial chain, not crash or diverge.
        o1 = bridge.pull_pages(pool, want, table, mesh=mesh, budget=3,
                               edge_buffer=False, fused=True)
        o2 = bridge.pull_pages(pool, want, table, mesh=mesh, budget=3,
                               edge_buffer=False, fused=False)
        check_equal("bufferless fallback", o1, o2)

    print("ALL OK")


if __name__ == "__main__":
    main()
