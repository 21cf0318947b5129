"""Multi-tenant orchestration validation on 8 virtual CPU devices.

Run as a subprocess by tests/test_distributed.py (auto-collected).  Covers
the tenancy acceptance contract on the real 8-way mem ring:

* per-tenant telemetry (served / spilled / pruned histograms) is bit-exact
  against the extended ref oracle for every program variant — uni / bi /
  pruned / load-balanced / hierarchical / group-masked — on both the pull
  and push paths,
* tenant share swaps are retrace-free: swapping the tenant-id lane, the
  window composition and the active budget on one jitted pull hits a
  single jit cache entry,
* the orchestrator end-to-end: board-anchored tenant leases on a 2x4
  fabric, schedule-composed request windows through the real datapath,
  measured per-tenant demand re-fitting the windows (interactive demand
  cap + work-conserving batch spill).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import bridge, ref, steering  # noqa: E402
from repro.core.control_plane import ControlPlane  # noqa: E402
from repro.core.memport import MemPortTable  # noqa: E402
from repro.core.topology import Topology  # noqa: E402
from repro.orchestrator import Orchestrator, TenantSpec  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

TELEM_FIELDS = ("slot_served", "loopback_served", "spilled", "pruned",
                "traffic", "epoch_cw", "epoch_ccw", "slot_intra",
                "tier_hops", "tenant_served", "tenant_spilled",
                "tenant_pruned")


def check_telem(name, got, exp):
    for f in TELEM_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(exp, f)),
            err_msg=f"{name}: {f}")
    print(f"ok: telemetry {name} == oracle")


def tenant_oracle_checks():
    """Tenant lane bit-exact vs the oracle for all six program variants."""
    mesh8 = make_mesh((8,), ("data",))
    n, ppn, page = 8, 8, 16
    rng = np.random.default_rng(41)
    pool = jnp.asarray(rng.normal(size=(n * ppn, page)).astype(np.float32))
    table = MemPortTable.striped(48, n, ppn)
    want = jnp.asarray(rng.integers(-1, 48, size=(n, 7)).astype(np.int32))
    lane = jnp.asarray(rng.integers(0, 4, size=(n, 7)).astype(np.int32))
    ab = jnp.asarray(rng.integers(1, 4, size=(n,)).astype(np.int32))

    topo = Topology.boards(2, 4)
    hier = steering.hierarchical_program(topo)
    mask = np.asarray(hier.rank_epoch) >= 0
    r8 = np.arange(n)
    mask[0, :] = topo.pair_intra(r8, (r8 + 1) % n)
    bi = steering.bidirectional_program(n)
    variants = [
        ("uni", steering.unidirectional_program(n)),
        ("bi", bi),
        ("pruned", steering.pruned_program(bi, [1, 2, 6])),
        ("load_balanced", steering.load_balanced_program(
            n, np.asarray([6, 3, 2, 0, 0, 1, 4], float))),
        ("hierarchical", hier),
        ("masked", steering.masked_ranks_program(hier, mask)),
    ]
    pull = jax.jit(functools.partial(
        bridge.pull_pages, mesh=mesh8, budget=3, topology=topo,
        collect_telemetry=True))
    push = jax.jit(functools.partial(
        bridge.push_pages, mesh=mesh8, budget=2, topology=topo,
        collect_telemetry=True))
    dest = np.stack([np.arange(4) + 6 * node for node in range(n)])
    dlane = jnp.asarray((dest % 4).astype(np.int32))
    payload = rng.normal(size=(n, 4, page)).astype(np.float32)
    for name, prog in variants:
        _, telem = pull(pool, want, table, program=prog,
                        active_budget=ab, tenant_ids=lane)
        exp = ref.expected_transfer_telemetry(
            np.asarray(want), table, prog, num_nodes=n, budget=3,
            active_budget=np.asarray(ab), topology=topo,
            tenant_ids=np.asarray(lane))
        check_telem(f"pull {name} tenants", telem, exp)
        # reconciliation: tenant sums == untagged counters
        np.testing.assert_array_equal(
            np.asarray(telem.tenant_served).sum(-1),
            np.asarray(telem.served_total()))
        _, ptelem = push(pool, jnp.asarray(dest), jnp.asarray(payload),
                         table, program=prog, tenant_ids=dlane)
        check_telem(f"push {name} tenants", ptelem,
                    ref.expected_transfer_telemetry(
                        dest, table, prog, num_nodes=n, budget=2,
                        topology=topo, tenant_ids=np.asarray(dlane)))

    # acceptance: tenant share swaps never retrace.  New lanes, new
    # windows (a different active budget) and new programs all hit the
    # single compiled entry per callable.
    for seed in (1, 2, 3):
        r2 = np.random.default_rng(seed)
        lane2 = jnp.asarray(r2.integers(0, 4, size=(n, 7)), jnp.int32)
        ab2 = jnp.asarray(r2.integers(1, 4, size=(n,)), jnp.int32)
        pull(pool, want, table, program=bi, active_budget=ab2,
             tenant_ids=lane2)
    assert pull._cache_size() == 1, pull._cache_size()
    assert push._cache_size() == 1, push._cache_size()
    print("ok: tenant share swaps retrace-free (1 cache entry)")


def orchestrator_e2e_checks():
    """Register -> lease -> compose -> measure -> re-fit on the real ring."""
    mesh8 = make_mesh((8,), ("data",))
    topo = Topology.boards(2, 4)
    n, ppn, page = 8, 16, 8
    cp = ControlPlane(n, ppn, num_logical=n * ppn, topology=topo)
    orc = Orchestrator(cp, budget=8, page_bytes=page * 4, control_period=1,
                       migrate=False)
    orc.register(TenantSpec(0, "chat", qos="interactive", share=1.0,
                            page_quota=32))
    orc.register(TenantSpec(1, "crawl", qos="batch", share=1.0))
    d0, l0 = orc.request_lease(0, 16)
    d1, l1 = orc.request_lease(1, 64, policy="striped")
    assert d0.admitted and d1.admitted
    # board anchoring: tenant 0's lease lives on board 0
    g = np.asarray(topo.group)
    home_col = np.asarray(cp.table().home)
    assert {int(g[int(home_col[p])]) for p in l0.region.page_ids} == {0}

    # chat offers 2 pages/node, crawl floods with 8/node
    chat_ids = np.asarray(l0.region.page_ids)
    crawl_ids = np.asarray(l1.region.page_ids)
    backlogs = {0: [chat_ids[i * 2:(i + 1) * 2].tolist() for i in range(n)],
                1: [crawl_ids[i * 8:(i + 1) * 8].tolist()
                    for i in range(n)]}
    want, lane, taken = orc.compose_requests(backlogs)
    assert want.shape[0] == n
    pool = jnp.asarray(np.random.default_rng(0).normal(
        size=(n * ppn, page)).astype(np.float32))
    out, telem = bridge.pull_pages(
        pool, jnp.asarray(want), orc.table(), mesh=mesh8,
        budget=orc.budget, program=orc.route_program(),
        active_budget=jnp.asarray(orc.active_budget()),
        topology=topo, collect_telemetry=True,
        tenant_ids=jnp.asarray(lane))
    exp = ref.expected_transfer_telemetry(
        want, orc.table(), orc.route_program(), num_nodes=n,
        budget=orc.budget, active_budget=orc.active_budget(),
        topology=topo, tenant_ids=lane)
    check_telem("orchestrator composed round", telem, exp)
    # the composed result is bit-exact vs the page oracle too
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(ref.pull_pages_ref(pool, jnp.asarray(want), orc.table(),
                                      pages_per_node=ppn,
                                      program=orc.route_program())))

    rep = orc.step(telem)
    assert rep["refit"]
    w = orc.schedule.windows
    # chat demand-capped (2/node), crawl takes the spilled budget
    assert w[0] >= 2 and w[1] > w[0], w
    assert sum(w.values()) <= orc.budget
    served = np.asarray(telem.tenant_served).sum(0)
    assert served[0] == 2 * n, served        # every chat page served
    print(f"ok: orchestrator e2e (windows {w}, chat served {served[0]}, "
          f"crawl served {served[1]})")
    print(orc.describe())


def kv_append_pad_checks():
    """A batch not divisible by the mesh must not phantom-write page 0.

    append() pads the per-node destination lists when b % n != 0; a zero
    pad would be a live push of all-zero payloads into logical page 0
    (sequence 0's first pooled KV page) on every flush step.
    """
    from repro.core import kvbridge
    mesh8 = make_mesh((8,), ("data",))
    b, kv, hd, pt, mp, n = 5, 2, 4, 4, 2, 8
    rng = np.random.default_rng(53)
    cache = kvbridge.init_cache(1, b, pt * mp, pt, kv, hd, mesh=mesh8,
                                mem_axis="data", dtype=jnp.float32)
    layer = jax.tree.map(lambda x: x[0], cache.layers)
    tails = rng.normal(size=(b, pt, kv, hd)).astype(np.float32)
    layer = kvbridge.PagedKVLayer(
        k_pool=layer.k_pool, v_pool=layer.v_pool,
        tail_k=jnp.asarray(tails), tail_v=jnp.asarray(tails))
    lengths = jnp.full((b,), pt - 1, jnp.int32)   # every tail flushes
    k_new = jnp.asarray(rng.normal(size=(b, kv, hd)).astype(np.float32))
    out = kvbridge.append(layer, cache.table, lengths, k_new, k_new,
                          page_tokens=pt, max_pages=mp, mesh=mesh8,
                          mem_axis="data", budget=2)
    home = np.asarray(cache.table.home)
    slot = np.asarray(cache.table.slot)
    ppn_kv = out.k_pool.shape[0] // n
    row0 = home[0] * ppn_kv + slot[0]             # sequence 0, page 0
    exp = tails[0].copy()
    exp[pt - 1] = np.asarray(k_new[0])
    np.testing.assert_array_equal(np.asarray(out.k_pool)[row0], exp)
    print("ok: kv append pad rows stay FREE (no phantom page-0 write)")


def main():
    assert jax.device_count() == 8, jax.devices()
    tenant_oracle_checks()
    orchestrator_e2e_checks()
    kv_append_pad_checks()
    print("ALL OK")


if __name__ == "__main__":
    main()
