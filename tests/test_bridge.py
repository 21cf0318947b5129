"""Bridge transfer-engine correctness: bridge == pure-jnp oracle.

Single-device (N=1 loopback) cases run here; multi-node ring tests run in a
subprocess with 8 virtual devices (see test_distributed.py).  Randomized
property tests live in test_bridge_properties.py (optional: hypothesis).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from topologies import fake_telem, make_pool

from repro.core import bridge, perfmodel, ref, steering
from repro.core.memport import FREE, MemPortTable
from repro.core.control_plane import ControlPlane
from repro.launch.mesh import make_mesh

make_pool_np = make_pool  # shared fixture (tests/topologies.py)


def test_pull_single_node_matches_ref():
    pool = make_pool_np(16, 8)
    table = MemPortTable.striped(12, 1, 16)
    want = jnp.asarray([[3, 0, 7, FREE, 11, 2]], jnp.int32)
    got = bridge.pull_pages(pool, want, table, mesh=None, budget=4)
    exp = ref.pull_pages_ref(pool, want, table, pages_per_node=16)
    np.testing.assert_allclose(got, exp)


def test_push_single_node_matches_ref():
    pool = make_pool_np(16, 8)
    table = MemPortTable.striped(12, 1, 16)
    dest = jnp.asarray([[5, 1, FREE, 9]], jnp.int32)
    payload = jnp.ones((1, 4, 8), jnp.float32) * jnp.arange(4)[None, :, None]
    got = bridge.push_pages(pool, dest, payload, table, mesh=None, budget=2)
    exp = ref.push_pages_ref(pool, dest, payload, table, pages_per_node=16)
    np.testing.assert_allclose(got, exp)


def test_memport_translate_free_passthrough():
    t = MemPortTable.striped(8, 2, 4)
    home, slot = t.translate(jnp.asarray([0, FREE, 7], jnp.int32))
    assert home[1] == FREE and slot[1] == FREE
    assert home[0] == 0 and slot[0] == 0
    assert home[7 % 3 if False else 2] >= 0


def test_memport_runtime_reprogram():
    t = MemPortTable.striped(8, 2, 4)
    t2 = t.program(np.array([3]), np.array([1]), np.array([2]))
    assert int(t2.home[3]) == 1 and int(t2.slot[3]) == 2
    # untouched rows preserved
    assert int(t2.home[0]) == int(t.home[0])


def test_control_plane_alloc_and_fail():
    cp = ControlPlane(num_nodes=4, pages_per_node=8, num_logical=64)
    region = cp.allocate(16, "kv", policy="striped")
    occ = cp.occupancy()
    assert occ.sum() == 16 and occ.max() == 4
    plan = cp.fail_node(2)
    assert len(plan) == 4  # node 2 held 4 pages
    assert all(s.new_home != 2 for s in plan)
    occ = cp.occupancy()
    assert occ[2] == 0 and occ.sum() == 16
    # table stays consistent
    t = cp.table()
    assert not np.any(np.asarray(t.home) == 2)
    region2 = cp.allocate(8, policy="hashed")
    t2 = cp.table()
    homes = np.asarray(t2.home)[region2.page_ids]
    assert not np.any(homes == 2)


def test_control_plane_straggler_rate_limits():
    cp = ControlPlane(num_nodes=4, pages_per_node=8, num_logical=8)
    for step in range(8):
        for n in range(4):
            cp.record_step_time(n, 1.0 if n != 3 else 2.5)
    budgets = cp.rate_limits(static_budget=8)
    assert list(budgets[:3]) == [8, 8, 8]
    assert budgets[3] == 4


def test_rate_limited_pull_matches_ref():
    """Throttled budget (overprovisioned rounds) still returns every page."""
    pool = make_pool_np(32, 4)
    table = MemPortTable.striped(24, 1, 32)
    want = jnp.arange(24, dtype=jnp.int32)[None, :]
    got = bridge.pull_pages(pool, want, table, mesh=None, budget=8,
                            overprovision=2, active_budget=jnp.int32(5))
    exp = ref.pull_pages_ref(pool, want, table, pages_per_node=32)
    np.testing.assert_allclose(got, exp)


def test_rate_limited_pull_single_node_drops_tail():
    """Regression: the n == 1 fast path must honour ``active_budget``.

    With budget=8, overprovision=1 and active_budget=5, 3 rounds serve only
    the first 15 of 24 requests — on a 1-device mesh exactly like on an
    N-device mesh (the rest spill off the final round and return zeros).
    """
    pool = make_pool_np(32, 4)
    table = MemPortTable.striped(24, 1, 32)
    want = jnp.arange(24, dtype=jnp.int32)[None, :]
    got = np.asarray(bridge.pull_pages(
        pool, want, table, mesh=None, budget=8, overprovision=1,
        active_budget=jnp.int32(5)))
    exp = np.asarray(ref.pull_pages_ref(pool, want, table, pages_per_node=32))
    np.testing.assert_allclose(got[0, :15], exp[0, :15])
    np.testing.assert_array_equal(got[0, 15:], np.zeros_like(exp[0, 15:]))


def test_loopback_pull_pads_multidim_pages():
    """Regression: the n == 1 path must trim round padding on the request
    dim, not the second-to-last *page* dim (multi-dim pages + pad > 0)."""
    rng = np.random.default_rng(5)
    pool = jnp.asarray(rng.normal(size=(8, 4, 2, 3)).astype(np.float32))
    table = MemPortTable.striped(8, 1, 8)
    want = jnp.asarray([[0, 3, 5, FREE, 7, 2]], jnp.int32)  # 6 reqs, budget 4
    got = bridge.pull_pages(pool, want, table, mesh=None, budget=4)
    exp = ref.pull_pages_ref(pool, want, table, pages_per_node=8)
    assert got.shape == (1, 6, 4, 2, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp))


def test_rate_limits_spill_restore_ends_with_clean_measurement():
    """Regression: the spill-feedback restore must key on the *last*
    measurement, not the EWMA (which never decays to zero), or a straggler
    could never be throttled again after a single historic spill."""
    from repro.telemetry import TelemetryAggregator
    n = 4
    cp = ControlPlane(num_nodes=n, pages_per_node=8, num_logical=8)
    for _ in range(8):
        for node in range(n):
            cp.record_step_time(node, 2.5 if node == 3 else 1.0)
    agg = TelemetryAggregator(n)

    def telem(spilled):
        return fake_telem(n, 4 * np.eye(n, dtype=np.int32), spilled=spilled)

    agg.update(telem([0, 0, 0, 6]))          # throttled step spilled
    assert cp.rate_limits(8, telemetry=agg)[3] == 8   # restore
    agg.update(telem([0, 0, 0, 0]))          # clean step measured
    assert agg.spilled[3] > 0                # EWMA still remembers...
    assert cp.rate_limits(8, telemetry=agg)[3] == 4   # ...throttle resumes


def test_rate_limited_push_single_node_drops_tail():
    """Regression: the write path must honour ``active_budget`` too.

    Pull throttled while push didn't — now both share the spill semantics:
    with budget=8, overprovision=1 and active_budget=5, 3 rounds write only
    the first 15 of 24 pages; the rest spill and their slots stay untouched.
    """
    pool = make_pool_np(32, 4)
    table = MemPortTable.striped(24, 1, 32)
    dest = jnp.arange(24, dtype=jnp.int32)[None, :]
    payload = (jnp.ones((1, 24, 4), jnp.float32)
               * jnp.arange(1, 25)[None, :, None])
    got = np.asarray(bridge.push_pages(
        pool, dest, payload, table, mesh=None, budget=8,
        active_budget=jnp.int32(5)))
    served = ref.rate_limit_mask(24, 8, 5)
    assert served.sum() == 15
    masked = jnp.where(jnp.asarray(served)[None, :], dest, FREE)
    exp = np.asarray(ref.push_pages_ref(pool, masked, payload, table,
                                        pages_per_node=32))
    np.testing.assert_allclose(got, exp)
    # the spilled pages' slots hold their original contents
    flat = np.asarray(ref.flat_index(table, jnp.arange(15, 24), 32))
    np.testing.assert_allclose(got[flat], np.asarray(pool)[flat])
    # overprovisioned rounds absorb the throttle: every page lands
    got_all = np.asarray(bridge.push_pages(
        pool, dest, payload, table, mesh=None, budget=8, overprovision=2,
        active_budget=jnp.int32(5)))
    exp_all = np.asarray(ref.push_pages_ref(pool, dest, payload, table,
                                            pages_per_node=32))
    np.testing.assert_allclose(got_all, exp_all)


# ---------------------------------------------------------------------------
# Route programs (runtime circuit schedules)
# ---------------------------------------------------------------------------

def test_route_program_epoch_counts():
    for n in (2, 3, 4, 5, 8, 16):
        uni = steering.unidirectional_program(n)
        bi = steering.bidirectional_program(n)
        uni.validate()
        bi.validate()
        assert uni.num_epochs() == n - 1
        assert bi.num_epochs() == n // 2
        assert list(uni.live_distances()) == list(range(1, n))
        assert list(bi.live_distances()) == list(range(1, n))


def test_route_program_is_runtime_pytree():
    """Programs are registered pytrees whose leaves are all arrays, so they
    can flow through jit without becoming static (no retrace on swap)."""
    p = steering.bidirectional_program(8)
    leaves = jax.tree.leaves(p)
    assert len(leaves) == 4  # offsets, epoch, live, rank_epoch (group mask)
    assert all(hasattr(l, "dtype") for l in leaves)
    # identical treedef AND shapes across every program variant -> same jit
    # cache entry (flat and hierarchical programs swap without retracing)
    from repro.core.topology import Topology
    t2 = jax.tree.structure(p)
    for q in (steering.unidirectional_program(8),
              steering.hierarchical_program(Topology.boards(2, 4))):
        assert jax.tree.structure(q) == t2
        assert all(a.shape == b.shape for a, b in
                   zip(jax.tree.leaves(q), leaves))


def test_bidirectional_offsets_shortest_way():
    p = steering.bidirectional_program(8)
    off = np.asarray(p.offsets)
    np.testing.assert_array_equal(off, [1, 2, 3, 4, -3, -2, -1])
    assert p.hops().max() == 4


def test_pruned_program_compacts_epochs():
    base = steering.bidirectional_program(8)
    p = steering.pruned_program(base, [2, 5, 7])
    p.validate()
    assert list(p.live_distances()) == [2, 5, 7]
    # cw: {+2}; ccw: {-3 (d=5), -1 (d=7)} -> 2 epochs, shortest first
    assert p.num_epochs() == 2
    ep = np.asarray(p.epoch)
    assert ep[6] == 0 and ep[4] == 1 and ep[1] == 0  # d=7, d=5, d=2
    with pytest.raises(ValueError):
        steering.pruned_program(base, [8])


def test_link_avoiding_program_directions():
    for bad in (+1, -1):
        p = steering.link_avoiding_program(8, bad)
        p.validate()
        off = np.asarray(p.offsets)
        assert (np.sign(off) == -bad).all()
    with pytest.raises(ValueError):
        steering.link_avoiding_program(8, 0)


def test_route_program_validate_rejects_incongruent():
    p = steering.unidirectional_program(4)
    bad = dataclasses.replace(p, offsets=jnp.asarray([1, 3, 3], jnp.int32))
    with pytest.raises(ValueError):
        bad.validate()
    # an inconsistent group mask (dead slot still serving ranks) is caught
    ghost = dataclasses.replace(
        p, live=jnp.asarray([True, False, True]))
    with pytest.raises(ValueError):
        ghost.validate()


def test_bridge_rejects_wrong_sized_program():
    with pytest.raises(ValueError):
        bridge._resolve_program(steering.unidirectional_program(4), 8)


def test_ref_oracle_honours_programs():
    """Requests whose ring distance has no wired circuit come back zeroed."""
    n, ppn = 4, 8
    pool = make_pool_np(n * ppn, 4)
    table = MemPortTable.striped(12, n, ppn)
    want = jnp.asarray(np.tile(np.arange(12, dtype=np.int32), (n, 1)))
    full = np.asarray(ref.pull_pages_ref(pool, want, table,
                                         pages_per_node=ppn))
    pruned = steering.pruned_program(steering.bidirectional_program(n), [1, 3])
    got = np.asarray(ref.pull_pages_ref(pool, want, table,
                                        pages_per_node=ppn, program=pruned))
    home = np.asarray(table.home)
    for node in range(n):
        for r in range(12):
            d = (home[r] - node) % n
            if d in (0, 1, 3):
                np.testing.assert_allclose(got[node, r], full[node, r])
            else:
                np.testing.assert_array_equal(got[node, r], 0.0)


def test_loopback_honours_program():
    """The n == 1 fast path applies the same program semantics (and oracle)
    as the N-device path: unwired logical distances drop their pages."""
    tn, ppn = 4, 8
    pool = make_pool_np(tn * ppn, 4)
    table = MemPortTable.striped(12, tn, ppn)
    want = jnp.asarray(np.arange(12, dtype=np.int32)[None, :])
    prog = steering.pruned_program(steering.bidirectional_program(tn), [1, 3])
    got = bridge.pull_pages(pool, want, table, mesh=None, budget=4,
                            table_nodes=tn, program=prog)
    exp = ref.pull_pages_ref(pool, want, table, pages_per_node=ppn,
                             program=prog)
    np.testing.assert_allclose(got, exp)
    full = np.asarray(ref.pull_pages_ref(pool, want, table,
                                         pages_per_node=ppn))
    assert not np.array_equal(np.asarray(got), full)  # distance 2 dropped
    # push path: unwired writes are dropped too
    payload = jnp.ones((1, 12, 4), jnp.float32)
    got_p = bridge.push_pages(pool, want, payload, table, mesh=None,
                              budget=4, table_nodes=tn, program=prog)
    exp_p = ref.push_pages_ref(pool, want, payload, table,
                               pages_per_node=ppn, program=prog)
    np.testing.assert_allclose(got_p, exp_p)
    # wrong-sized programs are rejected on the loopback path as well
    with pytest.raises(ValueError):
        bridge.pull_pages(pool, want, table, mesh=None, budget=4,
                          table_nodes=tn,
                          program=steering.bidirectional_program(8))


def test_control_plane_route_program():
    cp = ControlPlane(num_nodes=4, pages_per_node=8, num_logical=64)
    cp.allocate(8, policy="affinity", affinity=2)
    # node-0 requesters only reach distance 2
    p = cp.route_program(requesters=[0])
    assert list(p.live_distances()) == [2]
    # all requesters: distances {2-j mod 4} = {1, 2, 3}
    assert list(cp.route_program().live_distances()) == [1, 2, 3]
    # link failure reroutes everything the other way round
    cp.report_link_failure(+1)
    p = cp.route_program()
    off = np.asarray(p.offsets)
    assert (off[np.asarray(p.live)] < 0).all()
    cp.clear_link_failure()
    p = cp.route_program(prune=False)
    assert p.num_epochs() == 2  # bidirectional again: ceil(4/2)


def test_perfmodel_route_costs():
    uni = steering.unidirectional_program(8)
    bi = steering.bidirectional_program(8)
    s_uni = perfmodel.route_epoch_stats(uni)
    s_bi = perfmodel.route_epoch_stats(bi)
    assert s_uni["num_epochs"] == 7 and s_bi["num_epochs"] == 4
    assert s_bi["total_hops"] < s_uni["total_hops"]
    for eb in (True, False):
        assert (perfmodel.predict_round_latency_us(bi, 1 << 18, 8,
                                                   edge_buffer=eb)
                < perfmodel.predict_round_latency_us(uni, 1 << 18, 8,
                                                     edge_buffer=eb))
    pruned = steering.pruned_program(bi, [2])
    assert perfmodel.route_epoch_stats(pruned)["live_slots"] == 1


# ---------------------------------------------------------------------------
# ControlPlane fail_node / revive_node interplay
# ---------------------------------------------------------------------------

def test_fail_node_quarantines_slots():
    cp = ControlPlane(num_nodes=4, pages_per_node=8, num_logical=64)
    cp.allocate(16, policy="striped")
    cp.fail_node(1)
    assert cp.free_slots(1) == 0  # quarantined, not reusable
    # new allocations can never land on the dead node
    region = cp.allocate(8, policy="hashed")
    homes = np.asarray(cp.table().home)[region.page_ids]
    assert not np.any(homes == 1)


def test_revive_then_second_failure_rehomes_correctly():
    cp = ControlPlane(num_nodes=4, pages_per_node=8, num_logical=64)
    cp.allocate(12, policy="striped")
    cp.fail_node(1)
    cp.revive_node(1)
    # revived node's free list excludes nothing (its pages all moved away)
    assert cp.free_slots(1) == 8
    cp.allocate(4, policy="affinity", affinity=1)
    plan = cp.fail_node(1)
    assert len(plan) == 4
    assert all(s.old_home == 1 and s.new_home != 1 for s in plan)
    home, slot = np.asarray(cp._home), np.asarray(cp._slot)
    mapped = home != FREE
    # no slot double-booked after the fail -> revive -> fail cycle
    pairs = set(zip(home[mapped].tolist(), slot[mapped].tolist()))
    assert len(pairs) == mapped.sum()
    assert not np.any(home == 1)


def test_revive_preserves_occupied_slots():
    """Slots that still appear in the table are not handed back as free."""
    cp = ControlPlane(num_nodes=2, pages_per_node=6, num_logical=8)
    cp.allocate(2, policy="affinity", affinity=1)
    cp.fail_node(1)          # pages rehomed to node 0
    cp.revive_node(1)
    assert cp.free_slots(1) == 6
    cp.allocate(3, policy="affinity", affinity=1)
    cp.fail_node(0)          # node 0's pages (incl. migrated) move to node 1
    home = np.asarray(cp.table().home)
    mapped = home != FREE
    assert (home[mapped] == 1).all()


def test_route_program_keeps_failed_ranks_distances():
    """Regression: a failed node's *rank* still issues bridge requests (the
    mesh never shrinks), so pruning must not drop the distances it needs.

    2-node repro: fail node 1 -> all pages homed on node 0; rank 1 reaches
    them at ring distance 1, which an alive-nodes-only prune would cut —
    silently zeroing every page rank 1 pulls (e.g. zero_bridge restore)."""
    cp = ControlPlane(num_nodes=2, pages_per_node=8, num_logical=8)
    cp.allocate(4, policy="striped")
    cp.fail_node(1)
    prog = cp.route_program()
    assert list(prog.live_distances()) == [1]
    # pulled through the oracle: rank 1's requests survive the program
    pool = make_pool_np(16, 4)
    want = jnp.asarray(np.tile(np.arange(4, dtype=np.int32), (2, 1)))
    got = ref.pull_pages_ref(pool, want, cp.table(), pages_per_node=8,
                             program=prog)
    full = ref.pull_pages_ref(pool, want, cp.table(), pages_per_node=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full))


def test_release_respects_slot_quarantine():
    """fail -> release -> revive: releasing a region must not hand slots
    back to a dead node's free list (a heartbeat monitor may mark a node
    dead before any remap ran); revive reclaims them from the table."""
    cp = ControlPlane(num_nodes=2, pages_per_node=8, num_logical=16)
    region = cp.allocate(6, policy="affinity", affinity=1)
    # monitor-style death: marked dead, pages not (yet) remapped
    cp.nodes[1].alive = False
    cp._free[1] = []
    cp.release(region)
    assert cp.free_slots(1) == 0          # quarantine respected
    assert np.all(np.asarray(cp._home) == FREE)
    cp.revive_node(1)
    # revive rebuilds from the table: the released slots come back
    assert cp.free_slots(1) == 8
    region2 = cp.allocate(4, policy="affinity", affinity=1)
    assert np.all(np.asarray(cp.table().home)[region2.page_ids] == 1)
    # the full fail_node path stays consistent with release
    cp2 = ControlPlane(num_nodes=4, pages_per_node=8, num_logical=16)
    r = cp2.allocate(8, policy="striped")
    cp2.fail_node(2)
    cp2.release(r)                         # all pages re-homed to survivors
    assert cp2.free_slots(2) == 0
    assert sum(cp2.free_slots(i) for i in (0, 1, 3)) == 24


def test_migration_plan_roundtrips_through_table():
    """Applying the emitted MigrationSteps to the *old* table reproduces the
    control plane's new table exactly (the plan is a complete delta)."""
    cp = ControlPlane(num_nodes=4, pages_per_node=8, num_logical=64)
    cp.allocate(16, policy="striped")
    old_table = cp.table()
    plan = cp.fail_node(2)
    ids = np.asarray([s.page_id for s in plan])
    homes = np.asarray([s.new_home for s in plan])
    slots = np.asarray([s.new_slot for s in plan])
    rebuilt = old_table.program(ids, homes, slots)
    new_table = cp.table()
    np.testing.assert_array_equal(np.asarray(rebuilt.home),
                                  np.asarray(new_table.home))
    np.testing.assert_array_equal(np.asarray(rebuilt.slot),
                                  np.asarray(new_table.slot))
    # and the old coordinates in the plan match the old table
    for s in plan:
        assert int(old_table.home[s.page_id]) == s.old_home
        assert int(old_table.slot[s.page_id]) == s.old_slot


# ---------------------------------------------------------------------------
# Pipelined multi-channel round engine + push/pull parity bugfixes
# ---------------------------------------------------------------------------

def _one_node_mesh():
    return make_mesh((1,), ("data",))


def test_bridge_meshes_have_auto_axes():
    """The mesh builder pins Auto axes; the bridge refuses Explicit ones
    (jax.make_mesh's default) rather than mis-shard inside its maps."""
    from jax.sharding import AxisType, PartitionSpec as P
    assert _one_node_mesh().axis_types == (AxisType.Auto,)
    explicit = jax.make_mesh((1,), ("data",),
                             axis_types=(AxisType.Explicit,))
    with pytest.raises(ValueError, match="Auto axes"):
        bridge.shard_map(lambda x: x, explicit, in_specs=P("data"),
                         out_specs=P("data"), mem_axis="data")


def _run_pull_local(pool, want_row, active_budget, *, budget, rounds,
                    channels=1):
    """Drive bridge._pull_local directly (1-node mem axis) — the only way
    to hand the scan body inputs the public wrapper pre-sanitizes."""
    import functools
    from jax.sharding import PartitionSpec as P
    mesh = _one_node_mesh()
    table = MemPortTable.striped(pool.shape[0], 1, pool.shape[0])
    prog = steering.bidirectional_program(1)
    body = functools.partial(bridge._pull_local, axis="data", num_nodes=1,
                             budget=budget, rounds=rounds, edge_buffer=True,
                             channels=channels)

    def mapped(pool_l, want_l, ab):
        return body(pool_l, want_l[0], table, ab[0], prog)[None]

    return np.asarray(bridge.shard_map(
        mapped, mesh,
        in_specs=(P("data", None), P("data", None), P("data")),
        out_specs=P("data", None, None), mem_axis="data",
    )(pool, jnp.asarray(want_row)[None],
      jnp.asarray([active_budget], jnp.int32))[0])


def _run_push_local(pool, dest_row, payload_rows, active_budget, *, budget,
                    rounds, channels=1):
    import functools
    from jax.sharding import PartitionSpec as P
    mesh = _one_node_mesh()
    table = MemPortTable.striped(pool.shape[0], 1, pool.shape[0])
    prog = steering.bidirectional_program(1)
    body = functools.partial(bridge._push_local, axis="data", num_nodes=1,
                             budget=budget, rounds=rounds, channels=channels)

    def mapped(pool_l, dest_l, pay_l, ab):
        return body(pool_l, dest_l[0], pay_l[0], table, ab[0], prog)

    return np.asarray(bridge.shard_map(
        mapped, mesh,
        in_specs=(P("data", None), P("data", None),
                  P("data", None, None), P("data")),
        out_specs=P("data", None), mem_axis="data",
    )(pool, jnp.asarray(dest_row)[None],
      jnp.asarray(payload_rows)[None],
      jnp.asarray([active_budget], jnp.int32)))


def test_pull_push_signature_parity():
    """Regression: push_pages historically lacked pull's edge_buffer knob.
    Every shared bridge knob must exist on both paths with one default."""
    import inspect
    pull = inspect.signature(bridge.pull_pages).parameters
    push = inspect.signature(bridge.push_pages).parameters
    shared = ("mesh", "mem_axis", "budget", "edge_buffer", "channels",
              "overprovision", "active_budget", "program", "table_nodes",
              "collect_telemetry", "topology")
    for name in shared:
        assert name in pull, f"pull_pages lost {name!r}"
        assert name in push, f"push_pages missing {name!r}"
        assert pull[name].default == push[name].default, name
    locals_ = (inspect.signature(bridge._pull_local).parameters,
               inspect.signature(bridge._push_local).parameters)
    for name in ("edge_buffer", "channels"):
        assert all(name in p for p in locals_), name


def test_pull_local_rounds_zero_returns_request_shaped_zeros():
    """Regression: rounds == 0 with a non-empty ``want`` must return the
    [want.shape[0], *page] all-dropped zeros the docstring promises, not a
    zero-row array (the caller indexes it by request position)."""
    pool = make_pool_np(16, 4)
    want = np.asarray([3, 0, FREE, 7, 11], np.int32)
    got = _run_pull_local(pool, want, 8, budget=8, rounds=0)
    assert got.shape == (5, 4)
    np.testing.assert_array_equal(got, np.zeros((5, 4), np.float32))
    # telemetry counts every live request as a rate-limiter drop
    from repro.telemetry.counters import transfer_telemetry
    from repro.core.topology import Topology
    topo = Topology.flat(1)
    telem = transfer_telemetry(
        jnp.asarray(want), MemPortTable.striped(16, 1, 16),
        steering.bidirectional_program(1), jnp.int32(8), my=0, num_nodes=1,
        budget=8, rounds=0, topo=topo.tables(), num_groups=1)
    assert int(telem.spilled) == 4  # the FREE hole is not a live request
    assert int(telem.served_total()) == 0


@pytest.mark.parametrize("channels", [1, 2])
def test_pull_local_overdriven_budget_clamps(channels):
    """Regression: an ``active_budget`` above ``budget`` used to walk the
    round pointer past the final window, so ``dynamic_slice`` silently
    clamped and re-served tail requests into the wrong output rows."""
    pool = make_pool_np(16, 4)
    table = MemPortTable.striped(16, 1, 16)
    want = np.arange(16, dtype=np.int32)
    got = _run_pull_local(pool, want, 12, budget=8, rounds=2,
                          channels=channels)
    exp = np.asarray(ref.pull_pages_ref(pool, jnp.asarray(want)[None],
                                        table, pages_per_node=16))[0]
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("channels", [1, 2])
def test_push_local_overdriven_budget_clamps(channels):
    """Write-path twin of the clamp regression, plus spill accounting: the
    telemetry oracle (which clips) must agree with what actually landed."""
    pool = make_pool_np(16, 4)
    table = MemPortTable.striped(16, 1, 16)
    dest = np.arange(12, dtype=np.int32)
    padded = steering.pad_requests(dest, 2, 8)
    payload = np.zeros((16, 4), np.float32)
    payload[:12] = np.arange(1, 13, dtype=np.float32)[:, None]
    got = _run_push_local(pool, padded, payload, 9, budget=8, rounds=2,
                          channels=channels)
    exp = np.asarray(ref.push_pages_ref(
        pool, jnp.asarray(dest)[None], jnp.asarray(payload[None, :12]),
        table, pages_per_node=16))
    np.testing.assert_array_equal(got, exp)
    telem = ref.expected_transfer_telemetry(
        padded[None], table, None, num_nodes=1, budget=8, active_budget=9,
        overprovision=2)
    assert int(np.asarray(telem.spilled).sum()) == 0  # window covers all 12


def test_channels_loopback_and_serial_paths_identical():
    """channels is a no-op on the loopback path and must be accepted
    everywhere the serial engine runs (edge_buffer=False, n == 1)."""
    pool = make_pool_np(16, 8)
    table = MemPortTable.striped(12, 1, 16)
    want = jnp.asarray([[3, 0, 7, FREE, 11, 2]], jnp.int32)
    base = np.asarray(bridge.pull_pages(pool, want, table, mesh=None,
                                        budget=4))
    for ch in (2, 4):
        got = np.asarray(bridge.pull_pages(pool, want, table, mesh=None,
                                           budget=4, channels=ch))
        np.testing.assert_array_equal(got, base)
    with pytest.raises(ValueError):
        bridge.pull_pages(pool, want, table, mesh=None, budget=4, channels=0)
    with pytest.raises(ValueError):
        bridge.push_pages(pool, want, jnp.ones((1, 6, 8)), table, mesh=None,
                          budget=4, channels=-1)


def test_control_plane_select_channels():
    """Pipeline depth from measured wire occupancy: serial when idle or
    wire-bound (nothing worth hiding), deep when the RTT is a comparable
    share of the round (latency-bound: overlap wins)."""
    from repro.telemetry import TelemetryAggregator
    n = 8
    cp = ControlPlane(num_nodes=n, pages_per_node=8, num_logical=8)
    assert cp.select_channels(8, 1 << 18) == 1            # no measurement
    agg = TelemetryAggregator(n, page_bytes=4096)
    assert cp.select_channels(8, 4096, telemetry=agg) == 1  # idle wire
    tm = np.zeros((n, n), np.int32)
    for i in range(n):
        tm[i, (i + 1) % n] = 16
        tm[i, (i + 3) % n] = 8
    agg.update(fake_telem(n, tm))
    deep = cp.select_channels(8, 4096, telemetry=agg)      # latency-bound
    assert deep > 1
    assert deep <= 8
    assert cp.select_channels(8, 1 << 20, telemetry=agg) == 1  # wire-bound
    assert cp.select_channels(1, 4096, telemetry=agg) == 1     # budget floor
    # one step's raw BridgeTelemetry works like the aggregator
    assert cp.select_channels(8, 4096, telemetry=fake_telem(n, tm)) == deep
    # program-aware RTT: a schedule routing traffic the long way round pays
    # its real hop depth — the shortest-way fallback (min(d, N-d) = 1 hop
    # for distance 7) would call this wire-bound and stay serial
    tm_far = np.zeros((n, n), np.int32)
    for i in range(n):
        tm_far[i, (i + 7) % n] = 24
    agg_far = TelemetryAggregator(n, page_bytes=1 << 15)
    agg_far.update(fake_telem(n, tm_far))
    uni = steering.unidirectional_program(n)          # d=7 driven as +7 hops
    assert cp.select_channels(8, 1 << 15, telemetry=agg_far) == 1
    assert cp.select_channels(8, 1 << 15, telemetry=agg_far,
                              program=uni) > 1
