"""Multi-node bridge validation on 8 virtual CPU devices.

Run as a subprocess by tests/test_distributed.py (device count must be set
before jax initializes, so this cannot live inside the main pytest process).
Exits non-zero on any mismatch.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import bridge, ref, kvbridge, steering  # noqa: E402
from repro.core.memport import FREE, MemPortTable  # noqa: E402
from repro.core.control_plane import ControlPlane  # noqa: E402
from repro.core.topology import Topology  # noqa: E402
from repro.telemetry import TelemetryAggregator  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

TELEM_FIELDS = ("slot_served", "loopback_served", "spilled", "pruned",
                "traffic", "epoch_cw", "epoch_ccw", "slot_intra",
                "tier_hops", "tenant_served", "tenant_spilled",
                "tenant_pruned")


def check(name, got, exp, atol=1e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=atol,
                               err_msg=name)
    print(f"ok: {name}")


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = make_mesh((4, 2), ("data", "model"))
    n, ppn, page = 4, 8, 16
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(n * ppn, page)).astype(np.float32))

    with jax.set_mesh(mesh):
        # --- pull: striped placement, every node asks across the ring -------
        table = MemPortTable.striped(24, n, ppn)
        want = rng.integers(-1, 24, size=(n, 7)).astype(np.int32)
        got = bridge.pull_pages(pool, jnp.asarray(want), table, mesh=mesh,
                                budget=3)
        exp = ref.pull_pages_ref(pool, jnp.asarray(want), table,
                                 pages_per_node=ppn)
        check("pull striped", got, exp)

        # --- pull: adversarial placement (all pages on node 2) --------------
        cp = ControlPlane(num_nodes=n, pages_per_node=ppn, num_logical=8)
        cp.allocate(8, policy="affinity", affinity=2)
        t2 = cp.table()
        want2 = rng.integers(0, 8, size=(n, 5)).astype(np.int32)
        got = bridge.pull_pages(pool, jnp.asarray(want2), t2, mesh=mesh,
                                budget=2)
        exp = ref.pull_pages_ref(pool, jnp.asarray(want2), t2,
                                 pages_per_node=ppn)
        check("pull affinity(2)", got, exp)

        # --- pull: bufferless bridge gives identical results -----------------
        got = bridge.pull_pages(pool, jnp.asarray(want), table, mesh=mesh,
                                budget=3, edge_buffer=False)
        exp = ref.pull_pages_ref(pool, jnp.asarray(want), table,
                                 pages_per_node=ppn)
        check("pull bufferless", got, exp)

        # --- pull: runtime rate limiting (throttled budget) ------------------
        want3 = np.arange(16).reshape(4, 4).astype(np.int32)
        got = bridge.pull_pages(pool, jnp.asarray(want3), table, mesh=mesh,
                                budget=4, overprovision=2,
                                active_budget=jnp.int32(2))
        exp = ref.pull_pages_ref(pool, jnp.asarray(want3), table,
                                 pages_per_node=ppn)
        check("pull throttled", got, exp)

        # --- push: single-writer scatter -------------------------------------
        dest = np.full((n, 4), FREE, np.int32)
        for node in range(n):  # node i writes pages 6i .. 6i+3 (single writer)
            dest[node] = np.arange(4) + 6 * node
        payload = rng.normal(size=(n, 4, page)).astype(np.float32)
        got = bridge.push_pages(pool, jnp.asarray(dest), jnp.asarray(payload),
                                table, mesh=mesh, budget=2)
        exp = ref.push_pages_ref(pool, jnp.asarray(dest), jnp.asarray(payload),
                                 table, pages_per_node=ppn)
        check("push", got, exp)

        # --- elastic remap: fail a node, re-pull through new table -----------
        cp2 = ControlPlane(num_nodes=n, pages_per_node=ppn, num_logical=12)
        cp2.allocate(12, policy="striped")
        t3 = cp2.table()
        payload3 = rng.normal(size=(1, 12, page)).astype(np.float32)
        pool3 = jnp.zeros_like(pool)
        dest3 = np.full((n, 12), FREE, np.int32)
        dest3[0] = np.arange(12)
        pool3 = bridge.push_pages(pool3, jnp.asarray(dest3),
                                  jnp.asarray(np.broadcast_to(
                                      payload3, (n, 12, page))),
                                  t3, mesh=mesh, budget=4)
        plan = cp2.fail_node(1)
        t4 = cp2.table()
        # executor: copy migrated pages into their new homes (from the old
        # pool image, as a checkpoint restore would)
        flat_old = np.asarray(
            ref.flat_index(t3, jnp.arange(12, dtype=jnp.int32), ppn))
        pool_np = np.array(pool3)  # mutable copy
        for step in plan:
            pool_np[step.new_home * ppn + step.new_slot] = (
                pool_np[flat_old[step.page_id]])
        pool4 = jnp.asarray(pool_np)
        want4 = np.tile(np.arange(12, dtype=np.int32), (n, 1))
        got = bridge.pull_pages(pool4, jnp.asarray(want4), t4, mesh=mesh,
                                budget=4)
        exp = np.broadcast_to(payload3[0], (n, 12, page))
        check("pull after elastic remap", got, exp)

        # --- kvbridge: pull & push decode attention vs dense oracle ----------
        b, h, kv, hd, pt, mp = 4, 8, 4, 16, 4, 3
        cache = kvbridge.init_cache(1, b, pt * mp, pt, kv, hd, mesh=mesh,
                                    mem_axis="data", dtype=jnp.float32)
        layer = jax.tree.map(lambda x: x[0], cache.layers)
        lengths = jnp.asarray([5, 9, 0, 12], jnp.int32)
        s_max = pt * mp
        k_dense = rng.normal(size=(b, s_max, kv, hd)).astype(np.float32)
        v_dense = rng.normal(size=(b, s_max, kv, hd)).astype(np.float32)
        # fill pools + tails to mirror the dense cache
        kp = np.zeros(layer.k_pool.shape, np.float32)
        vp = np.zeros(layer.v_pool.shape, np.float32)
        tk = np.zeros((b, pt, kv, hd), np.float32)
        tv = np.zeros((b, pt, kv, hd), np.float32)
        home = np.asarray(cache.table.home)
        slot = np.asarray(cache.table.slot)
        ppn_kv = layer.k_pool.shape[0] // 4
        for bb in range(b):
            ln = int(lengths[bb])
            for p in range(mp):
                pid = bb * mp + p
                lo, hi = p * pt, min((p + 1) * pt, ln)
                if hi <= lo:
                    continue
                if hi - lo == pt:  # full page -> pool
                    row = home[pid] * ppn_kv + slot[pid]
                    kp[row, : hi - lo] = k_dense[bb, lo:hi]
                    vp[row, : hi - lo] = v_dense[bb, lo:hi]
                else:  # tail
                    tk[bb, : hi - lo] = k_dense[bb, lo:hi]
                    tv[bb, : hi - lo] = v_dense[bb, lo:hi]
        layer = kvbridge.PagedKVLayer(
            k_pool=jnp.asarray(kp), v_pool=jnp.asarray(vp),
            tail_k=jnp.asarray(tk), tail_v=jnp.asarray(tv))
        q = jnp.asarray(rng.normal(size=(b, h, hd)).astype(np.float32))
        oracle = kvbridge.decode_attention_ref(
            q, jnp.asarray(k_dense), jnp.asarray(v_dense), lengths)
        got_pull = kvbridge.decode_attention_pull(
            q, layer, cache.table, lengths, page_tokens=pt, max_pages=mp,
            mesh=mesh, mem_axis="data", budget=2)
        check("kv decode pull", got_pull, oracle, atol=2e-5)
        got_push = kvbridge.decode_attention_push(
            q, layer, cache.table, lengths, page_tokens=pt, max_pages=mp,
            mesh=mesh, mem_axis="data")
        check("kv decode push", got_push, oracle, atol=2e-5)

        # --- kvbridge append: tail write + page-boundary flush ---------------
        lens2 = jnp.asarray([3, 3, 3, 3], jnp.int32)
        layer2 = kvbridge.PagedKVLayer(
            k_pool=jnp.zeros_like(layer.k_pool),
            v_pool=jnp.zeros_like(layer.v_pool),
            tail_k=jnp.asarray(tk), tail_v=jnp.asarray(tv))
        k_new = jnp.asarray(rng.normal(size=(b, kv, hd)).astype(np.float32))
        v_new = jnp.asarray(rng.normal(size=(b, kv, hd)).astype(np.float32))
        layer3 = kvbridge.append(layer2, cache.table, lens2, k_new, v_new,
                                 page_tokens=pt, max_pages=mp, mesh=mesh,
                                 mem_axis="data")
        # page 0 of every sequence flushed (length 3 -> 4 == page_tokens)
        for bb in range(b):
            row = home[bb * mp] * ppn_kv + slot[bb * mp]
            exp_page = np.asarray(tk[bb]).copy()
            exp_page[3] = np.asarray(k_new[bb])
            check(f"append flush b{bb}",
                  np.asarray(layer3.k_pool)[row], exp_page)
        check("append tail reset", np.asarray(layer3.tail_k),
              np.zeros_like(tk))

    route_program_checks()
    telemetry_checks()
    hierarchical_checks()
    pipelined_checks()

    print("ALL OK")


def route_program_checks():
    """RouteProgram acceptance on a full 8-way mem ring.

    * switching unidirectional -> bidirectional -> pruned on the same jitted
      pull/push triggers no retrace (programs are runtime inputs),
    * every program's result is bit-exact against the program-aware oracle,
    * the bidirectional program covers all 7 distances in 8 // 2 = 4
      circuit epochs (vs 7 unidirectionally).
    """
    mesh8 = make_mesh((8,), ("data",))
    n, ppn, page = 8, 8, 16
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.normal(size=(n * ppn, page)).astype(np.float32))
    table = MemPortTable.striped(48, n, ppn)
    want = jnp.asarray(rng.integers(-1, 48, size=(n, 7)).astype(np.int32))

    uni = steering.unidirectional_program(n)
    bi = steering.bidirectional_program(n)
    assert uni.num_epochs() == n - 1, uni.num_epochs()
    # floor(N/2) in general; for the even 8-ring this equals ceil(8/2) = 4
    assert bi.num_epochs() == n // 2, bi.num_epochs()
    print(f"ok: route epochs uni={uni.num_epochs()} bi={bi.num_epochs()}")

    pull = jax.jit(functools.partial(bridge.pull_pages, mesh=mesh8, budget=3))
    exp = np.asarray(ref.pull_pages_ref(pool, want, table, pages_per_node=ppn))
    for name, prog in [("uni", uni), ("bi", bi),
                       ("avoid_cw", steering.link_avoiding_program(n, +1))]:
        got = np.asarray(pull(pool, want, table, program=prog))
        np.testing.assert_array_equal(got, exp, err_msg=f"pull {name}")
        print(f"ok: pull {name} bit-exact")
    # pruned-to-live-distances from the control plane (affinity placement)
    cp = ControlPlane(num_nodes=n, pages_per_node=ppn, num_logical=48)
    cp.allocate(8, policy="affinity", affinity=2)
    t_aff = cp.table()
    pr = cp.route_program()
    want_aff = jnp.asarray(rng.integers(0, 8, size=(n, 5)).astype(np.int32))
    got = np.asarray(pull(pool, want_aff, t_aff, program=pr))
    np.testing.assert_array_equal(
        got, np.asarray(ref.pull_pages_ref(pool, want_aff, t_aff,
                                           pages_per_node=ppn, program=pr)))
    np.testing.assert_array_equal(
        got, np.asarray(ref.pull_pages_ref(pool, want_aff, t_aff,
                                           pages_per_node=ppn)))
    print("ok: pull pruned (control-plane program) bit-exact")
    # a *wrongly* pruned program drops exactly the pages the oracle drops
    bad = steering.pruned_program(bi, range(2, n))
    got = np.asarray(pull(pool, want, table, program=bad))
    np.testing.assert_array_equal(
        got, np.asarray(ref.pull_pages_ref(pool, want, table,
                                           pages_per_node=ppn, program=bad)))
    assert not np.array_equal(got, exp), "pruning distance 1 dropped nothing"
    print("ok: pull wrong-prune drops distance-1 pages like the oracle")
    assert pull._cache_size() == 2, pull._cache_size()  # 2 table shapes only
    print("ok: program switches triggered no retrace")

    push = jax.jit(functools.partial(bridge.push_pages, mesh=mesh8, budget=2))
    dest = np.stack([np.arange(4) + 6 * node for node in range(n)])
    payload = rng.normal(size=(n, 4, page)).astype(np.float32)
    expp = np.asarray(ref.push_pages_ref(
        pool, jnp.asarray(dest), jnp.asarray(payload), table,
        pages_per_node=ppn))
    for name, prog in [("uni", uni), ("bi", bi)]:
        got = np.asarray(push(pool, jnp.asarray(dest), jnp.asarray(payload),
                              table, program=prog))
        np.testing.assert_array_equal(got, expp, err_msg=f"push {name}")
    assert push._cache_size() == 1, push._cache_size()
    print("ok: push programs bit-exact, no retrace")


def telemetry_checks():
    """In-band counters on a real 8-way mem ring.

    * pull/push counters under arbitrary programs and per-node throttles
      match the oracle's per-request walk exactly,
    * swapping programs / budgets with collection ON triggers no retrace,
    * a throttled push spills exactly the tail the rate limiter drops,
    * counters feed the aggregator and compile a load-balanced program.
    """
    mesh8 = make_mesh((8,), ("data",))
    n, ppn, page = 8, 8, 16
    rng = np.random.default_rng(11)
    pool = jnp.asarray(rng.normal(size=(n * ppn, page)).astype(np.float32))
    table = MemPortTable.striped(48, n, ppn)
    want = jnp.asarray(rng.integers(-1, 48, size=(n, 7)).astype(np.int32))
    ab = jnp.asarray(rng.integers(1, 4, size=(n,)).astype(np.int32))

    uni = steering.unidirectional_program(n)
    bi = steering.bidirectional_program(n)
    pruned = steering.pruned_program(bi, [1, 2, 6])
    pull = jax.jit(functools.partial(bridge.pull_pages, mesh=mesh8, budget=3,
                                     collect_telemetry=True))

    def check_telem(name, got, exp):
        for f in TELEM_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(exp, f)),
                err_msg=f"{name}: {f}")
        print(f"ok: telemetry {name} == oracle")

    telem_bi = None
    for name, prog in [("uni", uni), ("bi", bi), ("pruned", pruned)]:
        out, telem = pull(pool, want, table, program=prog, active_budget=ab)
        exp = ref.expected_transfer_telemetry(
            np.asarray(want), table, prog, num_nodes=n, budget=3,
            active_budget=np.asarray(ab))
        check_telem(f"pull {name}", telem, exp)
        if name == "bi":
            telem_bi = telem
    assert pull._cache_size() == 1, pull._cache_size()
    print("ok: telemetry collection retrace-free across programs/budgets")

    # throttled push: spilled tail leaves slots untouched, counters match
    dest = np.stack([np.arange(6) + 6 * node for node in range(n)])
    payload = rng.normal(size=(n, 6, page)).astype(np.float32)
    got, ptelem = bridge.push_pages(
        pool, jnp.asarray(dest), jnp.asarray(payload), table, mesh=mesh8,
        budget=3, active_budget=jnp.int32(2), collect_telemetry=True)
    served = ref.rate_limit_mask(6, 3, 2)          # 2 rounds x 2 lanes
    masked = jnp.asarray(np.where(served[None, :], dest, FREE))
    expp = ref.push_pages_ref(pool, masked, jnp.asarray(payload), table,
                              pages_per_node=ppn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expp))
    exp_pt = ref.expected_transfer_telemetry(
        dest, table, None, num_nodes=n, budget=3, active_budget=2)
    check_telem("push throttled", ptelem, exp_pt)
    assert int(np.asarray(ptelem.spilled).sum()) == n * 2
    print("ok: push rate-limiter parity on the 8-ring")

    # measured feedback: aggregate -> load-balanced program, bit-exact pull
    agg = TelemetryAggregator(n, page_bytes=page * 4)
    agg.update(telem_bi)
    cp = ControlPlane(num_nodes=n, pages_per_node=ppn, num_logical=48)
    cp.allocate(48, policy="striped")
    lb = cp.route_program(telemetry=agg)
    lb.validate()
    out_lb, telem_lb = pull(pool, want, table, program=lb, active_budget=ab)
    exp_lb = ref.expected_transfer_telemetry(
        np.asarray(want), table, lb, num_nodes=n, budget=3,
        active_budget=np.asarray(ab))
    check_telem("pull load-balanced", telem_lb, exp_lb)
    want_np = np.asarray(want)
    masked_want = np.stack([
        np.where(ref.rate_limit_mask(want_np.shape[1], 3, int(ab[i])),
                 want_np[i], FREE) for i in range(n)])
    np.testing.assert_array_equal(
        np.asarray(out_lb),
        np.asarray(ref.pull_pages_ref(pool, jnp.asarray(masked_want), table,
                                      pages_per_node=ppn, program=lb)))
    print("ok: telemetry-compiled load-balanced program bit-exact")


def hierarchical_checks():
    """Board + rack fabric acceptance on the real 8-way ring (2 boards x 4).

    * the hierarchical RouteProgram's transfers AND telemetry — including
      the per-tier counters — are bit-exact against the ref oracle,
    * swapping flat <-> hierarchical programs on the same jitted pull is
      retrace-free (one cache entry: the programs share one static shape),
    * the group mask really steers the datapath: masking an offset's
      board-crossing requesters drops exactly their pages, like the oracle,
    * a topology-aware control plane compiles a valid hierarchical program
      from placement.
    """
    mesh8 = make_mesh((8,), ("data",))
    topo = Topology.boards(2, 4)
    n, ppn, page = 8, 8, 16
    rng = np.random.default_rng(23)
    pool = jnp.asarray(rng.normal(size=(n * ppn, page)).astype(np.float32))
    table = MemPortTable.striped(48, n, ppn)
    want = jnp.asarray(rng.integers(-1, 48, size=(n, 7)).astype(np.int32))

    hier = steering.hierarchical_program(topo)
    hier.validate()
    steering.validate_hierarchical(hier, topo)
    bi = steering.bidirectional_program(n)

    def check_telem(name, got, exp):
        for f in TELEM_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(exp, f)),
                err_msg=f"{name}: {f}")
        print(f"ok: telemetry {name} == oracle")

    pull = jax.jit(functools.partial(bridge.pull_pages, mesh=mesh8, budget=3,
                                     topology=topo, collect_telemetry=True))
    exp_pages = np.asarray(ref.pull_pages_ref(pool, want, table,
                                              pages_per_node=ppn))
    for name, prog in [("flat bi", bi), ("hierarchical", hier),
                       ("flat bi again", bi)]:
        out, telem = pull(pool, want, table, program=prog)
        np.testing.assert_array_equal(np.asarray(out), exp_pages,
                                      err_msg=name)
        exp = ref.expected_transfer_telemetry(
            np.asarray(want), table, prog, num_nodes=n, budget=3,
            topology=topo)
        check_telem(name, telem, exp)
    # per-tier occupancy really split: the fabric has both tiers in play
    _, telem_h = pull(pool, want, table, program=hier)
    intra, inter = telem_h.tier_pages()
    assert int(np.asarray(intra).sum()) > 0
    assert int(np.asarray(inter).sum()) > 0
    assert int(np.asarray(telem_h.tier_hops)[:, 1].sum()) > 0
    print("ok: hierarchical per-tier telemetry live on both tiers")
    # acceptance: flat <-> hierarchical swaps share ONE jit cache entry
    assert pull._cache_size() == 1, pull._cache_size()
    print("ok: flat <-> hierarchical program swap triggered no retrace")

    # group-masked offsets steer the datapath: cut slot d=1's board-crossing
    # requesters (local ranks 3 — their +1 neighbour is the next board)
    mask = np.asarray(hier.rank_epoch) >= 0
    r = np.arange(n)
    mask[0, :] = topo.pair_intra(r, (r + 1) % n)
    masked = steering.masked_ranks_program(hier, mask)
    got_m, telem_m = pull(pool, want, table, program=masked)
    exp_m = ref.pull_pages_ref(pool, want, table, pages_per_node=ppn,
                               program=masked)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(exp_m))
    check_telem("group-masked", telem_m, ref.expected_transfer_telemetry(
        np.asarray(want), table, masked, num_nodes=n, budget=3,
        topology=topo))
    assert pull._cache_size() == 1, pull._cache_size()
    print("ok: group-masked offsets FREE-mask exactly the cut pairings")

    # topology-aware control plane: placement -> hierarchical program
    cp = ControlPlane(num_nodes=n, pages_per_node=ppn, num_logical=48,
                      topology=topo)
    cp.allocate(48, policy="striped")
    prog = cp.route_program()
    steering.validate_hierarchical(prog, topo)
    out_cp, _ = pull(pool, want, cp.table(), program=prog)
    np.testing.assert_array_equal(
        np.asarray(out_cp),
        np.asarray(ref.pull_pages_ref(pool, want, cp.table(),
                                      pages_per_node=ppn, program=prog)))
    assert pull._cache_size() == 1, pull._cache_size()
    print("ok: control-plane hierarchical program bit-exact, no retrace")

    # push path under the hierarchical program: bit-exact + tier counters
    dest = np.stack([np.arange(4) + 6 * node for node in range(n)])
    payload = rng.normal(size=(n, 4, page)).astype(np.float32)
    got_p, ptelem = bridge.push_pages(
        pool, jnp.asarray(dest), jnp.asarray(payload), table, mesh=mesh8,
        budget=2, program=hier, topology=topo, collect_telemetry=True)
    np.testing.assert_array_equal(
        np.asarray(got_p),
        np.asarray(ref.push_pages_ref(pool, jnp.asarray(dest),
                                      jnp.asarray(payload), table,
                                      pages_per_node=ppn, program=hier)))
    check_telem("push hierarchical", ptelem, ref.expected_transfer_telemetry(
        dest, table, hier, num_nodes=n, budget=2, topology=topo))


def pipelined_checks():
    """Pipelined multi-channel round engine on the real 8-way mem ring.

    * ``channels ∈ {1, 2, 4}`` pull/push results are bit-exact vs the
      serial engine for every program variant (uni / bi / pruned /
      load-balanced / hierarchical / group-masked) — and vs the pipelined
      ref oracle's independent chunk-schedule walk,
    * telemetry counters are bit-exact across depths (channels-blind),
    * throttled + overprovisioned transfers keep the spill semantics,
    * bufferless HLO regression: ``edge_buffer=False`` serializes N-1
      barriers on both paths — the epoch-0 loopback access included
      (historically the pull chain skipped it: N-2), the edge-buffered
      datapath has none.
    """
    mesh8 = make_mesh((8,), ("data",))
    n, ppn, page = 8, 8, 16
    rng = np.random.default_rng(31)
    pool = jnp.asarray(rng.normal(size=(n * ppn, page)).astype(np.float32))
    table = MemPortTable.striped(48, n, ppn)
    want = jnp.asarray(rng.integers(-1, 48, size=(n, 7)).astype(np.int32))
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

    # One jitted pull/push per depth; programs stay runtime inputs, so
    # the whole variant sweep compiles each engine exactly once.
    pulls = {ch: jax.jit(functools.partial(
        bridge.pull_pages, mesh=mesh8, budget=3, channels=ch,
        topology=topo, collect_telemetry=True)) for ch in (1, 2, 4)}
    pushes = {ch: jax.jit(functools.partial(
        bridge.push_pages, mesh=mesh8, budget=2, channels=ch))
        for ch in (1, 2, 4)}
    dest = np.stack([np.arange(4) + 6 * node for node in range(n)])
    payload = rng.normal(size=(n, 4, page)).astype(np.float32)
    for name, prog in variants:
        serial, telem_s = pulls[1](pool, want, table, program=prog)
        pserial = pushes[1](pool, jnp.asarray(dest),
                            jnp.asarray(payload), table, program=prog)
        for ch in (2, 4):
            piped, telem_p = pulls[ch](pool, want, table, program=prog)
            np.testing.assert_array_equal(
                np.asarray(piped), np.asarray(serial),
                err_msg=f"pull {name} ch={ch}")
            for f in TELEM_FIELDS:
                np.testing.assert_array_equal(
                    np.asarray(getattr(telem_p, f)),
                    np.asarray(getattr(telem_s, f)),
                    err_msg=f"telemetry {name} ch={ch}: {f}")
            exp = ref.pull_pages_pipelined_ref(
                pool, want, table, ppn, prog, budget=3, channels=ch)
            np.testing.assert_array_equal(np.asarray(piped),
                                          np.asarray(exp),
                                          err_msg=f"oracle {name} {ch}")
            ppiped = pushes[ch](pool, jnp.asarray(dest),
                                jnp.asarray(payload), table,
                                program=prog)
            np.testing.assert_array_equal(
                np.asarray(ppiped), np.asarray(pserial),
                err_msg=f"push {name} ch={ch}")
        print(f"ok: pipelined pull+push {name} bit-exact "
              f"(ch=2,4 + oracle)")

    # throttled + overprovisioned pipelined pull keeps spill semantics
    want3 = jnp.asarray(np.arange(32).reshape(8, 4).astype(np.int32))
    table32 = MemPortTable.striped(32, n, ppn)
    for ch in (2, 4):
        got = jax.jit(functools.partial(
            bridge.pull_pages, mesh=mesh8, budget=4, overprovision=2,
            channels=ch))(pool, want3, table32,
                          active_budget=jnp.int32(2))
        exp = ref.pull_pages_pipelined_ref(
            pool, want3, table32, ppn, None, budget=4, channels=ch,
            active_budget=2, overprovision=2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    print("ok: pipelined pull throttled/overprovisioned == oracle")

    # channels swaps retrace (static knob) but never change results;
    # programs still swap retrace-free at any depth
    for ch in (2, 4):
        assert pulls[ch]._cache_size() == 1, pulls[ch]._cache_size()
        assert pushes[ch]._cache_size() == 1, pushes[ch]._cache_size()
    print("ok: program swaps retrace-free at channels=2,4")

    # HLO regression: bufferless serialization barriers (incl. loopback)
    def barriers(f, *args):
        return jax.jit(f).lower(*args).as_text().count(
            "optimization_barrier")

    pull_nb = functools.partial(bridge.pull_pages, mesh=mesh8, budget=3,
                                edge_buffer=False)
    push_nb = functools.partial(bridge.push_pages, mesh=mesh8, budget=2,
                                edge_buffer=False)
    assert barriers(pull_nb, pool, want, table) == n - 1
    assert barriers(push_nb, pool, jnp.asarray(dest),
                    jnp.asarray(payload), table) == n - 1
    pull_eb = functools.partial(bridge.pull_pages, mesh=mesh8, budget=3)
    assert barriers(pull_eb, pool, want, table) == 0
    # bufferless results identical on both paths (serialization only)
    got_nb = bridge.pull_pages(pool, want, table, mesh=mesh8, budget=3,
                               edge_buffer=False, channels=4)
    np.testing.assert_array_equal(
        np.asarray(got_nb),
        np.asarray(ref.pull_pages_ref(pool, want, table,
                                      pages_per_node=ppn)))
    got_pb = bridge.push_pages(pool, jnp.asarray(dest),
                               jnp.asarray(payload), table, mesh=mesh8,
                               budget=2, edge_buffer=False)
    np.testing.assert_array_equal(
        np.asarray(got_pb),
        np.asarray(ref.push_pages_ref(pool, jnp.asarray(dest),
                                      jnp.asarray(payload), table,
                                      pages_per_node=ppn)))
    print("ok: bufferless barriers = N-1 (loopback chained), results "
          "bit-exact")


if __name__ == "__main__":
    main()
