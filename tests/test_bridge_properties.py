"""Randomized property tests for the bridge.

Split out of test_bridge.py so the deterministic suite is isolated from the
property-testing machinery: real hypothesis when installed (pinned in
requirements-dev.txt), the seeded fallback in hypofallback.py otherwise.
"""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # minimal environments
    from hypofallback import given, settings, st

from topologies import TELEM_FIELDS, make_pool

from repro.core import bridge, ref, steering
from repro.core.memport import FREE, MemPortTable
from repro.core.control_plane import ControlPlane
from repro.telemetry import counters as tcounters  # noqa: F401 (structure)

pytestmark = pytest.mark.property

make_pool_np = make_pool  # shared fixture (tests/topologies.py)


@settings(max_examples=25, deadline=None)
@given(
    num_logical=st.integers(1, 24),
    budget=st.integers(1, 9),
    seed=st.integers(0, 10_000),
)
def test_pull_property_random_requests(num_logical, budget, seed):
    """Any request list (dups, FREE holes, unmapped pages) matches the oracle."""
    rng = np.random.default_rng(seed)
    pool = make_pool_np(32, 4, seed)
    table = MemPortTable.striped(num_logical, 1, 32)
    r = int(rng.integers(1, 16))
    want = rng.integers(-1, num_logical, size=(1, r)).astype(np.int32)
    got = bridge.pull_pages(pool, jnp.asarray(want), table,
                            mesh=None, budget=budget)
    exp = ref.pull_pages_ref(pool, jnp.asarray(want), table, pages_per_node=32)
    np.testing.assert_allclose(got, exp)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), nodes=st.integers(1, 6))
def test_control_plane_invariants(seed, nodes):
    """No slot double-booked; every mapped page has a live home; the
    occupancy is the plain count, after random allocate / fail_node /
    release."""
    rng = np.random.default_rng(seed)
    cp = ControlPlane(num_nodes=nodes, pages_per_node=8, num_logical=64)
    regions = []
    # Keep total allocation at <= half capacity so a failed node's pages
    # always fit on survivors.
    remaining = nodes * 8 // 2
    for _ in range(int(rng.integers(1, 4))):
        n = int(rng.integers(1, 8))
        if n > remaining:
            break
        remaining -= n
        regions.append(cp.allocate(n, policy=str(rng.choice(
            ["striped", "hashed"]))))
    if nodes > 1 and rng.random() < 0.5:
        cp.fail_node(int(rng.integers(0, nodes)))
    if regions and rng.random() < 0.5:
        cp.release(regions.pop(int(rng.integers(len(regions)))))
    home, slot = np.asarray(cp._home), np.asarray(cp._slot)
    mapped = home != FREE
    pairs = set(zip(home[mapped].tolist(), slot[mapped].tolist()))
    assert len(pairs) == mapped.sum(), "slot double-booked"
    for h in home[mapped]:
        assert cp.nodes[h].alive, "page homed on dead node"
    # occupancy() is a plain count of the homed pages per node
    plain = [sum(1 for h in home if h == n) for n in range(nodes)]
    assert cp.occupancy().tolist() == plain


@settings(max_examples=20, deadline=None)
@given(
    num_nodes=st.integers(1, 6),
    budget=st.integers(1, 6),
    active_budget=st.integers(1, 6),
    overprovision=st.integers(1, 2),
    seed=st.integers(0, 10_000),
)
def test_pull_telemetry_matches_oracle_property(num_nodes, budget,
                                                active_budget, overprovision,
                                                seed):
    """Counters == the oracle's per-request walk for arbitrary programs,
    budgets, throttles and request lists (dups, FREE holes, unmapped)."""
    rng = np.random.default_rng(seed)
    tn, ppn = num_nodes, 8
    pool = make_pool_np(tn * ppn, 4, seed)
    num_logical = int(rng.integers(1, tn * ppn + 1))
    table = MemPortTable.striped(num_logical, tn, ppn)
    r = int(rng.integers(1, 16))
    # ids beyond num_logical-1 are invalid; stay in-range but allow FREE
    want = rng.integers(-1, num_logical, size=(1, r)).astype(np.int32)
    if tn > 1 and rng.random() < 0.7:
        keep = [d for d in range(1, tn) if rng.random() < 0.7]
        base = (steering.bidirectional_program(tn) if rng.random() < 0.5
                else steering.unidirectional_program(tn))
        program = steering.pruned_program(base, keep)
    else:
        program = None
    got, telem = bridge.pull_pages(
        pool, jnp.asarray(want), table, mesh=None, budget=budget,
        overprovision=overprovision, active_budget=jnp.int32(active_budget),
        table_nodes=tn, program=program, collect_telemetry=True)
    exp = ref.expected_transfer_telemetry(
        want, table, program, num_nodes=tn, budget=budget,
        active_budget=active_budget, overprovision=overprovision)
    for name in TELEM_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(telem, name)), np.asarray(getattr(exp, name)),
            err_msg=name)
    # conservation: live requests all end up served, spilled or pruned
    home = np.asarray(table.home)
    live = int(((want >= 0) & (home[np.clip(want, 0, None)] >= 0)).sum())
    total = (int(np.asarray(telem.served_total()).sum())
             + int(np.asarray(telem.spilled).sum())
             + int(np.asarray(telem.pruned).sum()))
    assert total == live
    # pushes count with identical semantics
    payload = rng.normal(size=(1, r, 4)).astype(np.float32)
    _, ptelem = bridge.push_pages(
        pool, jnp.asarray(want), jnp.asarray(payload), table, mesh=None,
        budget=budget, overprovision=overprovision,
        active_budget=jnp.int32(active_budget), table_nodes=tn,
        program=program, collect_telemetry=True)
    for name in ("slot_served", "spilled", "pruned", "traffic"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ptelem, name)),
            np.asarray(getattr(exp, name)), err_msg=f"push {name}")


@settings(max_examples=20, deadline=None)
@given(num_nodes=st.integers(2, 12), seed=st.integers(0, 10_000))
def test_load_balanced_program_properties(num_nodes, seed):
    """Random measured loads: congruent offsets, live == measured (when
    pruning), and the bottleneck direction is never worse than the static
    shortest-way split under the same loads."""
    rng = np.random.default_rng(seed)
    n = num_nodes
    w = np.where(rng.random(n - 1) < 0.6, rng.integers(0, 50, n - 1), 0)
    p = steering.load_balanced_program(n, w)
    p.validate()
    assert list(p.live_distances()) == (np.nonzero(w > 0)[0] + 1).tolist()
    off, live = np.asarray(p.offsets), np.asarray(p.live)
    ep = np.asarray(p.epoch)
    assert (ep[~live] == -1).all() and (off[~live] == 0).all()
    for e in set(ep[live].tolist()):
        at_e = live & (ep == e)
        assert (off[at_e] > 0).sum() <= 1 and (off[at_e] < 0).sum() <= 1

    def bottleneck(prog):
        o, lv = np.asarray(prog.offsets), np.asarray(prog.live)
        return max(w[lv & (o > 0)].sum(), w[lv & (o < 0)].sum())

    bi = steering.pruned_program(steering.bidirectional_program(n),
                                 (np.nonzero(w > 0)[0] + 1).tolist())
    assert bottleneck(p) <= bottleneck(bi)
    # unpruned keeps every distance wired (zero-weight ones ride along)
    p_full = steering.load_balanced_program(n, w, prune=False)
    assert list(p_full.live_distances()) == list(range(1, n))


@settings(max_examples=20, deadline=None)
@given(num_nodes=st.integers(2, 12), seed=st.integers(0, 10_000))
def test_route_program_properties(num_nodes, seed):
    """Random prunings stay congruent, cover exactly what they keep, and
    never use more epochs than the base program."""
    rng = np.random.default_rng(seed)
    base = (steering.bidirectional_program(num_nodes)
            if rng.random() < 0.5 else
            steering.unidirectional_program(num_nodes,
                                            direction=1 if rng.random() < 0.5
                                            else -1))
    keep = [d for d in range(1, num_nodes) if rng.random() < 0.6]
    p = steering.pruned_program(base, keep)
    p.validate()
    assert list(p.live_distances()) == sorted(keep)
    assert p.num_epochs() <= base.num_epochs()
    live = np.asarray(p.live)
    ep = np.asarray(p.epoch)
    off = np.asarray(p.offsets)
    # dead slots fully cleared
    assert (ep[~live] == -1).all() and (off[~live] == 0).all()
    # at most one circuit per direction per epoch
    for e in set(ep[live].tolist()):
        at_e = live & (ep == e)
        assert (off[at_e] > 0).sum() <= 1
        assert (off[at_e] < 0).sum() <= 1


@settings(max_examples=15, deadline=None)
@given(
    budget=st.integers(1, 8),
    active_budget=st.integers(1, 8),
    overprovision=st.integers(1, 2),
    seed=st.integers(0, 10_000),
)
def test_pipelined_channels_bit_exact_property(budget, active_budget,
                                               overprovision, seed):
    """Pipelined channels ∈ {1, 2, 4} serve bit-exactly what the serial
    engine serves — results and telemetry — over random ragged board+rack
    fabrics, hierarchical/masked/pruned programs, throttles and request
    lists (the pipeline reorders wire traffic, never what is served)."""
    from topologies import random_fabric
    from repro.core import steering as _steering

    rng = np.random.default_rng(seed)
    topo = random_fabric(rng)
    n, ppn = topo.num_nodes, 8
    pool = make_pool_np(n * ppn, 4, seed)
    num_logical = int(rng.integers(1, n * ppn + 1))
    table = MemPortTable.striped(num_logical, n, ppn)
    r = int(rng.integers(1, 16))
    want = rng.integers(-1, num_logical, size=(n, r)).astype(np.int32)

    choice = rng.random()
    if n == 1:
        program = None
    elif choice < 0.4:
        program = _steering.hierarchical_program(topo)
    elif choice < 0.7:
        base = _steering.hierarchical_program(topo)
        rank_live = rng.random(np.asarray(base.rank_epoch).shape) < 0.8
        program = _steering.masked_ranks_program(base, rank_live)
    else:
        keep = [d for d in range(1, n) if rng.random() < 0.7]
        program = _steering.pruned_program(
            _steering.bidirectional_program(n), keep)

    serial = ref.pull_pages_pipelined_ref(
        pool, jnp.asarray(want), table, ppn, program, budget=budget,
        channels=1, active_budget=active_budget, overprovision=overprovision)
    # the serial oracle must agree with the classic ref under the limiter
    mask = ref.rate_limit_mask(r, budget, active_budget, overprovision)
    masked = jnp.asarray(np.where(mask[None, :], want, FREE))
    np.testing.assert_array_equal(
        np.asarray(serial),
        np.asarray(ref.pull_pages_ref(pool, masked, table, ppn,
                                      program=program)))
    for channels in (2, 4):
        piped = ref.pull_pages_pipelined_ref(
            pool, jnp.asarray(want), table, ppn, program, budget=budget,
            channels=channels, active_budget=active_budget,
            overprovision=overprovision)
        np.testing.assert_array_equal(np.asarray(piped), np.asarray(serial))
        # the chunk schedule is a duplicate-free cover of the served window
        flat_sched = np.concatenate(
            ref.pipeline_schedule(r, budget, channels, active_budget,
                                  overprovision) or [np.zeros(0, int)])
        in_range = flat_sched[flat_sched < r]
        assert len(set(in_range.tolist())) == len(in_range)
        np.testing.assert_array_equal(np.sort(in_range), np.nonzero(mask)[0])
    # push: commits retire in chunk order; single-writer image identical
    dest_ids = rng.permutation(num_logical)[: min(r, num_logical)]
    dest = np.full((n, r), FREE, np.int32)
    dest[0, : len(dest_ids)] = dest_ids
    payload = rng.normal(size=(n, r, 4)).astype(np.float32)
    pser = ref.push_pages_pipelined_ref(
        pool, jnp.asarray(dest), jnp.asarray(payload), table, ppn, program,
        budget=budget, channels=1, active_budget=active_budget,
        overprovision=overprovision)
    for channels in (2, 4):
        ppiped = ref.push_pages_pipelined_ref(
            pool, jnp.asarray(dest), jnp.asarray(payload), table, ppn,
            program, budget=budget, channels=channels,
            active_budget=active_budget, overprovision=overprovision)
        np.testing.assert_array_equal(np.asarray(ppiped), np.asarray(pser))
    # telemetry is channels-blind by construction: the datapath counters are
    # computed from the request list + program alone, so one oracle serves
    # every depth (asserted against the live datapath in the 8-device suite)
    telem = ref.expected_transfer_telemetry(
        want, table, program, num_nodes=n, budget=budget,
        active_budget=active_budget, overprovision=overprovision,
        topology=topo)
    live = int(((want >= 0)
                & (np.asarray(table.home)[np.clip(want, 0, None)] >= 0)).sum())
    total = (int(np.asarray(telem.served_total()).sum())
             + int(np.asarray(telem.spilled).sum())
             + int(np.asarray(telem.pruned).sum()))
    assert total == live
