"""The bridge transfer engine: epoch-batched circuit transfers over a mesh axis.

This is the paper's datapath (Fig. 1) mapped onto a TPU pod:

* *time-multiplexing* — requests are coalesced into rounds of ``budget`` pages
  (the software rate limiter; ``active_budget`` can be lowered at **runtime**
  without recompiling, the remaining requests spill into later rounds);
* *request preparation & steering* — each request is translated through the
  :class:`~repro.core.memport.MemPortTable` and assigned to the datapath slot
  equal to its ring distance (a circuit = one static ``ppermute`` route);
* *software-defined circuit scheduling* — **which** slots are wired, in which
  physical ring direction, and at which circuit epoch is a runtime
  :class:`~repro.core.steering.RouteProgram` input compiled by the control
  plane: unidirectional (the historical fixed ring), bidirectional
  (min(d, N-d) shortest-way routing: ⌊N/2⌋ epochs instead of N-1), pruned to
  the distances that actually carry traffic, link-avoiding after a ring
  failure, or **hierarchical** for a board + rack fabric
  (:class:`~repro.core.topology.Topology`): the program's per-rank group
  mask splits every offset between its same-board requesters (concurrent
  local-ring circuits) and its board-crossing ones (exclusive gateway
  epochs).  Programs have fixed static shapes, so swapping them between
  steps — flat for hierarchical, like re-programming the memport table or
  lowering ``active_budget`` — never triggers a retrace;
* *serDES + circuit network* — one ``jax.lax.ppermute`` pair per live slot:
  request ids travel ``rank -> rank+d``, payload returns ``rank+d -> rank``.
  Every slot's wire permutation is **static** (circuit switching; note the
  +d and -(N-d) circuits are the *same permutation*, so direction is pure
  steering data), only the *contents* are runtime values.  Dead slots carry
  FREE requests, so their gather/scatter payload work is masked out;
* *edge buffering* — live slots within a round are independent dataflow
  chains, so the compiler overlaps them exactly like the paper's decoupled
  serdes clock domains pulling from edge buffers.  ``edge_buffer=False``
  inserts ``optimization_barrier`` between consecutive slots — starting
  from the epoch-0 loopback access — to model a bufferless bridge (a
  conservative serialization: it ignores the program's epoch pairing,
  which only affects the analytical cost model);
* *pipelined multi-channel rounds* — ``channels > 1`` splits each round's
  ``budget`` lanes into ``channels`` virtual channels and software-pipelines
  the scan body: chunk *g+1*'s **request flits** (the ``ppermute`` of slot
  ids) are issued while chunk *g*'s **data flits** are still in flight, a
  double-buffered carry of the in-flight ``(pending_req, pending_payload)``
  state with an epilogue chunk draining the pipeline.  Results and
  telemetry are bit-exact vs the serial engine for every ``channels`` (the
  pipeline reorders wire traffic, never what is served); ``channels=1`` *is*
  the serial engine, and a bufferless bridge (``edge_buffer=False``) has no
  buffers to hold overlapped flits, so it always runs serial;
* *lossless, no ack/retx* — ICI collectives are lossless and deterministic,
  so the assumption holds natively;
* *fused datapath* — ``fused=True`` (the default) replaces the per-slot
  mask → dynamic-slice gather → payload-commit op chain *and* the
  2·(N-1)·channels ``ppermute`` ladder with one epoch-batched engine: per
  round, one ``all_gather`` broadcasts every node's request window, the
  Pallas gather kernel (:func:`repro.kernels.bridge_gather.gather_pages`)
  serves all slots from the local pool shard, the payloads return through
  the exchange lowering picked by :func:`_fused_exchange_mode` (one
  ``all_to_all`` on TPU; one backward ``ppermute`` hop per slot off-TPU,
  where XLA's all-to-all emulation is copy-pathological), and the round
  commits without a per-slot select chain
  (:func:`~repro.kernels.bridge_gather.pull_commit` /
  :func:`~repro.kernels.bridge_gather.push_commit`, pool buffer donated
  via ``input_output_aliases``; an add-tree over the landed rows in
  ladder mode) — serve conditions, gather and commit fused exactly as the
  paper couples the transceiver datapath to the circuit network.  Pages
  and telemetry are bit-exact vs ``fused=False`` (the unfused chain stays
  as the escape hatch, and a bufferless bridge always runs the unfused
  serial engine — serialization barriers are the point there);
* *in-band telemetry* — ``collect_telemetry=True`` additionally returns a
  :class:`~repro.telemetry.counters.BridgeTelemetry` of per-slot served
  counts, spills, pruned drops and a traffic-matrix row, computed as masked
  integer sums with static shapes (swapping programs with collection on
  never retraces); the control plane closes the loop on it.  A per-request
  ``tenant_ids`` lane (runtime input, same shape as the request list)
  additionally attributes every outcome to its tenant in static
  ``[max_tenants]`` histograms — the measurement the orchestrator's
  multi-tenant QoS scheduler re-fits its budget shares from.

All functions exist in two forms: a ``*_local`` body to be used inside
``shard_map`` (N nodes on the mem axis) and a reference oracle in
``repro.core.ref`` used by tests (the oracle honours arbitrary programs).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.core.memport import FREE, MemPortTable
from repro.core import ref as _ref
from repro.core import steering
from repro.core.steering import RouteProgram
from repro.core.topology import Topology, TopoTables
from repro.kernels import bridge_gather as _bg
from repro.telemetry import counters as _telemetry


def shard_map(f, mesh, in_specs, out_specs, mem_axis=None):
    """jax.shard_map, manual ONLY over ``mem_axis`` (others stay auto).

    Partial-manual mode keeps the model axis under GSPMD control inside the
    body, so head/ff dims keep their automatic sharding (and non-divisible
    head counts keep working) while the bridge runs manual collectives over
    the mem axis.  check_vma must be True: the check_vma=False path rebuilds
    specs over *all* mesh axes and rejects partial manual.

    The mesh must have Auto axes (:func:`repro.launch.mesh.make_mesh`):
    with Explicit axes, shardings enter the array types and the bridge's
    Pallas operands and index arithmetic would each need one.  The map is
    staged through ``jax.jit`` (inlined under an outer jit): called
    eagerly, shard_map dispatches its body op by op, which made the
    8-device fused suite ten times slower on virtual CPU devices.
    """
    if AxisType.Explicit in mesh.axis_types:
        raise ValueError(
            f"bridge meshes need Auto axes, got {mesh.axis_types}: build the "
            "mesh with repro.launch.mesh.make_mesh")
    names = frozenset({mem_axis}) if mem_axis else frozenset(mesh.axis_names)
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, axis_names=names,
                                 check_vma=True))


# ---------------------------------------------------------------------------
# shard_map bodies
# ---------------------------------------------------------------------------

def _pvary(x: jax.Array, axis: str) -> jax.Array:
    """Mark ``x`` as varying over ``axis`` (VMA typing for scan carries)."""
    return jax.lax.pcast(x, axis, to="varying")


def _gather_local(pool_local: jax.Array, slots: jax.Array) -> jax.Array:
    """Masked local gather: FREE slots produce zeros."""
    valid = slots >= 0
    safe = jnp.where(valid, slots, 0)
    out = pool_local[safe]
    mask = valid.reshape(valid.shape + (1,) * (out.ndim - 1))
    return jnp.where(mask, out, jnp.zeros_like(out))


def _scatter_local(pool_local: jax.Array, slots: jax.Array,
                   payload: jax.Array) -> jax.Array:
    # FREE slots are routed out of bounds and dropped: a where-fallback would
    # scatter stale values onto slot 0 and race with live writes there.
    safe = jnp.where(slots >= 0, slots, pool_local.shape[0])
    return pool_local.at[safe].set(payload.astype(pool_local.dtype),
                                   mode="drop")


def _round_pull(pool_local: jax.Array, sub_ids: jax.Array, table: MemPortTable,
                program: RouteProgram, axis: str, num_nodes: int,
                edge_buffer: bool) -> jax.Array:
    """Serve one round of <=budget requests; returns [budget, *page_shape]."""
    my = jax.lax.axis_index(axis)
    home, slot = table.translate(sub_ids)
    dist = steering.ring_distance(home, my, num_nodes)

    # Epoch 0: loopback fast path (locally mapped region — no circuit hop).
    out = _gather_local(pool_local, jnp.where(dist == 0, slot, FREE))

    # A bufferless bridge serializes everything the datapath does in a
    # round, *including* the epoch-0 loopback access: chain it into the
    # barrier chain so the first circuit slot cannot launch under it.
    prev = out
    for k, d in enumerate(steering.default_route_schedule(num_nodes)):
        # Runtime steering: slot k carries traffic only if the program wires
        # it *for this rank* (the group mask — a hierarchical program may
        # serve an offset's same-board requesters while cutting its
        # board-crossing ones).  Dead pairings move FREE requests, so their
        # payload gathers are masked to zeros and their pages dropped.
        serve = ((dist == d) & program.live[k]
                 & (program.rank_epoch[k, my] >= 0))
        req = jnp.where(serve, slot, FREE)                         # [B]
        if not edge_buffer:
            # A bufferless bridge serializes slots: model it explicitly.
            req, prev = jax.lax.optimization_barrier((req, prev))
        fwd = [(j, (j + d) % num_nodes) for j in range(num_nodes)]
        bwd = [(j, (j - d) % num_nodes) for j in range(num_nodes)]
        # obs:* scopes tag each phase's HLO ops (metadata op_name) so
        # compiled-program attribution (obs.trace.phase_op_counts) can
        # apportion a round's dispatch cost per phase.
        with jax.named_scope("obs:wire_req"):
            req_at_home = jax.lax.ppermute(req, axis, perm=fwd)    # request flits
        with jax.named_scope("obs:gather"):
            payload = _gather_local(pool_local, req_at_home)       # remote read
        with jax.named_scope("obs:wire_data"):
            payload = jax.lax.ppermute(payload, axis, perm=bwd)    # data flits
        with jax.named_scope("obs:commit"):
            mask = serve.reshape((-1,) + (1,) * (payload.ndim - 1))
            out = jnp.where(mask, payload, out)
        prev = payload
    return out


# ---------------------------------------------------------------------------
# Pipelined multi-channel round engine (channels > 1)
# ---------------------------------------------------------------------------
#
# The serial engine completes every epoch of round r before round r+1 issues
# a single flit — the RTT of the deepest circuit is paid once per round with
# the wire idle underneath it.  The paper couples serial transceivers to a
# circuit network precisely so multiple outstanding transactions share the
# wire; the pipelined engine reproduces that in software: each round's budget
# splits into ``channels`` chunks, and while chunk g's data flits are still
# in flight, chunk g+1's request flits are already on the wire.  The carry is
# the classic double buffer — the in-flight (pending_req, pending_payload)
# state — and an epilogue chunk drains the pipeline after the scan.

def _pull_wire(pool_local: jax.Array, sub_ids: jax.Array, table: MemPortTable,
               program: RouteProgram, axis: str, num_nodes: int, my):
    """Request phase of one chunk: issue every live slot's request flits.

    Returns the in-flight pipeline state (the double-buffered carry): the
    request flits landed at their homes [S, cb], the serve masks [S, cb]
    and the epoch-0 loopback pages [cb, *page_shape] (local, no flit).
    """
    home, slot = table.translate(sub_ids)
    dist = steering.ring_distance(home, my, num_nodes)
    out0 = _gather_local(pool_local, jnp.where(dist == 0, slot, FREE))
    reqs, serves = [], []
    for k, d in enumerate(steering.default_route_schedule(num_nodes)):
        serve = ((dist == d) & program.live[k]
                 & (program.rank_epoch[k, my] >= 0))
        req = jnp.where(serve, slot, FREE)
        fwd = [(j, (j + d) % num_nodes) for j in range(num_nodes)]
        with jax.named_scope("obs:wire_req"):
            reqs.append(jax.lax.ppermute(req, axis, perm=fwd))
        serves.append(serve)
    return jnp.stack(reqs), jnp.stack(serves), out0


def _pull_drain(pool_local: jax.Array, pending, axis: str,
                num_nodes: int) -> jax.Array:
    """Data phase of one chunk: serve the in-flight request flits.

    Remote reads against the landed requests, returning data flits, merged
    over the chunk's loopback pages.  FREE in-flight requests (the pipeline
    prologue, dead slots) gather zeros and are masked out.
    """
    reqs, serves, out = pending
    for k, d in enumerate(steering.default_route_schedule(num_nodes)):
        bwd = [(j, (j - d) % num_nodes) for j in range(num_nodes)]
        with jax.named_scope("obs:gather"):
            payload = _gather_local(pool_local, reqs[k])           # remote read
        with jax.named_scope("obs:wire_data"):
            payload = jax.lax.ppermute(payload, axis, perm=bwd)    # data flits
        with jax.named_scope("obs:commit"):
            mask = serves[k].reshape((-1,) + (1,) * (payload.ndim - 1))
            out = jnp.where(mask, payload, out)
    return out


def _reassemble(chunks: jax.Array, want_len: int, lanes_per_round: int,
                active_budget: jax.Array, page_shape, dtype) -> jax.Array:
    """Re-assemble served round lanes into logical request order.

    ``chunks`` is [rounds * lanes_per_round, *page_shape] in (round, lane)
    order.  Round ``r`` served ``want[r*active_budget + k]`` in lane ``k``
    (k < active_budget); lanes beyond the live budget (and the pipelined
    engine's chunk padding) carried FREE requests and are dropped.
    """
    with jax.named_scope("obs:commit"):
        idx = jnp.arange(chunks.shape[0])
        r = idx // lanes_per_round
        k = idx % lanes_per_round
        dest = r * active_budget + k
        live = (k < active_budget) & (dest < want_len)
        dest = jnp.where(live, dest, 0)
        mask = live.reshape((-1,) + (1,) * len(page_shape))
        upd = jnp.where(mask, chunks, jnp.zeros_like(chunks))
        out = jnp.zeros((want_len,) + page_shape, dtype)
        return out.at[dest].add(upd)


def _pull_local(pool_local: jax.Array, want: jax.Array, table: MemPortTable,
                active_budget: jax.Array, program: RouteProgram, *, axis: str,
                num_nodes: int, budget: int, rounds: int,
                edge_buffer: bool, channels: int = 1,
                fused: bool = False) -> jax.Array:
    """Pull ``want`` pages ([rounds*budget], FREE-padded) through the bridge.

    Returns [want.shape[0], *page_shape]; requests the rate limiter never
    reaches (``rounds == 0``, spilled tails) come back as zeros.

    ``channels > 1`` runs the pipelined multi-channel engine (see the
    module docstring); 1 is the serial engine.  A bufferless bridge or a
    1-node ring has nothing to overlap — both always run serial.

    ``fused`` runs the epoch-batched fused engine instead
    (:func:`_pull_local_fused`): one collective pair + one Pallas kernel
    pair per round, bit-exact vs both unfused engines.  A bufferless
    bridge has no edge buffers to land a whole round's flits in, so it
    always runs the unfused serial engine.
    """
    want = want.reshape(-1)
    page_shape = pool_local.shape[1:]
    if rounds == 0:
        # All-dropped, correctly shaped: the docstring's contract even when
        # a caller hands a non-empty ``want`` to a zero-round transfer.
        return jnp.zeros((want.shape[0],) + page_shape, pool_local.dtype)
    # Clamp the (runtime) rate limiter to the lane budget: an overdriven
    # ``active_budget`` would walk ``ptr`` past the final round's window and
    # make ``dynamic_slice`` silently re-serve tail requests.
    active_budget = jnp.clip(active_budget, 0, budget)
    if fused and num_nodes > 1 and edge_buffer:
        return _pull_local_fused(
            pool_local, want, table, active_budget, program, axis=axis,
            num_nodes=num_nodes, budget=budget, rounds=rounds,
            channels=channels)
    pipelined = channels > 1 and num_nodes > 1 and edge_buffer

    if not pipelined:
        def body(ptr, _):
            # Rate limiter: only the first ``active_budget`` slots of this
            # round carry live requests; the pointer advances by the same
            # amount, so a throttled node simply uses more of its
            # (overprovisioned) rounds.
            sub = jax.lax.dynamic_slice(want, (ptr,), (budget,))
            lane = jnp.arange(budget)
            sub = jnp.where((lane < active_budget)
                            & (ptr + lane < want.shape[0]), sub, FREE)
            out = _round_pull(pool_local, sub, table, program, axis,
                              num_nodes, edge_buffer)
            return ptr + active_budget, out

        ptr0 = _pvary(jnp.int32(0), axis)
        _, chunks = jax.lax.scan(body, ptr0, None, length=rounds)
        return _reassemble(chunks.reshape(rounds * budget, *page_shape),
                           want.shape[0], budget, active_budget, page_shape,
                           pool_local.dtype)

    # Pipelined engine: rounds split into ``channels`` chunks of ``cb``
    # lanes; the scan body issues chunk g+1's request flits, then drains
    # chunk g's data flits (still in flight from the previous step) — the
    # double-buffered carry.  Emission is therefore shifted by one chunk:
    # the first emission is the pipeline prologue (all-FREE, dropped) and an
    # epilogue drain after the scan yields the final chunk.
    my = jax.lax.axis_index(axis)
    cb = -(-budget // channels)
    lane = jnp.arange(channels * cb)
    nslots = num_nodes - 1

    def empty_pending():
        return tuple(_pvary(x, axis) for x in (
            jnp.full((nslots, cb), FREE, jnp.int32),
            jnp.zeros((nslots, cb), bool),
            jnp.zeros((cb,) + page_shape, pool_local.dtype)))

    def body(carry, _):
        ptr, pending = carry
        window = jax.lax.dynamic_slice(want, (ptr,), (budget,))
        if channels * cb > budget:
            window = jnp.concatenate(
                [window, jnp.full((channels * cb - budget,), FREE,
                                  want.dtype)])
        window = jnp.where((lane < active_budget)
                           & (ptr + lane < want.shape[0]), window, FREE)
        outs = []
        for c in range(channels):
            inflight = _pull_wire(pool_local, window[c * cb:(c + 1) * cb],
                                  table, program, axis, num_nodes, my)
            outs.append(_pull_drain(pool_local, pending, axis, num_nodes))
            pending = inflight
        return (ptr + active_budget, pending), jnp.stack(outs)

    ptr0 = _pvary(jnp.int32(0), axis)
    (_, pending), chunks = jax.lax.scan(body, (ptr0, empty_pending()), None,
                                        length=rounds)
    last = _pull_drain(pool_local, pending, axis, num_nodes)   # epilogue
    flat = chunks.reshape((rounds * channels, cb) + page_shape)
    flat = jnp.concatenate([flat[1:], last[None]], 0)          # un-shift
    return _reassemble(flat.reshape((rounds * channels * cb,) + page_shape),
                       want.shape[0], channels * cb, active_budget,
                       page_shape, pool_local.dtype)


# ---------------------------------------------------------------------------
# Fused round engine (Pallas datapath kernels + epoch-batched wire rounds)
# ---------------------------------------------------------------------------
#
# The unfused engines move every circuit slot's flits as a separate
# ``ppermute`` pair — 2*(N-1) collectives per round (per chunk when
# pipelined), each a sync point, with per-slot gather/merge ops
# materializing an intermediate between them.  The fused engine batches a
# round's *entire* request traffic into one collective and collapses the
# node-local datapath into the :mod:`repro.kernels.bridge_gather` kernels:
#
#   1. ONE ``all_gather`` ships every node's request window [n, L] (the
#      round's request flits, all slots and channels together);
#   2. every node re-derives the steering for the requesters it serves from
#      the replicated table/program (pure local compute — the request
#      preparation unit runs where the data lives) and serves all slots in
#      :func:`~repro.kernels.bridge_gather.gather_pages` grids;
#   3. the payload flits return via the exchange lowering picked by
#      :func:`_fused_exchange_mode` — ONE ``all_to_all`` ("a2a": node h's
#      row j carries the pages it served for requester j; on the push
#      path, a second ``all_gather`` lands the write payloads), or one
#      backward ``ppermute`` hop per slot ("ladder");
#   4. the round retires without a per-slot select chain: in "a2a" mode
#      the ``pull_commit`` / ``push_commit`` kernel merges loopback +
#      landed payloads in one grid (pool buffer donated on push); in
#      "ladder" mode the schedule wires every distance to exactly one slot
#      and unserved lanes carry zero flits, so the pull commit is a pure
#      add-tree over the landed rows.
#
# Collective count per round drops from 2*(N-1)*channels to 2 ("a2a") or
# N ("ladder"), independent of pipeline depth; results and telemetry stay
# bit-exact vs the unfused engines (same serve conditions, same commit
# order — the fused round only batches wire traffic, never changes what is
# served).  With every channel's lanes riding the same collectives, the
# channels knob no longer multiplies dispatch overhead.

# Payload-exchange pattern for the fused pull engine: "a2a" batches every
# slot's data flits into one ``all_to_all``; "ladder" rotates each slot's
# row home with one ``ppermute`` hop.  Both are bit-exact; see
# :func:`_fused_exchange_mode` for the selection policy.
_FUSED_EXCHANGE: str | None = None


def _fused_exchange_mode() -> str:
    """Pick the fused pull engine's payload-exchange lowering.

    On TPU the single ``all_to_all`` is the whole point — one collective
    retires every slot's data flits.  XLA:CPU's all-to-all emulation is
    copy-pathological at large payloads (measured ~9x a ppermute ladder
    moving identical bytes at 256 KiB pages), so off-TPU the ladder wins
    wire-bound rounds while staying well under the unfused engine's
    2*(N-1) collectives (it drops the request ppermutes and the per-slot
    merge chain).  ``_FUSED_EXCHANGE`` overrides for A/B measurement.
    """
    if _FUSED_EXCHANGE is not None:
        return _FUSED_EXCHANGE
    return "a2a" if jax.default_backend() == "tpu" else "ladder"


def _fused_steering(allwin: jax.Array, table: MemPortTable,
                    program: RouteProgram, my, num_nodes: int):
    """Re-derive every node's steering from the replicated control plane.

    allwin: [n, L] the round's gathered request windows.  Returns
    (requester ring ranks [S], per-slot served pool rows [S, L] with FREE
    on unserved lanes) for the slots *this* node serves: slot k's
    requester sits at ring distance d_k behind us.
    """
    home_all, slot_all = table.translate(allwin)
    reqs, requesters = [], []
    for k, d in enumerate(steering.default_route_schedule(num_nodes)):
        requester = jnp.mod(my - d, num_nodes)
        dist = steering.ring_distance(home_all[requester], requester,
                                      num_nodes)
        serve = ((dist == d) & program.live[k]
                 & (program.rank_epoch[k, requester] >= 0))
        reqs.append(jnp.where(serve, slot_all[requester], FREE))
        requesters.append(requester)
    return jnp.stack(requesters), jnp.stack(reqs)


def _fused_window(want: jax.Array, ptr, budget: int, lanes: int, lane,
                  active_budget) -> jax.Array:
    """One round's request window, padded to ``lanes`` and rate-limited."""
    window = jax.lax.dynamic_slice(want, (ptr,), (budget,))
    if lanes > budget:
        window = jnp.concatenate(
            [window, jnp.full((lanes - budget,), FREE, want.dtype)])
    return jnp.where((lane < active_budget)
                     & (ptr + lane < want.shape[0]), window, FREE)


def _pull_local_fused(pool_local: jax.Array, want: jax.Array,
                      table: MemPortTable, active_budget: jax.Array,
                      program: RouteProgram, *, axis: str, num_nodes: int,
                      budget: int, rounds: int, channels: int) -> jax.Array:
    """Fused pull engine: 2 collectives + 2 kernels per round (see above)."""
    page_shape = pool_local.shape[1:]
    cb = -(-budget // channels)
    lanes = channels * cb
    lane = jnp.arange(lanes)
    sched = steering.default_route_schedule(num_nodes)
    my = jax.lax.axis_index(axis)
    exchange = _fused_exchange_mode()

    def body(ptr, _):
        window = _fused_window(want, ptr, budget, lanes, lane, active_budget)
        with jax.named_scope("obs:wire_req"):
            allwin = jax.lax.all_gather(window, axis)          # request flits
        src_rows, reqs = _fused_steering(allwin, table, program, my,
                                         num_nodes)
        home, slot = table.translate(window)
        dist = steering.ring_distance(home, my, num_nodes)
        loop_slot = jnp.where(dist == 0, slot, FREE)
        if exchange == "a2a":
            # Payload flits: node h's send row j is what it served for
            # requester j.  Steering the *request ids* into exchange row
            # order (a [n, lanes] int scatter) lets the gather kernel emit
            # payloads straight into the ``all_to_all`` layout — no
            # full-size zeros + payload-scatter materialization around the
            # collective.  Requester j then finds slot k's pages in the
            # row of its serving home (j + d_k), so the commit kernel's
            # per-lane choice indexes ``recv`` rows directly.
            reqs_by_row = jnp.full((num_nodes, lanes), FREE, jnp.int32)
            reqs_by_row = reqs_by_row.at[src_rows].set(reqs)
            with jax.named_scope("obs:gather"):
                send = _bg.gather_pages(pool_local, reqs_by_row)  # [n, L, ...]
            with jax.named_scope("obs:wire_data"):
                recv = jax.lax.all_to_all(send, axis, 0, 0)
            choice = jnp.where(dist == 0, 0, -1)
            for k, d in enumerate(sched):
                serve = ((dist == d) & program.live[k]
                         & (program.rank_epoch[k, my] >= 0))
                choice = jnp.where(serve, jnp.mod(my + d, num_nodes) + 1,
                                   choice)
            with jax.named_scope("obs:commit"):
                out = _bg.pull_commit(pool_local, recv, choice, loop_slot)
        else:
            # Rotation ladder: slot k's send lanes are ``reqs[k]`` verbatim
            # (what we serve for the requester d_k behind us), so each
            # slot's gathered flits ppermute straight back by distance.
            # The schedule wires every distance to exactly one slot and
            # unserved lanes gather zero flits, so the commit merge
            # degenerates to an add-tree over the landed rows + the
            # epoch-0 loopback gather — no staged exchange buffer, no
            # per-slot select chain, and XLA fuses the whole tree into a
            # single output pass.
            with jax.named_scope("obs:gather"):
                out = _bg.gather_pages(pool_local, loop_slot)
            for k, d in enumerate(sched):
                with jax.named_scope("obs:gather"):
                    flit = _bg.gather_pages(pool_local, reqs[k])
                with jax.named_scope("obs:wire_data"):
                    flit = jax.lax.ppermute(
                        flit, axis,
                        perm=[(j, (j - d) % num_nodes)
                              for j in range(num_nodes)])
                with jax.named_scope("obs:commit"):
                    out = out + flit
        return ptr + active_budget, out

    ptr0 = _pvary(jnp.int32(0), axis)
    _, chunks = jax.lax.scan(body, ptr0, None, length=rounds)
    return _reassemble(
        chunks.reshape((rounds * lanes,) + page_shape), want.shape[0],
        lanes, active_budget, page_shape, pool_local.dtype)


def _push_local_fused(pool_local: jax.Array, ids: jax.Array, pay: jax.Array,
                      table: MemPortTable, active_budget: jax.Array,
                      program: RouteProgram, *, axis: str, num_nodes: int,
                      budget: int, rounds: int, channels: int) -> jax.Array:
    """Fused push engine: batched data flits + 1 commit kernel per round.

    The write payloads travel batched — one ``all_gather`` in "a2a"
    exchange mode (every node lands the full round of data flits), one
    forward ``ppermute`` hop per slot in "ladder" mode (the same bytes the
    unfused engine moves, without its request-flit collectives) — and the
    round retires in a single
    :func:`~repro.kernels.bridge_gather.push_commit` grid against the
    **donated** pool shard, walking the serial engine's commit order.
    """
    cb = -(-budget // channels)
    lanes = channels * cb
    lane = jnp.arange(lanes)
    sched = steering.default_route_schedule(num_nodes)
    my = jax.lax.axis_index(axis)
    page_shape = pool_local.shape[1:]
    nrows = pool_local.shape[0]
    exchange = _fused_exchange_mode()

    def body(carry, _):
        pool_pad, ptr = carry
        window = _fused_window(ids, ptr, budget, lanes, lane, active_budget)
        dwin = jax.lax.dynamic_slice_in_dim(pay, ptr, budget)
        if lanes > budget:
            dwin = jnp.concatenate(
                [dwin, jnp.zeros((lanes - budget,) + page_shape, pay.dtype)])
        with jax.named_scope("obs:wire_req"):
            allwin = jax.lax.all_gather(window, axis)          # request flits
        src_rows, slots = _fused_steering(allwin, table, program, my,
                                          num_nodes)
        if exchange == "a2a":
            with jax.named_scope("obs:wire_data"):
                alldata = jax.lax.all_gather(dwin, axis)       # data flits
            landed = alldata[src_rows]                         # [S, L, ...]
        else:
            # Rotation ladder: requester j's flits for distance d land at
            # home (j + d) in one forward hop — slot k's landed data is
            # the window of the requester d_k behind us, no full-fabric
            # broadcast or landed-row re-gather.
            with jax.named_scope("obs:wire_data"):
                landed = jnp.stack([
                    jax.lax.ppermute(
                        dwin, axis,
                        perm=[(j, (j + d) % num_nodes)
                              for j in range(num_nodes)])
                    for d in sched])
        home, slot = table.translate(window)
        dist = steering.ring_distance(home, my, num_nodes)
        loop_slots = jnp.where(dist == 0, slot, FREE)
        slots_all = jnp.concatenate([loop_slots[None], slots])  # [S+1, lanes]
        with jax.named_scope("obs:commit"):
            pool_pad = _bg.push_commit(pool_pad, slots_all, dwin, landed,
                                       channels=channels, cb=cb)
        return (pool_pad, ptr + active_budget), None

    ptr0 = _pvary(jnp.int32(0), axis)
    (pool_pad, _), _ = jax.lax.scan(
        body, (_bg.pad_pool(pool_local), ptr0), None, length=rounds)
    return pool_pad[:nrows]


def _push_wire(sub_ids: jax.Array, data: jax.Array, table: MemPortTable,
               program: RouteProgram, axis: str, num_nodes: int, my):
    """Request phase of one push chunk: launch slot-id + payload flits.

    Push flits travel together in the request direction; the in-flight
    carry is (slots landed at home [S, cb], payload landed at home
    [S, cb, *page], loopback slots [cb], loopback payload [cb, *page]).
    """
    home, slot = table.translate(sub_ids)
    dist = steering.ring_distance(home, my, num_nodes)
    slots_h, datas_h = [], []
    for k, d in enumerate(steering.default_route_schedule(num_nodes)):
        serve = ((dist == d) & program.live[k]
                 & (program.rank_epoch[k, my] >= 0))
        req = jnp.where(serve, slot, FREE)
        fwd = [(j, (j + d) % num_nodes) for j in range(num_nodes)]
        with jax.named_scope("obs:wire_req"):
            slots_h.append(jax.lax.ppermute(req, axis, perm=fwd))
        with jax.named_scope("obs:wire_data"):
            datas_h.append(jax.lax.ppermute(data, axis, perm=fwd))
    return (jnp.stack(slots_h), jnp.stack(datas_h),
            jnp.where(dist == 0, slot, FREE), data)


def _push_commit(pool: jax.Array, pending) -> jax.Array:
    """Commit phase of one push chunk: scatter the landed flits home.

    Loopback first, then slots in order — the serial engine's write order,
    so the pipelined pool image is identical under the single-writer
    contract.  FREE slots (pipeline prologue, dead pairings) drop.
    """
    slots_h, datas_h, loop_slots, loop_data = pending
    with jax.named_scope("obs:commit"):
        pool = _scatter_local(pool, loop_slots, loop_data)
        for k in range(slots_h.shape[0]):
            pool = _scatter_local(pool, slots_h[k], datas_h[k])
        return pool


def _push_local(pool_local: jax.Array, dest_ids: jax.Array, payload: jax.Array,
                table: MemPortTable, active_budget: jax.Array,
                program: RouteProgram, *, axis: str, num_nodes: int,
                budget: int, rounds: int, edge_buffer: bool = True,
                channels: int = 1, fused: bool = False) -> jax.Array:
    """Write payload pages to their homes (single-writer contract).

    Rate-limiter parity with :func:`_pull_local`: each round writes only the
    first ``active_budget`` lanes and the pointer advances by the same
    amount, so requests past ``rounds * active_budget`` spill off the end of
    the (overprovisioned) round budget and are dropped.  ``edge_buffer`` and
    ``channels`` carry the same semantics as on the pull path: a bufferless
    bridge serializes the wire (loopback commit chained under the first
    slot's flits), and ``channels > 1`` pipelines chunk g+1's request/data
    flits over chunk g's commits (serial when bufferless or 1-node).
    ``fused`` batches each round into one collective pair + one donated
    commit kernel (:func:`_push_local_fused`; unfused-serial fallback when
    bufferless).
    """
    my = jax.lax.axis_index(axis)
    page_shape = pool_local.shape[1:]
    ids = dest_ids.reshape(-1)
    pay = payload.reshape((-1,) + page_shape)
    if rounds == 0:
        return pool_local
    active_budget = jnp.clip(active_budget, 0, budget)  # see _pull_local
    if fused and num_nodes > 1 and edge_buffer:
        return _push_local_fused(
            pool_local, ids, pay, table, active_budget, program, axis=axis,
            num_nodes=num_nodes, budget=budget, rounds=rounds,
            channels=channels)
    pipelined = channels > 1 and num_nodes > 1 and edge_buffer

    if not pipelined:
        def body(carry, _):
            pool, ptr = carry
            sub = jax.lax.dynamic_slice(ids, (ptr,), (budget,))
            data = jax.lax.dynamic_slice(
                pay, (ptr,) + (0,) * len(page_shape), (budget,) + page_shape)
            lane = jnp.arange(budget)
            sub = jnp.where((lane < active_budget)
                            & (ptr + lane < ids.shape[0]), sub, FREE)
            home, slot = table.translate(sub)
            dist = steering.ring_distance(home, my, num_nodes)
            pool = _scatter_local(pool, jnp.where(dist == 0, slot, FREE),
                                  data)
            prev = pool
            for k, d in enumerate(steering.default_route_schedule(num_nodes)):
                fwd = [(j, (j + d) % num_nodes) for j in range(num_nodes)]
                serve = ((dist == d) & program.live[k]
                         & (program.rank_epoch[k, my] >= 0))
                req = jnp.where(serve, slot, FREE)
                data_k = data
                if not edge_buffer:
                    # Bufferless: slot k's flits leave only after slot k-1's
                    # (and the epoch-0 loopback commit) — see _round_pull.
                    req, data_k, prev = jax.lax.optimization_barrier(
                        (req, data_k, prev))
                with jax.named_scope("obs:wire_req"):
                    slot_at_home = jax.lax.ppermute(req, axis, perm=fwd)
                with jax.named_scope("obs:wire_data"):
                    data_at_home = jax.lax.ppermute(data_k, axis, perm=fwd)
                with jax.named_scope("obs:commit"):
                    pool = _scatter_local(pool, slot_at_home, data_at_home)
                prev = data_at_home
            return (pool, ptr + active_budget), None

        ptr0 = _pvary(jnp.int32(0), axis)
        (pool_local, _), _ = jax.lax.scan(body, (pool_local, ptr0), None,
                                          length=rounds)
        return pool_local

    # Pipelined engine (mirror of _pull_local): issue chunk g+1's flits,
    # then commit chunk g's (carried in flight), epilogue commits the last.
    cb = -(-budget // channels)
    lane = jnp.arange(channels * cb)
    nslots = num_nodes - 1

    def empty_pending():
        return tuple(_pvary(x, axis) for x in (
            jnp.full((nslots, cb), FREE, jnp.int32),
            jnp.zeros((nslots, cb) + page_shape, pool_local.dtype),
            jnp.full((cb,), FREE, jnp.int32),
            jnp.zeros((cb,) + page_shape, pool_local.dtype)))

    def body(carry, _):
        pool, ptr, pending = carry
        window = jax.lax.dynamic_slice(ids, (ptr,), (budget,))
        dwin = jax.lax.dynamic_slice(
            pay, (ptr,) + (0,) * len(page_shape), (budget,) + page_shape)
        if channels * cb > budget:
            window = jnp.concatenate(
                [window, jnp.full((channels * cb - budget,), FREE,
                                  ids.dtype)])
            dwin = jnp.concatenate(
                [dwin, jnp.zeros((channels * cb - budget,) + page_shape,
                                 pay.dtype)])
        window = jnp.where((lane < active_budget)
                           & (ptr + lane < ids.shape[0]), window, FREE)
        for c in range(channels):
            inflight = _push_wire(window[c * cb:(c + 1) * cb],
                                  dwin[c * cb:(c + 1) * cb], table, program,
                                  axis, num_nodes, my)
            pool = _push_commit(pool, pending)
            pending = inflight
        return (pool, ptr + active_budget, pending), None

    ptr0 = _pvary(jnp.int32(0), axis)
    (pool_local, _, pending), _ = jax.lax.scan(
        body, (pool_local, ptr0, empty_pending()), None, length=rounds)
    return _push_commit(pool_local, pending)                   # epilogue


# ---------------------------------------------------------------------------
# Public API (shard_map wrappers)
# ---------------------------------------------------------------------------

def _mem_axis_size(mesh: Optional[Mesh], axis: str) -> int:
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]


def _resolve_program(program: Optional[RouteProgram],
                     num_nodes: int) -> RouteProgram:
    """Default program (full bidirectional coverage) + static shape check."""
    if program is None:
        return steering.bidirectional_program(num_nodes)
    if program.num_slots != num_nodes - 1:
        raise ValueError(
            f"route program has {program.num_slots} slots; a {num_nodes}-node "
            f"ring needs {num_nodes - 1}")
    return program


def _resolve_topology(topology: Optional[Topology],
                      num_nodes: int) -> Topology:
    """Default (flat single-board) fabric + node-count check.

    The topology is **static**: its tables enter the jitted datapath as
    constants, so a deployment's fabric shape never appears in the jit
    cache key — only a topology *change* retraces (as it must: it is a
    different machine).
    """
    if topology is None:
        return Topology.flat(num_nodes)
    if topology.num_nodes != num_nodes:
        raise ValueError(
            f"topology spans {topology.num_nodes} endpoints; the bridge has "
            f"{num_nodes}")
    return topology


def _loopback_telemetry(ids: jax.Array, table: MemPortTable,
                        program: Optional[RouteProgram], tn: int,
                        active_budget, budget: int, rounds: int,
                        topology: Optional[Topology],
                        tenant_ids: Optional[jax.Array] = None,
                        max_tenants: int = _telemetry.DEFAULT_MAX_TENANTS
                        ) -> _telemetry.BridgeTelemetry:
    """Telemetry for the 1-device path: row i of ``ids`` is logical
    requester i; the whole batch shares ``active_budget``'s first element
    (mirroring the loopback rate limiter)."""
    prog = _resolve_program(program, tn)
    topo = _resolve_topology(topology, tn)
    tt = topo.tables()
    ab = jnp.clip(jnp.asarray(active_budget).reshape(-1)[0], 0, budget)
    rows = ids.reshape((-1, ids.shape[-1]))
    if tenant_ids is None:
        tenant_ids = jnp.zeros_like(ids)
    trows = tenant_ids.reshape((-1, tenant_ids.shape[-1]))

    def per_row(row, my, trow):
        return _telemetry.transfer_telemetry(
            row, table, prog, ab, my=my, num_nodes=tn, budget=budget,
            rounds=rounds, topo=tt, num_groups=topo.num_groups,
            tenant_ids=trow, max_tenants=max_tenants)

    return jax.vmap(per_row)(rows, jnp.arange(rows.shape[0]), trows)


def _telemetry_specs(mem_axis: str) -> _telemetry.BridgeTelemetry:
    """shard_map out_specs for per-node telemetry (leading node dim)."""
    return _telemetry.BridgeTelemetry(
        slot_served=P(mem_axis, None), loopback_served=P(mem_axis),
        spilled=P(mem_axis), pruned=P(mem_axis), traffic=P(mem_axis, None),
        epoch_cw=P(mem_axis, None), epoch_ccw=P(mem_axis, None),
        slot_intra=P(mem_axis, None), tier_hops=P(mem_axis, None),
        tenant_served=P(mem_axis, None), tenant_spilled=P(mem_axis, None),
        tenant_pruned=P(mem_axis, None))


def _loopback_mask(flat: jax.Array, ids: jax.Array, table: MemPortTable,
                   program: Optional[RouteProgram], tn: int) -> jax.Array:
    """Apply a route program on the 1-device (loopback) fast path.

    The loopback circuit still models ``tn`` logical ring nodes: row i of
    ``ids`` is logical requester i, and requests whose logical ring distance
    has no wired circuit are dropped — identical semantics (and oracle) as
    the N-device path.
    """
    if program is None:
        return flat
    _resolve_program(program, tn)
    rows = ids.reshape((-1, ids.shape[-1]))
    served = _ref.served_mask(table, rows, program).reshape(-1)
    return jnp.where(served, flat, FREE)


def _resolve_channels(channels: int) -> int:
    if channels < 1:
        raise ValueError(f"channels must be >= 1, got {channels}")
    return int(channels)


def pull_pages(pool_pages: jax.Array, want: jax.Array, table: MemPortTable,
               *, mesh: Optional[Mesh], mem_axis: str = "data",
               budget: int = 8, edge_buffer: bool = True,
               channels: int = 1, overprovision: int = 1,
               active_budget: Optional[jax.Array] = None,
               program: Optional[RouteProgram] = None,
               table_nodes: int = 0, collect_telemetry: bool = False,
               topology: Optional[Topology] = None,
               tenant_ids: Optional[jax.Array] = None,
               max_tenants: int = 0, fused: bool = True):
    """Pull logical pages through the bridge.

    Args:
      pool_pages: [num_nodes * pages_per_node, *page_shape], sharded on dim 0
        over ``mem_axis`` (or unsharded when N == 1).
      want: [num_nodes, R] per-node request lists (logical page ids, FREE pad),
        sharded on dim 0.
      table: replicated memport table.
      program: runtime circuit schedule (default: full bidirectional
        coverage).  A **runtime input**: swapping unidirectional /
        bidirectional / pruned programs on a jitted caller never retraces.
      channels: pipeline depth of the round engine (static, like
        ``budget``).  1 = the serial engine; > 1 splits each round's budget
        into ``channels`` virtual channels and overlaps chunk g+1's request
        flits with chunk g's data flits (results and telemetry stay
        bit-exact — the pipeline reorders wire traffic, never what is
        served).  Ignored on the loopback path and under
        ``edge_buffer=False`` (a bufferless bridge cannot hold overlapped
        flits).
      table_nodes: logical node count of the table (0 = mesh size).  On a
        1-device mesh the pool may still model several logical memory nodes
        (loopback circuit); their slots flatten node-major.
      collect_telemetry: also return a per-node
        :class:`~repro.telemetry.counters.BridgeTelemetry` of what this
        transfer served/spilled/pruned.  The counters have static shapes, so
        with collection on, swapping programs / tables / budgets still never
        retraces (the flag itself is static: toggling it changes the output
        structure).
      topology: the static board + rack fabric
        (:class:`~repro.core.topology.Topology`, default: one flat board).
        Classifies each transfer's tier for the telemetry counters; its
        tables are compile-time constants, so flat and hierarchical
        *programs* swap on one trace.
      tenant_ids: optional [num_nodes, R] tenant-id lane aligned with
        ``want`` (a **runtime input**, like the table: swapping tenant
        shares / window compositions never retraces).  Attribution is
        observational — it bins the telemetry's per-tenant counters and
        never changes what is served.  None = all tenant 0; without
        ``collect_telemetry`` the lane is ignored entirely (never
        materialized on the hot path).
      max_tenants: static width of the per-tenant telemetry histograms
        (0 = the :data:`repro.telemetry.counters.DEFAULT_MAX_TENANTS`).
      fused: run each epoch through the fused Pallas datapath (default ON):
        serve-condition evaluation, the page gather and the payload commit
        collapse into one kernel pair per round, and the round's wire
        traffic batches into a single request ``all_gather`` plus the
        payload exchange (an ``all_to_all`` on TPU, one ``ppermute`` hop
        per slot off-TPU — :func:`_fused_exchange_mode`) instead of
        2·(N-1)·channels ``ppermute`` sync
        points.  Results and telemetry are bit-exact vs ``fused=False``
        (the escape hatch back to the unfused ppermute-chain engines); a
        bufferless bridge (``edge_buffer=False``) always runs unfused
        serial.  On the loopback path the fused gather runs as one
        :func:`~repro.kernels.bridge_gather.gather_pages` grid.
    Returns:
      [num_nodes, R, *page_shape] gathered pages, sharded on dim 0 — or
      ``(pages, telemetry)`` when ``collect_telemetry`` is set.
    """
    n = _mem_axis_size(mesh, mem_axis)
    channels = _resolve_channels(channels)
    if max_tenants <= 0:
        max_tenants = _telemetry.DEFAULT_MAX_TENANTS
    r = want.shape[-1]
    rounds = steering.num_rounds(r, budget, overprovision)
    if tenant_ids is not None and tenant_ids.shape != want.shape:
        raise ValueError(f"tenant_ids shape {tenant_ids.shape} != request "
                         f"shape {want.shape}")
    # The lane only feeds the telemetry counters: without collection it is
    # never materialized or threaded (no wasted operand on the hot path).
    if collect_telemetry and tenant_ids is None:
        tenant_ids = jnp.zeros(want.shape, jnp.int32)
    pad = rounds * budget - r
    if pad:
        want = jnp.concatenate(
            [want, jnp.full(want.shape[:-1] + (pad,), FREE, want.dtype)], -1)
        if collect_telemetry:
            tenant_ids = jnp.concatenate(
                [tenant_ids, jnp.zeros(tenant_ids.shape[:-1] + (pad,),
                                       tenant_ids.dtype)], -1)
    if active_budget is None:
        active_budget = jnp.int32(budget)

    if n == 1:
        tn = table_nodes or 1
        ppn = pool_pages.shape[0] // tn
        home, slot = table.translate(want.reshape(-1))
        flat = jnp.where(home >= 0, home * ppn + slot, FREE)
        # Rate-limiter parity with the N-device path: round ``r`` serves
        # request indices [r*ab, (r+1)*ab), so anything past rounds*ab spills
        # off the end of the (overprovisioned) round budget and is dropped.
        ab = jnp.clip(jnp.asarray(active_budget).reshape(-1)[0], 0, budget)
        idx = jnp.arange(want.shape[-1])
        served = jnp.broadcast_to(idx < rounds * ab, want.shape).reshape(-1)
        flat = jnp.where(served, flat, FREE)
        flat = _loopback_mask(flat, want, table, program, tn)
        if fused:
            out = _bg.gather_pages(pool_pages, flat)
        else:
            out = _gather_local(pool_pages, flat)
        out = out.reshape(want.shape + pool_pages.shape[1:])
        # Trim the round padding on the *request* dim (pages may be
        # multi-dimensional, so slice by position, not from the back).
        out = out[(slice(None),) * (want.ndim - 1) + (slice(0, r),)]
        if collect_telemetry:
            return out, _loopback_telemetry(want, table, program, tn,
                                            active_budget, budget, rounds,
                                            topology, tenant_ids, max_tenants)
        return out
    if table_nodes and table_nodes != n:
        raise ValueError(f"table has {table_nodes} nodes but mem axis "
                         f"{mem_axis!r} has {n}")
    program = _resolve_program(program, n)
    topo = _resolve_topology(topology, n)

    pages_spec = P(mem_axis, *([None] * (pool_pages.ndim - 1)))
    out_spec = P(mem_axis, *([None] * pool_pages.ndim))
    body = functools.partial(
        _pull_local, axis=mem_axis, num_nodes=n, budget=budget,
        rounds=rounds, edge_buffer=edge_buffer, channels=channels,
        fused=fused)
    ab_vec = jnp.clip(jnp.broadcast_to(active_budget, (n,)), 0, budget)

    def mapped(pool, want_l, table_l, ab, prog, tt, *ten_l):
        out = body(pool, want_l[0], table_l, ab[0], prog)
        if not collect_telemetry:
            return out[None]
        telem = _telemetry.transfer_telemetry(
            want_l[0], table_l, prog, ab[0],
            my=jax.lax.axis_index(mem_axis), num_nodes=n, budget=budget,
            rounds=rounds, topo=tt, num_groups=topo.num_groups,
            tenant_ids=ten_l[0][0], max_tenants=max_tenants)
        return out[None], jax.tree.map(lambda x: x[None], telem)

    out_specs = ((out_spec, _telemetry_specs(mem_axis))
                 if collect_telemetry else out_spec)
    in_specs = (pages_spec, P(mem_axis, None), P(), P(mem_axis), P(),
                TopoTables(group=P(), local_rank=P(), group_size=P()))
    args = (pool_pages, want, table, ab_vec, program, topo.tables())
    if collect_telemetry:
        in_specs += (P(mem_axis, None),)
        args += (tenant_ids,)
    out = shard_map(
        mapped, mesh, in_specs=in_specs, out_specs=out_specs,
        mem_axis=mem_axis,
    )(*args)
    if collect_telemetry:
        return out[0][:, :r], out[1]
    return out[:, :r]


def push_pages(pool_pages: jax.Array, dest: jax.Array, payload: jax.Array,
               table: MemPortTable, *, mesh: Optional[Mesh],
               mem_axis: str = "data", budget: int = 8,
               edge_buffer: bool = True, channels: int = 1,
               overprovision: int = 1,
               active_budget: Optional[jax.Array] = None,
               program: Optional[RouteProgram] = None,
               table_nodes: int = 0, collect_telemetry: bool = False,
               topology: Optional[Topology] = None,
               tenant_ids: Optional[jax.Array] = None,
               max_tenants: int = 0, fused: bool = True):
    """Write pages to their homes through the bridge (single-writer pages).

    Args:
      pool_pages: as in :func:`pull_pages` (returned updated).
      dest: [num_nodes, R] logical page ids each node writes.
      payload: [num_nodes, R, *page_shape].
      edge_buffer: as in :func:`pull_pages` — ``False`` models a bufferless
        bridge by serializing each round's wire activity (loopback commit,
        then slot after slot) with ``optimization_barrier``.
      channels: pipeline depth of the round engine, same semantics as in
        :func:`pull_pages` (chunk g+1's request/data flits overlap chunk
        g's commits; the pool image stays identical under the
        single-writer contract).
      active_budget: runtime rate limiter, same spill semantics as
        :func:`pull_pages`: each round writes only the first
        ``active_budget`` lanes, writes past ``rounds * active_budget``
        spill off the (overprovisioned) round budget and are dropped.
      program: runtime circuit schedule (default: full bidirectional
        coverage), same semantics as in :func:`pull_pages`.
      collect_telemetry: also return per-node write-path counters
        (:class:`~repro.telemetry.counters.BridgeTelemetry`).
      tenant_ids / max_tenants: per-request tenant attribution lane for the
        telemetry counters, same semantics as in :func:`pull_pages`.
      fused: run each epoch through the fused Pallas datapath, same
        semantics as in :func:`pull_pages` — on the write path the round's
        address flits batch into one ``all_gather``, data flits take the
        backend-picked payload exchange (an ``all_gather`` on TPU, one
        forward ``ppermute`` hop per slot off-TPU —
        :func:`_fused_exchange_mode`), and everything retires through one
        :func:`~repro.kernels.bridge_gather.push_commit` grid against the
        donated pool shard.
    """
    n = _mem_axis_size(mesh, mem_axis)
    channels = _resolve_channels(channels)
    if max_tenants <= 0:
        max_tenants = _telemetry.DEFAULT_MAX_TENANTS
    r = dest.shape[-1]
    rounds = steering.num_rounds(r, budget, overprovision)
    if tenant_ids is not None and tenant_ids.shape != dest.shape:
        raise ValueError(f"tenant_ids shape {tenant_ids.shape} != request "
                         f"shape {dest.shape}")
    if collect_telemetry and tenant_ids is None:
        tenant_ids = jnp.zeros(dest.shape, jnp.int32)
    pad = rounds * budget - r
    if pad:
        dest = jnp.concatenate(
            [dest, jnp.full(dest.shape[:-1] + (pad,), FREE, dest.dtype)], -1)
        if collect_telemetry:
            tenant_ids = jnp.concatenate(
                [tenant_ids, jnp.zeros(tenant_ids.shape[:-1] + (pad,),
                                       tenant_ids.dtype)], -1)
        zeros = jnp.zeros(payload.shape[:1] + (pad,) + payload.shape[2:],
                          payload.dtype)
        payload = jnp.concatenate([payload, zeros], 1)
    if active_budget is None:
        active_budget = jnp.int32(budget)

    if n == 1:
        tn = table_nodes or 1
        ppn = pool_pages.shape[0] // tn
        home, slot = table.translate(dest.reshape(-1))
        flat = jnp.where(home >= 0, home * ppn + slot, FREE)
        # Rate-limiter parity with the N-device path (see pull_pages).
        ab = jnp.clip(jnp.asarray(active_budget).reshape(-1)[0], 0, budget)
        idx = jnp.arange(dest.shape[-1])
        served = jnp.broadcast_to(idx < rounds * ab, dest.shape).reshape(-1)
        flat = jnp.where(served, flat, FREE)
        flat = _loopback_mask(flat, dest, table, program, tn)
        flat_pay = payload.reshape((-1,) + payload.shape[2:])
        if fused:
            out = _bg.scatter_pages(pool_pages, flat, flat_pay)
        else:
            out = _scatter_local(pool_pages, flat, flat_pay)
        if collect_telemetry:
            return out, _loopback_telemetry(dest, table, program, tn,
                                            active_budget, budget, rounds,
                                            topology, tenant_ids, max_tenants)
        return out
    if table_nodes and table_nodes != n:
        raise ValueError(f"table has {table_nodes} nodes but mem axis "
                         f"{mem_axis!r} has {n}")
    program = _resolve_program(program, n)
    topo = _resolve_topology(topology, n)

    pages_spec = P(mem_axis, *([None] * (pool_pages.ndim - 1)))
    body = functools.partial(_push_local, axis=mem_axis, num_nodes=n,
                             budget=budget, rounds=rounds,
                             edge_buffer=edge_buffer, channels=channels,
                             fused=fused)
    ab_vec = jnp.clip(jnp.broadcast_to(active_budget, (n,)), 0, budget)

    def mapped(pool, dest_l, pay_l, table_l, ab, prog, tt, *ten_l):
        out = body(pool, dest_l[0], pay_l[0], table_l, ab[0], prog)
        if not collect_telemetry:
            return out
        telem = _telemetry.transfer_telemetry(
            dest_l[0], table_l, prog, ab[0],
            my=jax.lax.axis_index(mem_axis), num_nodes=n, budget=budget,
            rounds=rounds, topo=tt, num_groups=topo.num_groups,
            tenant_ids=ten_l[0][0], max_tenants=max_tenants)
        return out, jax.tree.map(lambda x: x[None], telem)

    out_specs = ((pages_spec, _telemetry_specs(mem_axis))
                 if collect_telemetry else pages_spec)
    in_specs = (pages_spec, P(mem_axis, None),
                P(mem_axis, None, *([None] * (payload.ndim - 2))), P(),
                P(mem_axis), P(),
                TopoTables(group=P(), local_rank=P(), group_size=P()))
    args = (pool_pages, dest, payload, table, ab_vec, program, topo.tables())
    if collect_telemetry:
        in_specs += (P(mem_axis, None),)
        args += (tenant_ids,)
    return shard_map(
        mapped, mesh, in_specs=in_specs, out_specs=out_specs,
        mem_axis=mem_axis,
    )(*args)
