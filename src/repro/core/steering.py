"""Request preparation & steering (paper Fig. 1, dotted box).

Pure helpers shared by the transfer engine:

* ring distances (which request is served by which circuit),
* round/budget splitting (the software rate limiter),
* **route programs** — runtime-reprogrammable circuit schedules (which ring
  offset is wired at which circuit epoch, and in which direction).

A :class:`RouteProgram` is the software-defined analogue of the paper's
circuit control plane: a *runtime value* (registered pytree, arrays only)
that the orchestrator can swap between steps — unidirectional, bidirectional,
pruned, link-avoiding, or **hierarchical** for a board + rack fabric
(:func:`hierarchical_program`) — without ever recompiling the jitted
datapath.

Programs are compiled on the host: every builder does its arithmetic on
numpy arrays, and one that starts from a base program reads the base's host
copy (:meth:`RouteProgram.on_host`), never the device.  The device program
comes from one content-keyed constructor (:func:`make_program`) that keeps
the last :data:`PROGRAM_CACHE_SIZE` contents: a program equal to one already
on the device is that program, with no device put.

Key identity the programs exploit: on an N-ring the permutation
``rank -> rank + d (mod N)`` is *the same permutation* as
``rank -> rank - (N - d) (mod N)``.  Slot ``k`` of the datapath (serving
ring distance ``k + 1``) therefore has two physical realisations: a
clockwise circuit of ``k + 1`` hops or a counter-clockwise circuit of
``N - k - 1`` hops.  The program picks, per slot, the signed offset actually
driven (sign = direction, magnitude = hop count / which directed links are
held) and the circuit *epoch* at which the slot is wired.  One epoch can
host one circuit per direction (disjoint wire sets), so a bidirectional
program covers all N-1 distances in ⌊N/2⌋ epochs instead of N-1.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.memport import FREE
from repro.core.topology import Topology


def ring_distance(home: jnp.ndarray, my_rank, num_nodes: int) -> jnp.ndarray:
    """Epoch (ring hop count) at which a request to ``home`` is served."""
    d = jnp.mod(home - my_rank, num_nodes)
    return jnp.where(home == FREE, -1, d)


def num_rounds(num_requests: int, budget: int, overprovision: int = 1) -> int:
    """Static round count for ``num_requests`` at ``budget`` pages/round."""
    if num_requests == 0:
        return 0
    return -(-num_requests // max(budget, 1)) * max(overprovision, 1)


def default_route_schedule(num_nodes: int) -> list[int]:
    """Distances wired per slot: one full ring rotation (1 .. N-1).

    Epoch 0 (distance 0) is the local loopback fast path and never uses the
    circuit network, matching the paper's locally-mapped regions.  Kept for
    the datapath's static slot structure; the *runtime* schedule — which
    slot is live, in which direction, at which epoch — is a
    :class:`RouteProgram`.
    """
    return list(range(1, num_nodes))


# ---------------------------------------------------------------------------
# Route programs (runtime circuit schedules)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class RouteProgram:
    """A runtime circuit schedule for an N-node ring bridge.

    All three fields are arrays of static length ``N - 1`` (one entry per
    datapath slot; slot ``k`` serves ring distance ``k + 1``), so swapping
    programs on a jitted step never changes shapes and never retraces —
    exactly like ``active_budget``.

    Attributes:
      offsets: i32[N-1]  signed ring offset driven for slot k.  Must satisfy
        ``offsets[k] % N == k + 1`` when live; sign is the physical ring
        direction (+ = clockwise), ``|offsets[k]|`` the hop count on a flat
        ring (hierarchical realizations count hops via the Topology).  0 on
        dead slots.
      epoch:   i32[N-1]  base circuit epoch of slot k (the first epoch any
        requester drives it; two slots may share an epoch iff they drive
        opposite directions).  -1 on dead slots.
      live:    bool[N-1] dead slots carry no traffic: the datapath
        FREE-masks their requests, so their payload work is skipped and the
        oracle drops their pages (pruning / link avoidance).
      rank_epoch: i32[N-1, N]  the **group mask**: the epoch at which slot k
        serves requester rank r, or -1 when that (rank, slot) pairing is
        masked off — the datapath FREE-masks exactly those requests.  Flat
        programs broadcast ``epoch`` over the rank axis; hierarchical
        programs split a slot between an intra-board epoch (its same-board
        requesters, concurrent across boards) and a gateway epoch (its
        board-crossing requesters).  Same static shape for every program,
        so swapping flat and hierarchical programs never retraces.
    """

    offsets: jax.Array
    epoch: jax.Array
    live: jax.Array
    rank_epoch: jax.Array

    @property
    def num_slots(self) -> int:
        return self.offsets.shape[0]

    @property
    def num_nodes(self) -> int:
        return self.num_slots + 1

    # -- host-side accounting (benchmarks / perfmodel / tests) ---------------
    def on_host(self) -> "RouteProgram":
        """This program with read-only numpy fields in the device dtypes.

        A program :func:`make_program` put on the device answers with the
        host copy it was built from; any other reads each field once.
        """
        if all(isinstance(getattr(self, f), np.ndarray)
               and getattr(self, f).dtype == dt for f, dt in PROGRAM_FIELDS):
            return self
        host = _INSTALLED.host_of(self)
        return host if host is not None else _host_program(
            self.offsets, self.epoch, self.live, self.rank_epoch)

    def num_epochs(self) -> int:
        """Circuit epochs the program occupies (max served epoch + 1)."""
        served = self.rank_served()
        re = self.on_host().rank_epoch
        return int(re[served].max()) + 1 if served.any() else 0

    def live_distances(self) -> np.ndarray:
        """Ring distances with a wired circuit (sorted)."""
        return np.nonzero(self.on_host().live)[0] + 1

    def hops(self) -> np.ndarray:
        """Flat-ring hop count per slot (0 on dead slots)."""
        return np.abs(self.on_host().offsets)

    def rank_served(self) -> np.ndarray:
        """bool[N-1, N]: does slot k carry requester rank r's traffic."""
        h = self.on_host()
        return h.live[:, None] & (h.rank_epoch >= 0)

    def validate(self) -> None:
        """Raise on incongruent offsets or an inconsistent group mask."""
        n = self.num_nodes
        h = self.on_host()
        off, lv = h.offsets, h.live
        d = np.arange(1, n)
        bad = lv & ((off % n) != d)
        if bad.any():
            raise ValueError(
                f"slots {np.nonzero(bad)[0].tolist()} drive offsets "
                f"{off[bad].tolist()} incongruent with their distances")
        re = h.rank_epoch
        if re.shape != (n - 1, n):
            raise ValueError(f"rank_epoch has shape {re.shape}; expected "
                             f"{(n - 1, n)}")
        ghost = (~lv) & (re >= 0).any(1)
        if ghost.any():
            raise ValueError(f"dead slots {np.nonzero(ghost)[0].tolist()} "
                             "still carry rank epochs")
        idle = lv & ~(re >= 0).any(1)
        if idle.any():
            raise ValueError(f"live slots {np.nonzero(idle)[0].tolist()} "
                             "serve no rank")


def _rank_epoch_from(epoch: np.ndarray, live: np.ndarray) -> np.ndarray:
    """Flat broadcast: slot k serves every rank at its single epoch."""
    n = live.shape[0] + 1
    col = np.where(live, epoch, -1).astype(np.int64)
    return np.repeat(col[:, None], n, axis=1)


#: A program's fields and their dtypes on the device (and on the host).
PROGRAM_FIELDS = (("offsets", np.int32), ("epoch", np.int32),
                  ("live", np.bool_), ("rank_epoch", np.int32))

#: Distinct program contents kept on the device by :func:`make_program`.
PROGRAM_CACHE_SIZE = 8


def _host_program(*arrays) -> RouteProgram:
    """A RouteProgram of read-only numpy copies in the device dtypes."""
    fields = {}
    for (name, dtype), a in zip(PROGRAM_FIELDS, arrays):
        a = np.array(a, dtype)
        a.flags.writeable = False
        fields[name] = a
    return RouteProgram(**fields)


def content_key(program: RouteProgram) -> tuple:
    """Each field's shape and bytes in its device dtype: the fingerprint
    :func:`repro.obs.flight.program_digest` hashes.  Equal keys, equal
    programs."""
    h = program.on_host()
    return tuple((getattr(h, f).shape, getattr(h, f).tobytes())
                 for f, _ in PROGRAM_FIELDS)


class _DevicePrograms:
    """The last few route programs put on the device, keyed by content."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.puts = 0
        self._by_key: OrderedDict = OrderedDict()  # key -> (device, host)

    def get(self, host: RouteProgram) -> RouteProgram:
        key = content_key(host)
        hit = self._by_key.get(key)
        if hit is not None:
            self._by_key.move_to_end(key)
            return hit[0]
        dev = RouteProgram(**{f: jnp.asarray(getattr(host, f))
                              for f, _ in PROGRAM_FIELDS})
        self.puts += 1
        if any(isinstance(getattr(dev, f), jax.core.Tracer)
               for f, _ in PROGRAM_FIELDS):
            return dev  # built under a trace: nothing to keep
        self._by_key[key] = (dev, host)
        if len(self._by_key) > self.capacity:
            self._by_key.popitem(last=False)
        return dev

    def host_of(self, program: RouteProgram) -> Optional[RouteProgram]:
        for dev, host in self._by_key.values():
            if dev is program:
                return host
        return None


# One per process, as the builders are free functions; what it holds is
# immutable (frozen programs, read-only host copies), so callers share it.
_INSTALLED = _DevicePrograms(PROGRAM_CACHE_SIZE)


def device_puts() -> int:
    """Programs :func:`make_program` has put on the device so far (new
    contents; a content still kept is returned without a put)."""
    return _INSTALLED.puts


def make_program(offsets, epoch, live,
                 rank_epoch: Optional[np.ndarray] = None) -> RouteProgram:
    """The device RouteProgram of these host arrays (``rank_epoch``
    defaults to the flat broadcast of ``epoch``): the one already on the
    device when its content is, else a new one."""
    if rank_epoch is None:
        rank_epoch = _rank_epoch_from(np.asarray(epoch, np.int64),
                                      np.asarray(live, bool))
    return _INSTALLED.get(_host_program(offsets, epoch, live, rank_epoch))


def _pack_epochs(off: np.ndarray, live: np.ndarray) -> np.ndarray:
    """Flat base epochs: per direction, live slots by hop count (stable),
    one circuit per direction per epoch; -1 on dead slots."""
    epoch = np.full(off.shape, -1, np.int64)
    for sign in (1, -1):
        idx = np.nonzero(live & (np.sign(off) == sign))[0]
        order = np.argsort(np.abs(off[idx]), kind="stable")
        epoch[idx[order]] = np.arange(len(idx))
    return epoch


def unidirectional_program(num_nodes: int, direction: int = 1) -> RouteProgram:
    """One full ring rotation in one direction: N-1 circuit epochs.

    ``direction=+1`` reproduces the historical fixed schedule
    (``default_route_schedule``); ``-1`` drives every circuit the other way
    round (all counter-clockwise links, no clockwise link touched).
    """
    d = np.arange(1, num_nodes)
    off = d if direction >= 0 else -(num_nodes - d)
    hops = np.abs(off)
    return make_program(off, hops - 1, np.ones_like(d, bool))


def bidirectional_program(num_nodes: int) -> RouteProgram:
    """Shortest-way routing: distance d drives min(d, N-d) hops.

    Epoch e hosts the (e+1)-hop clockwise circuit and the (e+1)-hop
    counter-clockwise circuit simultaneously (disjoint wire sets), so all
    N-1 distances complete in ⌊N/2⌋ epochs — vs N-1 unidirectionally.
    """
    d = np.arange(1, num_nodes)
    back = num_nodes - d
    off = np.where(d <= back, d, -back)
    return make_program(off, np.abs(off) - 1, np.ones_like(d, bool))


def pruned_program(base: RouteProgram, live_distances) -> RouteProgram:
    """Keep only ``live_distances``; compact epochs per direction.

    Dead slots are FREE-masked by the datapath (their pages, if any were
    requested, come back as zeros — callers prune only distances they know
    carry no traffic).  Surviving flat circuits re-pack into consecutive
    epochs, shortest hop count first, one circuit per direction per epoch.
    A **hierarchical** base keeps its group mask instead: the surviving
    slots retain their per-rank intra/gateway epochs (re-packing them per
    direction would put two board-crossing circuits on one gateway epoch).
    """
    n = base.num_nodes
    keep = np.zeros((n - 1,), bool)
    for d in np.asarray(list(live_distances), np.int64).ravel():
        if not 0 < d < n:
            raise ValueError(f"distance {d} out of range for {n} nodes")
        keep[d - 1] = True
    host = base.on_host()
    re = host.rank_epoch
    flat = (re == re[:, :1]).all()  # every row uniform = no group mask
    if not flat:
        return masked_ranks_program(base, np.broadcast_to(keep[:, None],
                                                          re.shape))
    live = host.live & keep
    off = np.where(live, host.offsets, 0)
    return make_program(off, _pack_epochs(off, live), live)


def load_balanced_program(num_nodes: int, dist_weight,
                          prune: bool = True) -> RouteProgram:
    """Direction assignment minimizing the bottleneck direction's load.

    ``dist_weight[k]`` is the *measured* traffic (pages or bytes) carried at
    ring distance ``k + 1`` — typically
    :meth:`repro.telemetry.TelemetryAggregator.distance_pages`.  Circuits of
    one direction share that direction's links, so an edge-buffered round
    costs ``max(cw_load, ccw_load)`` wire time (the bottleneck term
    ``perfmodel.predict_round_latency_us`` models): instead of the static
    shortest-way split (min(d, N-d)), distances are partitioned greedily —
    heaviest first, each onto the currently lighter direction (ties prefer
    fewer hops).  Greedy is not optimal: where its bottleneck comes out
    larger than the shortest-way split's, the shortest-way split is kept.
    Zero-weight distances are pruned (``prune=True``) or kept on their
    shortest-way direction as free riders.  Epochs compact per direction,
    shortest hop count first, one circuit per direction per epoch.
    """
    n = num_nodes
    w = np.asarray(dist_weight, float).reshape(-1)
    if w.shape[0] != n - 1:
        raise ValueError(f"dist_weight has {w.shape[0]} entries; a {n}-node "
                         f"ring has {n - 1} distances")
    if (w < 0).any():
        raise ValueError("dist_weight must be non-negative")
    live = (w > 0) if prune else np.ones((n - 1,), bool)
    d = np.arange(1, n)
    shortest = np.where(live, np.where(d <= n - d, d, -(n - d)), 0)
    off = shortest.copy()
    loads = {1: 0.0, -1: 0.0}
    for k in sorted(np.nonzero(w > 0)[0].tolist(), key=lambda k: (-w[k], k)):
        if loads[1] < loads[-1]:
            sign = 1
        elif loads[-1] < loads[1]:
            sign = -1
        else:
            sign = int(np.sign(shortest[k]))
        off[k] = d[k] if sign == 1 else -(n - d[k])
        loads[sign] += w[k]

    def bottleneck(o):
        return max(w[o > 0].sum(), w[o < 0].sum())

    if bottleneck(off) > bottleneck(shortest):
        off = shortest
    return make_program(off, _pack_epochs(off, live), live)


def link_avoiding_program(num_nodes: int, failed_direction: int
                          ) -> RouteProgram:
    """Route every circuit away from a failed directed ring link.

    A d-hop circuit in one direction occupies *every* link of that
    direction (all N rank->rank+1 edges carry flits simultaneously), so a
    single failed directed link takes the whole direction down; the
    surviving direction still reaches every distance.  ``failed_direction``
    is +1 (a clockwise link died) or -1.
    """
    if failed_direction not in (1, -1):
        raise ValueError("failed_direction must be +1 or -1")
    return unidirectional_program(num_nodes, direction=-failed_direction)


# ---------------------------------------------------------------------------
# Hierarchical programs (board + rack tiers)
# ---------------------------------------------------------------------------

def hierarchical_program(topo: Topology, dist_weight=None, prune: bool = False,
                         live_distances=None,
                         intra_weight=None) -> RouteProgram:
    """Compile a two-tier circuit schedule for a board + rack fabric.

    Per slot (global ring offset d), the fabric realizes two kinds of
    circuits (the :mod:`repro.core.topology` contract):

    * its **intra-board** pairs travel each board's local ring concurrently
      — these are scheduled like a bidirectional flat program, one circuit
      per direction per epoch, ordered by local hop count;
    * its **inter-board** pairs funnel through the gateways — each such
      slot gets an exclusive epoch after the intra phase (a gateway hosts
      one circuit at a time), ordered by rack hop count.

    The split is the program's **group mask**: ``rank_epoch[k, r]`` carries
    the intra epoch for same-board requesters and the gateway epoch for
    board-crossing ones.  Directions are chosen per slot to minimize the
    total latency-weighted hop count over all pairs (board hops at
    ``board_hop_us``, rack hops at ``rack_hop_us``), so e.g. a wrap
    distance that is 3 global hops clockwise but 1 local hop
    counter-clockwise drives the short way.

    On a flat (single-board) topology this degenerates exactly to
    :func:`bidirectional_program`'s schedule.

    Args:
      dist_weight: optional measured per-distance loads ([N-1], e.g.
        ``TelemetryAggregator.distance_pages``); with ``prune=True``,
        zero-weight distances are cut.
      live_distances: explicit distance whitelist (placement
        reachability); overrides the weight-based pruning.
      intra_weight: optional measured intra-board share of ``dist_weight``
        ([N-1], e.g. ``TelemetryAggregator.distance_intra_pages``).  The
        direction vote then weighs each tier by its *measured* pages
        instead of its pair count — under intra-heavy traffic an offset's
        direction follows its loaded board-ring pairs even when most of
        its (idle) pairs cross boards.
    """
    n = topo.num_nodes
    if n < 2:
        raise ValueError("hierarchical programs need at least 2 nodes")
    s = n - 1
    live = np.ones((s,), bool)
    if live_distances is not None:
        live[:] = False
        for d in np.asarray(list(live_distances), np.int64).ravel():
            if not 0 < d < n:
                raise ValueError(f"distance {d} out of range for {n} nodes")
            live[d - 1] = True
    elif dist_weight is not None and prune:
        w = np.asarray(dist_weight, float).reshape(-1)
        if w.shape[0] != s:
            raise ValueError(f"dist_weight has {w.shape[0]} entries; a "
                             f"{n}-node ring has {s} distances")
        if (w < 0).any():
            raise ValueError("dist_weight must be non-negative")
        live = w > 0

    wi = wx = None
    if intra_weight is not None:
        wi = np.asarray(intra_weight, float).reshape(-1)
        if wi.shape[0] != s:
            raise ValueError(f"intra_weight has {wi.shape[0]} entries; a "
                             f"{n}-node ring has {s} distances")
        total = (np.asarray(dist_weight, float).reshape(-1)
                 if dist_weight is not None else wi)
        wx = np.maximum(total - wi, 0.0)

    r = np.arange(n)
    off = np.zeros((s,), np.int64)
    intra_mask = np.zeros((s, n), bool)
    local_hops = np.zeros((s,), np.int64)   # deepest intra circuit per slot
    rack_hops = np.zeros((s,), np.int64)    # deepest rack leg per slot
    for k in np.nonzero(live)[0]:
        d = k + 1
        h = (r + d) % n
        intra = topo.pair_intra(r, h)
        # Tier weights for the direction vote: measured pages when known,
        # pair counts otherwise (so the unmeasured compile's vote is the
        # plain latency-weighted hop sum over every pair).
        w_intra = float(wi[k]) if wi is not None else float(intra.sum())
        w_inter = float(wx[k]) if wx is not None else float((~intra).sum())
        costs = {}
        for sign in (1, -1):
            bh, rh = topo.pair_hops(r, h, sign)
            us = bh * topo.board_hop_us + rh * topo.rack_hop_us
            cost = 0.0
            if intra.any():
                cost += w_intra * float(us[intra].mean())
            if (~intra).any():
                cost += w_inter * float(us[~intra].mean())
            costs[sign] = cost
        if costs[1] < costs[-1]:
            sign = 1
        elif costs[-1] < costs[1]:
            sign = -1
        else:
            sign = 1 if d <= n - d else -1
        off[k] = d if sign == 1 else -(n - d)
        intra_mask[k] = intra
        bh, rh = topo.pair_hops(r, h, sign)
        local_hops[k] = bh[intra].max() if intra.any() else 0
        rack_hops[k] = rh[~intra].max() if (~intra).any() else 0

    # Intra phase: one circuit per direction per epoch, shallow rings first
    # (every board transfers concurrently — no gateway is touched).
    intra_epoch = np.full((s,), -1, np.int64)
    n_intra = 0
    for sign in (1, -1):
        idx = np.nonzero(live & intra_mask.any(1) & (np.sign(off) == sign))[0]
        order = idx[np.argsort(local_hops[idx], kind="stable")]
        intra_epoch[order] = np.arange(len(order))
        n_intra = max(n_intra, len(order))
    # Gateway phase: one board-crossing slot per epoch (gateways are
    # single-ported serdes endpoints), short rack legs first.
    inter_epoch = np.full((s,), -1, np.int64)
    idx = np.nonzero(live & (~intra_mask).any(1))[0]
    order = idx[np.argsort(rack_hops[idx], kind="stable")]
    inter_epoch[order] = n_intra + np.arange(len(order))

    rank_epoch = np.full((s, n), -1, np.int64)
    for k in np.nonzero(live)[0]:
        if intra_epoch[k] >= 0:
            rank_epoch[k, intra_mask[k]] = intra_epoch[k]
        if inter_epoch[k] >= 0:
            rank_epoch[k, ~intra_mask[k]] = inter_epoch[k]
    epoch = np.where(live & (rank_epoch >= 0).any(1),
                     np.where(rank_epoch >= 0, rank_epoch, np.iinfo(np.int64).max
                              ).min(1), -1)
    live = live & (rank_epoch >= 0).any(1)
    off = np.where(live, off, 0)
    return make_program(off, epoch, live, rank_epoch)


def masked_ranks_program(base: RouteProgram, rank_live) -> RouteProgram:
    """Group-mask a program: drop the (slot, requester) pairings where
    ``rank_live`` ([N-1, N] bool) is False.

    The datapath FREE-masks exactly the dropped pairings (their pages come
    back as zeros / their writes are dropped), mirroring how
    :func:`pruned_program` drops whole distances — this is the per-rank
    refinement a hierarchical fabric needs (e.g. cut only the
    board-crossing users of an offset).  Slots left serving nobody die
    entirely.
    """
    rank_live = np.asarray(rank_live, bool)
    # int64 up-cast: the stored rank_epoch is int32, and the int64 max
    # sentinel below would wrap to -1 in that dtype, zeroing every
    # surviving slot's base epoch (caught by bridgelint PC106).
    host = base.on_host()
    re = host.rank_epoch.astype(np.int64)
    if rank_live.shape != re.shape:
        raise ValueError(f"rank_live has shape {rank_live.shape}; program "
                         f"has {re.shape}")
    re = np.where(rank_live, re, -1)
    live = host.live & (re >= 0).any(1)
    off = np.where(live, host.offsets, 0)
    epoch = np.where(live,
                     np.where(re >= 0, re, np.iinfo(np.int64).max).min(1), -1)
    return make_program(off, epoch, live, re)


def validate_hierarchical(program: RouteProgram, topo: Topology) -> None:
    """Raise unless ``program`` is a sound schedule for ``topo``.

    Beyond :meth:`RouteProgram.validate`: in any epoch at most one slot may
    carry board-crossing traffic (no two slots target one gateway in the
    same epoch), and per direction at most one slot may carry intra-board
    traffic (circuits of one direction share each board ring's links).
    """
    program.validate()
    n = program.num_nodes
    if topo.num_nodes != n:
        raise ValueError(f"topology has {topo.num_nodes} nodes; program has "
                         f"{n}")
    re = np.asarray(program.rank_epoch)
    off = np.asarray(program.offsets)
    served = program.rank_served()
    for e in np.unique(re[served]):
        inter_at_e, intra_cw, intra_ccw = [], [], []
        for k in range(n - 1):
            ranks = np.nonzero(served[k] & (re[k] == e))[0]
            if ranks.size == 0:
                continue
            homes = (ranks + k + 1) % n
            intra = topo.pair_intra(ranks, homes)
            if (~intra).any():
                inter_at_e.append(k)
            if intra.any():
                (intra_cw if off[k] > 0 else intra_ccw).append(k)
        if len(inter_at_e) > 1:
            raise ValueError(
                f"epoch {e}: slots {inter_at_e} all cross boards — they "
                "contend for the gateways")
        for name, group in (("cw", intra_cw), ("ccw", intra_ccw)):
            if len(group) > 1:
                raise ValueError(
                    f"epoch {e}: slots {group} share the {name} board-ring "
                    "links")


def pad_requests(want: np.ndarray, rounds: int, budget: int) -> np.ndarray:
    """Pad a request list to [rounds * budget] with FREE sentinels."""
    out = np.full((rounds * budget,), FREE, dtype=np.int32)
    out[: len(want)] = want
    return out
