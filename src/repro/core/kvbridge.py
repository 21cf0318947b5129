"""Disaggregated KV cache through the bridge — the paper's case study, scaled.

The paper demonstrates its bridge by disaggregating *main memory* and letting
unmodified CPU masters run STREAM against it.  The pod-scale analogue of
"main memory" for LM serving is the **KV cache**: at 500 k context it dwarfs
every other tensor and pins the compute:memory ratio the paper wants to break.

Layout.  KV lives in page pools sharded over the *mem* axis (``data``):

    k_pool, v_pool : [num_slots, page_tokens, kv_heads, head_dim]

addressed through one :class:`~repro.core.memport.MemPortTable` shared by all
layers (placement is per (sequence, page); layers stack the pools).  The tail
(partially-filled) page of each sequence stays in a **local write buffer** —
the paper's edge-buffering applied to the write path — and is flushed through
the bridge exactly once when it fills (write-combining; 1/page_tokens of the
naive write-allocate traffic).

Three decode-attention placements:

* ``local``        — dense per-node cache, no bridge (baseline ceiling);
* ``bridge_pull``  — paper-faithful: the master *pulls* KV pages through the
  memport + ring-circuit datapath and computes attention locally, streaming
  page rounds through an online-softmax accumulator (cut-through: a page is
  consumed the moment it lands, never stored — literal under ``fused=True``,
  where each round folds into the flash-decode accumulators *inside* the
  attention grid, :mod:`repro.kernels.bridge_attention`, and the full
  ``[B, max_pages]`` pull buffer never materializes);
* ``bridge_push``  — beyond-paper: the *query* is broadcast to the memory
  nodes, each computes partial flash-decode attention over its resident
  pages, and partials merge with a log-sum-exp reduction.  Collective bytes
  drop from O(seq · kv_heads · head_dim) to O(heads · head_dim) per token.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import bridge
from repro.core.memport import FREE, MemPortTable
from repro.core.steering import RouteProgram
from repro.kernels.bridge_attention import stream_decode_accumulate
from repro.kernels.pallas_compat import resolve_interpret
from repro.telemetry import counters as telemetry_counters

NEG_INF = -1e30


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class PagedKVLayer:
    """Per-layer paged KV state (leading dims may be stacked over layers)."""

    k_pool: jax.Array        # [slots, T, kv, hd]  sharded (mem, None, None, None)
    v_pool: jax.Array        # [slots, T, kv, hd]
    tail_k: jax.Array        # [B, T, kv, hd]      batch-sharded write buffer
    tail_v: jax.Array        # [B, T, kv, hd]


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class PagedKVCache:
    """Whole-model paged cache: layers stacked on the leading axis."""

    layers: PagedKVLayer     # leaves: [L, ...]
    table: MemPortTable      # shared logical (b, page) -> (home, slot)
    lengths: jax.Array       # i32[B] tokens already cached
    page_tokens: int
    max_pages: int

    @property
    def batch(self) -> int:
        return self.lengths.shape[0]


def logical_page_ids(batch: int, max_pages: int) -> jnp.ndarray:
    """Logical id of page p of sequence b is b * max_pages + p."""
    return (jnp.arange(batch)[:, None] * max_pages
            + jnp.arange(max_pages)[None, :])


def init_cache(num_layers: int, batch: int, max_len: int, page_tokens: int,
               kv_heads: int, head_dim: int, *, mesh: Optional[Mesh],
               mem_axis: str = "data", dtype=jnp.bfloat16,
               table: Optional[MemPortTable] = None,
               lengths: Optional[jax.Array] = None) -> PagedKVCache:
    max_pages = -(-max_len // page_tokens)
    n = bridge._mem_axis_size(mesh, mem_axis)
    slots_per_node = -(-batch * max_pages // n)
    num_slots = n * slots_per_node
    if table is None:
        table = MemPortTable.striped(batch * max_pages, n, slots_per_node)

    # Sharding (pools over the mem axis) is applied by the caller: serve_step
    # places these with in_shardings / with_sharding_constraint.
    pools = jnp.zeros((num_layers, num_slots, page_tokens, kv_heads, head_dim),
                      dtype)
    tails = jnp.zeros((num_layers, batch, page_tokens, kv_heads, head_dim), dtype)
    layers = PagedKVLayer(k_pool=pools, v_pool=pools, tail_k=tails, tail_v=tails)
    if lengths is None:
        lengths = jnp.zeros((batch,), jnp.int32)
    return PagedKVCache(layers=layers, table=table, lengths=lengths,
                        page_tokens=page_tokens, max_pages=max_pages)


# ---------------------------------------------------------------------------
# Online-softmax helpers (flash-decode accumulators)
# ---------------------------------------------------------------------------

def _merge(m1, l1, o1, m2, l2, o2):
    """Merge two partial-softmax states (m: max, l: denom, o: weighted sum)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return m, l1 * a1 + l2 * a2, o1 * a1[..., None] + o2 * a2[..., None]


def _page_partial(q, k, v, valid):
    """Partial attention of q [B,H,hd] against one page set.

    k, v: [R, T, kv, hd]; valid: [R, T] bool; pages belong to sequences via
    ``seq_of_page`` handled by the caller (q already gathered per page).
    Returns per-page partials (m [R,H], l [R,H], o [R,H,hd]).
    """
    r, t, kv, hd = k.shape
    h = q.shape[-2]
    g = h // kv
    qf = q.reshape(r, kv, g, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scale = hd ** -0.5
    s = jnp.einsum("rkgd,rtkd->rkgt", qf, kf) * scale        # [R,kv,G,T]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [R,kv,G]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)                                   # [R,kv,G]
    o = jnp.einsum("rkgt,rtkd->rkgd", p, v.astype(jnp.float32))
    return (m.reshape(r, h), l.reshape(r, h), o.reshape(r, h, hd))


def _segment_combine(m, l, o, seg, num_segments):
    """LSE-combine per-page partials into per-sequence accumulators."""
    seg = jnp.where(seg >= 0, seg, num_segments)
    m_seq = jax.ops.segment_max(m, seg, num_segments=num_segments + 1)[:num_segments]
    m_seq = jnp.maximum(m_seq, NEG_INF)
    a = jnp.exp(m - m_seq[seg.clip(0, num_segments - 1)])
    a = jnp.where((seg < num_segments)[:, None], a, 0.0)
    l_seq = jax.ops.segment_sum(l * a, seg, num_segments=num_segments + 1)[:num_segments]
    o_seq = jax.ops.segment_sum(o * a[..., None], seg,
                                num_segments=num_segments + 1)[:num_segments]
    return m_seq, l_seq, o_seq


def _tail_partial(q, tail_k, tail_v, lengths, page_tokens):
    """Partial attention over the local write buffer (tail page)."""
    b, h, hd = q.shape
    kv = tail_k.shape[-2]
    g = h // kv
    start = (lengths // page_tokens) * page_tokens
    pos = start[:, None] + jnp.arange(page_tokens)[None, :]
    valid = pos < lengths[:, None]                            # [B, T]
    qf = q.reshape(b, kv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qf, tail_k.astype(jnp.float32))
    s = s * (hd ** -0.5)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p, tail_v.astype(jnp.float32))
    return m.reshape(b, h), l.reshape(b, h), o.reshape(b, h, hd)


# ---------------------------------------------------------------------------
# Append (write path): edge-buffered write combining
# ---------------------------------------------------------------------------

def append(layer: PagedKVLayer, table: MemPortTable, lengths: jax.Array,
           k_new: jax.Array, v_new: jax.Array, *, page_tokens: int,
           max_pages: int, mesh: Optional[Mesh], mem_axis: str = "data",
           budget: int = 8, edge_buffer: bool = True, channels: int = 1,
           program: Optional[RouteProgram] = None,
           collect_telemetry: bool = False, topology=None,
           tenant_of_seq: Optional[jax.Array] = None, max_tenants: int = 0,
           fused: bool = True):
    """Append one token's (k, v) [B, kv, hd] for one layer.

    Tokens land in the local tail buffer; when a sequence's tail page fills,
    that page is flushed through the bridge to its pooled home (one masked
    ``push_pages`` — sequences not at a boundary contribute FREE slots).
    ``edge_buffer`` / ``channels`` thread to the bridge write path
    (bufferless serialization / the pipelined multi-channel round engine);
    ``fused`` selects the fused Pallas commit datapath (the default — see
    :func:`repro.core.bridge.push_pages`).
    With ``collect_telemetry`` the write-path counters of both pushes (k and
    v pages both cross the wire) come back summed: ``(layer, telemetry)``.
    ``tenant_of_seq`` (i32[B], runtime input) attributes each sequence's
    flush traffic to its tenant in the telemetry's per-tenant bins.
    """
    b = lengths.shape[0]
    off = lengths % page_tokens
    tail_k = layer.tail_k.at[jnp.arange(b), off].set(k_new.astype(layer.tail_k.dtype))
    tail_v = layer.tail_v.at[jnp.arange(b), off].set(v_new.astype(layer.tail_v.dtype))

    page_full = (off == page_tokens - 1)
    page_idx = lengths // page_tokens
    dest = jnp.where(page_full & (page_idx < max_pages),
                     jnp.arange(b) * max_pages + page_idx, FREE)
    n = bridge._mem_axis_size(mesh, mem_axis)
    per_node = -(-b // n)
    pad = n * per_node - b

    def shape_for(x, fill=0):
        if pad:
            x = jnp.concatenate(
                [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], 0)
        return x.reshape((n, per_node) + x.shape[1:])

    # Padding rows (batch not a multiple of the mesh size) must carry FREE
    # destinations — a zero pad would be a live push of zero payloads into
    # logical page 0 (sequence 0's first KV page) every step.
    dest_n = shape_for(jnp.where(dest >= 0, dest, FREE).astype(jnp.int32),
                       fill=FREE)
    tenants_n = None
    if tenant_of_seq is not None:
        tenants_n = shape_for(tenant_of_seq.astype(jnp.int32))
    k_pool = bridge.push_pages(layer.k_pool, dest_n, shape_for(tail_k),
                               table, mesh=mesh, mem_axis=mem_axis,
                               budget=budget, edge_buffer=edge_buffer,
                               channels=channels, program=program,
                               collect_telemetry=collect_telemetry,
                               topology=topology, tenant_ids=tenants_n,
                               max_tenants=max_tenants, fused=fused)
    v_pool = bridge.push_pages(layer.v_pool, dest_n, shape_for(tail_v),
                               table, mesh=mesh, mem_axis=mem_axis,
                               budget=budget, edge_buffer=edge_buffer,
                               channels=channels, program=program,
                               collect_telemetry=collect_telemetry,
                               topology=topology, tenant_ids=tenants_n,
                               max_tenants=max_tenants, fused=fused)
    telem = None
    if collect_telemetry:
        k_pool, telem_k = k_pool
        v_pool, telem_v = v_pool
        telem = telemetry_counters.add(telem_k, telem_v)
    # A flushed tail restarts empty (zeros are fine: positions are masked).
    keep = ~page_full
    keep_m = keep[:, None, None, None]
    tail_k = jnp.where(keep_m, tail_k, jnp.zeros_like(tail_k))
    tail_v = jnp.where(keep_m, tail_v, jnp.zeros_like(tail_v))
    out = replace(layer, k_pool=k_pool, v_pool=v_pool,
                  tail_k=tail_k, tail_v=tail_v)
    if collect_telemetry:
        return out, telem
    return out


# ---------------------------------------------------------------------------
# Decode attention — three placements
# ---------------------------------------------------------------------------

def _finalize(m, l, o):
    l = jnp.maximum(l, 1e-30)
    return (o / l[..., None])


def _node_fold(mesh: Optional[Mesh], mem_axis: str, n: int):
    """Per-node :func:`stream_decode_accumulate` over node-major operands.

    Every argument has a leading ``n`` dim (sharded over the mem axis);
    ``seq`` holds node-local sequence indices.  A compiled Pallas kernel
    cannot be partitioned by the compiler, so on a TPU mesh it runs inside
    ``shard_map`` and each node folds the pages it pulled into the
    accumulators of its own sequences.  The Pallas interpreter (off-TPU)
    cannot run under ``shard_map``'s varying-axis checks; there one call
    folds every node's lanes, in the same per-sequence order.
    """
    def fold_all(q, k, v, seq, live, m, l, o):
        nodes, per = q.shape[:2]
        seq = jnp.where(seq >= 0, seq + (jnp.arange(nodes) * per)[:, None],
                        -1)
        flat = [x.reshape((-1,) + x.shape[2:]) for x in (q, k, v)]
        out = stream_decode_accumulate(
            *flat, seq.reshape(-1), live.reshape(-1),
            *(x.reshape((-1,) + x.shape[2:]) for x in (m, l, o)))
        return tuple(x.reshape((nodes, per) + x.shape[1:]) for x in out)

    if n == 1 or resolve_interpret(None):
        return fold_all
    spec = P(mem_axis)
    return bridge.shard_map(fold_all, mesh, in_specs=(spec,) * 8,
                            out_specs=(spec,) * 3, mem_axis=mem_axis)


def decode_attention_pull(q: jax.Array, layer: PagedKVLayer,
                          table: MemPortTable, lengths: jax.Array, *,
                          page_tokens: int, max_pages: int,
                          mesh: Optional[Mesh], mem_axis: str = "data",
                          budget: int = 8, edge_buffer: bool = True,
                          channels: int = 1,
                          program: Optional[RouteProgram] = None,
                          collect_telemetry: bool = False, topology=None,
                          tenant_of_seq: Optional[jax.Array] = None,
                          max_tenants: int = 0, fused: bool = True):
    """Paper-faithful: pull pages through the bridge, attend locally.

    q: [B, H, hd] -> out [B, H, hd].  Pages stream through an online-softmax
    accumulator in rounds of ``budget`` pages (cut-through consumption).
    ``program`` is the runtime circuit schedule threaded down to
    :func:`repro.core.bridge.pull_pages`; ``channels`` its pipelined
    multi-channel round overlap.  With ``collect_telemetry`` the summed
    counters of the k and v pulls come back too: ``(out, telemetry)``.
    ``tenant_of_seq`` (i32[B], runtime input) attributes each sequence's
    page pulls to its tenant in the telemetry's per-tenant bins.

    ``fused`` (default ON) makes the cut-through literal: each round of
    landed pages is consumed **inside the attention grid**
    (:func:`repro.kernels.bridge_attention.stream_decode_accumulate` folds
    the round straight into the flash-decode ``(m, l, acc)`` accumulators),
    so the peak pull footprint is one round of pages instead of the full
    ``[B, max_pages]`` buffer pair.  The pulled pages and the telemetry are
    bit-exact vs ``fused=False``; the attention output matches at float
    tolerance (the online accumulation visits pages in landing order).
    """
    b, h, hd = q.shape
    kv = layer.k_pool.shape[-2]
    n = bridge._mem_axis_size(mesh, mem_axis)
    per_node = -(-b // n)
    want_b = logical_page_ids(b, max_pages)                  # [B, P]
    # Only fully-flushed pages live in the pool.
    flushed = lengths // page_tokens                          # [B]
    want_b = jnp.where(jnp.arange(max_pages)[None, :] < flushed[:, None],
                       want_b, FREE).astype(jnp.int32)
    pad = n * per_node - b
    if pad:
        want_b = jnp.concatenate(
            [want_b, jnp.full((pad, max_pages), FREE, jnp.int32)], 0)
    want = want_b.reshape(n, per_node * max_pages)
    tenants = None
    if tenant_of_seq is not None:
        ten_b = jnp.broadcast_to(tenant_of_seq.astype(jnp.int32)[:, None],
                                 (b, max_pages))
        if pad:
            ten_b = jnp.concatenate(
                [ten_b, jnp.zeros((pad, max_pages), jnp.int32)], 0)
        tenants = ten_b.reshape(n, per_node * max_pages)

    pull_kw = dict(mesh=mesh, mem_axis=mem_axis, budget=budget,
                   edge_buffer=edge_buffer, channels=channels,
                   program=program, collect_telemetry=collect_telemetry,
                   topology=topology, max_tenants=max_tenants, fused=fused)
    telem = None
    if fused:
        # Streamed rounds: pull one bridge round of pages at a time and fold
        # it straight into the flash-decode accumulators — the materialized
        # state is (m, l, acc) + one round of pages, never the full pull
        # buffer.  Splitting one R-request transfer into R/budget 1-round
        # transfers moves the same flits through the same per-round
        # collectives (and, with no throttled active_budget, sums to
        # bit-exact telemetry: every round's spill count is zero either
        # way).
        #
        # Node i pulls the pages of its own sequences (request row i holds
        # sequences [i * per_node, (i + 1) * per_node)), so it also folds
        # them: queries and accumulators are split by node like the
        # requests, and each node's kernel sees only its landed lanes.
        rtot = want.shape[-1]
        rounds = -(-rtot // budget)
        q_n = q
        if pad:
            q_n = jnp.concatenate([q, jnp.zeros((pad, h, hd), q.dtype)], 0)
        q_n = q_n.reshape(n, per_node, h, hd)
        m_s = jnp.full((n, per_node, h), NEG_INF, jnp.float32)
        l_s = jnp.zeros((n, per_node, h), jnp.float32)
        o_s = jnp.zeros((n, per_node, h, hd), jnp.float32)
        first_seq = (jnp.arange(n) * per_node)[:, None]
        fold = _node_fold(mesh, mem_axis, n)
        for rnd in range(rounds):
            sl = slice(rnd * budget, min((rnd + 1) * budget, rtot))
            want_r = want[:, sl]
            ten_r = tenants[:, sl] if tenants is not None else None
            k_r = bridge.pull_pages(layer.k_pool, want_r, table,
                                    tenant_ids=ten_r, **pull_kw)
            v_r = bridge.pull_pages(layer.v_pool, want_r, table,
                                    tenant_ids=ten_r, **pull_kw)
            if collect_telemetry:
                k_r, telem_k = k_r
                v_r, telem_v = v_r
                round_t = telemetry_counters.add(telem_k, telem_v)
                telem = (round_t if telem is None
                         else telemetry_counters.add(telem, round_t))
            live = want_r >= 0
            # Logical page ids encode their sequence: id // max_pages.
            seq = jnp.where(live, want_r // max_pages - first_seq, -1)
            m_s, l_s, o_s = fold(q_n, k_r, v_r, seq, live, m_s, l_s, o_s)
        m_s = m_s.reshape(n * per_node, h)[:b]
        l_s = l_s.reshape(n * per_node, h)[:b]
        o_s = o_s.reshape(n * per_node, h, hd)[:b]
    else:
        k_pages = bridge.pull_pages(layer.k_pool, want, table,
                                    tenant_ids=tenants, **pull_kw)
        v_pages = bridge.pull_pages(layer.v_pool, want, table,
                                    tenant_ids=tenants, **pull_kw)
        if collect_telemetry:
            k_pages, telem_k = k_pages
            v_pages, telem_v = v_pages
            telem = telemetry_counters.add(telem_k, telem_v)
        # [n, per_node*max_pages, T, kv, hd] -> [B(+pad), P, T, kv, hd]
        k_pages = k_pages.reshape(n * per_node, max_pages, page_tokens,
                                  kv, hd)[:b]
        v_pages = v_pages.reshape(n * per_node, max_pages, page_tokens,
                                  kv, hd)[:b]

        flat_k = k_pages.reshape(b * max_pages, page_tokens, kv, hd)
        flat_v = v_pages.reshape(b * max_pages, page_tokens, kv, hd)
        seq_of_page = jnp.repeat(jnp.arange(b), max_pages)
        page_of = jnp.tile(jnp.arange(max_pages), b)
        pos = page_of[:, None] * page_tokens + jnp.arange(page_tokens)[None, :]
        valid = (pos < (flushed[seq_of_page] * page_tokens)[:, None])
        q_per_page = q[seq_of_page]
        m_p, l_p, o_p = _page_partial(q_per_page, flat_k, flat_v, valid)
        live = page_of < flushed[seq_of_page]
        seg = jnp.where(live, seq_of_page, -1)
        m_s, l_s, o_s = _segment_combine(m_p, l_p, o_p, seg, b)

    m_t, l_t, o_t = _tail_partial(q, layer.tail_k, layer.tail_v,
                                  lengths, page_tokens)
    m, l, o = _merge(m_s, l_s, o_s, m_t, l_t, o_t)
    out = _finalize(m, l, o).astype(q.dtype)
    if collect_telemetry:
        return out, telem
    return out


def decode_attention_push(q: jax.Array, layer: PagedKVLayer,
                          table: MemPortTable, lengths: jax.Array, *,
                          page_tokens: int, max_pages: int,
                          mesh: Optional[Mesh],
                          mem_axis: str = "data") -> jax.Array:
    """Beyond-paper: broadcast q, compute partial attention at the memory
    nodes, LSE-combine partials (compute-at-memory / distributed flash-decode).
    """
    b, h, hd = q.shape
    kv = layer.k_pool.shape[-2]
    num_slots = layer.k_pool.shape[0]
    n = bridge._mem_axis_size(mesh, mem_axis)
    slots_per_node = num_slots // n
    flushed = lengths // page_tokens

    # Inverse memport map: slot -> logical page (computed once per step).
    logical = jnp.arange(table.num_logical)
    home, slot = table.translate(logical.astype(jnp.int32))
    flat = jnp.where(home >= 0, home * slots_per_node + slot, num_slots)
    inv = jnp.full((num_slots + 1,), FREE, jnp.int32).at[flat].set(
        logical.astype(jnp.int32))[:num_slots]

    def partial_at_node(k_local, v_local, inv_local, q_all, flushed_all,
                        lengths_all):
        # k_local: [slots_local, T, kv, hd]; q_all replicated [B, H, hd].
        sl = inv_local.shape[0]
        seq = jnp.where(inv_local >= 0, inv_local // max_pages, -1)
        pg = jnp.where(inv_local >= 0, inv_local % max_pages, 0)
        live = (seq >= 0) & (pg < flushed_all[seq.clip(0, b - 1)])
        pos = pg[:, None] * page_tokens + jnp.arange(page_tokens)[None, :]
        valid = live[:, None] & (
            pos < (flushed_all[seq.clip(0, b - 1)] * page_tokens)[:, None])
        q_sel = q_all[seq.clip(0, b - 1)]
        m_p, l_p, o_p = _page_partial(q_sel, k_local, v_local, valid)
        seg = jnp.where(live, seq, -1)
        return _segment_combine(m_p, l_p, o_p, seg, b)

    if n == 1:
        m_s, l_s, o_s = partial_at_node(layer.k_pool, layer.v_pool, inv,
                                        q, flushed, lengths)
    else:
        def mapped(k_l, v_l, inv_l, q_all, fl, ln):
            m_l, l_l, o_l = partial_at_node(k_l, v_l, inv_l, q_all, fl, ln)
            # Cross-node LSE combine: pmax for the max, psum for the rest.
            m_g = jax.lax.pmax(m_l, mem_axis)
            a = jnp.exp(jnp.maximum(m_l, NEG_INF) - m_g)
            l_g = jax.lax.psum(l_l * a, mem_axis)
            o_g = jax.lax.psum(o_l * a[..., None], mem_axis)
            return m_g, l_g, o_g

        pool_spec = P(mem_axis, *([None] * 3))
        rep = P()
        m_s, l_s, o_s = bridge.shard_map(
            mapped, mesh,
            in_specs=(pool_spec, pool_spec, P(mem_axis), rep, rep, rep),
            out_specs=(rep, rep, rep), mem_axis=mem_axis,
        )(layer.k_pool, layer.v_pool, inv, q, flushed, lengths)

    m_t, l_t, o_t = _tail_partial(q, layer.tail_k, layer.tail_v,
                                  lengths, page_tokens)
    m, l, o = _merge(m_s, l_s, o_s, m_t, l_t, o_t)
    return _finalize(m, l, o).astype(q.dtype)


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: jax.Array) -> jax.Array:
    """Oracle: dense masked GQA decode attention.

    q: [B, H, hd]; k, v: [B, S, kv, hd]; positions >= lengths masked out.
    """
    b, h, hd = q.shape
    kv = k.shape[-2]
    g = h // kv
    qf = q.reshape(b, kv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qf, k.astype(jnp.float32)) * hd ** -0.5
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(b, h, hd).astype(q.dtype)
