"""Streaming decode attention over bridge-pulled KV page rounds.

``kvbridge.decode_attention_pull`` historically pulled **every** KV page of
every sequence through the bridge, materialized the full
``[B, max_pages, T, kv, hd]`` buffers, and only then ran the per-page
partial/segment-combine chain.  The fused datapath instead consumes each
round of landed pages **inside the attention grid**: one
:func:`stream_decode_accumulate` call folds a round's ``[W, T, kv, hd]``
flits into the running flash-decode accumulators ``(m, l, acc)``, so the
peak footprint is one round of pages (cut-through: a page is consumed the
moment it lands, never stored).

The kernel is the round-streamed sibling of
:mod:`repro.kernels.paged_attention`: grid ``(B, W)``, per-sequence
``(m, l, acc)`` carried in VMEM scratch across the round's lanes, with the
lane->sequence routing (a scalar-prefetch operand, derived from the landed
logical page ids) steering which grid steps update which sequence.  Only
fully-flushed pages travel through the bridge, so a live lane contributes
all ``T`` tokens — raggedness is handled by the caller's tail partial.

Numerics: float32 online softmax, identical update algebra to the unfused
``_page_partial`` + LSE-combine chain but applied in landing order, so
outputs agree to float tolerance (the pulled pages and telemetry stay
bit-exact — only the accumulation order differs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bridge_gather import out_like
from repro.kernels.pallas_compat import resolve_interpret

NEG_INF = -1e30


def _stream_kernel(seq_ref, live_ref, q_ref, k_ref, v_ref,
                   m_in_ref, l_in_ref, o_in_ref,
                   m_out_ref, l_out_ref, o_out_ref,
                   m_sc, l_sc, acc_sc, *, lanes: int, num_heads: int,
                   kv_heads: int):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _load():
        m_sc[...] = m_in_ref[...]
        l_sc[...] = l_in_ref[...]
        acc_sc[...] = o_in_ref[...]

    @pl.when((seq_ref[i] == b) & (live_ref[i] > 0))
    def _update():
        g = num_heads // kv_heads
        hd = q_ref.shape[-1]
        q = q_ref[...].astype(jnp.float32)               # [H, hd]
        k = k_ref[...].astype(jnp.float32)               # [T * kv, hd]
        v = v_ref[...].astype(jnp.float32)
        # One MXU pass scores every head against every (token, kv head)
        # row; row r of the page holds kv head r % kv, so each query head
        # keeps the columns of its own group and masks the rest out (their
        # probabilities are exactly 0 and add nothing to p @ v).
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (hd ** -0.5)                              # [H, T * kv]
        own = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
               == jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) % kv_heads)
        s = jnp.where(own, s, NEG_INF)
        m_prev = m_sc[...]                                # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(own, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * alpha + pv
        m_sc[...] = m_new

    @pl.when(i == lanes - 1)
    def _store():
        m_out_ref[...] = m_sc[...]
        l_out_ref[...] = l_sc[...]
        o_out_ref[...] = acc_sc[...]


def stream_decode_accumulate(q: jax.Array, k_pages: jax.Array,
                             v_pages: jax.Array, seq_ids: jax.Array,
                             live: jax.Array, m: jax.Array, l: jax.Array,
                             o: jax.Array, *, interpret=None):
    """Fold one landed page round into the flash-decode accumulators.

    q: [B, H, hd] decode queries; k_pages/v_pages: [W, T, kv, hd] this
    round's landed flits; seq_ids: i32[W] owning sequence per lane;
    live: bool/i32[W] lane carries a real page; m, l: f32[B, H];
    o: f32[B, H, hd] running (max, denom, weighted-sum) state.
    Returns the updated ``(m, l, o)``.

    Every block spans the full trailing dims of its operand, the tiling
    Mosaic accepts at any head count: a page is viewed as its
    ``[T * kv, hd]`` rows and the per-head statistics as ``[H, 1]`` columns.
    """
    b, h, hd = q.shape
    w, t, kv, _ = k_pages.shape
    if w == 0:
        return m, l, o
    kernel = functools.partial(_stream_kernel, lanes=w, num_heads=h,
                               kv_heads=kv)

    def per_seq(bi, i, sq, lv):
        return (bi, 0, 0)

    def per_lane(bi, i, sq, lv):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, w),
        in_specs=[
            pl.BlockSpec((None, h, hd), per_seq),
            pl.BlockSpec((None, t * kv, hd), per_lane),
            pl.BlockSpec((None, t * kv, hd), per_lane),
            pl.BlockSpec((None, h, 1), per_seq),
            pl.BlockSpec((None, h, 1), per_seq),
            pl.BlockSpec((None, h, hd), per_seq),
        ],
        out_specs=[
            pl.BlockSpec((None, h, 1), per_seq),
            pl.BlockSpec((None, h, 1), per_seq),
            pl.BlockSpec((None, h, hd), per_seq),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),
        ],
    )
    m2, l2, o2 = pl.pallas_call(
        kernel, grid_spec=grid_spec, name="bridge_stream_attention",
        out_shape=[
            out_like((b, h, 1), jnp.float32, q, k_pages, m),
            out_like((b, h, 1), jnp.float32, q, k_pages, m),
            out_like((b, h, hd), jnp.float32, q, k_pages, m),
        ],
        interpret=resolve_interpret(interpret),
    )(seq_ids.astype(jnp.int32), live.astype(jnp.int32), q,
      k_pages.reshape(w, t * kv, hd), v_pages.reshape(w, t * kv, hd),
      m.astype(jnp.float32)[..., None], l.astype(jnp.float32)[..., None],
      o.astype(jnp.float32))
    return m2[..., 0], l2[..., 0], o2
