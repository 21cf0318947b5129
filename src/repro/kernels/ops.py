"""Jitted public wrappers for the Pallas kernels.

``interpret=None`` resolves through the shared policy in
:mod:`repro.kernels.pallas_compat`: native compilation on a TPU backend,
the Pallas interpreter on every other backend (the kernel bodies then
execute as traced jax ops, which is how the CPU tests check them).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _flash
from repro.kernels import paged_attention as _paged
from repro.kernels import stream as _stream
from repro.kernels.pallas_compat import default_interpret as _default_interpret


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stream_copy(c, *, block_rows=_stream.DEFAULT_BLOCK_ROWS, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _stream.stream_copy(c, block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("q", "block_rows", "interpret"))
def stream_scale(c, q=3.0, *, block_rows=_stream.DEFAULT_BLOCK_ROWS,
                 interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _stream.stream_scale(c, q, block_rows=block_rows,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stream_add(a, b, *, block_rows=_stream.DEFAULT_BLOCK_ROWS,
               interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _stream.stream_add(a, b, block_rows=block_rows,
                              interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("q", "block_rows", "interpret"))
def stream_triad(b, c, q=3.0, *, block_rows=_stream.DEFAULT_BLOCK_ROWS,
                 interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _stream.stream_triad(b, c, q, block_rows=block_rows,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_offset", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    bq=128, bk=512, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _flash.flash_attention(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, bq=bq, bk=bk,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("max_pages", "interpret"))
def paged_attention(q, k_pool, v_pool, page_table, lengths, *,
                    max_pages, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _paged.paged_attention(q, k_pool, v_pool, page_table, lengths,
                                  max_pages=max_pages, interpret=interpret)
