"""Fused bridge datapath kernels: serve/steer -> page gather -> commit.

The unfused bridge engine runs every epoch as a chain of discrete XLA ops —
per-slot serve masking, a ``dynamic-slice`` gather per circuit slot, and a
``where``-merge (pull) or scatter (push) per slot — materializing an
intermediate per step.  These Pallas kernels collapse each side of the wire
into **one** ``pallas_call`` walking the pool block-by-block, exactly the
paper's transceiver datapath where the request preparation & steering unit
programs the DMA engine and the payload moves in a single steered
transaction:

* the serve condition (RouteProgram group/FREE masking, loopback vs circuit
  steering) is evaluated into **scalar-prefetch** operands — the memport
  lookup result that steers each grid step's pool DMA, as in
  :mod:`repro.kernels.paged_attention`;
* :func:`gather_pages` serves every landed request of an epoch in one grid
  (FREE requests produce zero flits);
* :func:`pull_commit` retires an epoch on the requester side: the epoch-0
  loopback gather from the local shard and the returned circuit payloads
  commit into the output in one grid — no per-slot ``where`` chain;
* :func:`push_commit` / :func:`scatter_pages` retire the write path on the
  serving side with the pool buffer **donated** (``input_output_aliases``):
  the grid scatters payloads in the serial engine's commit order (sequential
  grid => later writes win, matching the oracle), and FREE lanes are steered
  into a sacrificial pad row — the kernel equivalent of the unfused path's
  ``mode="drop"`` scatter.

Pages move as whole flits, so a kernel block is always one whole page,
viewed as a 2-D ``(sublane, lane)`` tile (:func:`_page_tiles`): a KV page
``[T, kv, hd]`` as ``[T * kv, hd]``, a flat page of ``e`` elements as
``[e // 128, 128]`` (or ``[1, e]`` when 128 does not divide ``e``).  The
block then spans the full trailing dims of its operand, which Mosaic
accepts for any page size; a ``(1, e)`` block of a ``[rows, e]`` array is
refused (its second-minor dim is neither 8-aligned nor the array's).
The wrappers follow the shared execution policy in
:mod:`repro.kernels.pallas_compat`: compiled on TPU.  Off-TPU they do NOT
run the generic Pallas interpreter: it re-materializes the full output
(and every carried buffer) once per grid step, which at 256 KiB pages
costs more than the wire traffic it steers.  Instead each wrapper
executes the identical block program as vectorized ``lax`` ops — same
steering, same masked fetches, same sequential-grid write order (scatter
shadowing is resolved explicitly, so duplicate commits stay
deterministic) — keeping tier-1 bit-faithful to the TPU kernels at
datapath speed.  ``tests/test_tpu_compile.py`` checks that the kernels
themselves compile for a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_compat import resolve_interpret


def _flatten_pages(pool: jax.Array):
    """[slots, *page_shape] -> ([slots, E], page_shape)."""
    page_shape = pool.shape[1:]
    e = int(np.prod(page_shape)) if page_shape else 1
    return pool.reshape(pool.shape[0], e), page_shape, e


def _page_tiles(page_shape) -> tuple[int, int]:
    """The ``(sublane, lane)`` view one kernel block takes of a page."""
    if len(page_shape) >= 2:
        return int(np.prod(page_shape[:-1])), int(page_shape[-1])
    e = int(np.prod(page_shape)) if page_shape else 1
    return (e // 128, 128) if e % 128 == 0 else (1, e)


def _tiled(x: jax.Array, lead: int, page_shape) -> jax.Array:
    """[*lead dims, *page_shape] -> [*lead dims, sublane, lane]."""
    return x.reshape(x.shape[:lead] + _page_tiles(page_shape))


def out_like(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A kernel output varying over every mesh axis its operands vary over
    (inside the bridge's ``shard_map``, ``check_vma`` requires it)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _page_spec(lead: int, page_shape, index_map) -> pl.BlockSpec:
    """One whole page per grid step; the ``lead`` leading dims squeezed.

    ``index_map`` returns the leading (row) indices only; the page's own
    tile index is always 0.
    """
    def full(*args):
        return tuple(index_map(*args)) + (0, 0)
    return pl.BlockSpec((None,) * lead + _page_tiles(page_shape), full)


def _obs_scope(name: str):
    """Tag a kernel entry point's ops with an ``obs:<phase>`` named scope.

    The scope lands in HLO metadata ``op_name``, so
    :func:`repro.obs.trace.phase_op_counts` attributes a compiled
    program's instructions (and their dispatch cost) to datapath phases
    even when the caller forgot its own scope.
    """
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


# ---------------------------------------------------------------------------
# Pull side
# ---------------------------------------------------------------------------

def _gather_kernel(req_ref, pool_ref, out_ref):
    valid = req_ref[pl.program_id(0)] >= 0
    out_ref[...] = jnp.where(valid, pool_ref[...], jnp.zeros_like(out_ref))


def _gather_pages_lax(pool2: jax.Array, flat: jax.Array) -> jax.Array:
    """Off-TPU gather grid: one clamped row fetch + FREE zero-mask."""
    page = pool2[jnp.maximum(flat, 0)]
    return jnp.where((flat >= 0)[:, None], page, jnp.zeros((), pool2.dtype))


@_obs_scope("obs:gather")
def gather_pages(pool: jax.Array, reqs: jax.Array, *,
                 interpret=None) -> jax.Array:
    """Serve an epoch's landed requests in one kernel.

    pool: [slots, *page_shape]; reqs: i32[...] pool rows (FREE < 0).
    Returns reqs.shape + page_shape — ``pool[req]`` per lane, zeros for FREE
    lanes.  The request ids are a scalar-prefetch operand steering each grid
    step's pool DMA (FREE lanes are clamped to row 0 for the fetch and
    zero-masked in the kernel body).
    """
    pool2, page_shape, e = _flatten_pages(pool)
    shape = reqs.shape
    flat = reqs.reshape(-1).astype(jnp.int32)
    w = flat.shape[0]
    if w == 0:
        return jnp.zeros(shape + page_shape, pool.dtype)
    if resolve_interpret(interpret):
        return _gather_pages_lax(pool2, flat).reshape(shape + page_shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w,),
        in_specs=[_page_spec(1, page_shape,
                             lambda i, rq: (jnp.maximum(rq[i], 0),))],
        out_specs=_page_spec(1, page_shape, lambda i, rq: (i,)),
    )
    out = pl.pallas_call(
        _gather_kernel, grid_spec=grid_spec, name="bridge_gather",
        out_shape=out_like((w,) + _page_tiles(page_shape), pool.dtype,
                            pool, flat),
    )(flat, _tiled(pool, 1, page_shape))
    return out.reshape(shape + page_shape)


def _pull_commit_kernel(choice_ref, loop_ref, pool_ref, pay_ref, out_ref):
    i = pl.program_id(0)
    c = choice_ref[i]
    loop_ok = loop_ref[i] >= 0
    zero = jnp.zeros_like(out_ref)
    local = jnp.where(loop_ok, pool_ref[...], zero)
    page = jnp.where(c >= 1, pay_ref[...], local)
    out_ref[...] = jnp.where(c >= 0, page, zero)


def _pull_commit_lax(pool2, pay2, choice, loop_slot) -> jax.Array:
    """Off-TPU commit grid: per-lane source select as three masked fetches."""
    s = pay2.shape[0]
    local = _gather_pages_lax(pool2, loop_slot)
    sel = jnp.clip(choice - 1, 0, s - 1)
    circ = jnp.take_along_axis(pay2, sel[None, :, None], axis=0)[0]
    page = jnp.where((choice >= 1)[:, None], circ, local)
    return jnp.where((choice >= 0)[:, None], page, jnp.zeros((), pool2.dtype))


@_obs_scope("obs:commit")
def pull_commit(pool: jax.Array, payloads: jax.Array, choice: jax.Array,
                loop_slot: jax.Array, *, interpret=None) -> jax.Array:
    """Retire a pull epoch: loopback gather + payload commit in one kernel.

    pool: [slots, *page_shape] (local shard, read-only);
    payloads: [S, L, *page_shape] returned circuit flits (slot-major);
    choice: i32[L] per-lane serving source — ``-1`` dead (zeros), ``0``
    epoch-0 loopback (gather ``pool[loop_slot]``), ``k+1`` circuit slot k;
    loop_slot: i32[L] local pool row for loopback lanes (FREE elsewhere).
    Returns [L, *page_shape].
    """
    pool2, page_shape, e = _flatten_pages(pool)
    s = payloads.shape[0]
    lanes = choice.shape[0]
    pay2 = payloads.reshape(s, lanes, e)
    if resolve_interpret(interpret):
        out = _pull_commit_lax(pool2, pay2, choice.astype(jnp.int32),
                               loop_slot.astype(jnp.int32))
        return out.reshape((lanes,) + page_shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lanes,),
        in_specs=[
            _page_spec(1, page_shape,
                       lambda i, ch, lp: (jnp.maximum(lp[i], 0),)),
            _page_spec(2, page_shape,
                       lambda i, ch, lp: (jnp.clip(ch[i] - 1, 0, s - 1), i)),
        ],
        out_specs=_page_spec(1, page_shape, lambda i, ch, lp: (i,)),
    )
    out = pl.pallas_call(
        _pull_commit_kernel, grid_spec=grid_spec, name="bridge_pull_commit",
        out_shape=out_like((lanes,) + _page_tiles(page_shape), pool.dtype,
                            pool, payloads, choice, loop_slot),
    )(choice.astype(jnp.int32), loop_slot.astype(jnp.int32),
      _tiled(pool, 1, page_shape), _tiled(payloads, 2, page_shape))
    return out.reshape((lanes,) + page_shape)


# ---------------------------------------------------------------------------
# Push side (donated pool)
# ---------------------------------------------------------------------------

def pad_pool(pool: jax.Array) -> jax.Array:
    """Append the sacrificial drop row FREE pushes are steered into."""
    return jnp.concatenate([pool, jnp.zeros((1,) + pool.shape[1:], pool.dtype)])


def _push_commit_kernel(rows_ref, pool_ref, loop_ref, landed_ref, out_ref):
    del rows_ref, pool_ref          # steering only / aliased output init
    k = pl.program_id(1)
    out_ref[...] = jnp.where(k == 0, loop_ref[...],
                             landed_ref[...]).astype(out_ref.dtype)


def _shadow_to(rows: jax.Array, drop_row: int) -> jax.Array:
    """Steer writes shadowed by a later grid step into the drop row.

    The sequential grid's last-write-wins contract made explicit, so the
    off-TPU scatter never leans on XLA's duplicate-index update order
    (officially unspecified).  Quadratic in the round's write count — a few
    dozen lanes — never in page bytes.
    """
    t = jnp.arange(rows.shape[0])
    shadowed = ((rows[None, :] == rows[:, None])
                & (t[None, :] > t[:, None])).any(1)
    return jnp.where(shadowed, drop_row, rows)


def _push_commit_lax(pool_pad: jax.Array, rows: jax.Array,
                     loop_data: jax.Array, landed_data: jax.Array,
                     channels: int, cb: int) -> jax.Array:
    """Off-TPU push grid: shadow-resolve in (c, k, b) grid order, then
    retire every commit row with one in-place scatter per source buffer —
    the landed flits scatter straight from where they arrived, no
    flattened grid-order staging of the payload bytes."""
    s1, lanes = rows.shape
    drop = pool_pad.shape[0] - 1
    # grid step t = (c*s1 + k)*cb + b  ->  slot k, lane = c*cb + b
    t = jnp.arange(channels * s1 * cb)
    k_t = (t // cb) % s1
    lane_t = (t // (s1 * cb)) * cb + t % cb
    flat = _shadow_to(rows[k_t, lane_t], drop)
    # back to [s1, lanes]: with shadowed writes steered to the drop row,
    # every surviving write is the grid's final value, so the per-slot
    # scatters below can run in any order.
    kk, ll = jnp.meshgrid(jnp.arange(s1), jnp.arange(lanes), indexing="ij")
    res = flat[((ll // cb) * s1 + kk) * cb + ll % cb]
    out = pool_pad.at[res[0]].set(loop_data.astype(pool_pad.dtype))
    for k in range(1, s1):
        out = out.at[res[k]].set(landed_data[k - 1].astype(pool_pad.dtype))
    return out


@_obs_scope("obs:commit")
def push_commit(pool_pad: jax.Array, slots_all: jax.Array,
                loop_data: jax.Array, landed_data: jax.Array, *,
                channels: int, cb: int, interpret=None) -> jax.Array:
    """Retire one push round into the (donated) padded pool.

    pool_pad: [slots + 1, *page_shape] local shard with the sacrificial
    drop row appended (:func:`pad_pool`); returned updated, buffer aliased
    (it stays in HBM: the kernel only writes the rows it commits).
    slots_all: i32[S + 1, L] commit rows — row 0 the epoch-0 loopback slots,
    row k+1 circuit slot k's landed slots (FREE < 0 drops).
    loop_data: [L, *page_shape] local payloads; landed_data:
    [S, L, *page_shape] landed flits.
    L = channels * cb; the grid runs chunk-major, loopback first within each
    chunk — the serial engine's commit order, so duplicate rows resolve
    identically (sequential grid, later write wins).
    """
    slots = pool_pad.shape[0] - 1
    page_shape = pool_pad.shape[1:]
    s1 = slots_all.shape[0]
    rows = jnp.where(slots_all >= 0, slots_all, slots).astype(jnp.int32)
    if resolve_interpret(interpret):
        return _push_commit_lax(pool_pad, rows, loop_data, landed_data,
                                channels, cb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(channels, s1, cb),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            _page_spec(1, page_shape, lambda c, k, b, rw: (c * cb + b,)),
            _page_spec(2, page_shape,
                       lambda c, k, b, rw: (jnp.maximum(k - 1, 0),
                                            c * cb + b)),
        ],
        out_specs=_page_spec(1, page_shape,
                             lambda c, k, b, rw: (rw[k, c * cb + b],)),
    )
    out = pl.pallas_call(
        _push_commit_kernel, grid_spec=grid_spec, name="bridge_push_commit",
        out_shape=out_like((slots + 1,) + _page_tiles(page_shape),
                            pool_pad.dtype, pool_pad, rows, loop_data,
                            landed_data),
        input_output_aliases={1: 0},
    )(rows, _tiled(pool_pad, 1, page_shape),
      _tiled(loop_data, 1, page_shape), _tiled(landed_data, 2, page_shape))
    return out.reshape(pool_pad.shape)


def _scatter_kernel(rows_ref, pool_ref, data_ref, out_ref):
    del rows_ref, pool_ref
    out_ref[...] = data_ref[...].astype(out_ref.dtype)


@_obs_scope("obs:commit")
def scatter_pages(pool: jax.Array, slots: jax.Array, data: jax.Array, *,
                  interpret=None) -> jax.Array:
    """One-kernel masked scatter: ``pool.at[slots].set(data, mode="drop")``.

    pool: [slots, *page_shape]; slots: i32[W] (FREE < 0 drops);
    data: [W, *page_shape].  The loopback (1-node) commit path: FREE lanes
    steer into the sacrificial pad row and are trimmed, live duplicates
    resolve last-write-wins (sequential grid).  The padded pool buffer is
    donated to the kernel.
    """
    pool2, page_shape, e = _flatten_pages(pool)
    w = slots.shape[0]
    if w == 0:
        return pool
    nrows = pool2.shape[0]
    rows = jnp.where(slots >= 0, slots, nrows).astype(jnp.int32)
    data2 = data.reshape(w, e)
    if resolve_interpret(interpret):
        out = pad_pool(pool2).at[_shadow_to(rows, nrows)].set(
            data2.astype(pool2.dtype))
        return out[:nrows].reshape(pool.shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            _page_spec(1, page_shape, lambda i, rw: (i,)),
        ],
        out_specs=_page_spec(1, page_shape, lambda i, rw: (rw[i],)),
    )
    out = pl.pallas_call(
        _scatter_kernel, grid_spec=grid_spec, name="bridge_scatter",
        out_shape=out_like((nrows + 1,) + _page_tiles(page_shape),
                            pool.dtype, pool, rows, data),
        input_output_aliases={1: 0},
    )(rows, _tiled(pad_pool(pool), 1, page_shape),
      _tiled(data, 1, page_shape))
    return out[:nrows].reshape(pool.shape)
