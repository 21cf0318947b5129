"""The bridge kernels compile for a TPU v5e (nothing runs).

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached: these tests hand it the shapes of the main path
and assert that Mosaic accepts each Pallas kernel (``tpu_custom_call`` in
the compiled program).  Off-TPU the kernel wrappers run lax stand-ins, so
without these tests nothing would check that the kernels themselves
compile.  The topology is described inside a fixture, never at import.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import bridge, steering
from repro.core.memport import MemPortTable
from repro.kernels import bridge_gather as bg
from repro.kernels import pallas_compat
from repro.kernels.bridge_attention import stream_decode_accumulate
from repro.launch.mesh import make_mesh

# Page shapes of the main path: granite-3-8b's KV page (16 tokens x 8 kv
# heads x 128, e = 16384 in bf16), the bridge benchmark's 4 KiB and
# 256 KiB float32 pages, h2o-danube's head_dim 120 and a flat page whose
# element count 128 does not divide.
PAGES = {
    "granite_kv": ((16, 8, 128), jnp.bfloat16),
    "flat_4KiB": ((1024,), jnp.float32),
    "flat_256KiB": ((65536,), jnp.float32),
    "danube_kv_hd120": ((16, 8, 120), jnp.bfloat16),
    "flat_1000_bf16": ((1000,), jnp.bfloat16),
}
ROWS, LANES, SLOTS = 64, 8, 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases(page, dtype):
    """(kernel wrapper with interpret=False, operand shapes)."""
    i32 = jnp.int32
    return {
        "gather_pages": (
            lambda p, r: bg.gather_pages(p, r, interpret=False),
            [((ROWS,) + page, dtype), ((4, LANES), i32)]),
        "pull_commit": (
            lambda p, pay, c, lp: bg.pull_commit(p, pay, c, lp,
                                                 interpret=False),
            [((ROWS,) + page, dtype), ((SLOTS, LANES) + page, dtype),
             ((LANES,), i32), ((LANES,), i32)]),
        "push_commit": (
            lambda p, s, ld, la: bg.push_commit(p, s, ld, la, channels=2,
                                                cb=LANES // 2,
                                                interpret=False),
            [((ROWS + 1,) + page, dtype), ((SLOTS + 1, LANES), i32),
             ((LANES,) + page, dtype), ((SLOTS, LANES) + page, dtype)]),
        "scatter_pages": (
            lambda p, s, d: bg.scatter_pages(p, s, d, interpret=False),
            [((ROWS,) + page, dtype), ((LANES,), i32),
             ((LANES,) + page, dtype)]),
    }


@pytest.mark.parametrize("page_name", sorted(PAGES))
@pytest.mark.parametrize("kernel", ["gather_pages", "pull_commit",
                                    "push_commit", "scatter_pages"])
def test_bridge_kernel_compiles_for_v5e(one_chip, kernel, page_name):
    page, dtype = PAGES[page_name]
    fn, shapes = _kernel_cases(page, dtype)[kernel]
    txt = _compiled_text(fn, *(_spec(one_chip, s, d) for s, d in shapes))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("head_dim", [128, 120])
def test_stream_attention_compiles_for_v5e(one_chip, head_dim):
    """granite-3-8b's decode geometry: 8 slots, 32 heads, 8 kv heads."""
    b, h, kv, t, w = 8, 32, 8, 16, 8
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    shapes = [((b, h, head_dim), bf16), ((w, t, kv, head_dim), bf16),
              ((w, t, kv, head_dim), bf16), ((w,), i32), ((w,), i32),
              ((b, h), f32), ((b, h), f32), ((b, h, head_dim), f32)]
    txt = _compiled_text(
        lambda *a: stream_decode_accumulate(*a, interpret=False),
        *(_spec(one_chip, s, d) for s, d in shapes))
    assert "tpu_custom_call" in txt


def test_fused_pull_round_compiles_for_four_chips(topo, monkeypatch):
    """A fused pull round over a 4-chip ``data`` mesh: the gather and
    commit kernels sit inside the shard_map and the payloads return
    through one all-to-all."""
    monkeypatch.setattr(pallas_compat, "default_interpret", lambda: False)
    monkeypatch.setattr(bridge, "_FUSED_EXCHANGE", "a2a")
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    nodes, ppn, reqs, page = 4, 32, 24, (16, 8, 128)
    rep = NamedSharding(mesh, P())

    def replicated(tree):
        return jax.tree.map(lambda a: _spec(rep, a.shape, a.dtype), tree)

    pool = _spec(NamedSharding(mesh, P("data")), (nodes * ppn,) + page,
                 jnp.bfloat16)
    want = _spec(NamedSharding(mesh, P("data")), (nodes, reqs), jnp.int32)
    table = replicated(MemPortTable.striped(nodes * ppn, nodes, ppn))
    program = replicated(steering.bidirectional_program(nodes))
    pull = jax.jit(functools.partial(bridge.pull_pages, mesh=mesh, budget=8))
    txt = pull.lower(pool, want, table, program=program).compile().as_text()
    assert "all-to-all" in txt
    assert "tpu_custom_call" in txt
