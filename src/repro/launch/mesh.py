"""Mesh builders.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — device counts are locked at first jax init, and
only launch/dryrun.py (or the real pod launcher) sets them.

Every mesh of the system is built by :func:`make_mesh`, with Auto axes.
The datapath runs partial-manual ``shard_map`` over the mem axis and leaves
every other axis (and everything outside the maps) to the GSPMD
partitioner; ``jax.make_mesh``'s default of Explicit axes would instead put
shardings into array types, which the bridge's eager entry points, its
Pallas operands and its index arithmetic do not carry.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis Auto (see the module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 4, model: int = 2):
    """Small mesh for the 8-virtual-device subprocess tests."""
    return make_mesh((data, model), ("data", "model"))


def make_board_mesh(num_boards: int = 2, board_size: int = 4, **topo_hw):
    """1-D mem-axis mesh over a board + rack fabric.

    Returns ``(mesh, topology)``: the mesh's ``data`` axis enumerates the
    fabric's endpoints board-major (rank = board * board_size + local
    rank), and the :class:`~repro.core.topology.Topology` describes the
    two tiers for the bridge's steering / telemetry / perfmodel.
    ``topo_hw`` forwards per-tier wire constants (``rack_link_gbps`` etc.).
    """
    from repro.core.topology import Topology
    mesh = make_mesh((num_boards * board_size,), ("data",))
    return mesh, Topology.boards(num_boards, board_size, **topo_hw)


def make_production_board_mesh(*, num_boards: int = 16,
                               board_size: int = 16, **topo_hw):
    """Rack-scale fabric: 16 boards x 16 endpoints (256 chips) by default."""
    return make_board_mesh(num_boards, board_size, **topo_hw)
