"""Shared Pallas execution-mode policy for every kernel in the repo.

Kernels compile natively on a TPU backend and nowhere else: on any other
backend they run in interpret mode (or, for the bridge datapath kernels,
as their lax stand-ins — see :mod:`repro.kernels.bridge_gather`), which
is how tier-1 checks them on CPU.  There is no override: on a TPU every
kernel is the compiled kernel.  :func:`default_interpret` is the single
source of truth for that choice.
"""
from __future__ import annotations

from typing import Optional

import jax


def default_interpret() -> bool:
    """Interpret mode exactly when the default backend is not a TPU."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve an ``interpret=None`` kernel argument to the shared default."""
    return default_interpret() if interpret is None else bool(interpret)
