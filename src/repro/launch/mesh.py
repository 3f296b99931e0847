"""Production meshes (DESIGN §6).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run sets XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.distributed.context import DistContext


def _mk(shape, axes):
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_dist(mesh) -> DistContext:
    axes = mesh.axis_names
    return DistContext(mesh=mesh,
                       data_axes=("data",) if "data" in axes else (),
                       model_axis="model",
                       pod_axis="pod" if "pod" in axes else None)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU tests (requires host-platform device count)."""
    return _mk((n_data, n_model), ("data", "model"))


def make_session_mesh(n_shards: Optional[int] = None):
    """1-D mesh over the SESSION axis for the sharded serve engine: each
    device owns one arena shard (a contiguous block of session rows —
    see `serve.arena`).  Per-session CCM state is tiny and independent,
    so the session axis is the embarrassingly-parallel one; model
    parallelism composes separately (ROADMAP).  Defaults to every alive
    device."""
    n = n_shards if n_shards is not None else jax.device_count()
    if n < 1:
        raise ValueError("session mesh needs at least one device")
    return _mk((n,), ("shards",))


def available_mesh(model_parallel: int = 1):
    """Elastic: build the best mesh from whatever devices are alive."""
    n = jax.device_count()
    nm = model_parallel
    while n % nm:
        nm -= 1
    return _mk((n // nm, nm), ("data", "model"))
