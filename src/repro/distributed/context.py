"""Distribution context threaded through model apply functions.

Model code is mesh-agnostic; when a ``DistContext`` is provided, modules that
need explicit SPMD control (MoE dispatch, sequence-parallel attention) use
``shard_map`` over the named axes. When ``None`` (unit tests, single device),
pure local computation is used.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class DistContext:
    mesh: object                     # jax.sharding.Mesh (or AbstractMesh)
    data_axes: Tuple[str, ...] = ("data",)   # batch/token sharding axes
    model_axis: str = "model"                # TP axis
    pod_axis: Optional[str] = None           # cross-pod axis (composes w/ data)

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        return ((self.pod_axis,) if self.pod_axis else ()) + tuple(self.data_axes)

    @property
    def n_model(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def n_data(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n


def divisible(n: int, by: int) -> bool:
    return by > 0 and n % by == 0
