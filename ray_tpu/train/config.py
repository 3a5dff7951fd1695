"""Shared training configs (reference: python/ray/air/config.py —
ScalingConfig / RunConfig / CheckpointConfig / FailureConfig dataclasses
consumed by Trainer.fit)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from ..parallel.mesh import MeshSpec


@dataclasses.dataclass
class ScalingConfig:
    """How to scale training (reference: air/config.py ScalingConfig).

    TPU-native twist: instead of `num_workers × use_gpu`, the unit of
    scale is a device mesh. `num_workers` is the number of host
    processes in the gang (1 = single-controller); `mesh` is the
    per-gang parallelism layout; `resources_per_worker` feeds the
    placement-group request when the gang is scheduled on a cluster
    (without a "TPU" entry each worker leases an equal share of the
    chips the cluster advertises — see WorkerGroup).
    """

    num_workers: int = 1
    mesh: Optional[MeshSpec] = None
    resources_per_worker: Optional[Dict[str, float]] = None

    def resolved_mesh(self) -> MeshSpec:
        return self.mesh if self.mesh is not None else MeshSpec.auto()


@dataclasses.dataclass
class CheckpointConfig:
    """(reference: air/config.py CheckpointConfig — top-k retention)."""

    num_to_keep: Optional[int] = None
    checkpoint_frequency: int = 0  # steps; 0 = only on report()


@dataclasses.dataclass
class FailureConfig:
    """(reference: air/config.py FailureConfig.max_failures)."""

    max_failures: int = 0


@dataclasses.dataclass
class RunConfig:
    """(reference: air/config.py RunConfig — name + storage + FT)."""

    name: Optional[str] = None
    storage_path: Optional[str] = None
    checkpoint_config: CheckpointConfig = dataclasses.field(
        default_factory=CheckpointConfig
    )
    failure_config: FailureConfig = dataclasses.field(
        default_factory=FailureConfig
    )


@dataclasses.dataclass
class Result:
    """What Trainer.fit returns (reference: air/result.py)."""

    metrics: Dict[str, Any]
    checkpoint_path: Optional[str]
    error: Optional[BaseException] = None
    metrics_history: list = dataclasses.field(default_factory=list)
