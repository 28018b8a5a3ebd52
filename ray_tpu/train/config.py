"""Trainer configuration dataclasses.

Reference: ``python/ray/air/config.py:94`` (ScalingConfig), ``:723``
(RunConfig), ``:523`` (FailureConfig), ``:574`` (CheckpointConfig). The
TPU-shaped addition: ``ScalingConfig.mesh`` — a `MeshSpec` describing
the global device mesh the worker gang assembles.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from ..parallel.mesh import MeshSpec


@dataclasses.dataclass
class ScalingConfig:
    """How many host workers, what resources each, what device mesh.

    num_workers: one per TPU host; CPU-only training uses plain actors.
    ``use_tpu`` gives each worker a whole host's chips — as many ``TPU``
    as the cluster's TPU nodes advertise (1 on a one-chip machine, 4 on
    a v5e 2x2 host) — so gang placement lands on TPU hosts and each
    worker's process owns its host's chips. ``resources_per_worker``
    overrides the amount.
    """

    num_workers: int = 1
    use_tpu: bool = False
    resources_per_worker: Optional[Dict[str, float]] = None
    placement_strategy: str = "STRICT_SPREAD"
    mesh: Optional[MeshSpec] = None

    def bundle(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker or {})
        res.setdefault("CPU", 1.0)
        if self.use_tpu and "TPU" not in res:
            res["TPU"] = _chips_per_host()
        return res


def _chips_per_host() -> float:
    """``TPU`` on the cluster's TPU nodes (the smallest, so that every
    worker fits one). With none alive yet — an autoscaled cluster that
    adds hosts on demand — a v5e host's four, which the placement group
    then waits for."""
    from .. import nodes
    counts = [n["resources"]["TPU"] for n in nodes()
              if n["alive"] and n["resources"].get("TPU", 0) >= 1]
    return float(min(counts)) if counts else 4.0


@dataclasses.dataclass
class FailureConfig:
    """max_failures: worker-gang restarts before giving up; -1 = infinite."""

    max_failures: int = 0


@dataclasses.dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_frequency: int = 0


@dataclasses.dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: Optional[FailureConfig] = None
    checkpoint_config: Optional[CheckpointConfig] = None
    verbose: int = 0
