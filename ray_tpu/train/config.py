"""Training run configuration (ref: python/ray/air/config.py —
ScalingConfig/RunConfig/CheckpointConfig/FailureConfig; train/v2/api/config.py).

TPU deltas: ``resources_per_worker`` defaults to one host's worth of chips
when ``use_tpu`` is set (as many as the cluster's TPU hosts really have),
and workers are gang-placed with STRICT_SPREAD so each host of a slice
gets exactly one controller process (SPMD multi-controller model,
SURVEY §7.1)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional


@dataclass
class ScalingConfig:
    """Shape of the worker gang."""

    num_workers: int = 1
    use_tpu: bool = False
    resources_per_worker: Optional[Dict[str, float]] = None
    # PG strategy for the gang; STRICT_SPREAD = one worker per host (the TPU
    # slice model), PACK = colocate when possible (CPU tests, small jobs)
    placement_strategy: str = "PACK"

    def worker_resources(self, host_chips: float = 0.0) -> Dict[str, float]:
        """One worker's bundle. ``host_chips`` is the chip count of the
        cluster's TPU hosts (the largest ``TPU`` total of any live node):
        a ``use_tpu`` worker defaults to all of one host's chips, and a
        request for more than any host has can never be granted — it
        fails here with a message instead of waiting in the scheduler."""
        if self.resources_per_worker:
            bundle = dict(self.resources_per_worker)
        elif self.use_tpu:
            bundle = {"CPU": 1.0, "TPU": float(host_chips)}
        else:
            return {"CPU": 1.0}
        if self.use_tpu and not 0 < bundle.get("TPU", 0.0) <= host_chips:
            raise ValueError(
                f"use_tpu worker asks for TPU={bundle.get('TPU', 0.0)} but "
                f"the largest TPU host in the cluster has {host_chips} "
                f"chips: no node can ever grant it. Set "
                f"resources_per_worker={{\"TPU\": n}} with 0 < n <= "
                f"{host_chips}, or start the node where its chips are "
                f"visible.")
        return bundle


@dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None          # None = keep all


@dataclass
class FailureConfig:
    max_failures: int = 0                      # gang restarts allowed


@dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None         # default: /tmp/ray_tpu_results
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)
    failure_config: FailureConfig = field(default_factory=FailureConfig)
