"""User-facing TPU pod helpers.

Reference: python/ray/util/accelerators/tpu.py —
get_current_pod_name():7, get_current_pod_worker_count():21; plus the
slice-gang primitive SURVEY.md §7 phase 3 calls for: an atomic
"reserve all K hosts of one slice" built from a STRICT_SPREAD
placement group over the slice's per-host resources.
"""

from __future__ import annotations

from typing import Optional

from ..._private.accelerators.tpu import (
    TPUAcceleratorManager,
    chips_per_host,
    pod_type_num_chips,
    pod_worker_count,
)
from ..placement_group import PlacementGroup, placement_group


def get_current_pod_name() -> Optional[str]:
    """Name of the TPU pod this host belongs to (None off-TPU)."""
    return TPUAcceleratorManager.get_current_node_tpu_name()


def get_current_pod_worker_count() -> Optional[int]:
    """Number of hosts in this host's pod slice."""
    pod_type = TPUAcceleratorManager.get_current_node_accelerator_type()
    if pod_type is None:
        return None
    return pod_worker_count(pod_type)


def get_num_tpu_chips_on_node() -> int:
    return TPUAcceleratorManager.get_current_node_num_accelerators()


def cluster_tpu_chips() -> int:
    """Chips the connected cluster advertises (0 with no runtime, or
    none detected). Libraries whose actors host a JAX program size
    their `num_tpus` request from this, so the same call lands on the
    chip where there is one and on the CPU where there is not."""
    from ..._private.worker import global_worker

    worker = global_worker()
    if worker is None:
        return 0
    resources = worker.call("cluster_resources")["resources"]
    return int(resources.get("TPU", 0))


def slice_placement_group(
    pod_type: str,
    pod_name: Optional[str] = None,
    name: str = "",
) -> PlacementGroup:
    """Gang-reserve one whole TPU slice: one bundle per host, each
    claiming the host's full chip set, STRICT_SPREAD so bundles land on
    distinct hosts. Pass `pod_name` to pin the reservation to a
    specific slice (each of its hosts advertises `{pod_name}: 1`).

    The returned group is the scheduling unit for SPMD gangs: lease one
    worker per bundle and run the pjit program across them.
    """
    hosts = pod_worker_count(pod_type)
    per_host = chips_per_host(pod_type)
    bundle = {"TPU": float(per_host)}
    if pod_name:
        bundle[pod_name] = 1.0
    return placement_group(
        [dict(bundle) for _ in range(hosts)],
        strategy="STRICT_SPREAD",
        name=name,
    )


__all__ = [
    "cluster_tpu_chips",
    "get_current_pod_name",
    "get_current_pod_worker_count",
    "get_num_tpu_chips_on_node",
    "pod_type_num_chips",
    "slice_placement_group",
]
