"""TPU accelerator manager.

Reference: python/ray/_private/accelerators/tpu.py — chip detection via
/dev/accel* (:107), per-worker visibility via TPU_VISIBLE_CHIPS +
host-bounds env vars (:155-195), pod-type/worker-id from GCE metadata
or GKE env vars (:198-271), and the slice-scheduling auto-resources
`TPU-{pod_type}-head` + pod-name (:334-397) that make SPMD gang
scheduling expressible as ordinary resource requests.

One process per chip set: libtpu gives a chip to one process at a
time, so a TPU worker is scoped at spawn to exactly the chips its
lease holds (`chip_scope_env`, the reference's TPU_VISIBLE_CHIPS +
bounds recipe). A lease for every chip on the node leaves libtpu's
defaults alone, which is what an SPMD program spanning the host wants;
two `num_tpus=1` actors on a four-chip host get two different chips
(SURVEY.md §7 hard part 1: "the worker pool must pin TPU workers").

Cloud metadata is read from env vars only (GCE metadata-server lookups
are gated out: zero-egress environments hang on them). The overrides
RT_TPU_* exist so tests and fake clusters can model pod topology.
"""

from __future__ import annotations

import glob
import os
import re
from functools import lru_cache
from typing import Dict, Iterable, Optional, Sequence, Tuple

from .base import AcceleratorManager

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"

# Generation -> chips per host (a v5e host has 4 or 8 chips; 4 is the
# common pod-slice shape; overridable via RT_TPU_CHIPS_PER_HOST).
_DEFAULT_CHIPS_PER_HOST = {
    "v2": 4,
    "v3": 4,
    "v4": 4,
    "v5e": 4,
    "v5p": 4,
    "v6e": 4,
}

_POD_TYPE_RE = re.compile(r"^(v\d+[a-z]*)-(\d+)$")

#: Chips one process may hold short of the whole host -> the chip
#: grid libtpu is told to expect (reference: tpu.py:155-195
#: TPU_CHIPS_PER_HOST_BOUNDS_{1,2}_CHIP_CONFIG; 4-of-8 is the same
#: recipe one size up).
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def pick_chips(
    free: Iterable[int], n: int, total: int
) -> Optional[Tuple[int, ...]]:
    """The lowest aligned block of `n` chip ids that is entirely in
    `free`, or None. Aligned (0-1, 2-3; 0-3, 4-7) because a
    multi-chip process needs chips that are neighbours on the host's
    grid, not any `n` that happen to be idle."""
    free = set(free)
    for start in range(0, total - n + 1, n):
        block = tuple(range(start, start + n))
        if free.issuperset(block):
            return block
    return None


def chip_scope_env(
    chips: Sequence[int], chips_on_node: int
) -> Dict[str, str]:
    """Environment that scopes one worker process to `chips`. Empty
    when the worker holds every chip on the node (libtpu's defaults
    already describe the host)."""
    if len(chips) >= chips_on_node:
        return {}
    bounds = _CHIP_BOUNDS.get(len(chips))
    if bounds is None:
        raise ValueError(
            f"no host topology for a {len(chips)}-chip worker; ask for "
            f"{sorted(_CHIP_BOUNDS)} chips or all {chips_on_node}"
        )
    return {
        TPU_VISIBLE_CHIPS_ENV: ",".join(str(c) for c in chips),
        "TPU_CHIPS_PER_HOST_BOUNDS": bounds,
        "TPU_HOST_BOUNDS": "1,1,1",
    }


def _env(*names: str) -> Optional[str]:
    for name in names:
        value = os.environ.get(name)
        if value:
            return value
    return None


class TPUAcceleratorManager(AcceleratorManager):
    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return TPU_VISIBLE_CHIPS_ENV

    @staticmethod
    @lru_cache()
    def get_current_node_num_accelerators() -> int:
        override = os.environ.get("RT_TPU_CHIPS")
        if override is not None:
            return int(override)
        chips = glob.glob("/dev/accel*")
        if chips:
            return len(chips)
        try:
            entries = os.listdir("/dev/vfio")
        except FileNotFoundError:
            return 0
        return len([e for e in entries if e.isdigit()])

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        """Pod type like 'v5e-16' (generation-chips across the slice)."""
        return _env("RT_TPU_POD_TYPE", "TPU_ACCELERATOR_TYPE")

    @staticmethod
    def get_current_node_tpu_name() -> Optional[str]:
        return _env("RT_TPU_NAME", "TPU_NAME")

    @staticmethod
    def get_current_node_tpu_worker_id() -> Optional[int]:
        raw = _env("RT_TPU_WORKER_ID", "TPU_WORKER_ID")
        if raw is None:
            return None
        try:
            return int(raw)
        except ValueError:
            return None

    @staticmethod
    def is_valid_tpu_accelerator_type(pod_type: str) -> bool:
        return _POD_TYPE_RE.match(pod_type) is not None

    @staticmethod
    def get_extra_resources_and_labels(
        num_accelerators: int,
    ) -> Tuple[Dict[str, float], Dict[str, str]]:
        resources: Dict[str, float] = {}
        labels: Dict[str, str] = {}
        pod_type = TPUAcceleratorManager.get_current_node_accelerator_type()
        pod_name = TPUAcceleratorManager.get_current_node_tpu_name()
        worker_id = TPUAcceleratorManager.get_current_node_tpu_worker_id()
        if pod_type:
            labels["rt.io/tpu-pod-type"] = pod_type
            # Worker 0 of a slice advertises the head marker so one
            # task can claim the whole slice atomically (reference:
            # tpu.py:334 `TPU-{pod_type}-head`).
            if worker_id == 0 or worker_id is None:
                resources[f"TPU-{pod_type}-head"] = 1.0
        if pod_name:
            labels["rt.io/tpu-pod-name"] = pod_name
            # Every host of the slice carries the pod-name resource so
            # a STRICT_SPREAD placement group over it gang-reserves the
            # slice (reference: tpu.py:397).
            resources[pod_name] = 1.0
        if worker_id is not None:
            labels["rt.io/tpu-worker-id"] = str(worker_id)
        return resources, labels


def pod_type_num_chips(pod_type: str) -> int:
    """Total chips in a slice, from the pod type ('v5e-16' -> 16)."""
    m = _POD_TYPE_RE.match(pod_type)
    if not m:
        raise ValueError(f"bad TPU pod type {pod_type!r}")
    generation, count = m.group(1), int(m.group(2))
    # v2/v3 pod types count cores (2 per chip); v4+ count chips
    # (reference: tpu.py get_num_tpu_visible_chips_per_host).
    if generation in ("v2", "v3"):
        return count // 2
    return count


def chips_per_host(pod_type: str) -> int:
    override = os.environ.get("RT_TPU_CHIPS_PER_HOST")
    if override:
        return int(override)
    m = _POD_TYPE_RE.match(pod_type)
    generation = m.group(1) if m else "v5e"
    per_host = _DEFAULT_CHIPS_PER_HOST.get(generation, 4)
    return min(per_host, pod_type_num_chips(pod_type))


def pod_worker_count(pod_type: str) -> int:
    """Number of hosts in a slice."""
    total = pod_type_num_chips(pod_type)
    per_host = chips_per_host(pod_type)
    return max(1, (total + per_host - 1) // per_host)
