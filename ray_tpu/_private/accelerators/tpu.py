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
import sys
import time
from functools import lru_cache
from typing import Dict, Iterable, Optional, Sequence, Tuple

from ..worker_forkserver import _proc_stat
from .base import AcceleratorManager

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"

#: How long anything waits for the previous holder of a chip to be
#: gone: a TPU spawn for a worker of its own daemon, a daemon's
#: shutdown for the workers it killed, a TPU worker for another
#: session's leftover.
CHIP_WAIT_S = 30.0

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


def chip_device_files(chips: Sequence[int], dev: str = "/dev") -> set:
    """Device files of `chips`: `/dev/accel<i>`, or the i-th numbered
    group under `/dev/vfio` (a group's number is the IOMMU's, not the
    chip's). Empty on a host without them."""
    accel = {f"{dev}/accel{c}" for c in chips}
    files = {f for f in accel if os.path.exists(f)}
    try:
        groups = sorted(
            (e for e in os.listdir(f"{dev}/vfio") if e.isdigit()), key=int
        )
    except OSError:
        groups = []
    files.update(
        f"{dev}/vfio/{groups[c]}" for c in chips if c < len(groups)
    )
    return files


def chip_holders(
    chips: Sequence[int],
    skip: Iterable[int] = (),
    proc: str = "/proc",
    dev: str = "/dev",
) -> list:
    """Processes, other than `skip` and this one, that still hold
    `chips`, as (pid, state, command): libtpu gives a chip to one
    process at a time, and a worker scoped to a chip that its previous
    holder has not let go fails at its first JAX call. A holder is

    - a process with one of the chips' device files open
      (`/proc/<pid>/fd`), or
    - a thread group whose leader is a zombie and which still counts
      other threads (`ps`: `Zl`): it has closed its files, so nothing
      says WHAT it held, and the kernel's release of a device file
      runs in exactly that state, for seconds after a four-chip
      program's kill (PERF.md section 7). Counted only on a host that
      has the device files, and only to be waited for a bounded time.

    None on a host without the device files (CPU hosts, fake chips)."""
    files = chip_device_files(chips, dev)
    if not files:
        return []
    skip = set(skip) | {os.getpid()}
    holders = []
    try:
        pids = [int(e) for e in os.listdir(proc) if e.isdigit()]
    except OSError:
        return []
    for pid in pids:
        stat = None if pid in skip else _proc_stat(pid, proc)
        if stat is None:
            continue
        state, _, threads = stat
        if state == "Z":
            held = threads > 1
        else:
            held = False
            try:
                for fd in os.listdir(f"{proc}/{pid}/fd"):
                    if os.readlink(f"{proc}/{pid}/fd/{fd}") in files:
                        held = True
                        break
            except OSError:
                pass
        if held:
            holders.append((
                pid, state + ("l" if threads > 1 else ""),
                # (a zombie has no command line left, only its name)
                _proc_text(f"{proc}/{pid}/cmdline").replace("\0", " ").strip()
                or f"[{_proc_text(f'{proc}/{pid}/comm').strip()}]",
            ))
    return holders


def await_chip_holders(
    chips: Sequence[int], timeout: float = CHIP_WAIT_S, **where
) -> float:
    """Wait, at most `timeout` seconds, until no other process holds
    `chips` (`chip_holders`) -> the seconds waited. One line on
    standard error when that was over a second, or when the chips are
    still held and the caller goes on to find out for itself: how
    long, for which pids, in which state."""
    start = time.monotonic()
    seen = holders = chip_holders(chips, **where)
    while holders and time.monotonic() - start < timeout:
        time.sleep(0.1)
        seen, holders = holders, chip_holders(chips, **where)
    waited = time.monotonic() - start
    if holders or waited > 1.0:
        print(
            f"ray_tpu: waited {waited:.1f} s for chips {list(chips)}, "
            "held by another session's "
            + "; ".join(
                f"pid {pid} ({state}) {cmd}" for pid, state, cmd in seen
            )
            + (": still held, going on" if holders else ""),
            file=sys.stderr, flush=True,
        )
    return waited


def wait_for_chips_at_tpu_init(chips: Sequence[int]) -> None:
    """Called once by a worker scoped to `chips`, before it runs
    anything: what another session left on the chips (its daemon gone,
    its killed worker's threads 14-20 s over a four-chip device's
    teardown) is waited for when THIS process's JAX initialises its
    TPU backend, the last moment that is early enough. The worker's
    own start (its imports, its first task's set-up: 9 s of a train
    run's) then runs beside the leftover's teardown; waiting before
    the spawn instead put all of the teardown into a run's set-up
    (PERF.md section 6, PR 59). Where this JAX has no such seam the
    wait is made here and now; on a host without the chips' device
    files (fake chips) there is nobody to wait for."""
    if not chip_device_files(chips):
        return
    try:
        from jax._src import xla_bridge

        init = xla_bridge._init_backend
    except Exception:
        await_chip_holders(chips)
        return

    def init_after_wait(platform):
        if platform == "tpu":
            await_chip_holders(chips)
        return init(platform)

    xla_bridge._init_backend = init_after_wait


def _proc_text(path: str) -> str:
    try:
        with open(path, "rb") as f:
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""


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
