"""TPU accelerator manager: slice-topology detection + worker pinning.

TPU-native re-design of the reference's ``TPUAcceleratorManager``
(``python/ray/_private/accelerators/tpu.py:71``): chip count and pod
topology come from the TPU runtime's environment variables (the libtpu
launcher exports them on real slices), the pod "head" host exports a
``TPU-<pod_type>-head`` marker resource so a multi-host slice can be
gang-scheduled by claiming exactly one head, and per-worker chip pinning is
``TPU_VISIBLE_CHIPS`` plus a JAX platform pin (a chip is process-exclusive:
an unpinned worker importing jax would steal it). The scheduler decides the
owner: work that holds a ``TPU`` grant runs in workers of the ``tpu`` pool
(:func:`worker_pool_key`), and the node agent pins every other worker to
the CPU when it starts it (``node.worker_spawn_env``). One chip-holding
process per host at a time is what this supports: a granted worker sees
every chip of its host, because nothing sets per-process chip bounds.

Topology math: a pod type ``v5p-128`` names 128 *cores*; v2–v4 and v5p have
2 cores/chip, v5e and v6e 1 core/chip; hosts hold 4 chips (8 for v5p).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

from .accelerator import AcceleratorManager

# cores per chip by generation prefix
_CORES_PER_CHIP = {"v2": 2, "v3": 2, "v4": 2, "v5p": 2, "v5litepod": 1,
                   "v5e": 1, "v6e": 1}
_CHIPS_PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5p": 4, "v5litepod": 8,
                   "v5e": 8, "v6e": 8}

# Env vars the TPU runtime / GKE export on slice VMs.
ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"      # e.g. "v5p-128"
WORKER_ID_ENV = "TPU_WORKER_ID"                     # "0".."n-1" in the pod
WORKER_HOSTNAMES_ENV = "TPU_WORKER_HOSTNAMES"       # comma-separated
CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"  # e.g. "2,2,1"
TOPOLOGY_ENV = "TPU_TOPOLOGY"                       # e.g. "4x4x8"
VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
NUM_CHIPS_OVERRIDE_ENV = "RAY_TPU_CHIPS"            # explicit override

#: Worker-pool key (``RAY_TPU_ENV_KEY``) of workers that may own the chip.
TPU_POOL = "tpu"


def worker_pool_key(env_key: str, resources: Optional[Dict[str, float]]) -> str:
    """The worker pool a task, actor or lease draws from: work that holds
    a ``TPU`` grant never shares a process with work that does not."""
    if (resources or {}).get("TPU", 0) <= 0:
        return env_key
    return f"{TPU_POOL}+{env_key}" if env_key else TPU_POOL


def holds_tpu_grant(pool_key: str) -> bool:
    return pool_key == TPU_POOL or pool_key.startswith(TPU_POOL + "+")


def _generation(pod_type: str) -> Optional[str]:
    for gen in sorted(_CORES_PER_CHIP, key=len, reverse=True):
        if pod_type.startswith(gen):
            return gen
    return None


class TPUAcceleratorManager(AcceleratorManager):
    resource_name = "TPU"

    # ------------------------------------------------------------ detection

    def get_current_node_num_accelerators(self) -> int:
        override = os.environ.get(NUM_CHIPS_OVERRIDE_ENV)
        if override:
            return int(float(override))
        bounds = os.environ.get(CHIPS_PER_HOST_BOUNDS_ENV)
        if bounds:
            n = 1
            for d in bounds.split(","):
                n *= int(d)
            return n
        pod = self.get_current_node_accelerator_type()
        if pod:
            gen = _generation(pod)
            if gen:
                total_chips = self.get_pod_num_chips(pod)
                per_host = _CHIPS_PER_HOST[gen]
                return min(total_chips, per_host)
        return 0

    def get_current_node_accelerator_type(self) -> Optional[str]:
        return os.environ.get(ACCELERATOR_TYPE_ENV) or None

    @staticmethod
    def get_pod_num_chips(pod_type: str) -> int:
        """Total chips in the slice named by ``pod_type`` (cores/gen math)."""
        gen = _generation(pod_type)
        try:
            cores = int(pod_type.rsplit("-", 1)[1])
        except (IndexError, ValueError):
            return 0
        if gen is None:
            return 0
        return max(1, cores // _CORES_PER_CHIP[gen])

    def get_current_pod_worker_count(self) -> int:
        hostnames = os.environ.get(WORKER_HOSTNAMES_ENV)
        if hostnames:
            return len([h for h in hostnames.split(",") if h])
        pod = self.get_current_node_accelerator_type()
        if pod:
            gen = _generation(pod)
            if gen:
                chips = self.get_pod_num_chips(pod)
                per_host = _CHIPS_PER_HOST[gen]
                return max(1, -(-chips // per_host))
        return 1

    def get_current_node_tpu_worker_id(self) -> int:
        try:
            return int(os.environ.get(WORKER_ID_ENV, "0"))
        except ValueError:
            return 0

    def get_pod_slice_markers(self, num_chips: float) -> Dict[str, float]:
        """Slice marker resources for a host known to hold ``num_chips``.

        Scheduling a 1-unit ``TPU-<pod>-head`` bundle lands a task on the
        slice's first host, from which a mesh worker group fans out to every
        host in the slice — the reference's pod-slice scheduling trick
        (``tpu.py:71`` sets e.g. ``TPU-v4-8-head``).
        """
        pod = self.get_current_node_accelerator_type()
        if not pod or num_chips <= 0:
            return {}
        out = {f"TPU-{pod}": float(num_chips)}
        if self.get_current_node_tpu_worker_id() == 0:
            out[f"TPU-{pod}-head"] = 1.0
        slice_id = self.get_current_slice_id()
        if slice_id:
            # Unique-per-slice marker: every host of one slice exports the
            # same id, so the scheduler can confine a placement group to
            # one ICI domain (STRICT_ICI) — two same-type slices are
            # otherwise indistinguishable by the TPU-<pod> markers alone.
            out[f"TPU-slice-{slice_id}"] = 1.0
        return out

    @staticmethod
    def get_current_slice_id() -> Optional[str]:
        """Stable identity shared by all hosts of this slice.

        Every host in a slice sees the same ``TPU_WORKER_HOSTNAMES`` (the
        GKE/TPU-VM runtime exports it); its hash names the ICI domain.
        ``TPU_NAME`` wins when present (explicit, human-readable).
        """
        name = os.environ.get("TPU_NAME")
        if name:
            return name
        hostnames = os.environ.get(WORKER_HOSTNAMES_ENV)
        if hostnames:
            import hashlib

            return hashlib.sha1(hostnames.encode()).hexdigest()[:12]
        return None

    def get_current_node_extra_resources(self) -> Dict[str, float]:
        return self.get_pod_slice_markers(
            self.get_current_node_num_accelerators())

    def get_current_node_topology(self) -> Optional[str]:
        return os.environ.get(TOPOLOGY_ENV) or None

    # -------------------------------------------------------------- pinning

    def get_visible_accelerator_ids_env_var(self) -> str:
        return VISIBLE_CHIPS_ENV

    def set_visible_accelerators(self, env: Dict[str, str],
                                 ids: List[str]) -> None:
        env[VISIBLE_CHIPS_ENV] = ",".join(ids)
        if not ids:
            # No chips granted: pin the worker's JAX to CPU so importing jax
            # doesn't grab the (process-exclusive) chip.
            env["RAY_TPU_JAX_PLATFORM"] = "cpu"
