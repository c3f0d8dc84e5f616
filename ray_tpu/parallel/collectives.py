"""Collective communication API, lowered to XLA collectives over ICI.

Analog of ``ray.util.collective`` (``python/ray/util/collective/collective.py:
258-615`` — allreduce/reduce/broadcast/allgather/reducescatter/send/recv over
NCCL/Gloo). The TPU-native design has no runtime communicator: these
functions are *traced* inside ``jax.shard_map`` (or jit with sharding
constraints) and compile to ICI collectives. The "group" is a mesh axis
name, not an NCCL communicator object.

Two tiers:
  * in-program (this module's jax functions) — the hot path
  * host-level (``HostCollectiveGroup``) — control-plane reductions between
    actors on CPU, via the object store (the Gloo analog), for small
    metadata like metric aggregation.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size as _axis_size

AxisName = Union[str, Sequence[str]]


def allreduce(x, axis: AxisName = "dp", op: str = "sum"):
    """All-reduce over a mesh axis (inside shard_map)."""
    if op == "sum":
        return lax.psum(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    raise ValueError(f"unsupported op {op!r}")


def allgather(x, axis: AxisName = "dp", *, tiled: bool = True, gather_axis: int = 0):
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reducescatter(x, axis: AxisName = "dp", *, scatter_axis: int = 0):
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                            tiled=True)


def broadcast(x, axis: AxisName = "dp", root: int = 0):
    """Every participant gets root's value."""
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis)


def alltoall(x, axis: AxisName = "sp", *, split_axis: int,
             concat_axis: int):
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def permute(x, axis: AxisName, shift: int = 1):
    """Ring shift by ``shift`` along a mesh axis (ppermute)."""
    n = _axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def send_recv(x, axis: AxisName, pairs: List[tuple]):
    """Explicit point-to-point pattern (compiled ppermute)."""
    return lax.ppermute(x, axis, pairs)


def axis_index(axis: AxisName):
    return lax.axis_index(axis)


def axis_size(axis: AxisName):
    return _axis_size(axis)


class HostCollectiveGroup:
    """CPU-side collectives between actors via the object store.

    The Gloo-tier analog (``gloo_collective_group.py``): rank 0 gathers,
    reduces with numpy, and publishes; other ranks poll a named KV slot.
    Only for small control-plane data (metrics, rendezvous info) — tensor
    traffic belongs in compiled collectives.
    """

    def __init__(self, group_name: str, world_size: int, rank: int):
        self.group_name = group_name
        self.world_size = world_size
        self.rank = rank
        self._round = 0

    def _kv(self):
        from .._private.worker import global_worker

        return global_worker()

    def allreduce(self, arr, op: str = "sum", timeout: float = 60.0):
        import pickle
        import time

        import numpy as np

        w = self._kv()
        ns = f"col:{self.group_name}"
        key = f"r{self._round}:{self.rank}"
        w.kv_put(key, pickle.dumps(np.asarray(arr)), ns=ns)
        deadline = time.time() + timeout
        parts = {}
        while len(parts) < self.world_size:
            for r in range(self.world_size):
                if r in parts:
                    continue
                blob = w.kv_get(f"r{self._round}:{r}", ns=ns)
                if blob is not None:
                    parts[r] = pickle.loads(blob)
            if time.time() > deadline:
                raise TimeoutError(
                    f"allreduce timed out: {len(parts)}/{self.world_size}")
            if len(parts) < self.world_size:
                time.sleep(0.005)
        # Everyone finishing round r implies everyone has READ round r-1,
        # so our own r-1 slot can be garbage-collected (bounds KV growth;
        # a restarted member reusing the name then blocks loudly instead of
        # silently averaging stale data).
        if self._round > 0:
            w.kv_del(f"r{self._round - 1}:{self.rank}", ns=ns)
        self._round += 1
        stacked = np.stack([parts[r] for r in range(self.world_size)])
        if op == "sum":
            return stacked.sum(0)
        if op == "mean":
            return stacked.mean(0)
        if op == "max":
            return stacked.max(0)
        if op == "min":
            return stacked.min(0)
        raise ValueError(f"unsupported op {op!r}")

    def barrier(self, timeout: float = 60.0):
        self.allreduce([1.0], timeout=timeout)
