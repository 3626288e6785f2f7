"""Collective communication — host-side groups + in-graph ICI mapping.

Counterpart of the reference's `ray.util.collective`
(`util/collective/collective.py`: allreduce :258, reduce :311, broadcast
:373, allgather :423, reducescatter :472, send/recv :531/:594, GroupManager
:40, NCCL backend `collective_group/nccl_collective_group.py:127`).

TPU-native split (SURVEY.md §5.8):

- **Device-data collectives belong in the graph**: `jax.lax.psum` /
  `all_gather` / `ppermute` / `all_to_all` inside a jitted mesh program,
  compiled by XLA onto ICI. Use `ray_tpu.parallel` for those; this module's
  table maps every reference verb to its in-graph equivalent.
- **Host-data collectives** (checkpoint shards, sample batches, rendezvous —
  things NCCL's gloo fallback did) run here over the object store, via a
  rendezvous actor per group. This dogfoods the actor runtime the same way
  the reference's GLOOGroup rides its own store.

SCALE BOUNDARY: every rank's array funnels through the one rendezvous
actor — O(world_size * bytes) through a single process per op. That is
the right shape for control-plane payloads (histograms, metrics,
rendezvous blobs) and the WRONG shape for gradients or activations;
arrays above COLLECTIVE_MAX_BYTES are refused with a pointer to the
in-graph mapping below, so nobody ships model state through this path
by accident.

In-graph mapping (for code inside shard_map/pjit over a Mesh axis ``ax``):

    allreduce(t, op=SUM)   ->  jax.lax.psum(t, ax)        # or pmean
    allgather(t)           ->  jax.lax.all_gather(t, ax)
    reducescatter(t)       ->  jax.lax.psum_scatter(t, ax)
    broadcast(t, src)      ->  implicit (replicated sharding), or
                               jax.lax.all_gather + index
    send/recv ring         ->  jax.lax.ppermute(t, ax, perm)
    alltoall               ->  jax.lax.all_to_all(t, ax, ...)
    barrier()              ->  psum(0) data dependency
"""

from __future__ import annotations

import threading

import numpy as np

import ray_tpu
from ray_tpu.exceptions import RayTpuError

_REDUCE_OPS = {
    "sum": lambda xs: _tree_reduce(np.add, xs),
    "prod": lambda xs: _tree_reduce(np.multiply, xs),
    "max": lambda xs: _tree_reduce(np.maximum, xs),
    "min": lambda xs: _tree_reduce(np.minimum, xs),
    "mean": lambda xs: _tree_reduce(np.add, xs) / len(xs),
}


def _tree_reduce(op, xs):
    acc = np.asarray(xs[0], dtype=np.result_type(xs[0]))
    for x in xs[1:]:
        acc = op(acc, x)
    return acc


class _RendezvousActor:
    """One per collective group; methods run with max_concurrency=world so
    all ranks rendezvous inside (three-phase barrier: deposit, reduce,
    drain)."""

    def __init__(self, world_size: int):
        self.world = world_size
        self.lock = threading.Lock()
        self.slots: dict[int, object] = {}
        self.mailbox: dict[tuple, object] = {}
        self.barrier = threading.Barrier(world_size)
        self.result = None

    def _exchange(self, rank, value, combine):
        with self.lock:
            self.slots[rank] = value
        i = self.barrier.wait()
        if i == 0:
            # Snapshot + clear between the two barriers: no rank can be
            # depositing for the next round until everyone passes the
            # second barrier, and nobody passes the *next* round's first
            # barrier until all have read this round's result.
            ordered = [self.slots[r] for r in sorted(self.slots)]
            self.slots = {}
            self.result = combine(ordered)
        self.barrier.wait()
        return self.result

    def allreduce(self, rank, arr, op):
        return self._exchange(rank, arr, _REDUCE_OPS[op])

    def allgather(self, rank, arr):
        return self._exchange(rank, arr, lambda xs: list(xs))

    def reducescatter(self, rank, arr, op):
        full = self._exchange(rank, arr, _REDUCE_OPS[op])
        chunks = np.array_split(full, self.world)
        return chunks[rank]

    def broadcast(self, rank, arr, src):
        out = self._exchange(rank, arr, lambda xs: xs[src])
        return out

    def barrier_op(self, rank):
        self._exchange(rank, None, lambda xs: None)
        return True

    def put_p2p(self, dst, tag, arr):
        with self.lock:
            self.mailbox[(dst, tag)] = arr
        return True

    def take_p2p(self, dst, tag, timeout=60.0):
        import time
        deadline = time.time() + timeout
        while time.time() < deadline:
            with self.lock:
                if (dst, tag) in self.mailbox:
                    return self.mailbox.pop((dst, tag))
            time.sleep(0.005)
        raise TimeoutError(f"recv timeout (dst={dst}, tag={tag})")


_local = threading.local()



def _guard_size(arr):
    """Refuse model-state-sized payloads: the rendezvous actor is a
    control-plane funnel (O(world * bytes) through one process). Big
    tensors belong in-graph — see the mapping table in the module
    docstring — or in the object store directly."""
    from ray_tpu._private import config as _config
    nbytes = getattr(arr, "nbytes", None)
    if nbytes is None:
        # non-buffer payloads (lists, dicts of arrays): len() counts
        # ELEMENTS, not bytes — measure the actual wire size instead
        # (control-plane payloads are small; one extra pickle is cheap)
        try:
            import cloudpickle
            nbytes = len(cloudpickle.dumps(arr))
        except Exception:
            return arr      # unpicklable: the send itself will say so
    cap = _config.get("COLLECTIVE_MAX_BYTES")
    if nbytes > cap:
        raise RayTpuError(
            f"host-side collective payload is {nbytes} bytes "
            f"(> COLLECTIVE_MAX_BYTES={cap}): this path funnels every "
            "rank through one rendezvous actor and is for control-plane "
            "data only. Move device tensors in-graph (jax.lax.psum/"
            "all_gather over a Mesh axis; ray_tpu.parallel) or ship "
            "them via the object store.")
    return arr


class CollectiveGroup:
    """Client handle bound to (group_name, rank)."""

    def __init__(self, name: str, world_size: int, rank: int):
        self.name = name
        self.world_size = world_size
        self.rank = rank
        actor_name = f"_rtpu_collective:{name}"
        try:
            self._actor = ray_tpu.get_actor(actor_name)
            return
        except ValueError:
            pass
        cls = ray_tpu.remote(_RendezvousActor)
        try:
            cls.options(
                name=actor_name,
                max_concurrency=max(2 * world_size, 4),
            ).remote(world_size)
        except Exception:
            pass
        # Ranks race to create the group actor, and under pipelined
        # submission a lost naming race surfaces as an error object on
        # the creation return — not as a raised exception here. The
        # head's name table is the single authority either way: bind to
        # whichever creation it registered, polling briefly until the
        # winner's (possibly in-flight) registration lands.
        import time as _time
        deadline = _time.monotonic() + 30.0
        while True:
            try:
                self._actor = ray_tpu.get_actor(actor_name)
                return
            except ValueError:
                if _time.monotonic() > deadline:
                    raise
                _time.sleep(0.01)

    def allreduce(self, arr, op: str = "sum"):
        return ray_tpu.get(self._actor.allreduce.remote(
            self.rank, _guard_size(arr), op))

    def allgather(self, arr):
        return ray_tpu.get(self._actor.allgather.remote(
            self.rank, _guard_size(arr)))

    def reducescatter(self, arr, op: str = "sum"):
        return ray_tpu.get(
            self._actor.reducescatter.remote(
                self.rank, _guard_size(arr), op))

    def broadcast(self, arr, src: int = 0):
        return ray_tpu.get(self._actor.broadcast.remote(
            self.rank, _guard_size(arr), src))

    def barrier(self):
        return ray_tpu.get(self._actor.barrier_op.remote(self.rank))

    def send(self, arr, dst: int, tag: int = 0):
        return ray_tpu.get(self._actor.put_p2p.remote(
            dst, tag, _guard_size(arr)))

    def recv(self, src: int, tag: int = 0, timeout: float = 60.0):
        return ray_tpu.get(
            self._actor.take_p2p.remote(self.rank, tag, timeout))


def init_collective_group(world_size: int, rank: int,
                          backend: str = "store",
                          group_name: str = "default") -> CollectiveGroup:
    """Join a named collective group (reference:
    `collective.init_collective_group`). backend="store" is the host-data
    path; device data should use in-graph collectives (module docstring)."""
    if backend not in ("store", "gloo", "nccl"):
        raise ValueError(f"unknown backend {backend!r}")
    g = CollectiveGroup(group_name, world_size, rank)
    if not hasattr(_local, "groups"):
        _local.groups = {}
    _local.groups[group_name] = g
    return g


def destroy_collective_group(group_name: str = "default") -> None:
    """Forget a group and end its rendezvous actor (reference:
    `collective.destroy_collective_group`); from one process, once every
    rank is done. Left alone the actor holds its worker process until the
    session ends."""
    getattr(_local, "groups", {}).pop(group_name, None)
    try:
        ray_tpu.kill(ray_tpu.get_actor(f"_rtpu_collective:{group_name}"))
    except ValueError:
        pass        # never made, or destroyed already


def _group(group_name: str) -> CollectiveGroup:
    groups = getattr(_local, "groups", {})
    if group_name not in groups:
        raise RayTpuError(
            f"collective group {group_name!r} not initialized in this "
            f"process; call init_collective_group first")
    return groups[group_name]


# Module-level functional API mirroring the reference's call shapes.

def allreduce(arr, group_name: str = "default", op: str = "sum"):
    return _group(group_name).allreduce(arr, op)


def allgather(arr, group_name: str = "default"):
    return _group(group_name).allgather(arr)


def reducescatter(arr, group_name: str = "default", op: str = "sum"):
    return _group(group_name).reducescatter(arr, op)


def broadcast(arr, src_rank: int = 0, group_name: str = "default"):
    return _group(group_name).broadcast(arr, src_rank)


def barrier(group_name: str = "default"):
    return _group(group_name).barrier()


def send(arr, dst_rank: int, group_name: str = "default", tag: int = 0):
    return _group(group_name).send(arr, dst_rank, tag)


def recv(src_rank: int, group_name: str = "default", tag: int = 0,
         timeout: float = 60.0):
    return _group(group_name).recv(src_rank, tag, timeout)
