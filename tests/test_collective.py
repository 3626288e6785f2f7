"""Host-side collective group tests (reference:
`python/ray/util/collective/tests/`)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import collective as col


def _rank_fn(rank, world):
    from ray_tpu.util import collective as col
    col.init_collective_group(world, rank, group_name="g1")
    out = col.allreduce(np.full(4, rank + 1.0), group_name="g1")
    gathered = col.allgather(np.array([rank]), group_name="g1")
    bcast = col.broadcast(np.array([rank * 10.0]), src_rank=2,
                          group_name="g1")
    return out, [int(g[0]) for g in gathered], float(bcast[0])


def test_collective_allreduce_allgather_broadcast(ray_session):
    world = 3
    fn = ray_tpu.remote(_rank_fn)
    refs = [fn.remote(r, world) for r in range(world)]
    results = ray_tpu.get(refs, timeout=180)
    expect_sum = sum(r + 1.0 for r in range(world))
    for out, gathered, bcast in results:
        np.testing.assert_allclose(out, np.full(4, expect_sum))
        assert gathered == [0, 1, 2]
        assert bcast == 20.0
    col.destroy_collective_group("g1")
    with pytest.raises(ValueError):     # its rendezvous actor went with it
        ray_tpu.get_actor("_rtpu_collective:g1")
    col.destroy_collective_group("g1")  # and once more is no error


def test_collective_send_recv(ray_session):
    def sender():
        from ray_tpu.util import collective as col
        g = col.init_collective_group(2, 0, group_name="p2p")
        g.send(np.array([7.0]), dst=1)
        return True

    def receiver():
        from ray_tpu.util import collective as col
        g = col.init_collective_group(2, 1, group_name="p2p")
        return float(g.recv(src=0)[0])

    s = ray_tpu.remote(sender).remote()
    r = ray_tpu.remote(receiver).remote()
    assert ray_tpu.get(r, timeout=120) == 7.0
    assert ray_tpu.get(s, timeout=120)
    col.destroy_collective_group("p2p")


def test_named_group_create_race_converges(ray_session):
    """All ranks racing to create the group's rendezvous actor must bind
    to the SAME actor. Under pipelined submission the losing create no
    longer raises at `.remote()` (the name collision surfaces as an
    error object), so the client must re-resolve through the head's name
    table instead of trusting its own handle."""
    def join(rank, world):
        from ray_tpu.util import collective as col
        g = col.init_collective_group(world, rank, group_name="race")
        return g._actor._actor_id

    world = 4
    fn = ray_tpu.remote(join)
    refs = [fn.remote(r, world) for r in range(world)]
    ids = ray_tpu.get(refs, timeout=120)
    assert len(set(ids)) == 1, ids
    col.destroy_collective_group("race")


def test_collective_refuses_big_tensors(ray_session):
    """The host-side group is a control-plane funnel (one rendezvous
    actor); model-state-sized payloads must be refused with a pointer at
    the in-graph path, not silently bottlenecked."""
    from ray_tpu.exceptions import RayTpuError

    g = col.CollectiveGroup("cap_test", world_size=1, rank=0)
    assert g.allreduce(np.ones(8)).sum() == 8.0          # small: fine
    with pytest.raises(RayTpuError, match="in-graph"):
        g.allreduce(np.zeros(80 << 20, np.uint8))        # 80MB: refused
    col.destroy_collective_group("cap_test")
