"""Multi-node cluster tests: scheduling across daemons, cross-node object
transfer, placement strategies, and node-death recovery.

Counterpart of the reference's `test_multi_node*.py` +
`test_placement_group*.py` over the one-host multi-raylet Cluster fixture
(`python/ray/cluster_utils.py:99`): each "node" is a real HostDaemon
subprocess with its own object store and worker pool, only the resource
shapes are fake.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util.scheduling_strategies import (
    NodeAffinitySchedulingStrategy,
    PlacementGroupSchedulingStrategy,
)

BIG = 512 * 1024    # > INLINE_OBJECT_MAX_BYTES: forces the store/data plane


def where():
    return os.environ.get("RAY_TPU_NODE_ID", "head")


@pytest.fixture(scope="module")
def cluster(ray_session):
    c = Cluster.attach()
    c.add_node({"CPU": 2, "red": 2})
    c.add_node({"CPU": 2, "blue": 2})
    yield c
    for nid in list(c.node_ids):
        try:
            c.kill_node(nid)
        except Exception:
            pass
    time.sleep(0.5)


def test_node_registration(cluster):
    nodes = cluster.list_nodes()
    assert sum(1 for n in nodes if n.get("head")) == 1
    # dead nodes from other test modules may linger in the shared
    # session's membership table; check only this fixture's nodes
    mine = [n for n in nodes if n["node_id"] in cluster.node_ids]
    assert len(mine) == 2
    assert all(n["alive"] for n in mine)
    total = ray_tpu.cluster_resources()
    assert total.get("red") == 2.0
    assert total.get("blue") == 2.0


def test_remote_node_execution(cluster):
    @ray_tpu.remote(resources={"red": 1})
    def f(x):
        return where(), x * 2

    node, val = ray_tpu.get(f.remote(21), timeout=60)
    assert val == 42
    assert node == cluster.node_ids[0]


def test_cross_node_object_transfer(cluster):
    """Driver-put array consumed on a node; node-produced array read by the
    driver — both directions of the pull plane."""
    arr = np.arange(BIG, dtype=np.uint8)
    ref = ray_tpu.put(arr)

    @ray_tpu.remote(resources={"red": 1})
    def consume(a):
        return where(), int(a.sum()), np.ones(BIG, np.uint8)

    node, s, ones = ray_tpu.get(consume.remote(ref), timeout=60)
    assert node == cluster.node_ids[0]
    assert s == int(arr.sum())
    assert ones.shape == (BIG,)
    assert int(ones.sum()) == BIG


def test_node_to_node_transfer(cluster):
    """Object produced on red is consumed on blue: peer-to-peer pull."""
    @ray_tpu.remote(resources={"red": 1})
    def produce():
        return np.full(BIG, 7, np.uint8)

    @ray_tpu.remote(resources={"blue": 1})
    def consume(a):
        return where(), int(a[:10].sum())

    ref = produce.remote()
    node, s = ray_tpu.get(consume.remote(ref), timeout=60)
    assert node == cluster.node_ids[1]
    assert s == 70


def test_spillback_when_head_full(cluster):
    """More concurrent CPU=1 tasks than the head has CPUs: the cluster
    scheduler spills the surplus to daemon nodes
    (cluster_task_manager.cc:44 spillback equivalent)."""
    @ray_tpu.remote(num_cpus=1)
    def slow():
        time.sleep(1.0)
        return where()

    n = 8   # head has 4 CPUs, each extra node 2
    hosts = ray_tpu.get([slow.remote() for _ in range(n)], timeout=120)
    assert len(set(hosts)) >= 2, hosts


def test_node_affinity(cluster):
    nid = cluster.node_ids[1]

    @ray_tpu.remote(num_cpus=1)
    def f():
        return where()

    pinned = f.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(node_id=nid))
    assert ray_tpu.get(pinned.remote(), timeout=60) == nid
    head = f.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(node_id="head"))
    assert ray_tpu.get(head.remote(), timeout=60) == "head"


def test_spread_strategy(cluster):
    @ray_tpu.remote(num_cpus=1)
    def f(i):
        time.sleep(0.2)
        return where()

    spread = f.options(scheduling_strategy="SPREAD")
    hosts = ray_tpu.get([spread.remote(i) for i in range(6)], timeout=120)
    assert len(set(hosts)) >= 2, hosts


def test_strict_spread_placement_group(cluster):
    from ray_tpu.util.placement_group import (
        placement_group, remove_placement_group)
    pg = placement_group([{"CPU": 1}] * 3, strategy="STRICT_SPREAD")

    @ray_tpu.remote(num_cpus=1)
    def f():
        time.sleep(0.3)
        return where()

    refs = [f.options(scheduling_strategy=PlacementGroupSchedulingStrategy(placement_group=pg)).remote() for _ in range(3)]
    hosts = ray_tpu.get(refs, timeout=120)
    assert len(set(hosts)) == 3, hosts
    remove_placement_group(pg)


def test_strict_spread_infeasible(cluster):
    from ray_tpu.exceptions import PlacementGroupError
    from ray_tpu.util.placement_group import placement_group
    with pytest.raises(PlacementGroupError):
        placement_group([{"CPU": 1}] * 10, strategy="STRICT_SPREAD")


def test_strict_pack_stays_on_one_node(cluster):
    from ray_tpu.util.placement_group import (
        placement_group, remove_placement_group)
    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="STRICT_PACK")

    @ray_tpu.remote(num_cpus=1)
    def f():
        time.sleep(0.2)
        return where()

    hosts = ray_tpu.get(
        [f.options(scheduling_strategy=PlacementGroupSchedulingStrategy(placement_group=pg)).remote() for _ in range(2)],
        timeout=120)
    assert len(set(hosts)) == 1, hosts
    remove_placement_group(pg)


def test_actor_on_remote_node(cluster):
    @ray_tpu.remote(resources={"blue": 1})
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self, k=1):
            self.n += k
            return self.n

        def host(self):
            return where()

    c = Counter.remote()
    assert ray_tpu.get(c.host.remote(), timeout=60) == cluster.node_ids[1]
    assert ray_tpu.get(c.incr.remote(), timeout=60) == 1
    assert ray_tpu.get(c.incr.remote(5), timeout=60) == 6
    ray_tpu.kill(c)


def test_named_actor_on_remote_node(cluster):
    @ray_tpu.remote(resources={"red": 1})
    class KV:
        def __init__(self):
            self.d = {}

        def put(self, k, v):
            self.d[k] = v
            return True

        def get(self, k):
            return self.d.get(k)

    KV.options(name="mnkv").remote()
    h = ray_tpu.get_actor("mnkv")
    assert ray_tpu.get(h.put.remote("a", 1), timeout=60)
    assert ray_tpu.get(h.get.remote("a"), timeout=60) == 1
    ray_tpu.kill(h)


def test_nested_submission_from_node_worker(cluster):
    """A task on a daemon submits a subtask (scheduled anywhere) and gets
    its result — the proxied submit/get path."""
    @ray_tpu.remote(num_cpus=1)
    def inner(x):
        return x + 1

    @ray_tpu.remote(resources={"blue": 1})
    def outer():
        ref = inner.remote(41)
        return where(), ray_tpu.get(ref, timeout=60)

    node, val = ray_tpu.get(outer.remote(), timeout=120)
    assert node == cluster.node_ids[1]
    assert val == 42


def _train_loop_report_host(config):
    from ray_tpu.train import session
    rank = session.get_world_rank()
    # metrics_history only carries rank 0's reports (reference behavior),
    # so every rank records its host on the shared filesystem instead
    with open(os.path.join(config["out"], f"rank{rank}.txt"), "w") as f:
        f.write(where())
    session.report({"host": where(), "rank": rank})


def test_trainer_spans_nodes(cluster, tmp_path):
    """JaxTrainer with STRICT_SPREAD places its worker gang on distinct
    nodes and completes the jax.distributed rendezvous across them — the
    multi-host Train path (worker_group.py setup_distributed seam)."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    trainer = JaxTrainer(
        _train_loop_report_host,
        train_loop_config={"out": str(tmp_path)},
        scaling_config=ScalingConfig(
            num_workers=2,
            resources_per_worker={"CPU": 1},
            placement_strategy="STRICT_SPREAD"),
        run_config=RunConfig(name="span", storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.error is None, result.error
    hosts = {open(os.path.join(tmp_path, f"rank{r}.txt")).read()
             for r in range(2)}
    assert len(hosts) == 2, hosts


class TestNodeFailure:
    """Chaos: SIGKILL a whole daemon (its workers die with it) and assert
    recovery — the NodeKillerActor pattern (test_utils.py:1400)."""

    def test_task_retry_on_node_death(self, ray_session):
        c = Cluster.attach()
        n1 = c.add_node({"CPU": 2, "green": 2})
        n2 = c.add_node({"CPU": 2, "green": 2})

        @ray_tpu.remote(resources={"green": 1}, max_retries=2)
        def slow_ok():
            time.sleep(3.0)
            return where()

        # occupy n1 first by locality of nothing — both fit; pin attempt 1
        # to n1 via soft affinity so the kill hits the running attempt
        ref = slow_ok.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=n1, soft=True)).remote()
        time.sleep(1.0)     # let it start on n1
        c.kill_node(n1)
        host = ray_tpu.get(ref, timeout=120)
        assert host in (n2, "head")
        c.kill_node(n2)

    def test_object_lost_and_copy_promotion(self, ray_session):
        from ray_tpu.exceptions import ObjectLostError
        c = Cluster.attach()
        n1 = c.add_node({"CPU": 2, "purple": 2})

        @ray_tpu.remote(resources={"purple": 1})
        def produce(tag):
            return np.full(BIG, tag, np.uint8)

        @ray_tpu.remote(resources={"purple": 1})
        def put_obj():
            # ray_tpu.put inside a task: the object lives in the node's
            # store with NO lineage (puts are not reconstructable, as in
            # the reference) — losing the node loses it for good
            return ray_tpu.put(np.full(BIG, 4, np.uint8))

        # (a) object pulled to head before the kill survives via promotion
        survivor = produce.remote(3)
        a = ray_tpu.get(survivor, timeout=60)    # head now caches a copy
        # (b) a put object never pulled is lost with the node
        doomed = ray_tpu.get(put_obj.remote(), timeout=60)
        time.sleep(1.0)  # let it finish sealing on the node
        c.kill_node(n1)
        time.sleep(0.5)
        again = ray_tpu.get(survivor, timeout=60)
        assert int(again[0]) == 3 and np.array_equal(a, again)
        with pytest.raises(ObjectLostError):
            ray_tpu.get(doomed, timeout=10)

    def test_object_reconstruction_on_node_death(self, ray_session):
        """The only copy of a task-produced object dies with its node;
        get() still returns it — lineage resubmission re-executes the
        producing task on a surviving node."""
        c = Cluster.attach()
        n1 = c.add_node({"CPU": 2, "silver": 2})

        @ray_tpu.remote(resources={"silver": 1})
        def produce(tag):
            return np.full(BIG, tag, np.uint8), where()

        ref = produce.remote(9)
        ray_tpu.wait([ref], timeout=60)     # sealed on n1, never pulled
        n2 = c.add_node({"CPU": 2, "silver": 2})
        c.kill_node(n1)
        arr, host = ray_tpu.get(ref, timeout=120)
        assert int(arr[0]) == 9 and arr.shape == (BIG,)
        assert host == n2       # re-executed on the surviving node
        c.kill_node(n2)

    def test_reconstruction_chain_feeds_consumer(self, ray_session):
        """A consumer whose dependency is lost mid-flight gets requeued
        (without burning a retry) and completes once the dep is rebuilt."""
        c = Cluster.attach()
        n1 = c.add_node({"CPU": 2, "iron": 2})

        @ray_tpu.remote(resources={"iron": 1})
        def produce():
            return np.full(BIG, 5, np.uint8)

        @ray_tpu.remote(num_cpus=1)
        def consume(arr):
            return int(arr[0]) + len(arr)

        ref = produce.remote()
        ray_tpu.wait([ref], timeout=60)     # sealed on n1 (only iron node)
        n2 = c.add_node({"CPU": 2, "iron": 2})
        c.kill_node(n1)
        time.sleep(1.0)     # let the head observe the death
        out = ray_tpu.get(consume.remote(ref), timeout=120)
        assert out == 5 + BIG
        c.kill_node(n2)

    def test_hard_affinity_to_dead_node_fails_fast(self, ray_session):
        from ray_tpu.exceptions import SchedulingError
        c = Cluster.attach()
        n1 = c.add_node({"CPU": 1, "pink": 1})
        c.kill_node(n1)
        time.sleep(1.0)

        @ray_tpu.remote(num_cpus=1)
        def f():
            return 1

        ref = f.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=n1)).remote()
        with pytest.raises(SchedulingError):
            ray_tpu.get(ref, timeout=30)

    def test_actor_restart_on_node_death(self, ray_session):
        c = Cluster.attach()
        n1 = c.add_node({"CPU": 2, "orange": 2})

        @ray_tpu.remote(num_cpus=1, max_restarts=1, max_task_retries=1)
        class Svc:
            def host(self):
                return where()

        svc = Svc.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=n1, soft=True)).remote()
        assert ray_tpu.get(svc.host.remote(), timeout=60) == n1
        c.kill_node(n1)
        # restarted incarnation lands wherever resources exist (head);
        # max_task_retries lets a call that raced the death be retried
        host = ray_tpu.get(svc.host.remote(), timeout=120)
        assert host == "head"
        ray_tpu.kill(svc)       # it holds a CPU of the shared session
