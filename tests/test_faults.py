"""Deterministic fault-injection harness (`ray_tpu/util/faults.py`):
seeded plans replay the identical fire sequence, netaddr delay/drop
present exactly like a slow/lossy control channel, and a dropped
control message surfaces as a TYPED timeout at the attach client — not
a hang and not a spurious dead-channel error."""

import os
import pickle
import threading
import time

import pytest

import ray_tpu
from ray_tpu.exceptions import GetTimeoutError
from ray_tpu.util import faults


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _drive(plan, site, n):
    """Install `plan`, hit `site` n times, return the fired log."""
    faults.install(plan)
    for _ in range(n):
        try:
            faults.check(site)
        except faults.FaultInjected:
            pass
    return faults.fired()


def test_seeded_plan_replays_identically():
    def build():
        return (faults.FaultPlan(seed=7)
                .fail("x", p=0.3, times=None)
                .delay("x", delay_s=0.0, at=5, times=2))

    first = _drive(build(), "x", 40)
    assert first, "a p=0.3 spec over 40 visits must fire at least once"
    assert ("x", 5, "delay") in first and ("x", 6, "delay") in first
    # same seed, same plan -> byte-identical fire sequence
    assert _drive(build(), "x", 40) == first
    # a different seed flips some coins
    other = _drive(faults.FaultPlan(seed=8).fail("x", p=0.3, times=None),
                   "x", 40)
    assert [v for (_, v, a) in other if a == "fail"] != \
           [v for (_, v, a) in first if a == "fail"]


def test_count_gated_specs_and_clear():
    plan = faults.FaultPlan().fail("s", at=2, times=2)
    faults.install(plan)
    fired_at = []
    for visit in range(6):
        try:
            faults.check("s")
        except faults.FaultInjected:
            fired_at.append(visit)
    assert fired_at == [2, 3]
    faults.clear()
    assert faults.active() is None
    assert faults.check("s") is None      # no plan: fast no-op


def test_plan_pickles_for_actor_shipping():
    plan = (faults.FaultPlan(seed=3)
            .kill("engine.emit", at=20)
            .drop("netaddr.send", at=1, times=3)
            .delay("engine.tick", delay_s=0.25, p=0.5))
    back = pickle.loads(pickle.dumps(plan))
    assert back.seed == 3
    assert [(s.site, s.action, s.at, s.times, s.p, s.delay_s)
            for s in back.specs] == \
           [(s.site, s.action, s.at, s.times, s.p, s.delay_s)
            for s in plan.specs]


@pytest.fixture
def conn_pair(tmp_path):
    """A netaddr listener/client pair over UDS (accept runs on a side
    thread — `netaddr.client` blocks in the authkey handshake)."""
    from ray_tpu._private import netaddr
    addr = str(tmp_path / "chan.sock")
    lst = netaddr.listener(addr, b"k")
    box = {}

    def accept():
        box["server"] = lst.accept()

    t = threading.Thread(target=accept, daemon=True)
    t.start()
    client = netaddr.client(addr, b"k")
    t.join(timeout=10)
    assert "server" in box
    yield client, box["server"]
    client.close()
    box["server"].close()
    lst.close()


def test_netaddr_drop_loses_exactly_the_planned_message(tmp_path):
    from ray_tpu._private import netaddr
    faults.install(faults.FaultPlan().drop("netaddr.send", at=0))
    addr = str(tmp_path / "chan.sock")
    lst = netaddr.listener(addr, b"k")
    box = {}
    t = threading.Thread(target=lambda: box.update(s=lst.accept()),
                         daemon=True)
    t.start()
    client = netaddr.client(addr, b"k")   # wrapped: plan declares sites
    t.join(timeout=10)
    server = box["s"]
    try:
        client.send("lost")               # visit 0: dropped on the floor
        assert not server.poll(0.3)
        client.send("kept")               # visit 1: passes through
        assert server.poll(5)
        assert server.recv() == "kept"
    finally:
        client.close()
        server.close()
        lst.close()


def test_netaddr_delay_adds_planned_latency(conn_pair):
    client, server = conn_pair
    # the pair was dialed with no plan -> unwrapped; wrap explicitly so
    # the test controls exactly one side
    faults.install(faults.FaultPlan().delay("netaddr.send", delay_s=0.3))
    slow = faults.maybe_wrap_connection(client, "netaddr")
    t0 = time.perf_counter()
    slow.send("late")
    assert time.perf_counter() - t0 >= 0.3    # send blocked by the plan
    assert server.poll(5)
    assert server.recv() == "late"
    assert faults.fired() == [("netaddr.send", 0, "delay")]


def test_dropped_control_message_is_typed_timeout(ray_session):
    """Satellite: a lost control request must surface as GetTimeoutError
    (retryable, typed) at the attach client — not an indefinite hang,
    not ConnectionError (the channel is fine; one message vanished)."""
    from ray_tpu._private.attach import AttachClient
    session_dir = ray_tpu._worker.get_client().node.session_dir
    # visit 0 is RegisterWorker (must survive); visit 1 is the first
    # control request — that one vanishes
    faults.install(faults.FaultPlan().drop("netaddr.send", at=1))
    client = AttachClient(session_dir)
    try:
        with pytest.raises(GetTimeoutError):
            client.control("list_nodes", timeout=2.0)
        assert ("netaddr.send", 1, "drop") in faults.fired()
        faults.clear()
        # channel is still healthy: the next request round-trips
        nodes = client.control("list_nodes", timeout=30.0)
        assert any(n.get("alive") for n in nodes)
    finally:
        faults.clear()
        client.close()


def test_batched_frame_faults_stay_per_logical_message(tmp_path):
    """A coalesced burst rides ONE wire frame, but the fault proxy sits
    OUTSIDE the frame layer: seeded drop decisions hit individual
    logical messages, and the survivors keep FIFO order."""
    from ray_tpu._private import netaddr
    faults.install(faults.FaultPlan(seed=11)
                   .drop("netaddr.send", at=2)
                   .drop("netaddr.send", at=5))
    addr = str(tmp_path / "chan.sock")
    lst = netaddr.listener(addr, b"k")
    box = {}
    t = threading.Thread(target=lambda: box.update(s=lst.accept()),
                         daemon=True)
    t.start()
    client = netaddr.client(addr, b"k")
    t.join(timeout=10)
    server = box["s"]
    bc = client._conn          # the BatchedConnection under the proxy
    try:
        # Hold the wire so the burst queues behind it — the flusher then
        # drains all survivors into a single _Batch frame.
        with bc._wire_lock:
            for i in range(8):
                client.send(i)
        bc.flush(timeout=5.0)
        got = []
        while server.poll(1.0):
            got.append(server.recv())
            if server._in:
                # unpacked siblings from the same wire frame: proof the
                # burst really coalesced
                box["framed"] = True
        assert got == [0, 1, 3, 4, 6, 7]   # visits 2 and 5 vanished
        assert box.get("framed"), "burst did not coalesce into a frame"
        assert [(s, v) for s, v, a in faults.fired() if a == "drop"] \
            == [("netaddr.send", 2), ("netaddr.send", 5)]
    finally:
        client.close()
        server.close()
        lst.close()


def test_two_readers_that_answer_each_other_never_stop_reading(conn_pair):
    """Each end's reader thread answers every message with one of 64 KiB,
    and both ends start with a burst that fills the socket's buffers. A
    reader that wrote to the wire itself would wait for its peer to read
    while the peer, in the mirror state, waits for it (a tier-1 run stood
    so for 270 s in `test_gbdt_trainer_multiworker_parity`: the head's
    reader in `_on_pipelined_submit`, the worker's in its own send). The
    channel hands its reader's sends to the flusher instead."""
    blob, rounds = b"x" * (64 << 10), 60
    got = {}

    def reader(name, conn):
        n = 0
        while n < 2 * rounds:       # the peer's burst and its answers to ours
            conn.recv()
            n += 1
            if n <= rounds:
                conn.send(blob)
        got[name] = n

    ends = dict(zip(("client", "server"), conn_pair))
    threads = [threading.Thread(target=reader, args=end, daemon=True)
               for end in ends.items()]
    for t in threads:
        t.start()
    for conn in ends.values():      # 60 x 64 KiB each way, nobody blocks here
        burst = threading.Thread(
            target=lambda c=conn: [c.send(blob) for _ in range(rounds)],
            daemon=True)
        burst.start()
        threads.append(burst)
    for t in threads:
        t.join(timeout=30)
    assert got == {"client": 2 * rounds, "server": 2 * rounds}
