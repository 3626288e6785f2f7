"""DAG API (.bind graphs) and durable workflows.

Counterpart of the reference's `python/ray/dag/tests/` (bind/execute,
InputNode, class nodes, diamond sharing) and `python/ray/workflow/tests/`
(checkpointed steps, resume-after-failure, output retrieval).
"""

import pytest

import ray_tpu
from ray_tpu import workflow
from ray_tpu.dag import InputNode, MultiOutputNode


@pytest.fixture
def cluster(ray_session):
    return ray_session


def test_function_dag_execute(cluster):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    @ray_tpu.remote
    def mul(a, b):
        return a * b

    dag = mul.bind(add.bind(1, 2), add.bind(3, 4))
    assert ray_tpu.get(dag.execute()) == 21


def test_diamond_shared_subtree_runs_once(cluster):
    @ray_tpu.remote
    def source():
        import os
        return os.urandom(8).hex()   # unique per invocation

    @ray_tpu.remote
    def pair(a, b):
        return (a, b)

    s = source.bind()
    a, b = ray_tpu.get(pair.bind(s, s).execute())
    assert a == b   # memoized: one task for the shared node


def test_input_node(cluster):
    @ray_tpu.remote
    def scale(x, k):
        return x * k

    with InputNode() as inp:
        dag = scale.bind(inp, 10)
    assert ray_tpu.get(dag.execute(7)) == 70
    assert ray_tpu.get(dag.execute(3)) == 30


def test_input_attribute_access(cluster):
    @ray_tpu.remote
    def use(a, b):
        return a - b

    with InputNode() as inp:
        dag = use.bind(inp["x"], inp["y"])
    assert ray_tpu.get(dag.execute({"x": 9, "y": 4})) == 5


def test_class_node_and_methods(cluster, kills_its_actors):
    @ray_tpu.remote
    class Accum:
        def __init__(self, start):
            self.v = start

        def add(self, x):
            self.v += x
            return self.v

    node = Accum.bind(100)
    dag = node.add.bind(5)
    assert ray_tpu.get(dag.execute()) == 105


def test_multi_output(cluster):
    @ray_tpu.remote
    def f(i):
        return i * 2

    dag = MultiOutputNode([f.bind(1), f.bind(2), f.bind(3)])
    assert ray_tpu.get(dag.execute()) == [2, 4, 6]


# ---------------------------------------------------------------------------
# workflows
# ---------------------------------------------------------------------------

@pytest.fixture
def wf_store(tmp_path):
    workflow.init(str(tmp_path))
    yield str(tmp_path)


def test_workflow_run_and_replay(cluster, wf_store):
    @ray_tpu.remote
    def step_a():
        import os
        return os.urandom(8).hex()   # unique per actual execution

    @ray_tpu.remote
    def step_b(x):
        return "out:" + x

    dag = step_b.bind(step_a.bind())
    first = workflow.run(dag, workflow_id="w1")
    assert workflow.get_status("w1") == "SUCCESSFUL"
    assert workflow.get_output("w1") == first
    # re-running replays from storage: same value => steps NOT re-executed
    assert workflow.run(dag, workflow_id="w1") == first


def test_workflow_resume_after_failure(cluster, wf_store):
    @ray_tpu.remote
    def first():
        return 1

    @ray_tpu.remote
    def flaky(x, fail_marker):
        import os
        if os.path.exists(fail_marker):
            raise RuntimeError("injected failure")
        return x + 100

    marker = wf_store + "/fail_on"
    open(marker, "w").close()
    dag = flaky.bind(first.bind(), marker)
    with pytest.raises(RuntimeError):
        workflow.run(dag, workflow_id="w2")
    assert workflow.get_status("w2") == "FAILED"

    # clear the fault; resume executes only the failed step (step 'first'
    # replays from its checkpoint)
    import os
    os.remove(marker)
    assert workflow.resume("w2") == 101
    assert workflow.get_status("w2") == "SUCCESSFUL"


def test_workflow_with_input(cluster, wf_store):
    @ray_tpu.remote
    def double(x):
        return x * 2

    with InputNode() as inp:
        dag = double.bind(inp)
    assert workflow.run(dag, workflow_id="w3", dag_input=21) == 42


def test_workflow_parallel_siblings(cluster, wf_store):
    """Independent branches are submitted together, not serialized: the
    execution windows of sibling steps must overlap (timestamp evidence,
    not wall-clock bounds, so cold worker spawn can't flake the test)."""
    import time as _time

    @ray_tpu.remote
    def slow(i):
        start = _time.time()
        _time.sleep(0.5)
        return (start, _time.time())

    @ray_tpu.remote
    def gather(a, b, c):
        return [a, b, c]

    dag = gather.bind(slow.bind(1), slow.bind(2), slow.bind(3))
    spans = workflow.run(dag, workflow_id="wpar")
    overlaps = sum(
        1 for i in range(3) for j in range(i + 1, 3)
        if spans[i][0] < spans[j][1] and spans[j][0] < spans[i][1])
    assert overlaps >= 1, f"no sibling steps overlapped: {spans}"


def test_workflow_input_mismatch_rejected(cluster, wf_store):
    @ray_tpu.remote
    def fail_step(x):
        raise RuntimeError("fail")

    with InputNode() as inp:
        dag = fail_step.bind(inp)
    with pytest.raises(RuntimeError):
        workflow.run(dag, workflow_id="wmix", dag_input=1)
    # retry with a DIFFERENT input under the same id must be rejected
    with pytest.raises(ValueError, match="different"):
        workflow.run(dag, workflow_id="wmix", dag_input=2)


def test_workflow_stale_running_is_resumable(cluster, wf_store):
    @ray_tpu.remote
    def one():
        return 1

    workflow.run(one.bind(), workflow_id="wstale")
    # simulate a kill -9 mid-run: status RUNNING with a dead runner pid
    import json as _json
    meta_path = wf_store + "/wstale/meta.json"
    meta = _json.loads(open(meta_path).read())
    meta["status"] = "RUNNING"
    meta["pid"] = 2 ** 22 + 12345   # beyond pid_max on this box
    open(meta_path, "w").write(_json.dumps(meta))
    assert workflow.get_status("wstale") == "RESUMABLE"
    assert workflow.resume("wstale") == 1
    assert workflow.get_status("wstale") == "SUCCESSFUL"


def test_workflow_list_and_delete(cluster, wf_store):
    @ray_tpu.remote
    def one():
        return 1

    workflow.run(one.bind(), workflow_id="wlist")
    ids = [w.workflow_id for w in workflow.list_all()]
    assert "wlist" in ids
    workflow.delete("wlist")
    assert "wlist" not in [w.workflow_id for w in workflow.list_all()]


# ---------------------------------------------------------------------------
# dynamic workflows (continuations), content-based identity, management
# (reference: workflow_executor.py:32 continuations; api.cancel/resume_all)
# ---------------------------------------------------------------------------


def test_workflow_recursive_continuation(cluster, wf_store):
    """A step that returns a DAG recurses durably: factorial via
    continuation, checkpointed at every level."""
    @ray_tpu.remote
    def fact(n, acc=1):
        if n <= 1:
            return acc
        return fact.bind(n - 1, acc * n)

    assert workflow.run(fact.bind(5), workflow_id="wrec") == 120
    assert workflow.get_status("wrec") == "SUCCESSFUL"
    # every recursion level left a namespaced checkpoint
    import os
    steps = os.listdir(os.path.join(wf_store, "wrec", "steps"))
    assert sum(1 for s in steps if "fact" in s) >= 5, steps


def test_workflow_continuation_resume(cluster, wf_store, tmp_path):
    """Crash mid-continuation: completed sub-steps replay from their
    namespaced checkpoints on resume."""
    marker = tmp_path / "boom"
    count = tmp_path / "count"

    @ray_tpu.remote
    def chain(n):
        with open(count, "a") as f:
            f.write("x")
        if n == 2 and marker.exists():
            raise RuntimeError("boom")
        if n <= 0:
            return "done"
        return chain.bind(n - 1)

    marker.write_text("1")
    with pytest.raises(Exception):
        workflow.run(chain.bind(4), workflow_id="wcr")
    assert workflow.get_status("wcr") == "FAILED"
    ran_before = len(count.read_text())
    marker.unlink()
    assert workflow.resume("wcr") == "done"
    # levels 4 and 3 replayed from checkpoints; only the failed level
    # (2) and deeper re-ran
    ran_after = len(count.read_text()) - ran_before
    assert ran_after == 3, (ran_before, ran_after)


def test_workflow_edit_invalidates_step(cluster, wf_store, tmp_path):
    """Content-based identity: editing a step's CODE re-executes it on
    the next run instead of silently replaying the stale checkpoint
    (the positional-id failure mode)."""
    a_runs = tmp_path / "a_runs"
    b_runs = tmp_path / "b_runs"

    @ray_tpu.remote
    def upstream():
        with open(a_runs, "a") as f:
            f.write("x")
        return 10

    @ray_tpu.remote
    def downstream(x):
        with open(b_runs, "a") as f:
            f.write("x")
        return x + 1

    assert workflow.run(downstream.bind(upstream.bind()),
                        workflow_id="wedit") == 11
    assert (len(a_runs.read_text()), len(b_runs.read_text())) == (1, 1)

    # unchanged DAG: pure replay, nothing re-executes
    assert workflow.run(downstream.bind(upstream.bind()),
                        workflow_id="wedit") == 11
    assert (len(a_runs.read_text()), len(b_runs.read_text())) == (1, 1)

    # EDIT downstream's code: it (and only it) must re-execute
    @ray_tpu.remote
    def downstream(x):  # noqa: F811
        with open(b_runs, "a") as f:
            f.write("x")
        return x + 2

    assert workflow.run(downstream.bind(upstream.bind()),
                        workflow_id="wedit") == 12
    assert (len(a_runs.read_text()), len(b_runs.read_text())) == (1, 2)

    # EDIT upstream's code: upstream re-runs AND downstream's identity
    # changes with its input lineage, so both re-execute
    @ray_tpu.remote
    def upstream():  # noqa: F811
        with open(a_runs, "a") as f:
            f.write("x")
        return 20
    assert workflow.run(downstream.bind(upstream.bind()),
                        workflow_id="wedit") == 22
    assert (len(a_runs.read_text()), len(b_runs.read_text())) == (2, 3)


def test_workflow_cancel_and_resume_all(cluster, wf_store, tmp_path):
    """cancel() stops the run at a step boundary keeping checkpoints;
    resume_all() picks up every non-successful workflow."""
    import threading
    import time as _time

    @ray_tpu.remote
    def slow(i):
        import time as _t
        _t.sleep(0.5)
        return i

    @ray_tpu.remote
    def combine(a, b):
        return a + b

    # cancel from the driver while steps are in flight; the executor
    # observes it at its next step boundary
    canceller = threading.Timer(0.2, workflow.cancel, args=("wcancel",))
    dag = combine.bind(slow.bind(1), slow.bind(2))
    canceller.start()
    try:
        with pytest.raises(workflow.WorkflowCancelledError):
            workflow.run(dag, workflow_id="wcancel")
    finally:
        canceller.join()
    assert workflow.get_status("wcancel") == "CANCELED"

    out = workflow.resume_all()
    assert out.get("wcancel") == 3
    assert workflow.get_status("wcancel") == "SUCCESSFUL"
