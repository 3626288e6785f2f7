"""Batch inference (Predictor/BatchPredictor) and tracing spans.

Counterpart of the reference's `train/tests/test_predictor.py`,
`test_batch_predictor.py`, and `tests/test_tracing.py`.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train import BatchPredictor, Checkpoint, JaxPredictor
from ray_tpu.util import tracing


@pytest.fixture
def cluster(ray_session):
    return ray_session


def _linear_apply(params, x):
    return x @ params["w"] + params["b"]


def test_jax_predictor_roundtrip():
    params = {"w": np.ones((4, 2), np.float32),
              "b": np.zeros(2, np.float32)}
    ckpt = Checkpoint.from_dict({"params": params})
    pred = JaxPredictor.from_checkpoint(ckpt, apply_fn=_linear_apply,
                                        input_column="x")
    batch = {"x": np.ones((8, 4), np.float32)}
    out = pred._predict_numpy(batch)
    assert out["predictions"].shape == (8, 2)
    np.testing.assert_allclose(out["predictions"], 4.0)
    # plain-array input path
    out2 = pred.predict(np.ones((3, 4), np.float32))
    np.testing.assert_allclose(out2["predictions"], 4.0)


def test_batch_predictor_over_dataset(cluster):
    from ray_tpu import data as rdata
    params = {"w": np.full((4, 1), 2.0, np.float32),
              "b": np.zeros(1, np.float32)}
    ckpt = Checkpoint.from_dict({"params": params})
    bp = BatchPredictor.from_checkpoint(
        ckpt, JaxPredictor, apply_fn=_linear_apply, input_column="x")
    ds = rdata.from_items(
        [{"x": np.ones(4, np.float32) * i, "id": i} for i in range(32)])
    out = bp.predict(ds, batch_size=8).take_all()
    assert len(out) == 32
    by_id = {int(r["id"]): r for r in out}
    np.testing.assert_allclose(by_id[3]["predictions"], 24.0)
    np.testing.assert_allclose(by_id[0]["predictions"], 0.0)


def test_tracing_spans_nest_and_export():
    tracing.clear_spans()
    tracing.enable_tracing()
    with tracing.span("outer", {"k": "v"}):
        with tracing.span("inner"):
            pass
    spans = tracing.get_spans()
    inner = next(s for s in spans if s["name"] == "inner")
    outer = next(s for s in spans if s["name"] == "outer")
    assert inner["parent_span_id"] == outer["span_id"]
    assert inner["trace_id"] == outer["trace_id"]
    assert outer["end_ns"] > outer["start_ns"]

    events = tracing.spans_to_chrome_trace()
    assert any(e["name"] == "outer" for e in events)


def test_tracing_error_status():
    tracing.clear_spans()
    tracing.enable_tracing()
    with pytest.raises(ValueError):
        with tracing.span("boom"):
            raise ValueError("x")
    s = next(s for s in tracing.get_spans() if s["name"] == "boom")
    assert s["status"] == "ERROR" and "ValueError" in \
        s["attributes"]["exception"]


def test_tracing_inside_tasks(cluster):
    tracing.enable_tracing()

    @ray_tpu.remote
    def traced_work(i):
        from ray_tpu.util import tracing as t
        with t.span("work", {"i": i}):
            return i * 2

    assert ray_tpu.get([traced_work.remote(i) for i in range(3)]) == \
        [0, 2, 4]
