"""Gradient-boosted decision trees over the worker-group spine.

Counterpart of the reference's `train/xgboost/xgboost_trainer.py` and
`train/lightgbm/lightgbm_trainer.py`: distributed boosting where each
worker holds a data shard and per-node gradient histograms are
allreduced so every worker grows the IDENTICAL tree (exactly rabit's
histogram-sync scheme, minus rabit — the rendezvous is this framework's
own collective group).

Three trainers:

- `GBDTTrainer` — the native implementation (`_HistGBDT`, pure numpy):
  histogram splits, logistic or squared-error loss, shrinkage,
  lambda-regularized leaf weights. Deterministic: an N-worker fit
  produces bit-identical trees to a single-process fit on the
  concatenated data, which the tests assert. This is the path that
  works on a bare image.
- `XGBoostTrainer` / `LightGBMTrainer` — thin adapters that fit the
  real libraries when installed (single-node multi-thread v1; their
  C-level distributed modes need their own comm setup) and raise a
  clear ImportError otherwise. They share the dataset/session/
  checkpoint plumbing with GBDTTrainer.
"""

from __future__ import annotations

import numpy as np

from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.trainer import JaxTrainer, Result


# ---------------------------------------------------------------------------
# native histogram GBDT
# ---------------------------------------------------------------------------

class _Tree:
    __slots__ = ("feature", "threshold", "left", "right", "value")

    def __init__(self):
        # arrays indexed by node id; leaves have feature == -1
        self.feature: list = []
        self.threshold: list = []
        self.left: list = []
        self.right: list = []
        self.value: list = []

    def add_node(self):
        self.feature.append(-1)
        self.threshold.append(0.0)
        self.left.append(-1)
        self.right.append(-1)
        self.value.append(0.0)
        return len(self.feature) - 1

    def predict(self, X: np.ndarray) -> np.ndarray:
        out = np.zeros(len(X))
        feature = np.asarray(self.feature)
        threshold = np.asarray(self.threshold)
        left = np.asarray(self.left)
        right = np.asarray(self.right)
        value = np.asarray(self.value)
        node = np.zeros(len(X), np.int64)
        # depth-bounded trees: iterate until every row is at a leaf
        for _ in range(64):
            f = feature[node]
            live = f >= 0
            if not live.any():
                break
            go_left = np.where(
                live, X[np.arange(len(X)), np.maximum(f, 0)]
                <= threshold[node], False)
            node = np.where(live,
                            np.where(go_left, left[node], right[node]),
                            node)
        return value[node]


class _HistGBDT:
    """Histogram gradient boosting with a pluggable histogram allreduce.

    All split decisions are taken on ALLREDUCED (grad, hess) histograms,
    so every rank grows the same tree from different shards — the core
    invariant of distributed xgboost (`approx`/`hist` tree method)."""

    def __init__(self, objective: str = "squared_error",
                 n_estimators: int = 50, max_depth: int = 3,
                 learning_rate: float = 0.3, n_bins: int = 64,
                 reg_lambda: float = 1.0, min_child_weight: float = 1e-3):
        self.objective = objective
        self.n_estimators = n_estimators
        self.max_depth = max_depth
        self.learning_rate = learning_rate
        self.n_bins = n_bins
        self.reg_lambda = reg_lambda
        self.min_child_weight = min_child_weight
        self.trees: list[_Tree] = []
        self.base_score = 0.0
        self.bin_edges: np.ndarray | None = None

    # -- loss ----------------------------------------------------------

    def _grad_hess(self, y, pred):
        if self.objective == "binary:logistic":
            p = 1.0 / (1.0 + np.exp(-pred))
            return p - y, np.maximum(p * (1.0 - p), 1e-12)
        return pred - y, np.ones_like(y)          # squared error

    # -- fitting -------------------------------------------------------

    def fit(self, X: np.ndarray, y: np.ndarray, allreduce=None,
            eval_cb=None):
        """`allreduce(arr) -> arr` sums float64 arrays across ranks
        (None = single process). `eval_cb(round, model)` runs after each
        boosting round (the session.report seam)."""
        X = np.asarray(X, np.float64)
        y = np.asarray(y, np.float64)
        # `allreduce((arr, op))` with op in {"sum", "min", "max"}
        ar = allreduce or (lambda payload: np.asarray(payload[0]))

        # global uniform bins from allreduced min/max (the approximate-
        # quantile sketch of xgboost's approx mode, simplified: uniform
        # bins are deterministic and rank-agnostic, which the
        # multi-worker == single-process parity contract needs)
        local_min = X.min(axis=0) if len(X) else np.full(
            X.shape[1], np.inf)
        local_max = X.max(axis=0) if len(X) else np.full(
            X.shape[1], -np.inf)
        gmin = ar((local_min, "min"))
        gmax = ar((local_max, "max"))
        n_feat = X.shape[1]
        span = np.where(gmax > gmin, gmax - gmin, 1.0)
        self.bin_edges = gmin[None, :] + span[None, :] * (
            np.arange(1, self.n_bins)[:, None] / self.n_bins)
        binned = np.empty_like(X, dtype=np.int32)
        for f in range(n_feat):
            binned[:, f] = np.searchsorted(
                self.bin_edges[:, f], X[:, f], side="right")

        # base score: global mean (sum trick)
        tot = ar((np.asarray([y.sum(), float(len(y))]), "sum"))
        self.base_score = float(tot[0] / max(tot[1], 1.0))
        if self.objective == "binary:logistic":
            p = np.clip(self.base_score, 1e-6, 1 - 1e-6)
            self.base_score = float(np.log(p / (1 - p)))
        pred = np.full(len(y), self.base_score)

        for r in range(self.n_estimators):
            g, h = self._grad_hess(y, pred)
            tree = _Tree()
            root = tree.add_node()
            # node id -> boolean row mask on THIS shard
            frontier = [(root, np.ones(len(y), bool), 0)]
            while frontier:
                node, mask, depth = frontier.pop()
                gh = self._node_hist(binned, g, h, mask, n_feat)
                gh = ar((gh, "sum"))
                gsum, hsum = gh[0].sum(axis=1)[0], gh[1].sum(axis=1)[0]
                leaf_val = -gsum / (hsum + self.reg_lambda)
                tree.value[node] = leaf_val * self.learning_rate
                if depth >= self.max_depth:
                    continue
                feat, thr_bin, gain = self._best_split(gh)
                if feat < 0 or gain <= 1e-12:
                    continue
                tree.feature[node] = feat
                tree.threshold[node] = float(
                    self.bin_edges[thr_bin, feat]
                    if thr_bin < self.n_bins - 1 else np.inf)
                go_left = binned[:, feat] <= thr_bin
                lmask = mask & go_left
                rmask = mask & ~go_left
                tree.left[node] = tree.add_node()
                tree.right[node] = tree.add_node()
                frontier.append((tree.left[node], lmask, depth + 1))
                frontier.append((tree.right[node], rmask, depth + 1))
            self.trees.append(tree)
            pred += tree.predict(np.asarray(X))
            if eval_cb is not None:
                eval_cb(r, self)
        return self

    def _node_hist(self, binned, g, h, mask, n_feat):
        """(2, n_feat, n_bins) grad/hess histogram of this node's rows
        on THIS shard — the only thing that crosses ranks."""
        out = np.zeros((2, n_feat, self.n_bins))
        gm, hm = g[mask], h[mask]
        bm = binned[mask]
        for f in range(n_feat):
            out[0, f] = np.bincount(bm[:, f], weights=gm,
                                    minlength=self.n_bins)
            out[1, f] = np.bincount(bm[:, f], weights=hm,
                                    minlength=self.n_bins)
        return out

    def _best_split(self, gh):
        """xgboost gain over the cumulative histogram, all features at
        once."""
        G, H = gh[0], gh[1]                       # [n_feat, n_bins]
        Gl = np.cumsum(G, axis=1)[:, :-1]         # left of each edge
        Hl = np.cumsum(H, axis=1)[:, :-1]
        Gt, Ht = G.sum(axis=1, keepdims=True), H.sum(axis=1,
                                                     keepdims=True)
        Gr, Hr = Gt - Gl, Ht - Hl
        lam = self.reg_lambda
        gain = (Gl ** 2 / (Hl + lam) + Gr ** 2 / (Hr + lam)
                - Gt ** 2 / (Ht + lam))
        ok = (Hl > self.min_child_weight) & (Hr > self.min_child_weight)
        gain = np.where(ok, gain, -np.inf)
        flat = int(np.argmax(gain))
        feat, thr = divmod(flat, gain.shape[1])
        best = gain[feat, thr]
        if not np.isfinite(best) or best <= 0:
            return -1, -1, 0.0
        return feat, thr, float(best)

    # -- inference -----------------------------------------------------

    def predict_raw(self, X: np.ndarray) -> np.ndarray:
        X = np.asarray(X, np.float64)
        out = np.full(len(X), self.base_score)
        for t in self.trees:
            out += t.predict(X)
        return out

    def predict(self, X: np.ndarray) -> np.ndarray:
        raw = self.predict_raw(X)
        if self.objective == "binary:logistic":
            return (raw > 0).astype(np.int64)
        return raw

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        raw = self.predict_raw(X)
        return 1.0 / (1.0 + np.exp(-raw))


# ---------------------------------------------------------------------------
# trainers over the worker-group spine
# ---------------------------------------------------------------------------

def _rows_to_xy(rows, label_column):
    feats = sorted(k for k in rows[0] if k != label_column)
    y = np.asarray([r[label_column] for r in rows], np.float64)
    X = np.column_stack([
        np.asarray([r[k] for r in rows], np.float64) for k in feats])
    return X, y, feats


def _gbdt_train_loop(config: dict):
    """Runs on every worker: shard in, allreduced histograms, identical
    model out (rank 0 checkpoints it)."""
    from ray_tpu.train import session
    from ray_tpu.util.collective import CollectiveGroup

    rows = session.get_dataset_shard("train").take_all()
    X, y, feats = _rows_to_xy(rows, config["label_column"])
    world = session.get_world_size()
    rank = session.get_world_rank()
    if world > 1:
        group = CollectiveGroup(config["group_name"], world, rank)

        def ar(payload):
            arr, op = payload
            return np.asarray(group.allreduce(np.asarray(arr), op=op))
    else:
        def ar(payload):
            return np.asarray(payload[0])

    model = _HistGBDT(**config["params"])

    def eval_cb(rnd, m):
        if rnd % config.get("report_every", 10) == 0 or \
                rnd == m.n_estimators - 1:
            session.report({"round": rnd})

    model.fit(X, y, allreduce=ar, eval_cb=eval_cb)
    pred = model.predict(X)
    if config["params"].get("objective") == "binary:logistic":
        local = np.asarray([(pred == y).sum(), float(len(y))])
        agg = ar((local, "sum"))
        metric = {"train_accuracy": float(agg[0] / max(agg[1], 1.0))}
    else:
        local = np.asarray([((pred - y) ** 2).sum(), float(len(y))])
        agg = ar((local, "sum"))
        metric = {"train_rmse": float(np.sqrt(agg[0] / max(agg[1], 1.0)))}
    ckpt = None
    if rank == 0:
        ckpt = Checkpoint.from_dict(
            {"model": model, "feature_columns": feats})
    session.report({**metric, "done": True}, checkpoint=ckpt)


class GBDTTrainer(JaxTrainer):
    """Distributed histogram gradient boosting (native backend).

    Usage matches the reference's GBDT trainers::

        trainer = GBDTTrainer(
            label_column="y", params={"objective": "binary:logistic",
                                      "n_estimators": 30, "max_depth": 3},
            datasets={"train": ds},
            scaling_config=ScalingConfig(num_workers=2))
        result = trainer.fit()
        model = result.checkpoint.to_dict()["model"]
    """

    def __init__(self, *, label_column: str, params: dict | None = None,
                 datasets: dict, scaling_config: ScalingConfig | None = None,
                 run_config: RunConfig | None = None):
        import uuid
        cfg = {
            "label_column": label_column,
            "params": dict(params or {}),
            "group_name": f"gbdt_{uuid.uuid4().hex[:8]}",
        }
        super().__init__(
            _gbdt_train_loop, train_loop_config=cfg,
            scaling_config=scaling_config or ScalingConfig(),
            run_config=run_config, datasets=datasets)

    def fit(self):
        from ray_tpu.util.collective import destroy_collective_group
        try:
            return super().fit()
        finally:
            destroy_collective_group(self.config["group_name"])


def _lib_train_loop(config: dict):
    """XGBoost / LightGBM fit on the worker group (v1: each library's
    own threading parallelizes within the worker; rank 0 fits on its
    shard when world > 1 — callers wanting全-data fits use 1 worker)."""
    from ray_tpu.train import session
    lib = config["lib"]
    rows = session.get_dataset_shard("train").take_all()
    X, y, feats = _rows_to_xy(rows, config["label_column"])
    if lib == "xgboost":
        import xgboost as xgb
        dtrain = xgb.DMatrix(X, label=y, feature_names=feats)
        booster = xgb.train(config["params"], dtrain,
                            num_boost_round=config["num_boost_round"])
        blob = booster.save_raw()
    else:
        import lightgbm as lgb
        train_set = lgb.Dataset(X, label=y)
        booster = lgb.train(config["params"], train_set,
                            num_boost_round=config["num_boost_round"])
        blob = booster.model_to_string()
    ckpt = None
    if session.get_world_rank() == 0:
        ckpt = Checkpoint.from_dict(
            {"model_blob": blob, "lib": lib, "feature_columns": feats})
    session.report({"done": True}, checkpoint=ckpt)


class _LibGBDTTrainer(JaxTrainer):
    _lib = ""

    def __init__(self, *, label_column: str, params: dict | None = None,
                 num_boost_round: int = 10, datasets: dict,
                 scaling_config: ScalingConfig | None = None,
                 run_config: RunConfig | None = None):
        import importlib
        try:
            importlib.import_module(self._lib)
        except ImportError as e:
            raise ImportError(
                f"{type(self).__name__} requires the '{self._lib}' "
                f"package, which is not installed in this image; the "
                f"native GBDTTrainer provides distributed boosting "
                f"without it") from e
        cfg = {"label_column": label_column, "params": dict(params or {}),
               "num_boost_round": num_boost_round, "lib": self._lib}
        super().__init__(
            _lib_train_loop, train_loop_config=cfg,
            scaling_config=scaling_config or ScalingConfig(),
            run_config=run_config, datasets=datasets)


class XGBoostTrainer(_LibGBDTTrainer):
    """Reference: `train/xgboost/xgboost_trainer.py`."""
    _lib = "xgboost"


class LightGBMTrainer(_LibGBDTTrainer):
    """Reference: `train/lightgbm/lightgbm_trainer.py`."""
    _lib = "lightgbm"
