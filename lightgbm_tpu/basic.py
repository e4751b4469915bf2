"""User-facing ``Dataset`` and ``Booster``.

API-shaped after the reference's Python package
(reference: python-package/lightgbm/basic.py — ``Dataset`` lazy
construction at :1742, ``Booster`` at :2983, ``update`` at :3437). Where
the reference binds a C core through ctypes, this package's core is the
JAX/XLA boosting layer, so these classes adapt parameters and NumPy/pandas
inputs and delegate to :mod:`lightgbm_tpu.boosting`.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .boosting import create_boosting
from .boosting.gbdt import GBDT
from .config import Config
from .io.dataset import BinnedDataset
from .metric import create_metric, resolve_metric_names
from .utils import log

_ArrayLike = Union[np.ndarray, Sequence]


class LightGBMError(Exception):
    pass


def _to_2d_float(data) -> np.ndarray:
    if hasattr(data, "toarray"):  # scipy sparse (csr/csc/coo)
        data = data.toarray()
    elif hasattr(data, "values"):  # pandas
        data = data.values
    arr = np.asarray(data)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if arr.dtype not in (np.float32, np.float64):
        arr = arr.astype(np.float64)
    return arr


class Dataset:
    """Lazy-constructed training data (reference: basic.py ``Dataset``;
    construction deferred to first use like ``construct`` at
    basic.py:2114)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._handle: Optional[BinnedDataset] = None
        self.used_indices: Optional[np.ndarray] = None

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        if self._handle is not None:
            return self
        if self.reference is not None:
            self.reference.construct()
        config = Config.from_params(self.params)
        reference = (self.reference._handle
                     if self.reference is not None else None)
        # a validation set of a linear-tree training set is scored on its
        # raw values too
        keep_raw = bool(config.linear_tree) or (
            reference is not None and reference.raw_data is not None)
        if hasattr(self.data, "tocsc") and not keep_raw:
            # scipy sparse stays sparse until binning (per-column pass +
            # EFB in BinnedDataset.from_matrix); no densification
            data = self.data
        else:
            data = _to_2d_float(self.data)
        feature_names = None
        if isinstance(self.feature_name, (list, tuple)):
            feature_names = list(self.feature_name)
        elif hasattr(self.data, "columns"):
            feature_names = [str(c) for c in self.data.columns]
        cat = self.categorical_feature
        if cat == "auto":
            cat = None
        self._handle = BinnedDataset.from_matrix(
            data, config, label=self.label, weights=self.weight,
            group=self.group, init_score=self.init_score,
            feature_names=feature_names, categorical_feature=cat,
            reference=reference, keep_raw_data=keep_raw)
        if self.free_raw_data:
            self.data = None
        return self

    @property
    def handle(self) -> BinnedDataset:
        self.construct()
        return self._handle

    # ------------------------------------------------------------------
    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._handle is not None and label is not None:
            self._handle.metadata.set_label(label)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._handle is not None:
            self._handle.metadata.set_weights(weight)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._handle is not None:
            self._handle.metadata.set_group(group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._handle is not None:
            self._handle.metadata.set_init_score(init_score)
        return self

    def get_label(self):
        if self._handle is not None:
            return self._handle.metadata.label
        return self.label

    def get_weight(self):
        if self._handle is not None:
            return self._handle.metadata.weights
        return self.weight

    def get_group(self):
        if self._handle is not None and \
                self._handle.metadata.query_boundaries is not None:
            qb = self._handle.metadata.query_boundaries
            return np.diff(qb)
        return self.group

    def num_data(self) -> int:
        return self.handle.num_data

    def num_feature(self) -> int:
        return self.handle.num_total_features

    def get_feature_name(self) -> List[str]:
        return list(self.handle.feature_names)

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        """reference: Dataset.create_valid (basic.py)."""
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params)

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row subset sharing this dataset's bin mappers
        (reference: Dataset.subset, basic.py)."""
        self.construct()
        idx = np.asarray(used_indices, dtype=np.int64)
        sub = Dataset.__new__(Dataset)
        sub.params = dict(params or self.params)
        sub.reference = self
        sub.free_raw_data = True
        sub.data = None
        sub.label = None
        sub.weight = None
        sub.group = None
        sub.init_score = None
        sub.feature_name = self.feature_name
        sub.categorical_feature = self.categorical_feature
        sub.used_indices = idx
        import copy
        h = BinnedDataset()
        src = self._handle
        h.bins = src.bins[idx]  # row subset keeps the bundle layout
        h.bundle = src.bundle
        h.bin_mappers = src.bin_mappers
        h.used_feature_map = src.used_feature_map
        h.num_total_features = src.num_total_features
        h.feature_names = src.feature_names
        h.num_bin_per_feature = src.num_bin_per_feature
        h.max_num_bin = src.max_num_bin
        h.monotone_constraints = src.monotone_constraints
        h.feature_penalty = src.feature_penalty
        if src.raw_data is not None:
            h.raw_data = src.raw_data[idx]
        from .io.dataset import Metadata
        md = Metadata(len(idx))
        md.set_label(np.asarray(src.metadata.label)[idx])
        if src.metadata.weights is not None:
            md.set_weights(np.asarray(src.metadata.weights)[idx])
        if src.metadata.init_score is not None:
            isc = np.asarray(src.metadata.init_score).reshape(
                -1, src.metadata.num_data)
            md.set_init_score(isc[:, idx].reshape(-1))
        if src.metadata.query_boundaries is not None:
            # rebuild group sizes from the subset rows' query ids (cv's
            # group-aware folds keep queries whole, so runs of equal ids
            # reconstruct the original groups)
            qb = np.asarray(src.metadata.query_boundaries)
            qid = np.searchsorted(qb, idx, side="right") - 1
            change = np.concatenate([[True], qid[1:] != qid[:-1]])
            starts = np.flatnonzero(change)
            sizes = np.diff(np.concatenate([starts, [len(idx)]]))
            md.set_group(sizes)
        h.metadata = md
        sub._handle = h
        return sub


class Booster:
    """reference: basic.py ``Booster`` (:2983)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        self.params = dict(params or {})
        self.config = Config.from_params(self.params)
        self._train_set = train_set
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._valid_names: List[str] = []
        if train_set is not None:
            if train_set._handle is None:
                # dataset-level knobs (monotone_constraints, max_bin,
                # categorical_feature, ...) passed at the Booster level
                # must reach construction, same precedence as
                # engine.train: the dataset's own params win (reference:
                # Booster::Booster passes the params string into
                # Dataset construction, c_api.cpp)
                train_set.params = dict(self.params,
                                        **(train_set.params or {}))
            train_set.construct()
            self.inner: GBDT = create_boosting(self.config,
                                               train_set.handle)
        elif model_file is not None:
            with open(model_file) as f:
                s = f.read()
            self.inner = create_boosting(self.config)
            self.inner.load_model_from_string(s)
            self.best_iteration = -1
        elif model_str is not None:
            self.inner = create_boosting(self.config)
            self.inner.load_model_from_string(model_str)
        else:
            raise LightGBMError(
                "Booster needs train_set, model_file or model_str")

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct()
        self.inner.add_valid_data(data.handle)
        self._valid_names.append(name)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration (reference: basic.py:3437; custom fobj
        path __boost at :3508). Returns True when training should stop."""
        if fobj is not None:
            label = self.inner.train_data.metadata.label
            grad, hess = fobj(np.asarray(self.inner.train_score).squeeze(),
                              self._train_set)
            return self.inner.train_one_iter(np.asarray(grad),
                                             np.asarray(hess))
        return self.inner.train_one_iter()

    def rollback_one_iter(self) -> "Booster":
        self.inner.rollback_one_iter()
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """Update training parameters between iterations (reference:
        Booster.reset_parameter → LGBM_BoosterResetParameter →
        GBDT::ResetConfig). Supports the per-iteration schedulable
        subset (learning_rate, bagging, regularization...)."""
        import dataclasses
        self.params.update(params)
        cfg = Config.from_params(self.params)
        self.config = cfg
        inner = self.inner
        inner.config = cfg
        inner.shrinkage_rate = float(cfg.learning_rate)
        if getattr(inner, "learner", None) is not None:
            inner.learner.config = cfg
            from .ops_refresh import refresh_learner_params
            refresh_learner_params(inner.learner, cfg)
        if getattr(inner, "sample_strategy", None) is not None:
            # strategies cache config-derived draw state (fractions,
            # freq, GOSS warm-up); refresh re-derives it so scheduled
            # bagging params keep their pre-refactor live semantics
            inner.sample_strategy.refresh_config(cfg)
        return self

    @property
    def current_iteration(self) -> int:
        return self.inner.current_iteration

    def num_trees(self) -> int:
        return len(self.inner.models)

    def num_model_per_iteration(self) -> int:
        return self.inner.num_tree_per_iteration

    # ------------------------------------------------------------------
    def eval_train(self, feval=None) -> List[Tuple]:
        return self._eval(None, "training", feval)

    def eval_valid(self, feval=None) -> List[Tuple]:
        out = []
        for i in range(len(self.inner.valid_data)):
            name = (self._valid_names[i] if i < len(self._valid_names)
                    else "valid_%d" % i)
            out.extend(self._eval(i, name, feval))
        return out

    def _eval(self, valid_idx: Optional[int], name: str,
              feval=None) -> List[Tuple]:
        # one eval pass = one gbdt::eval_metrics scope + one `eval`
        # event, via the shared instrumentation point in boosting/gbdt.py
        from .boosting.gbdt import run_instrumented_eval
        self.inner._flush_valid_pending()  # eval-hoisting deferrals
        out = run_instrumented_eval(
            self.inner.iter,
            lambda: self._eval_inner(valid_idx, name, feval))
        self.inner.flush_linear_counts()
        return out

    def _eval_inner(self, valid_idx: Optional[int], name: str,
                    feval=None) -> List[Tuple]:
        from .boosting.gbdt import compute_metrics, fetch_scores
        inner = self.inner
        if valid_idx is None:
            score = fetch_scores(inner.train_score)
            metrics = inner.train_metrics
            if not metrics:
                # build lazily so eval_train works without
                # is_provide_training_metric
                metrics = []
                for mname in resolve_metric_names(inner.config,
                                                  inner.config.objective):
                    m = create_metric(mname, inner.config)
                    if m is not None:
                        m.init(inner.train_data.metadata, inner.num_data)
                        metrics.append(m)
                inner.train_metrics = metrics
            label_holder = inner.train_data
        else:
            vd = inner.valid_data[valid_idx]
            score = vd.scores
            metrics = vd.metrics
            label_holder = vd.dataset
        sq = score[:, 0] if inner.num_tree_per_iteration == 1 else score
        out = compute_metrics(name, metrics, sq, inner.objective)
        if feval is not None:
            for fe in (feval if isinstance(feval, (list, tuple))
                       else [feval]):
                ds = _FevalDataset(label_holder)
                res = fe(sq if inner.num_tree_per_iteration == 1
                         else score, ds)
                if isinstance(res, tuple):
                    res = [res]
                for mname, v, is_higher in res:
                    out.append((name, mname, v, is_higher))
        return out

    # ------------------------------------------------------------------
    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, **kwargs) -> np.ndarray:
        X = _to_2d_float(data)
        # predict_disable_shape_check (config.h:805): by default a
        # feature-count mismatch is an error, not a silent misprediction
        n_feat = self.inner.max_feature_idx + 1
        if (X.ndim == 2 and X.shape[1] != n_feat
                and not bool(kwargs.get(
                    "predict_disable_shape_check",
                    self.config.predict_disable_shape_check))):
            raise ValueError(
                "The number of features in data (%d) is not the same as "
                "it was in training data (%d). You can set "
                "predict_disable_shape_check=true to discard this "
                "error, but please be aware what you are doing."
                % (X.shape[1], n_feat))
        ni = -1 if num_iteration is None else int(num_iteration)
        if ni <= 0 and self.best_iteration > 0:
            ni = self.best_iteration
        if pred_leaf:
            return self.inner.predict_leaf_index(X, start_iteration, ni)
        if pred_contrib:
            return self.inner.predict_contrib(X, start_iteration, ni)
        out = self._predict_stacked(X, start_iteration, ni, raw_score,
                                    kwargs)
        if out is not None:
            return out
        return self.inner.predict(X, raw_score=raw_score,
                                  start_iteration=start_iteration,
                                  num_iteration=ni)

    # batches below this ride the host walk — a device dispatch (plus a
    # possible first-bucket compile) only pays off on real batches
    _kDeviceMinRows = 256

    @staticmethod
    def _host_walk_warning(reason: str) -> None:
        """A FORCED device predict that must decline emits an assertable
        ``perf_warning`` event (never silent — the round-5 lesson): the
        ISSUE 11 contract is that linear-leaf, EFB-bundled, and f64
        batches all take the device fast path, so any remaining host
        walk under ``predict_on_device=True`` is an exception worth
        surfacing."""
        from .obs import events as obs_events
        from .utils import log
        log.warning("predict_on_device declined to the host walk: %s"
                    % reason)
        obs_events.emit("perf_warning", component="serve.host_walk",
                        message=reason)

    def _predict_stacked(self, X: np.ndarray, start_iteration: int,
                         num_iteration: int, raw_score: bool,
                         kwargs: Dict) -> Optional[np.ndarray]:
        """Fast path: one device dispatch through serve.StackedForest
        (bucketed compile cache kept across calls). Linear-leaf models
        pack their per-leaf fits into the stacked arrays and f64 rows
        ride the double-double encoding, so both keep the bit-exact
        device path. Returns None — fall back to the host walk — only
        when the stacked path cannot reproduce the host result
        BIT-FOR-BIT: pred_early_stop, feature-count mismatch, or mixed
        per-feature missing types (text-loaded edge case); a FORCED
        decline emits a ``perf_warning`` event."""
        forced = kwargs.get("predict_on_device")
        if forced is not None and not forced:
            return None
        if forced is None:
            # auto mode: only worth it where a device dispatch beats the
            # vectorized host walk — real batches on an accelerator. On
            # CPU backends the walk is the same XLA gathers plus compile
            # overhead, so auto stays off (kwarg True still forces).
            if (not self.config.predict_on_device
                    or X.shape[0] < self._kDeviceMinRows):
                return None
            import jax
            if jax.default_backend() == "cpu":
                return None
        if self.config.pred_early_stop or kwargs.get("pred_early_stop"):
            if forced:
                self._host_walk_warning(
                    "pred_early_stop is a host-loop contract")
            return None
        inner = self.inner
        models = inner._used_models(start_iteration, num_iteration)
        if not models:
            return None
        if X.shape[1] != inner.max_feature_idx + 1:
            if forced:
                self._host_walk_warning(
                    "feature count %d != model's %d"
                    % (X.shape[1], inner.max_feature_idx + 1))
            return None
        # cache the packed forest until the model slice changes. Object
        # identity is not enough: refit and DART normalization mutate
        # leaf values IN PLACE, so the key fingerprints the leaf
        # contents (O(total leaves), ~1ms at 500x255 — cheap next to a
        # >=256-row predict)
        import hashlib
        fp = hashlib.blake2b(digest_size=8)
        for t in models:
            fp.update(t.leaf_value[:t.num_leaves].tobytes())
            if t.is_linear:
                fp.update(t.leaf_const[:t.num_leaves].tobytes())
        key = (len(inner.models), fp.hexdigest(),
               start_iteration, num_iteration)
        cached = getattr(self, "_stacked_cache", None)
        if cached is None or cached[0] != key:
            from .serve import BucketedPredictor, StackedForest
            try:
                forest = StackedForest.from_gbdt(inner, start_iteration,
                                                 num_iteration)
            except ValueError as e:
                if forced:
                    self._host_walk_warning(
                        "model cannot stack: %s" % e)
                self._stacked_cache = (key, None)
                return None
            self._stacked_cache = (key, BucketedPredictor(
                forest, model_version=key))
            cached = self._stacked_cache
        predictor = cached[1]
        if predictor is None:
            if forced:
                self._host_walk_warning("model cannot stack (cached)")
            return None
        kind = ("raw" if raw_score or inner.objective is None
                else "value")
        return predictor.predict(X, output_kind=kind)

    # ------------------------------------------------------------------
    def refit(self, data, label, weight=None,
              decay_rate: Optional[float] = None) -> "Booster":
        """Recompute every leaf value from ``(data, label)`` over the
        FROZEN tree structure (reference: Booster.refit →
        GBDT::RefitTree) — the refresh loop's incremental update. Runs
        as a pure device replay: one stacked-forest leaf walk plus
        per-leaf ``segment_sum`` gradient statistics
        (``boosting/refit.py:refit_model_device``), no host tree walk.
        Mutates this booster in place and returns it; the packed
        predict cache re-keys itself off the leaf-value fingerprint.

        ``decay_rate`` defaults to ``config.refit_decay_rate``:
        ``new = decay*old + (1-decay)*shrinkage*optimum`` per leaf.
        """
        from .boosting.refit import refit_model_device
        X = np.asarray(data, dtype=np.float64)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        y = np.asarray(label, dtype=np.float64)
        if X.shape[0] != len(y):
            raise ValueError("refit data has %d rows but %d labels"
                             % (X.shape[0], len(y)))
        if decay_rate is None:
            decay_rate = float(self.config.refit_decay_rate)
        inner = self.inner
        # refit freezes structure and the stacked walk reads ONLY
        # structure, so one packed forest serves every refit cycle
        # until training appends trees (leaf values ride separately)
        key = (len(inner.models),
               sum(t.num_leaves for t in inner.models))
        cached = getattr(self, "_refit_forest", None)
        if cached is None or cached[0] != key:
            from .serve import StackedForest
            cached = (key, StackedForest.from_gbdt(inner))
            self._refit_forest = cached
        refit_model_device(inner, X, y, weight=weight,
                           decay_rate=decay_rate, forest=cached[1])
        return self

    # ------------------------------------------------------------------
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        ni = self._resolve_num_iteration(num_iteration)
        self.inner.save_model(filename, start_iteration, ni)
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        ni = self._resolve_num_iteration(num_iteration)
        return self.inner.save_model_to_string(start_iteration, ni)

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> dict:
        """Model as a JSON-serializable dict (reference:
        Booster.dump_model → LGBM_BoosterDumpModel → GBDT::DumpModel)."""
        ni = self._resolve_num_iteration(num_iteration)
        return self.inner.dump_model(start_iteration, ni, importance_type)

    def _resolve_num_iteration(self, num_iteration) -> int:
        if num_iteration is None:
            return self.best_iteration if self.best_iteration > 0 else -1
        return int(num_iteration)

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        ni = -1 if iteration is None else iteration
        return self.inner.feature_importance(importance_type, ni)

    def feature_name(self) -> List[str]:
        return list(self.inner.feature_names)

    def num_feature(self) -> int:
        return self.inner.max_feature_idx + 1

    # pickle via model string round-trip (reference: basic.py __getstate__)
    def __getstate__(self):
        state = self.__dict__.copy()
        state["_model_str"] = self.model_to_string(num_iteration=-1)
        state.pop("inner", None)
        state.pop("_train_set", None)
        state.pop("_stacked_cache", None)  # device arrays don't pickle
        state.pop("_refit_forest", None)
        return state

    def __setstate__(self, state):
        model_str = state.pop("_model_str", None)
        self.__dict__.update(state)
        self._train_set = None
        if model_str is not None:
            self.inner = create_boosting(self.config)
            self.inner.load_model_from_string(model_str)


class _FevalDataset:
    """Duck-typed Dataset passed to custom fevals (exposes get_label /
    get_weight / get_group like the reference's Dataset)."""

    def __init__(self, binned: BinnedDataset):
        self._b = binned

    def get_label(self):
        return np.asarray(self._b.metadata.label)

    def get_weight(self):
        w = self._b.metadata.weights
        return None if w is None else np.asarray(w)

    def get_group(self):
        qb = self._b.metadata.query_boundaries
        return None if qb is None else np.diff(qb)
