"""GBDT boosting driver.

TPU-native equivalent of the reference's ``GBDT``
(reference: src/boosting/gbdt.cpp; interface include/LightGBM/boosting.h:27).
Division of labor on TPU: the per-iteration hot path (gradients, sampling,
tree growth, score update) runs on device; the host orchestrates iterations
and keeps the model (list of host ``Tree``s), mirroring the CUDA build where
``boosting_on_gpu_`` keeps gradients/scores device-resident
(reference: src/boosting/gbdt.cpp:102, src/boosting/cuda/cuda_score_updater.*).

Training score update uses the learner's final row→leaf partition — a
device gather of the tree's leaf values — rather than re-walking the tree
(the trick the reference's CUDADataPartition::UpdateTrainScore uses,
src/treelearner/cuda/cuda_data_partition.cu).

Quantized-gradient training (``Config.use_quantized_grad``,
``quant_grad_bits`` ∈ {8, 16}; reference: GBDT's gradient_discretizer_
member, src/treelearner/gradient_discretizer.cpp): each tree's (grad,
hess) rows discretize to signed integers with a per-iteration scale and
stochastic rounding (``ops/quantize.py quantize_gh``) and every
learner accumulates integer histograms (exact, order-invariant, half
the psum bytes on meshes) that the split scan dequantizes once. The
discretization runs inside the learners' gh-staging step
(``CapabilityMixin._quantize_stage``) so the draw happens on the
unpadded row vector — padding-invariant across serial/mesh learners.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..obs import compile as obs_compile
from ..obs import events as obs_events
from ..obs import health as obs_health
from ..obs import trace as obs_trace
from ..obs.registry import registry as obs
from ..io.binning import MissingType
from ..io.dataset import BinnedDataset
from ..metric import Metric, create_metric, resolve_metric_names
from ..models.tree import Tree
from ..objective import ObjectiveFunction, create_objective
from ..treelearner import create_tree_learner
from ..utils import log
from ..utils.scalars import dev_f32, dev_i32
from .sample_strategy import create_sample_strategy

kEpsilon = 1e-15
_K_MIN_SCORE = -np.inf

# Per-iteration score plumbing, jitted so the hot loop performs no
# implicit host-to-device transfers (eager slices / .at updates turn
# their index scalars into device buffers on every call; the
# transfer_guard sanitizer in tests/test_jaxlint.py pins this). The
# class index is a TRACED scalar (utils/scalars.dev_i32), so one
# compile serves every class — a static index would compile per class
# and trip the retrace warning past 32 classes. jax names a program
# after its function and a device trace shows no other name, so each
# body is a function named as it is registered (``gbdt.score_delta``
# runs as ``jit_gbdt_score_delta``).
def gbdt_take_col(m, k):
    return m[:, k]


def gbdt_score_delta(score, leaf_values, leaf_of_row, k):
    return score.at[:, k].add(leaf_values[leaf_of_row])


def gbdt_score_add_col(score, delta, k):
    return score.at[:, k].add(delta)


def gbdt_valid_score_add(score, delta, k):
    # the validation side of the score update, a program of its own name
    return gbdt_score_add_col(score, delta, k)


def gbdt_score_set_col(score, col, k):
    return score.at[:, k].set(col)


_take_col = obs_compile.instrument_jit("gbdt.take_col", gbdt_take_col)
_apply_leaf_delta = obs_compile.instrument_jit(
    "gbdt.score_delta", gbdt_score_delta)
_add_score_col = obs_compile.instrument_jit(
    "gbdt.score_add_col", gbdt_score_add_col)
_add_valid_score_col = obs_compile.instrument_jit(
    "gbdt.valid_score_add", gbdt_valid_score_add)
_set_score_col = obs_compile.instrument_jit(
    "gbdt.score_set_col", gbdt_score_set_col)


def eval_hoist_due(count: int, last_count: int, eval_k: int,
                   final: bool) -> bool:
    """THE eval-hoisting grid predicate (``tpu_eval_iterations=k``),
    shared by the engine's batched + per-iteration loops and the GBDT
    CLI loop so the contract cannot drift between them: evaluation is
    due when the iteration count crossed a multiple of k since the
    last eval (an ABSOLUTE grid — a checkpoint-resumed run evaluates
    at the same iterations as an uninterrupted one), always at the
    final/stopping point, and always with hoisting off (k <= 1)."""
    return (eval_k <= 1 or final
            or (count // eval_k) > (last_count // eval_k))


def run_instrumented_eval(iter_idx: int, compute):
    """THE instrumentation point for metric evaluation: every eval path
    (``Booster._eval`` and the CLI loop's ``GBDT.eval_metrics``) funnels
    through here, so one evaluation pass = exactly one
    ``gbdt::eval_metrics`` stage scope + one ``eval`` event. Previously
    both paths carried their own copy of this wrapper (ROADMAP open
    item: double instrumentation)."""
    with obs.scope("gbdt::eval_metrics"):
        out = compute()
    if out and obs_events.enabled():
        obs_events.emit("eval", iter=iter_idx,
                        results=[{"dataset": ds, "metric": name,
                                  "value": float(v)}
                                 for ds, name, v, _ in out])
    return out


def fetch_scores(scores_dev) -> np.ndarray:
    """Host f64 copy of device scores for the metrics, which evaluate
    on the host. ``gbdt::eval_fetch`` is the wait for the programs
    that still write them (the validation walk of the newest tree) and
    the copy; ``gbdt::eval_compute`` below is host arithmetic during
    which the device idles. Both lie inside ``gbdt::eval_metrics``."""
    with obs.scope("gbdt::eval_fetch"):
        return np.asarray(scores_dev, dtype=np.float64)


def compute_metrics(dataset_name: str, metrics: Sequence[Metric], score,
                    objective) -> List[Tuple[str, str, float, bool]]:
    """(dataset_name, metric_name, value, is_bigger_better) of every
    metric over host scores, under ``gbdt::eval_compute``."""
    with obs.scope("gbdt::eval_compute"):
        return [(dataset_name, name, v, m.factor_to_bigger_better > 0)
                for m in metrics
                for name, v in zip(m.name, m.eval(score, objective))]


def _device_tree_outputs(tree: Tree, bins_dev, dataset: BinnedDataset,
                         bin_meta):
    """``(delta, dtree, trips)``: the device [n] f32 per-row output of
    one tree over the dataset's binned rows via the traversal of
    ops/predict.py, the tree as the device holds it and the hops the walk
    ran for every row (None where the tree was scored all nodes at
    once); a linear tree then takes its leaves' linear values over the
    dataset's raw values on the device (``ops/linear.py``); stumps are a
    constant (``dtree`` and ``trips`` None: no traversal), a zero-valued
    stump has no ``delta`` either. Shared by train-side (DART/rollback)
    and valid-side scoring."""
    from ..ops.predict import (build_device_tree, predict_leaf_on_device,
                               tree_output_on_device)
    if dataset.bundle is not None:
        dtree = build_device_tree(
            tree, bin_meta, max(int(dataset.bundle.num_bundled_bins), 2),
            bundle=dataset.bundle)
    else:
        dtree = build_device_tree(
            tree, bin_meta, max(int(dataset.max_num_bin), 2))
    if dtree is None:  # stump: constant value
        if tree.num_leaves >= 1 and tree.leaf_value[0] != 0.0:
            return jnp.full((dataset.num_data,),
                            np.float32(tree.leaf_value[0])), None, None
        return None, None, None
    raw = dataset.raw_device() if tree.is_linear else None
    if raw is not None:
        from ..ops.linear import tree_output
        leaf, trips = predict_leaf_on_device(bins_dev, dtree)
        return tree_output(raw, leaf, tree), dtree, trips
    delta, trips = tree_output_on_device(bins_dev, dtree)
    return delta, dtree, trips


class ValidData:
    """One validation set: binned rows aligned with the training mappers +
    incrementally maintained scores (reference: GBDT::AddValidDataset,
    gbdt.cpp:182, ScoreUpdater per valid set). Bins and scores live on
    device; per-iteration tree scoring is a vectorized device traversal
    (ops/predict.py), not a host walk — the analogue of the reference's
    CUDA valid-set score updater (src/boosting/cuda/cuda_score_updater.*)."""

    def __init__(self, dataset: BinnedDataset, metrics: List[Metric],
                 num_tree_per_iteration: int):
        self.dataset = dataset
        self.metrics = metrics
        self.bins_dev = jnp.asarray(dataset.bins)
        scores = np.zeros((dataset.num_data, num_tree_per_iteration),
                          dtype=np.float32)
        if dataset.metadata.init_score is not None:
            init = np.asarray(dataset.metadata.init_score, dtype=np.float64)
            scores += init.reshape(num_tree_per_iteration, -1).T
        self.scores_dev = jnp.asarray(scores)
        if dataset.raw_data is not None:
            # a set scored by linear leaves adds outputs placed on the
            # device by the fit (the mesh learner's committed partition)
            # beside a model's first, constant tree's: scores placed
            # there from the start keep the addition one program
            self.scores_dev = jax.device_put(self.scores_dev,
                                             jax.devices()[0])

    @property
    def scores(self) -> np.ndarray:
        """Host f64 snapshot (metrics evaluate on host)."""
        return fetch_scores(self.scores_dev)

    def add_tree(self, tree: Tree, class_id: int, bin_meta,
                 sign: float = 1.0) -> None:
        delta, dtree, trips = _device_tree_outputs(tree, self.bins_dev,
                                                   self.dataset, bin_meta)
        if delta is None:
            return
        if obs.enabled and dtree is not None:
            self._count_scoring(tree, dtree, trips)
            if tree.is_linear:
                # rows whose linear output was taken (ops/linear.py)
                obs.inc("linear/valid_rows", self.dataset.num_data)
        if sign != 1.0:
            delta = delta * np.float32(sign)
        self.scores_dev = _add_valid_score_col(self.scores_dev, delta,
                                               dev_i32(class_id))

    def _count_scoring(self, tree: Tree, dtree, trips) -> None:
        """A tree scored all nodes at once (``trips`` None) counts in
        ``valid/trees_all_nodes``, and its rows times its padded node
        count in ``valid/node_decisions``. A walked tree counts in
        ``valid/trees_walked`` and in the hop counters:
        ``valid/walk_hops_run``: rows times ``trips``, the hops the
        lockstep loop of ``ops/predict.py`` ran for every row, as the
        walk reports them; ``valid/walk_hops_needed``: rows times the
        tree's mean leaf depth weighted by ``leaf_count``, the hops the
        rows need if they fall into the leaves as the training rows
        (those in the bag) did. An estimate: counting these rows' own
        leaves would take a device pass that an untraced run does not
        make."""
        rows = self.dataset.num_data
        if trips is None:
            obs.inc("valid/trees_all_nodes")
            obs.inc("valid/node_decisions", rows * int(dtree.feat.shape[0]))
            return
        obs.inc("valid/trees_walked")
        depth = tree.leaf_depth[:tree.num_leaves]
        weight = np.asarray(tree.leaf_count[:tree.num_leaves],
                            dtype=np.float64)
        mean_depth = np.average(depth, weights=weight) if weight.any() \
            else depth.mean()
        obs.inc("valid/walk_hops_run", rows * trips)
        obs.inc("valid/walk_hops_needed", int(round(rows * mean_depth)))

    def _tree_outputs(self, tree: Tree, bin_meta):
        """Device [n] f32 output of one tree over this valid set."""
        return _device_tree_outputs(tree, self.bins_dev, self.dataset,
                                    bin_meta)[0]

    def add_const(self, val: float, class_id: int) -> None:
        self.scores_dev = self.scores_dev.at[:, class_id].add(
            np.float32(val))

    def multiply(self, factor: float, class_id: int) -> None:
        self.scores_dev = self.scores_dev.at[:, class_id].multiply(
            np.float32(factor))


class GBDT:
    """reference: src/boosting/gbdt.cpp (Init at :52, Train at :229,
    TrainOneIter at :334)."""

    submodel_name = "tree"

    def __init__(self, config: Config, train_data: Optional[BinnedDataset],
                 objective: Optional[ObjectiveFunction] = None):
        self.config = config
        self.train_data = train_data
        self.objective: Optional[ObjectiveFunction] = objective
        self.models: List[Tree] = []
        self.iter = 0
        self.num_init_iteration = 0
        self.best_iteration = -1
        self.shrinkage_rate = float(config.learning_rate)
        self.average_output = False
        self.loaded_parameter = ""
        # valid-set tree replays deferred by the batched driver until an
        # evaluation actually needs the scores (eval hoisting): (tree,
        # class_id) pairs flushed — in append order, so the f32 add
        # sequence is unchanged — by _flush_valid_pending
        self._valid_pending: List[Tuple[Tree, int]] = []

        if config.objective in ("multiclass", "multiclassova"):
            self.num_class = int(config.num_class)
        elif config.objective in ("custom", "none"):
            # custom fobj drives num_class trees per iteration
            # (reference: gbdt.cpp num_tree_per_iteration_ = num_class_
            # regardless of objective; grads arrive class-major)
            self.num_class = max(int(config.num_class), 1)
        else:
            self.num_class = 1

        if train_data is not None:
            self._init_train(train_data)
        else:
            # prediction-only booster (model loaded from string)
            self.num_tree_per_iteration = self.num_class
            self.max_feature_idx = 0
            self.feature_names: List[str] = []
            self.feature_infos: List[str] = []
            self.label_idx = 0
            self.monotone_constraints: List[int] = []

    # ------------------------------------------------------------------
    def _init_train(self, train_data: BinnedDataset) -> None:
        # which platform actually executes is telemetry, not a tail
        # string (obs/health.py; round-5 silent-CPU-fallback lesson)
        obs_health.record_backend_once(source="gbdt_init")
        config = self.config
        if self.objective is None and config.objective not in (
                "custom", "none"):
            self.objective = create_objective(config.objective, config)
        if self.objective is not None:
            self.objective.init(train_data.metadata, train_data.num_data)
            self.num_tree_per_iteration = \
                self.objective.num_model_per_iteration
            if (self.objective.is_renew_tree_output
                    and config.monotone_constraints
                    and any(int(v) != 0
                            for v in config.monotone_constraints)):
                # reference contract (gbdt.cpp:94): leaf-output renewal
                # (l1/quantile/mape/huber/fair) overwrites the clamped
                # outputs, so monotonicity cannot be honored
                log.fatal("Cannot use ``monotone_constraints`` in %s "
                          "objective, please disable it."
                          % config.objective)
        else:
            self.num_tree_per_iteration = self.num_class
        self.num_data = train_data.num_data
        self.max_feature_idx = train_data.num_total_features - 1
        self.feature_names = list(train_data.feature_names)
        self.feature_infos = train_data.feature_infos()
        self.label_idx = 0
        mc = train_data.monotone_constraints
        self.monotone_constraints = (
            [] if mc is None else [int(v) for v in np.asarray(mc)])

        self.learner = create_tree_learner(config, train_data)
        self._train_bins_dev = None
        self.sample_strategy = create_sample_strategy(
            config, self.num_data, self.num_tree_per_iteration)
        self.sample_strategy.reset_metadata(train_data.metadata)

        K = self.num_tree_per_iteration
        self._has_init_score = train_data.metadata.init_score is not None
        self.train_score = jnp.asarray(self._initial_score())
        # training-grid drift baseline (obs/quality.py); set by the
        # engine from a spilled dataset or by a checkpoint resume, and
        # persisted by ft/checkpoint.save alongside the model
        self.quality_profile = None

        self.class_need_train = [True] * K
        if self.objective is not None:
            self.class_need_train = [
                self.objective.class_need_train(k) for k in range(K)]

        # metrics over training data (is_provide_training_metric)
        self.train_metrics: List[Metric] = []
        if config.is_provide_training_metric:
            for name in resolve_metric_names(
                    config, config.objective):
                m = create_metric(name, config)
                if m is not None:
                    m.init(train_data.metadata, train_data.num_data)
                    self.train_metrics.append(m)

        self.valid_data: List[ValidData] = []
        # (leaves, device counts) of linear fits not yet counted
        self._linear_counts: List[tuple] = []
        # early-stopping state per (valid set, metric):
        self._best_score: List[List[float]] = []
        self._best_iter: List[List[int]] = []
        self._best_msg: List[List[str]] = []

        # cached per-feature bin metadata for host-side binned traversal
        ds = train_data
        self._bin_meta = (
            np.asarray([m.num_bin - 1 for m in ds.bin_mappers],
                       dtype=np.int32),
            np.asarray([m.default_bin for m in ds.bin_mappers],
                       dtype=np.int32),
            np.asarray([m.missing_type for m in ds.bin_mappers],
                       dtype=np.int32),
        )

    # ------------------------------------------------------------------
    def add_valid_data(self, valid_data: BinnedDataset,
                       names: Optional[List[str]] = None) -> None:
        """reference: GBDT::AddValidDataset (gbdt.cpp:182)."""
        if not hasattr(valid_data, "bins"):
            # ValidData keeps its binned rows + scores device-resident;
            # a sharded (out-of-core) dataset has no resident matrix
            log.fatal("sharded datasets cannot be validation sets; "
                      "bin the validation rows in-memory (they are "
                      "scored per tree, not histogrammed)")
        # deferred replays target the PRE-registration valid sets; the
        # new set replays the full model list below
        self._flush_valid_pending()
        metrics = []
        for name in resolve_metric_names(self.config, self.config.objective):
            m = create_metric(name, self.config)
            if m is not None:
                m.init(valid_data.metadata, valid_data.num_data)
                metrics.append(m)
        vd = ValidData(valid_data, metrics, self.num_tree_per_iteration)
        # replay existing model
        for i in range(self.iter + self.num_init_iteration):
            for k in range(self.num_tree_per_iteration):
                idx = i * self.num_tree_per_iteration + k
                if idx < len(self.models):
                    vd.add_tree(self.models[idx], k, self._bin_meta)
        self.valid_data.append(vd)
        n_metrics = len(metrics)
        if self.config.first_metric_only:
            n_metrics = min(n_metrics, 1)
        self._best_score.append([_K_MIN_SCORE] * n_metrics)
        self._best_iter.append([0] * n_metrics)
        self._best_msg.append([""] * n_metrics)

    # ------------------------------------------------------------------
    def _boost_from_average(self, class_id: int) -> float:
        """reference: GBDT::BoostFromAverage (gbdt.cpp:309)."""
        if (self.models or self._has_init_score or self.objective is None):
            return 0.0
        if self.config.boost_from_average \
                or self.train_data.num_features == 0:
            init_score = self.objective.boost_from_score(class_id)
            if abs(init_score) > kEpsilon:
                self._add_const_score(init_score, class_id)
                log.info("Start training from score %f" % init_score)
                return init_score
        elif self.objective.name in ("regression_l1", "quantile", "mape"):
            log.warning("Disabling boost_from_average in %s may cause the "
                        "slow convergence" % self.objective.name)
        return 0.0

    def _add_const_score(self, val: float, class_id: int) -> None:
        self.train_score = self.train_score.at[:, class_id].add(
            np.float32(val))
        for vd in self.valid_data:
            vd.add_const(val, class_id)

    # ------------------------------------------------------------------
    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (reference: GBDT::TrainOneIter,
        gbdt.cpp:334). Returns True when training should stop (no
        splittable leaves anywhere)."""
        t_iter0 = time.perf_counter()
        K = self.num_tree_per_iteration
        init_scores = [0.0] * K
        with obs.scope("gbdt::gradients"):
            if grad is None or hess is None:
                if self.objective is None:
                    log.fatal("No objective function provided")
                for k in range(K):
                    init_scores[k] = self._boost_from_average(k)
                # jitted column view: an eager [:, 0] slice performs an
                # implicit scalar transfer per iteration (the slice
                # start indices become device buffers) — the sanitizer
                # test pins this loop transfer-free
                score = _take_col(self.train_score, dev_i32(0)) \
                    if K == 1 else self.train_score
                g, h = self.objective.get_gradients(score)
            else:
                g = jnp.asarray(np.asarray(grad, dtype=np.float32))
                h = jnp.asarray(np.asarray(hess, dtype=np.float32))
                if K > 1:
                    g = g.reshape(K, self.num_data).T
                    h = h.reshape(K, self.num_data).T
            if K > 1 and g.ndim == 1:
                g = g.reshape(K, self.num_data).T
                h = h.reshape(K, self.num_data).T
            obs.watch_ready("gbdt::gradients", (g, h))

        with obs.scope("gbdt::bagging"):
            g, h, bag = self.sample_strategy.bagging(self.iter, g, h)

        should_continue = False
        new_trees = []
        for k in range(K):
            # jitted per-class column gather (traced k: one compile
            # serves all classes; eager slicing would transfer the
            # slice indices per class per iteration)
            gk = g if K == 1 else _take_col(g, dev_i32(k))
            hk = h if K == 1 else _take_col(h, dev_i32(k))
            tree: Optional[Tree] = None
            if self.class_need_train[k] and self.train_data.num_features > 0:
                with obs.scope("tree::grow"):
                    tree, leaf_of_row = self.learner.train(gk, hk, bag)
            if tree is not None and tree.num_leaves > 1:
                should_continue = True
                if (self.objective is not None
                        and self.objective.is_renew_tree_output):
                    score_np = np.asarray(
                        self.train_score[:, k], dtype=np.float64)
                    leaf_np = np.asarray(leaf_of_row)
                    mask = (None if bag is None
                            else np.asarray(bag) > 0)
                    self.objective.renew_tree_output(
                        tree, score_np, leaf_np, mask)
                # piecewise-linear leaves (reference:
                # LinearTreeLearner::CalculateLinear): the first tree of
                # a model keeps its constants, the others are fit on the
                # device
                fit_linear = (self.config.linear_tree
                              and len(self.models) >= K)
                if self.config.linear_tree and not fit_linear:
                    tree.set_constant_linear()
                tree.apply_shrinkage(self.shrinkage_rate)
                if fit_linear:
                    self._fit_linear(tree, gk, hk, bag, leaf_of_row, k)
                else:
                    self._update_score(tree, leaf_of_row, k)
                if abs(init_scores[k]) > kEpsilon:
                    tree.add_bias(init_scores[k])
            else:
                # constant tree the first iteration (reference:
                # gbdt.cpp:407-418)
                if len(self.models) < K:
                    if (self.objective is not None
                            and not self.config.boost_from_average
                            and not self._has_init_score):
                        init_scores[k] = \
                            self.objective.boost_from_score(k)
                        self._add_const_score(init_scores[k], k)
                    tree = Tree(1)
                    tree.leaf_value[0] = init_scores[k]
                else:
                    tree = Tree(1)
            new_trees.append(tree)

        if not should_continue:
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if len(self.models) >= K:
                return True
            # keep the constant trees of the very first iteration
            self.models.extend(new_trees)
            self.iter += 1
            self._emit_iter_event(new_trees, t_iter0)
            return True

        self.models.extend(new_trees)
        self.iter += 1
        self._emit_iter_event(new_trees, t_iter0)
        return False

    def _emit_iter_event(self, new_trees: List[Tree], t_start: float,
                         batched: bool = False,
                         seconds: Optional[float] = None) -> None:
        """Per-iteration training event (iter index, wall time, tree
        shape); eval results ride the separate ``eval`` event emitted by
        eval_metrics (evaluation is metric_freq-gated). Also the
        per-iteration device-memory sampling point (HBM gauges /
        live-buffer fallback — cheap no-op when telemetry is off)."""
        obs_trace.sample_iteration(self.iter)
        if not obs_events.enabled():
            return
        if seconds is None:
            seconds = time.perf_counter() - t_start
        trees = [{"num_leaves": int(t.num_leaves),
                  "depth": int(t.leaf_depth[:max(t.num_leaves, 1)].max())}
                 for t in new_trees if t is not None]
        obs_events.emit(
            "train_iter", iter=self.iter, seconds=round(seconds, 6),
            batched=batched, trees=trees)

    # ------------------------------------------------------------------
    # Device-resident batched iterations (mesh learners): amortize the
    # per-iteration dispatch/sync cost of a remote chip by running T
    # iterations per dispatch (parallel/data_parallel.py train_many).
    # ------------------------------------------------------------------
    # per-iteration host logic in a subclass (DART's drop/normalize,
    # RF's refit averaging) cannot run inside the device scan; each
    # boosting mode opts in explicitly
    _supports_batched = True

    def can_train_batched(self) -> bool:
        """True when T iterations can run without host participation:
        single-model objective with deterministic gradients, no
        leaf-output renewal or linear refits (host-side percentiles /
        least squares per tree), a sample strategy whose draw keys on
        the iteration index (bagging/GOSS fold_in — see
        sample_strategy.py; custom strategies without ``apply_traced``
        decline), and a learner whose scan needs no per-tree host
        state."""
        return (self._supports_batched
                and self.objective is not None
                and not self.objective.is_renew_tree_output
                and not getattr(self.objective,
                                "has_stochastic_gradients", False)
                and not self.config.linear_tree
                and getattr(self.sample_strategy, "supports_device_draw",
                            lambda: False)()
                and len(self.models) >= 1  # iter 0 seeds boost_from_avg
                and all(self.class_need_train)
                and getattr(self.learner, "supports_train_many",
                            lambda: False)())

    def train_batch(self, n_iters: int) -> bool:
        """Run ``n_iters`` boosting iterations in one device dispatch;
        returns True when training should stop (an iteration grew no
        tree in any class). Caller must have checked
        can_train_batched()."""
        from ..treelearner.grow import (apply_split_record,
                                        record_is_valid)
        from .sample_strategy import SampleStrategy
        t_batch0 = time.perf_counter()
        learner = self.learner
        K = self.num_tree_per_iteration
        base = learner._tree_idx
        if K == 1:
            seeds = [(learner._extra_seed + 7919 * (base + 1 + t))
                     & 0x7FFFFFFF for t in range(n_iters)]
            score0 = _take_col(self.train_score, dev_i32(0))
        else:
            seeds = [[(learner._extra_seed
                       + 7919 * (base + 1 + t * K + k)) & 0x7FFFFFFF
                      for k in range(K)] for t in range(n_iters)]
            score0 = self.train_score
        # the scanned iteration numbers drive the sample strategy's
        # on-device fold_in draws — the exact indices the looped path's
        # per-iteration ``bagging(self.iter, ...)`` calls would consume
        iters = np.arange(self.iter, self.iter + n_iters, dtype=np.int32)
        sample = (None
                  if type(self.sample_strategy) is SampleStrategy
                  else self.sample_strategy)
        with obs.scope("tree::train_batch_dispatch"):
            score_t, recs = learner.train_many(
                self.objective.get_gradients, sample, score0, seeds,
                iters, self.shrinkage_rate)
            # jaxlint: disable=JLT001 -- the batch's single deliberate
            # sync: n_iters trees' split records read back in one hop
            recs_h = jax.device_get(recs)
        t_dispatch = time.perf_counter() - t_batch0
        kb = max(learner.L - 1, 1)
        stopped = False
        applied = 0
        for t in range(n_iters):
            iter_trees = []
            grew_any = False
            for k in range(K):
                tree = Tree(learner.L)
                grew = False
                for i in range(kb):
                    r = jax.tree_util.tree_map(
                        lambda a: a[t, k, i] if K > 1 else a[t, i],
                        recs_h)
                    if not record_is_valid(r):
                        break
                    apply_split_record(tree, self.train_data, r)
                    grew = True
                if grew:
                    tree.apply_shrinkage(self.shrinkage_rate)
                    grew_any = True
                else:
                    # class grew nothing: zero-valued stump, exactly the
                    # looped path's constant tree (device added zero)
                    tree = Tree(1)
                iter_trees.append(tree)
            if not grew_any:
                # no-splittable-leaves in ANY class: the device added
                # zero output for this and every later step, so the
                # score is consistent with stopping here
                # (reference: gbdt.cpp:407)
                log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
                stopped = True
                break
            with obs.scope("tree::apply_records"):
                for k, tree in enumerate(iter_trees):
                    self.models.append(tree)
                    if tree.num_leaves > 1 and self.valid_data:
                        # valid-set replay DEFERRED to the next eval
                        # (eval hoisting): the per-tree device traversal
                        # leaves the iteration loop; flush order ==
                        # append order, so the f32 add sequence — and
                        # the eval results — are unchanged
                        self._valid_pending.append((tree, k))
            self.iter += 1
            applied += 1
            # wall time amortized over the batch: the dispatch is one
            # fused device program covering every iteration in it
            self._emit_iter_event(iter_trees, 0.0, batched=True,
                                  seconds=t_dispatch / n_iters)
        if obs_events.enabled():
            # ground-truth dispatch cost: the fused program ran all
            # n_iters on device even when the host stopped applying
            # early, so summing the amortized train_iter seconds
            # under-counts on early stop — this event carries the total
            obs_events.emit("train_batch", n_iters=n_iters,
                            applied=applied, stopped=stopped,
                            seconds=round(t_dispatch, 6))
        # score_t is correct even for a partial batch: a stump step (and
        # every step after it, which sees the same score and grows the
        # same stump) contributed zero output on device
        if K == 1:
            self.train_score = _set_score_col(self.train_score, score_t,
                                              dev_i32(0))
        else:
            self.train_score = score_t
        return stopped

    def _flush_valid_pending(self) -> None:
        """Replay valid-set tree outputs the batched driver deferred
        (train_batch appends; every reader of valid scores — eval,
        rollback, a late add_valid_data — flushes first)."""
        if not self._valid_pending:
            return
        pending, self._valid_pending = self._valid_pending, []
        with obs.scope("tree::apply_records"):
            for tree, k in pending:
                for vd in self.valid_data:
                    vd.add_tree(tree, k, self._bin_meta)

    # ------------------------------------------------------------------
    def _initial_score(self) -> np.ndarray:
        """[N, K] f32 starting scores: zeros plus the metadata
        init_score in its class-major-to-column layout — THE layout
        convention shared by training-score init and the
        recheck_scores replay (one definition, so the two cannot
        drift)."""
        K = self.num_tree_per_iteration
        score = np.zeros((self.num_data, K), dtype=np.float32)
        if self._has_init_score \
                and self.train_data.metadata.init_score is not None:
            init = np.asarray(self.train_data.metadata.init_score,
                              dtype=np.float64)
            score += init.reshape(K, -1).T.astype(np.float32)
        return score

    # ------------------------------------------------------------------
    def recheck_scores(self, reason: str = "") -> float:
        """Batched-eval double-check (ROADMAP gap): replay every model
        tree over the training rows on device and compare the summed
        outputs against the incrementally maintained ``train_score``.
        Called ONCE at the transition when a quantized batched run
        degrades to per-iteration training — the hand-off point
        between the fused scan's device-maintained scores and the
        looped path — and emits one ``batched_eval_recheck`` event
        carrying the max deviation (plus a Warning when it exceeds
        the f32 replay tolerance). Returns the max abs deviation."""
        if not hasattr(self.train_data, "bins"):
            return 0.0  # sharded datasets cannot replay resident rows
        K = self.num_tree_per_iteration
        replay_dev = jnp.asarray(self._initial_score())
        for idx, tree in enumerate(self.models):
            delta = self._tree_outputs_train(tree)
            if delta is not None:
                replay_dev = replay_dev.at[:, idx % K].add(delta)
        # jaxlint: disable=JLT001 -- one-shot verification sync at the
        # batched->looped transition (the event below is the point)
        diff = float(jnp.max(jnp.abs(replay_dev - self.train_score)))
        # jaxlint: disable=JLT001 -- same one-shot verification sync
        scale = max(float(jnp.max(jnp.abs(self.train_score))), 1.0)
        ok = diff <= 1e-3 * scale
        obs_events.emit("batched_eval_recheck", reason=reason,
                        iter=self.iter, trees=len(self.models),
                        max_abs_diff=round(diff, 9), ok=ok)
        if not ok:
            log.warning(
                "batched-eval recheck at the batched->looped "
                "transition found score deviation %.3g (replay of %d "
                "trees vs the incrementally maintained device score)"
                % (diff, len(self.models)))
        return diff

    # ------------------------------------------------------------------
    def _update_score(self, tree: Tree, leaf_of_row: jnp.ndarray,
                      class_id: int) -> None:
        """Device gather of leaf outputs over the learner's final
        partition (reference: GBDT::UpdateScore, gbdt.cpp:475)."""
        with obs.scope("gbdt::score_update"):
            self._update_score_inner(tree, leaf_of_row, class_id)
            obs.watch_ready("gbdt::score_update", self.train_score)

    def _update_score_inner(self, tree: Tree, leaf_of_row: jnp.ndarray,
                            class_id: int) -> None:
        # leaf values padded to the configured num_leaves so every
        # tree shares ONE compiled gather+add per class (a
        # tree-sized vector would retrace per leaf count); the
        # jnp.asarray transfer is the explicit per-tree host→device
        # hop of the new leaf outputs
        L = max(int(self.config.num_leaves), tree.num_leaves, 1)
        lv = np.zeros(L, dtype=np.float32)
        lv[:tree.num_leaves] = tree.leaf_value[:tree.num_leaves]
        self.train_score = _apply_leaf_delta(
            self.train_score, jnp.asarray(lv), leaf_of_row,
            dev_i32(class_id))
        for vd in self.valid_data:
            vd.add_tree(tree, class_id, self._bin_meta)

    def _fit_linear(self, tree: Tree, grad, hess, bag, leaf_of_row,
                    class_id: int) -> None:
        """The tree's linear leaves fit on the device over the training
        rows' raw values, and every row's score, training and
        validation, moved by its leaf's linear value (``ops/linear.py``);
        the coefficients stay on the device with the tree until the
        model is read, so the host waits for nothing here."""
        from ..ops import linear as linear_ops
        raw = self.train_data.raw_device()
        if raw is None:
            log.fatal("linear_tree requires the raw feature values; "
                      "construct the Dataset with linear_tree=True")
        self.flush_linear_counts()
        with obs.scope("tree::linear_paths"):
            leaves = max(int(self.config.num_leaves), tree.num_leaves)
            feat, valid = linear_ops.path_table(tree, leaves)
        with obs.scope("gbdt::score_update"):
            lin, delta, counts = linear_ops.fit_tree(
                raw, grad, hess, bag, leaf_of_row, tree, feat, valid,
                leaves, dev_f32(self.shrinkage_rate),
                dev_f32(float(self.config.linear_lambda)))
            tree.attach_linear(lin)
            self.train_score = _add_score_col(self.train_score, delta,
                                              dev_i32(class_id))
            for vd in self.valid_data:
                vd.add_tree(tree, class_id, self._bin_meta)
            obs.watch_ready("gbdt::score_update", self.train_score)
        if obs.enabled:
            obs.inc("linear/trees_fit")
            self._linear_counts.append((tree.num_leaves, counts))

    def flush_linear_counts(self) -> None:
        """``linear/leaves_fit``, ``linear/leaves_const``,
        ``linear/path_features`` (their branches' features),
        ``linear/rows_fit`` and, over those rows,
        ``linear/row_features`` and ``linear/row_features_sq`` (the sums
        of the features each row's leaf kept and of their square, which
        the work of a fit and of the linear outputs is counted from) of
        the trees fit since the last call, counted while the stage timer
        is on. Their
        fits are done whenever the host has waited for anything after
        them (the next tree's records, the validation scores), so the
        read waits for nothing; the evaluation calls it."""
        pending = getattr(self, "_linear_counts", [])
        self._linear_counts = []
        for leaves, counts in pending:
            # jaxlint: disable=JLT001 -- five ints of a fit the host has
            # already waited past (docstring); stage timer on only
            got = jax.device_get(counts)
            fit, features, rows, row_features, row_features_sq = (
                int(v) for v in got)
            obs.inc("linear/leaves_fit", fit)
            obs.inc("linear/leaves_const", leaves - fit)
            obs.inc("linear/path_features", features)
            obs.inc("linear/rows_fit", rows)
            obs.inc("linear/row_features", row_features)
            obs.inc("linear/row_features_sq", row_features_sq)

    # ------------------------------------------------------------------
    def rollback_one_iter(self) -> None:
        """reference: GBDT::RollbackOneIter (gbdt.cpp:438)."""
        if self.iter <= 0:
            return
        self._flush_valid_pending()
        K = self.num_tree_per_iteration
        for k in range(K):
            tree = self.models[-K + k]
            delta = self._tree_outputs_train(tree)
            if delta is not None:
                self.train_score = self.train_score.at[:, k].add(-delta)
            for vd in self.valid_data:
                vd.add_tree(tree, k, self._bin_meta, sign=-1.0)
        del self.models[-K:]
        self.iter -= 1

    def _train_bins_device(self) -> jnp.ndarray:
        """Device-resident [N, F] binned training rows, reusing the
        learner's buffer when its layout matches (the serial learner keeps
        [N+1, F]; feature-parallel pads features, so it gets a copy)."""
        if self._train_bins_dev is None:
            if not hasattr(self.train_data, "bins"):
                log.fatal("this operation re-scores training rows from "
                          "the resident bin matrix (DART drops, "
                          "rollback); not supported with sharded "
                          "out-of-core datasets")
            lb = getattr(self.learner, "bins", None)
            if self.train_data.bundle is not None:
                # bundled traversal needs the bundled [N, G] layout (the
                # LUT DeviceTree reads bundle columns); mesh learners may
                # hold an unbundled copy, so never reuse theirs here
                self._train_bins_dev = jnp.asarray(self.train_data.bins)
            elif lb is not None and lb.ndim == 2 \
                    and lb.shape[0] >= self.num_data \
                    and lb.shape[1] == self.train_data.num_features:
                self._train_bins_dev = lb[:self.num_data]
            else:
                self._train_bins_dev = jnp.asarray(self.train_data.bins)
        return self._train_bins_dev

    def _tree_outputs_train(self, tree: Tree):
        """Device [N] f32 output of one tree over the training rows (used
        by rollback/DART score adjustments; the per-iteration score update
        itself reuses the learner's partition in _update_score)."""
        return _device_tree_outputs(
            tree, self._train_bins_device(), self.train_data,
            self._bin_meta)[0]

    # ------------------------------------------------------------------
    def eval_metrics(self) -> List[Tuple[str, str, float, bool]]:
        """Evaluate all metrics; returns (dataset_name, metric_name,
        value, is_bigger_better) tuples."""
        self._flush_valid_pending()
        out = run_instrumented_eval(self.iter, self._eval_metrics_inner)
        self.flush_linear_counts()
        return out

    def _eval_metrics_inner(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        sets = [("valid_%d" % i, vd.metrics, vd.scores_dev)
                for i, vd in enumerate(self.valid_data)]
        if self.train_metrics:
            sets.insert(0, ("training", self.train_metrics,
                            self.train_score))
        for name, metrics, scores_dev in sets:
            score = fetch_scores(scores_dev)
            if self.num_tree_per_iteration == 1:
                score = score[:, 0]
            out.extend(compute_metrics(name, metrics, score,
                                       self.objective))
        return out

    def _check_early_stopping(self, eval_list) -> bool:
        """reference: GBDT::OutputMetric early-stopping bookkeeping
        (gbdt.cpp:535-590). Tracks every value of every metric (all
        ``eval_at`` positions), per valid set; ``first_metric_only``
        restricts to the first metric's values."""
        if self.config.early_stopping_round <= 0 or not self.valid_data:
            return False
        stop = False
        for i, vd in enumerate(self.valid_data):
            ds_name = "valid_%d" % i
            entries = [(name, v, bigger) for ds, name, v, bigger
                       in eval_list if ds == ds_name]
            if self.config.first_metric_only and vd.metrics:
                first_names = set(vd.metrics[0].name)
                entries = [e for e in entries if e[0] in first_names]
            if len(self._best_score[i]) != len(entries):
                # lazily size the per-(metric, position) trackers
                self._best_score[i] = [_K_MIN_SCORE] * len(entries)
                self._best_iter[i] = [0] * len(entries)
            for j, (name, v, bigger) in enumerate(entries):
                cur = v * (1.0 if bigger else -1.0)
                if cur > self._best_score[i][j]:
                    self._best_score[i][j] = cur
                    self._best_iter[i][j] = self.iter
                elif (self.iter - self._best_iter[i][j]
                        >= self.config.early_stopping_round):
                    stop = True
        if stop:
            best = max((b for bi in self._best_iter for b in bi),
                       default=self.iter)
            self.best_iteration = best
            log.info("Early stopping at iteration %d, the best iteration "
                     "round is %d" % (self.iter, best))
        return stop

    # ------------------------------------------------------------------
    def train(self, snapshot_freq: int = -1,
              model_output_path: str = "",
              callbacks: Optional[Sequence[Callable]] = None,
              checkpoint_dir: str = "",
              checkpoint_freq: int = -1) -> None:
        """Full training loop (reference: GBDT::Train, gbdt.cpp:229).

        metric_freq gates only the *printing* of metrics; early stopping
        evaluates every iteration like the reference (OutputMetric runs
        whenever early_stopping_round > 0, gbdt.cpp:461). ``callbacks``
        follow the python callback protocol (CallbackEnv; EarlyStopException
        stops training).

        ``checkpoint_dir`` + ``checkpoint_freq`` write crash-consistent
        resume checkpoints (ft/checkpoint.py) — unlike ``snapshot_freq``
        model snapshots these capture scores + RNG state, so a killed
        run resumes bit-identically via :meth:`load_checkpoint`."""
        from ..callback import CallbackEnv, EarlyStopException
        callbacks = list(callbacks or [])
        cbs_before = sorted(
            [cb for cb in callbacks
             if getattr(cb, "before_iteration", False)],
            key=lambda cb: getattr(cb, "order", 0))
        cbs_after = sorted(
            [cb for cb in callbacks
             if not getattr(cb, "before_iteration", False)],
            key=lambda cb: getattr(cb, "order", 0))
        begin_iter = self.iter
        end_iter = int(self.config.num_iterations)
        es_round = self.config.early_stopping_round
        # eval hoisting (tpu_eval_iterations=k): evaluation — and the
        # early-stopping check it feeds — runs on the absolute every-k
        # iteration grid (plus the final iteration), so a resumed run
        # evaluates at the same iterations as an uninterrupted one;
        # the patience window still counts in iterations
        eval_k = max(int(getattr(self.config, "tpu_eval_iterations", 1)),
                     1)
        for it in range(begin_iter, end_iter):
            for cb in cbs_before:
                cb(CallbackEnv(model=self, params={}, iteration=it,
                               begin_iteration=begin_iter,
                               end_iteration=end_iter,
                               evaluation_result_list=None))
            finished = self.train_one_iter()
            eval_list = None
            eval_due = True
            if not finished:
                eval_due = eval_hoist_due(self.iter, self.iter - 1,
                                          eval_k,
                                          self.iter >= end_iter)
                need_output = (self.config.metric_freq > 0
                               and self.iter % self.config.metric_freq == 0
                               and eval_due)
                need_eval = eval_due and (
                    need_output or cbs_after
                    or (es_round > 0 and self.valid_data))
                if need_eval:
                    eval_list = self.eval_metrics()
                if need_output:
                    for ds, name, v, _ in eval_list:
                        log.info("Iteration:%d, %s %s : %g"
                                 % (self.iter, ds, name, v))
                if es_round > 0 and self.valid_data \
                        and eval_list is not None \
                        and self._check_early_stopping(eval_list):
                    # drop the over-trained models
                    K = self.num_tree_per_iteration
                    n_drop = (self.iter - self.best_iteration)
                    del self.models[len(self.models) - n_drop * K:]
                    self.iter = self.best_iteration
                    finished = True
            try:
                # after-callbacks fire only at eval points (same
                # contract as the engine loops): feeding early_stopping
                # an empty evaluation list on a skipped iteration would
                # abort its _init
                for cb in (cbs_after if eval_due else []):
                    cb(CallbackEnv(model=self, params={}, iteration=it,
                                   begin_iteration=begin_iter,
                                   end_iteration=end_iter,
                                   evaluation_result_list=[
                                       (ds, name, v, bigger) for
                                       ds, name, v, bigger
                                       in (eval_list or [])]))
            except EarlyStopException as e:
                self.best_iteration = e.best_iteration + 1
                finished = True
            if snapshot_freq > 0 and self.iter % snapshot_freq == 0 \
                    and model_output_path:
                self.save_model(model_output_path
                                + ".snapshot_iter_%d" % self.iter)
            if checkpoint_dir and checkpoint_freq > 0 \
                    and self.iter % checkpoint_freq == 0:
                self.save_checkpoint(checkpoint_dir)
            if finished:
                break
        if checkpoint_dir:
            self.save_checkpoint(checkpoint_dir)
        # the sharded learner's cross-iteration sweep stash pins one
        # staged shard buffer; no further tree will consume it now
        rel = getattr(self.learner, "release_prefetch", None)
        if rel is not None:
            rel()

    # ------------------------------------------------------------------
    # Prediction over raw feature matrices (host)
    # ------------------------------------------------------------------
    def _used_models(self, start_iteration: int = 0,
                     num_iteration: int = -1) -> List[Tree]:
        K = self.num_tree_per_iteration
        total_iter = len(self.models) // K
        start = max(0, min(start_iteration, total_iter))
        end = total_iter if num_iteration <= 0 \
            else min(start + num_iteration, total_iter)
        return self.models[start * K:end * K]

    def predict_raw(self, X: np.ndarray, start_iteration: int = 0,
                    num_iteration: int = -1,
                    pred_early_stop: Optional[bool] = None,
                    pred_early_stop_freq: Optional[int] = None,
                    pred_early_stop_margin: Optional[float] = None
                    ) -> np.ndarray:
        X = np.asarray(X, dtype=np.float64)
        K = self.num_tree_per_iteration
        n = X.shape[0]
        out = np.zeros((n, K), dtype=np.float64)
        models = self._used_models(start_iteration, num_iteration)
        if pred_early_stop is None:
            pred_early_stop = bool(self.config.pred_early_stop)
        # reference restricts prediction early stop to classification
        # (CreatePredictionEarlyStopInstance: "binary"/"multiclass" only)
        if pred_early_stop and self.objective is not None \
                and self.objective.name in ("binary", "multiclass",
                                            "multiclassova"):
            # margin-based per-row early exit (reference:
            # src/boosting/prediction_early_stop.cpp — binary margin
            # 2|score|, multiclass top1−top2, checked every round_period
            # iterations)
            freq = int(pred_early_stop_freq
                       if pred_early_stop_freq is not None
                       else self.config.pred_early_stop_freq)
            margin_thr = float(pred_early_stop_margin
                               if pred_early_stop_margin is not None
                               else self.config.pred_early_stop_margin)
            freq = max(freq, 1)
            active = np.arange(n)
            n_iters = len(models) // max(K, 1)
            for it in range(n_iters):
                if len(active) == 0:
                    break
                Xa = X[active]
                for k in range(K):
                    out[active, k] += models[it * K + k].predict(Xa)
                if (it + 1) % freq == 0 and it + 1 < n_iters:
                    if K == 1:
                        margin = 2.0 * np.abs(out[active, 0])
                    else:
                        part = np.partition(out[active], K - 2, axis=1)
                        margin = part[:, K - 1] - part[:, K - 2]
                    active = active[margin < margin_thr]
        else:
            for i, tree in enumerate(models):
                out[:, i % K] += tree.predict(X)
        if self.average_output and models:
            out /= max(len(models) // K, 1)
        return out[:, 0] if K == 1 else out

    def predict(self, X: np.ndarray, raw_score: bool = False,
                start_iteration: int = 0,
                num_iteration: int = -1, **kwargs) -> np.ndarray:
        raw = self.predict_raw(X, start_iteration, num_iteration, **kwargs)
        if raw_score or self.objective is None:
            return raw
        return self.objective.convert_output(raw)

    def predict_leaf_index(self, X: np.ndarray, start_iteration: int = 0,
                           num_iteration: int = -1) -> np.ndarray:
        X = np.asarray(X, dtype=np.float64)
        models = self._used_models(start_iteration, num_iteration)
        out = np.zeros((X.shape[0], len(models)), dtype=np.int32)
        for i, tree in enumerate(models):
            out[:, i] = tree.predict_leaf_index(X)
        return out

    def predict_contrib(self, X: np.ndarray, start_iteration: int = 0,
                        num_iteration: int = -1) -> np.ndarray:
        """SHAP contributions (reference: predict_contrib /
        Tree::PredictContrib, tree.h:139)."""
        from ..models.shap import predict_contrib as _pc
        models = self._used_models(start_iteration, num_iteration)
        return _pc(models, X, self.max_feature_idx + 1,
                   self.num_tree_per_iteration)

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           num_iteration: int = -1) -> np.ndarray:
        """reference: GBDT::FeatureImportance
        (src/boosting/gbdt_model_text.cpp:680+)."""
        n = self.max_feature_idx + 1
        imp = np.zeros(n, dtype=np.float64)
        for tree in self._used_models(0, num_iteration):
            ni = tree.num_internal
            for j in range(ni):
                f = tree.split_feature[j]
                if importance_type == "split":
                    imp[f] += 1.0
                else:
                    imp[f] += max(tree.split_gain[j], 0.0)
        return imp

    # ------------------------------------------------------------------
    # Model text I/O (reference: src/boosting/gbdt_model_text.cpp)
    # ------------------------------------------------------------------
    def save_model_to_string(self, start_iteration: int = 0,
                             num_iteration: int = -1,
                             importance_type: str = "split") -> str:
        """reference: GBDT::SaveModelToString
        (gbdt_model_text.cpp:311-408)."""
        lines = [self.submodel_name, "version=v3",
                 "num_class=%d" % self.num_class,
                 "num_tree_per_iteration=%d" % self.num_tree_per_iteration,
                 "label_index=%d" % self.label_idx,
                 "max_feature_idx=%d" % self.max_feature_idx]
        if self.objective is not None:
            lines.append("objective=%s" % self.objective.to_string())
        if self.average_output:
            lines.append("average_output")
        lines.append("feature_names=" + " ".join(self.feature_names))
        if self.monotone_constraints:
            lines.append("monotone_constraints="
                         + " ".join(str(v)
                                    for v in self.monotone_constraints))
        lines.append("feature_infos=" + " ".join(self.feature_infos))

        models = self._used_models(start_iteration, num_iteration)
        tree_strs = []
        tree_sizes = []
        for i, tree in enumerate(models):
            s = "Tree=%d\n%s\n" % (i, tree.to_string())
            tree_strs.append(s)
            tree_sizes.append(len(s))
        lines.append("tree_sizes=" + " ".join(str(s) for s in tree_sizes))
        lines.append("")
        out = "\n".join(lines) + "\n"
        out += "".join(tree_strs)
        out += "end of trees\n"
        # saved_feature_importance_type (config.h:586): 0=split, 1=gain
        imp_type = ("gain" if int(getattr(
            self.config, "saved_feature_importance_type", 0)) == 1
            else "split")
        imp = self.feature_importance(imp_type, num_iteration)
        pairs = [(imp[i], self.feature_names[i])
                 for i in range(len(imp)) if imp[i] > 0]
        pairs.sort(key=lambda p: -p[0])
        out += "\nfeature_importances:\n"
        for v, name in pairs:
            out += ("%s=%d\n" % (name, int(v)) if imp_type == "split"
                    else "%s=%s\n" % (name, repr(float(v))))
        out += "\nparameters:\n%s\nend of parameters\n" % \
            self.config.to_param_string()
        return out

    def save_model(self, filename: str, start_iteration: int = 0,
                   num_iteration: int = -1) -> None:
        # tmp+rename: a crash mid-write must leave the previous model
        # file (or nothing), never a truncated one that parses as a
        # shorter model — the same discipline as trace segments and
        # checkpoints (utils/atomic.py)
        from ..utils.atomic import atomic_write
        atomic_write(filename,
                     self.save_model_to_string(start_iteration,
                                               num_iteration))

    def dump_model(self, start_iteration: int = 0,
                   num_iteration: int = -1,
                   importance_type: str = "split") -> Dict:
        """JSON-dump structure (reference: GBDT::DumpModel,
        gbdt_model_text.cpp:21-170)."""
        d: Dict = {
            "name": self.submodel_name,
            "version": "v3",
            "num_class": self.num_class,
            "num_tree_per_iteration": self.num_tree_per_iteration,
            "label_index": self.label_idx,
            "max_feature_idx": self.max_feature_idx,
        }
        if self.objective is not None:
            d["objective"] = self.objective.to_string()
        d["average_output"] = bool(self.average_output)
        d["feature_names"] = list(self.feature_names)
        d["monotone_constraints"] = list(self.monotone_constraints or [])
        infos: Dict = {}
        for i, info in enumerate(self.feature_infos):
            if i >= len(self.feature_names):
                break
            if info.startswith("["):
                lo, hi = info[1:-1].split(":")
                infos[self.feature_names[i]] = {
                    "min_value": float(lo), "max_value": float(hi),
                    "values": []}
            elif info != "none":
                vals = [int(v) for v in info.split(":")]
                infos[self.feature_names[i]] = {
                    "min_value": min(vals), "max_value": max(vals),
                    "values": vals}
        d["feature_infos"] = infos
        models = self._used_models(start_iteration, num_iteration)
        tree_info = []
        for i, tree in enumerate(models):
            tj = tree.to_json()
            tj["tree_index"] = i
            tree_info.append(tj)
        d["tree_info"] = tree_info
        imp = self.feature_importance(importance_type, num_iteration)
        d["feature_importances"] = {
            self.feature_names[i]: (int(imp[i]) if
                                    importance_type == "split"
                                    else float(imp[i]))
            for i in range(len(imp)) if imp[i] > 0}
        return d

    def save_model_to_cpp(self, filename: str) -> None:
        """``convert_model`` task output (reference:
        GBDT::SaveModelToIfElse, gbdt_model_text.cpp:286)."""
        from ..models.codegen import model_to_cpp
        from ..utils.atomic import atomic_write
        atomic_write(filename, model_to_cpp(self))

    # ------------------------------------------------------------------
    # Crash-consistent checkpoint/resume (ft/checkpoint.py)
    # ------------------------------------------------------------------
    def save_checkpoint(self, directory: str,
                        keep: Optional[int] = None) -> str:
        """Write one atomically-finalized checkpoint directory holding
        the FULL resume state — trees, iteration/early-stop
        bookkeeping, every RNG sequence position (bagging/GOSS/DART/
        feature-fraction/quantize counters), and the training-score
        bits. Resuming via :meth:`load_checkpoint` continues the run
        bit-identically (docs/RELIABILITY.md)."""
        from ..ft import checkpoint as _ckpt
        return _ckpt.save(self, directory, keep=keep)

    def load_checkpoint(self, directory: str) -> Optional[Dict]:
        """Restore this (freshly initialized, same-dataset) booster
        from the newest valid checkpoint under ``directory``; returns
        the checkpoint state dict, or None when no valid checkpoint
        exists. Corrupt checkpoints are skipped loudly."""
        from ..ft import checkpoint as _ckpt
        return _ckpt.load_latest(self, directory)

    def load_model_from_string(self, s: str) -> None:
        """reference: GBDT::LoadModelFromString
        (gbdt_model_text.cpp:421)."""
        from ..objective import load_objective_from_string
        lines = s.splitlines()
        kv: Dict[str, str] = {}
        i = 0
        while i < len(lines) and not lines[i].startswith("Tree="):
            line = lines[i]
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k] = v
            elif line.strip() == "average_output":
                self.average_output = True
            i += 1
        self.num_class = int(kv.get("num_class", 1))
        self.num_tree_per_iteration = int(
            kv.get("num_tree_per_iteration", self.num_class))
        self.label_idx = int(kv.get("label_index", 0))
        self.max_feature_idx = int(kv.get("max_feature_idx", 0))
        self.feature_names = kv.get("feature_names", "").split()
        self.feature_infos = kv.get("feature_infos", "").split()
        if "objective" in kv:
            self.objective = load_objective_from_string(
                kv["objective"], self.config)
        # parse trees (shared block parser: models/tree.py)
        from ..models.tree import parse_tree_blocks
        self.models = parse_tree_blocks("\n".join(lines[i:]))
        self.num_init_iteration = \
            len(self.models) // max(self.num_tree_per_iteration, 1)
        self.iter = 0

    @property
    def current_iteration(self) -> int:
        return len(self.models) // max(self.num_tree_per_iteration, 1)

    # ------------------------------------------------------------------
    def align_trees_to_dataset(self, dataset: BinnedDataset) -> None:
        """Restore bin-space node fields (split_feature_inner,
        threshold_in_bin, categorical bin masks) on text-loaded trees so
        binned traversal works for continued training (reference:
        continued training re-links the loaded model to the Dataset's
        bin mappers via Tree's train-time fields)."""
        from ..models.tree import kCategoricalMask
        for tree in self.models:
            for node in range(tree.num_internal):
                real_f = int(tree.split_feature[node])
                inner = dataset.inner_feature_index(real_f)
                tree.split_feature_inner[node] = max(inner, 0)
                if inner < 0:
                    continue
                mapper = dataset.bin_mappers[inner]
                if tree.decision_type[node] & kCategoricalMask:
                    cat_idx = int(tree.threshold_in_bin[node])
                    nb = mapper.num_bin
                    cats = np.array(
                        [mapper.bin_2_categorical[b] if
                         b < len(mapper.bin_2_categorical) else -1
                         for b in range(nb)], dtype=np.float64)
                    tree.cat_bin_masks[node] = \
                        tree._cat_contains(cat_idx, cats)
                else:
                    tree.threshold_in_bin[node] = mapper.value_to_bin(
                        np.array([tree.threshold[node]]))[0]
