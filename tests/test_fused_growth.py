"""Whole-tree on-device growth (ISSUE 8, ISSUE 33).

Three contracts:

1. One grower — the serial learner (one `serial.fused_tree` dispatch
   per tree) and the data-parallel learner on a one-device mesh (the
   program the benchmark's cells run) both run treelearner/grow.py's
   whole-tree loop and split step, and grow bit-identical trees AND
   train scores across the capability matrix (exact / quantized8 /
   quantized16 x bagging x multiclass x basic monotone x the one-hot
   histogram); the sharded K-splits-per-sweep frontier stays
   bit-identical to in-memory training while cutting shard stagings.
2. Dispatch count — ≤ 3 grow dispatches per tree (stage_gh + root +
   ONE split_batches), asserted from the trace layer's stage spans.
3. The batched-iterations lift — quantized-gradient runs batch through
   `train_many` (scan-carried fold_in tree counter + alive flag) and
   match the looped path under the documented batched-path tolerance;
   a quantized batched->looped transition re-verifies scores once
   (`batched_eval_recheck` event).
"""
import importlib.util
import json
import os
import tempfile

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.shards import ShardedBinnedDataset
from lightgbm_tpu.obs import events as obs_events
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.obs.registry import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "trace_report", os.path.join(REPO, "tools", "trace_report.py"))
trace_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_report)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    yield
    obs_trace.configure(None)
    obs_events.configure(None)
    registry.drain_ready(timeout=10.0)
    registry.disable()
    registry.timer.sampling = False


def _data(n=800, f=6, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] - X[:, 1] + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


BASE = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
        "min_data_in_leaf": 5, "bin_construct_sample_cnt": 1000}


def _train(ds, params, iters=3):
    booster = create_boosting(
        Config.from_params(dict(params, num_iterations=iters)), ds)
    for _ in range(iters):
        booster.train_one_iter()
    return booster


def _train_matrix(params, X, y, iters=3):
    ds = BinnedDataset.from_matrix(
        X, Config.from_params(dict(params)), label=y)
    return _train(ds, params, iters)


def _scores_bits(b):
    return np.asarray(b.train_score, dtype=np.float32).view(np.uint32)


# ---------------------------------------------------------------------------
# serial vs one-device mesh: BIT parity matrix
# ---------------------------------------------------------------------------

SERIAL = {"tree_learner": "serial"}           # given, so it is honoured
MESH1 = {"tree_learner": "data", "mesh_shape": "data=1"}


def _train_pair(params, X, y, iters=3):
    bs = _train_matrix(dict(params, **SERIAL), X, y, iters)
    bm = _train_matrix(dict(params, **MESH1), X, y, iters)
    assert type(bs.learner).__name__ == "SerialTreeLearner"
    assert type(bm.learner).__name__ == "DataParallelTreeLearner"
    assert bm.learner.mesh.devices.size == 1
    return bs, bm


def _tree_fields(tree):
    return dict(line.split("=", 1)
                for line in tree.to_string().splitlines() if "=" in line)


_GAINS = ("split_gain",)
_SUMS = ("leaf_value", "leaf_weight", "internal_value", "internal_weight")


def _assert_same_trees_and_scores(bs, bm, integer_sums=False):
    """What the pair gives bit for bit, and what it cannot.

    Structure, thresholds, counts and so the partitions: always equal.
    Both learners hand the same rows in the same ascending order to a
    histogram that adds in row order (scatter) or in integers
    (quantized), and the serial learner's pad rows and pad features add
    zeros.

    Leaf values, weights and train scores: bit-equal where the sums are
    integers (``integer_sums``: quantized gradients). In float32 the
    root's totals are one reduction over all R rows, R is 4,096-row
    blocks in the serial learner and the row count in the mesh learner,
    and XLA's reduction tree changes with the length: the totals, and
    every right child's ``total - left`` after them, may differ in the
    last bit. Held to float32 rounding there.

    ``split_gain``: float32 rounding always. The mesh learner's programs
    close over the split parameters as constants, the serial learner's
    take them as arguments, and XLA folds a constant into the gain's
    arithmetic (a difference of sums of hundreds) in another order.
    Which leaf and which threshold win is equal, as the structure
    shows."""
    assert len(bs.models) == len(bm.models) > 0
    close = _GAINS if integer_sums else _GAINS + _SUMS
    for ts, tm in zip(bs.models, bm.models):
        fs, fm = _tree_fields(ts), _tree_fields(tm)
        assert ts.num_leaves > 1 and set(close) <= set(fs)
        for key in close:
            np.testing.assert_allclose(
                np.array(fs.pop(key).split(), dtype=float),
                np.array(fm.pop(key).split(), dtype=float),
                rtol=2e-5, atol=1e-4 if key in _GAINS else 1e-7,
                err_msg=key)
        assert fs == fm
    if integer_sums:
        assert np.array_equal(_scores_bits(bs), _scores_bits(bm))
    else:
        np.testing.assert_allclose(np.asarray(bs.train_score),
                                   np.asarray(bm.train_score),
                                   rtol=2e-5, atol=1e-6)


class TestSerialVsMeshParity:
    """The serial learner, which nearly every CPU test grows its trees
    through, against ``tree_learner=data`` on a one-device mesh, which
    every cell of the benchmark runs: one split step and one whole-tree
    loop (treelearner/grow.py), so the same trees and scores, bit for
    bit where ``_assert_same_trees_and_scores`` says the pair can give
    it. Their row and feature padding differ (4096-row blocks and
    8-feature blocks against none), and so do their compaction
    buckets."""

    @pytest.mark.parametrize("extra", [
        pytest.param({}, id="exact"),
        pytest.param({"use_quantized_grad": True}, id="quantized8"),
        pytest.param({"use_quantized_grad": True,
                      "quant_grad_bits": 16}, id="quantized16"),
        pytest.param({"bagging_fraction": 0.7, "bagging_freq": 1},
                     id="bagging"),
        # heaviest cell of the matrix (extra_trees retraces the split
        # kernel); the randomized-threshold path keeps dedicated
        # coverage in the slow tier
        pytest.param({"extra_trees": True}, id="extra_trees",
                     marks=pytest.mark.slow),
        pytest.param({"monotone_constraints": [1, -1, 0, 0, 0, 0]},
                     id="basic_monotone"),
    ])
    def test_same_trees_and_scores(self, extra):
        X, y = _data()
        bs, bm = _train_pair(dict(BASE, **extra), X, y)
        _assert_same_trees_and_scores(
            bs, bm, integer_sums="use_quantized_grad" in extra)

    def test_multiclass(self):
        # noisy labels: on separable ones a class's gradients flatten
        # after a few splits, its gains sink to rounding noise, and
        # whether one is above zero is a matter of the last bit
        rng = np.random.RandomState(5)
        X = rng.randn(700, 5)
        y = np.argmax(X[:, :3] + 0.5 * rng.randn(700, 3),
                      axis=1).astype(np.float64)
        params = dict(BASE, objective="multiclass", num_class=3,
                      bin_construct_sample_cnt=700)
        bs, bm = _train_pair(params, X, y)
        assert len(bs.models) == 9
        _assert_same_trees_and_scores(bs, bm)

    def test_onehot_histogram(self):
        """``hist_backend=onehot``: the histogram is a contraction over
        the bucket's rows, and the two learners' buckets differ in size
        (2,048 rows against 400 here): one more float32 sum whose order
        may differ, under the same tolerance."""
        X, y = _data()
        bs, bm = _train_pair(dict(BASE, hist_backend="onehot"), X, y)
        _assert_same_trees_and_scores(bs, bm)

    def test_forced_splits_then_growth(self, tmp_path):
        """A forced-split preamble hands the frontier to the whole-tree
        loop mid-tree (start_leaf > 1): the forced root and its forced
        left child hold, growth continues to num_leaves, and two runs
        give the same trees. Serial only: the mesh learners ignore
        forced splits, with a warning."""
        path = tmp_path / "forced.json"
        path.write_text(json.dumps(
            {"feature": 0, "threshold": 0.0,
             "left": {"feature": 1, "threshold": 0.0}}))
        X, y = _data()
        params = dict(BASE, forcedsplits_filename=str(path), **SERIAL)
        first = _train_matrix(params, X, y)
        again = _train_matrix(params, X, y)
        assert [t.to_string() for t in first.models] == \
            [t.to_string() for t in again.models]
        free = _train_matrix(dict(BASE, **SERIAL), X, y)
        t0 = first.models[0]
        assert t0.num_leaves == BASE["num_leaves"]
        # node 0 is the forced root, node 1 its forced left child
        assert int(t0.split_feature[0]) == 0
        assert int(t0.left_child[0]) == 1
        assert int(t0.split_feature[1]) == 1
        for node, feature in ((0, 0), (1, 1)):
            mapper = first.learner.dataset.bin_mappers[feature]
            assert int(t0.threshold_in_bin[node]) == int(
                mapper.value_to_bin(np.asarray([0.0]))[0])
        # and it is the forcing that put them there
        assert free.models[0].to_string() != t0.to_string()


# ---------------------------------------------------------------------------
# dispatch-count regression: ≤ 3 grow dispatches per tree (trace spans)
# ---------------------------------------------------------------------------

GROW_SCOPES = ("tree::stage_gh", "tree::root_histogram",
               "tree::split_batches")


class TestDispatchCount:
    def test_fused_le3_dispatches_per_tree_from_trace(self, tmp_path):
        """Exported trace spans: each tree::grow span contains exactly
        one stage_gh + one root_histogram + ONE split_batches span."""
        path = str(tmp_path / "trace.json")
        registry.reset()
        registry.enable(sampling=True)
        obs_trace.configure(path)
        X, y = _data(600)
        iters = 3
        _train_matrix(dict(BASE, num_leaves=31), X, y, iters=iters)
        obs_trace.flush()
        doc = trace_report.load_trace(path)
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        n_grow = sum(1 for e in spans if e["name"] == "tree::grow")
        assert n_grow == iters
        for scope in GROW_SCOPES:
            n = sum(1 for e in spans if e["name"] == scope)
            assert n == iters, (scope, n)
        per_tree = sum(1 for e in spans
                       if e["name"] in GROW_SCOPES) / iters
        assert per_tree <= 3.0


# ---------------------------------------------------------------------------
# sharded K-splits-per-sweep: parity + staging cut
# ---------------------------------------------------------------------------

class TestShardedFrontierBatch:
    def _source(self, X, y, chunk=300):
        def src():
            for lo in range(0, X.shape[0], chunk):
                yield X[lo:lo + chunk], y[lo:lo + chunk].astype(
                    np.float32)
        return src

    @pytest.mark.parametrize("extra", [
        {}, {"use_quantized_grad": True},
    ], ids=["exact", "quantized8"])
    def test_kbatch_bit_identical_and_fewer_stagings(self, tmp_path,
                                                     extra):
        """K pending splits per sweep: bit-identical trees AND scores
        vs in-memory training (the K=1 contract of
        tests/test_shards.py), with strictly fewer shard stagings —
        the validated speculation must accept multi-split rounds on
        this fixture, and rejected-slot reverts must leave the final
        partition exact (scores are bit-compared)."""
        X, y = _data(1000)
        params = dict(BASE, tpu_frontier_splits=8, **extra)
        ds_mem = BinnedDataset.from_matrix(
            X, Config.from_params(dict(params)), label=y)
        b_mem = _train(ds_mem, params, iters=4)
        registry.reset()
        registry.enable()
        ds_sh = ShardedBinnedDataset.from_chunk_source(
            self._source(X, y), Config.from_params(dict(params)),
            str(tmp_path), shard_rows=334, total_rows=1000)
        b_sh = _train(ds_sh, params, iters=4)
        staged = registry.count("io/shards_staged")
        registry.disable()
        assert b_sh.save_model_to_string() == b_mem.save_model_to_string()
        assert np.array_equal(_scores_bits(b_sh), _scores_bits(b_mem))
        # one-split-per-sweep would stage shards x sweeps = 3 x 15 x 4
        # = 180; the K-batch must come in well under
        assert staged < 150, staged

    def test_k1_matches_k8(self, tmp_path):
        X, y = _data(1000)
        boosters = {}
        for K in (1, 8):
            params = dict(BASE, tpu_frontier_splits=K)
            ds = ShardedBinnedDataset.from_chunk_source(
                self._source(X, y), Config.from_params(dict(params)),
                str(tmp_path / str(K)), shard_rows=400,
                total_rows=1000)
            boosters[K] = _train(ds, params, iters=3)
        assert [t.to_string() for t in boosters[1].models] == \
            [t.to_string() for t in boosters[8].models]


# ---------------------------------------------------------------------------
# batched iterations x quantized gradients (the gating lift)
# ---------------------------------------------------------------------------

def _assert_trees_match(t1, t2):
    """The documented batched-path tolerance (tests/
    test_batched_training.py), widened on gains and values for
    quantized mode: the f32-lr-on-device score drift can flip
    individual stochastic-rounding draws, which nudges gains and
    small-hessian leaf outputs while structure and counts stay
    exactly equal."""
    assert t1.num_leaves == t2.num_leaves
    ni = t1.num_internal
    np.testing.assert_array_equal(t1.split_feature[:ni],
                                  t2.split_feature[:ni])
    np.testing.assert_array_equal(t1.threshold_in_bin[:ni],
                                  t2.threshold_in_bin[:ni])
    np.testing.assert_array_equal(t1.leaf_count[:t1.num_leaves],
                                  t2.leaf_count[:t2.num_leaves])
    np.testing.assert_allclose(t1.leaf_value[:t1.num_leaves],
                               t2.leaf_value[:t2.num_leaves],
                               rtol=2e-3, atol=1e-6)
    np.testing.assert_allclose(t1.split_gain[:ni], t2.split_gain[:ni],
                               rtol=1e-3, atol=1e-3)


def _make_mesh_booster(extra, n=2000, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 8).astype(np.float32)
    y = (X[:, 0] + 0.7 * X[:, 1] + 0.2 * rng.randn(n) > 0).astype(float)
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
              "min_data_in_leaf": 20, "tree_learner": "data",
              "mesh_shape": "data=1"}
    params.update(extra)
    return (lgb.Booster(params=params,
                        train_set=lgb.Dataset(X, label=y)), X, y)


class TestQuantizedBatched:
    @pytest.mark.parametrize("extra", [
        pytest.param({"use_quantized_grad": True}, id="quantized8"),
        # ~50s and redundant with quantized8 for the batched-vs-looped
        # property (only the grad dtype widens): slow tier keeps it
        pytest.param({"use_quantized_grad": True,
                      "quant_grad_bits": 16}, id="quantized16",
                     marks=pytest.mark.slow),
        pytest.param({"use_quantized_grad": True,
                      "bagging_fraction": 0.7, "bagging_freq": 1},
                     id="quantized8-bagging"),
    ])
    def test_batched_matches_looped(self, extra):
        a, X, y = _make_mesh_booster(extra)
        b, _, _ = _make_mesh_booster(extra)
        a.update()
        b.update()
        assert a.inner.can_train_batched()  # the lifted exclusion
        assert not a.inner.train_batch(4)
        for _ in range(4):
            b.update()
        assert len(a.inner.models) == len(b.inner.models) == 5
        for t1, t2 in zip(a.inner.models, b.inner.models):
            _assert_trees_match(t1, t2)
        # the device tree counter advanced through the scan: the NEXT
        # looped tree must draw the key the all-looped path draws
        a.update()
        b.update()
        _assert_trees_match(a.inner.models[-1], b.inner.models[-1])

    def test_multiclass_quantized_batched(self):
        rng = np.random.RandomState(41)
        X = rng.randn(1500, 6).astype(np.float32)
        y = np.argmax(X[:, :3] + 0.3 * rng.randn(1500, 3),
                      axis=1).astype(float)
        params = {"objective": "multiclass", "num_class": 3,
                  "verbosity": -1, "num_leaves": 15,
                  "min_data_in_leaf": 30, "tree_learner": "data",
                  "mesh_shape": "data=1", "use_quantized_grad": True}
        a = lgb.Booster(params=params,
                        train_set=lgb.Dataset(X, label=y))
        b = lgb.Booster(params=dict(params),
                        train_set=lgb.Dataset(X, label=y))
        a.update()
        b.update()
        assert a.inner.can_train_batched()
        a.inner.train_batch(3)
        for _ in range(3):
            b.update()
        assert len(a.inner.models) == len(b.inner.models) == 12
        for t1, t2 in zip(a.inner.models, b.inner.models):
            _assert_trees_match(t1, t2)

    def test_recheck_event_at_transition(self, tmp_path):
        """A quantized run that leaves batched mode mid-run re-verifies
        the device scores once: one batched_eval_recheck event with a
        sub-tolerance deviation."""
        log_path = str(tmp_path / "ev.jsonl")
        obs_events.configure(log_path)
        try:
            rng = np.random.RandomState(0)
            X = rng.randn(1200, 6).astype(np.float32)
            y = (X[:, 0] + 0.3 * rng.randn(1200) > 0).astype(float)
            # 6 rounds at batch 3: iter0 looped, one batch of 3, then a
            # 2-iteration looped tail -> exactly one transition
            lgb.train({"objective": "binary", "verbosity": -1,
                       "num_leaves": 15, "use_quantized_grad": True,
                       "tpu_batch_iterations": 3,
                       "tree_learner": "data", "mesh_shape": "data=1"},
                      lgb.Dataset(X, label=y), num_boost_round=6)
        finally:
            obs_events.configure(None)
        evs = [json.loads(line) for line in open(log_path)]
        rec = [e for e in evs if e.get("event") == "batched_eval_recheck"]
        assert len(rec) == 1
        assert rec[0]["reason"] == "batched_to_looped"
        assert rec[0]["ok"] is True


# ---------------------------------------------------------------------------
# transfer-guard sanitizer over a warmed FUSED iteration
# ---------------------------------------------------------------------------

class TestFusedTransferGuard:
    @pytest.mark.parametrize("extra", [
        {}, {"use_quantized_grad": True},
    ], ids=["exact", "quantized8"])
    def test_warmed_fused_iteration_no_implicit_transfers(self, extra):
        """The fused grow loop performs no implicit host transfers: the
        only per-tree hops are the explicit record read-back and the
        utils/scalars device scalars — and with the device-side tree
        counter, quantized staging performs NO per-tree seed transfer
        at all."""
        import jax
        X, y = _data(500)
        params = dict(BASE, num_leaves=7, **extra)
        ds = BinnedDataset.from_matrix(
            X, Config.from_params(dict(params)), label=y)
        booster = create_boosting(
            Config.from_params(dict(params, num_iterations=10)), ds)
        for _ in range(2):
            booster.train_one_iter()
        with jax.transfer_guard("disallow"):
            booster.train_one_iter()
        assert booster.iter == 3
