"""One-hot sparse tables through Exclusive Feature Bundling on the learner
the benchmark's cells run (a mesh of one device), as ``allstate-train``
runs them, at a small size:

- trees and scores follow the plain statement of the one-hot table
  (``benchmark/reference/gbdt_onehot.py``: an indicator's bin 1 where the
  row holds its level, bin 0 the leaf's total less bin 1);
- the zero bin of a bundled feature is rebuilt exactly in the integer path;
- the bundling budget, upstream's default ``max_conflict_rate`` 0.0,
  bundles no two columns that meet in a sampled row, and the former
  budget of 1e-4 bounds the sampled conflicts by its own count;
- a row in conflict keeps the member of the higher column, in the program
  and in the reference;
- nothing compiles in a window of 20 iterations, and the validation walk
  over the bundle bins gives the host's predictions;
- the counters ``efb/*`` and the scope ``obs_unpack`` say what ran.
"""
import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from benchmark.generators import onehot_sparse
from benchmark.harness import program
from benchmark.harness.train import CompileCounter
from benchmark.reference import gbdt_onehot
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io import efb
from lightgbm_tpu.obs.registry import registry
from lightgbm_tpu.ops.histogram import unpack_bundle_histogram
from lightgbm_tpu.treelearner.grow import build_bundle_tables

ROWS, HOLD, LEAVES, STEPS = 4000, 1000, 15, 3
DATA = {"table_seed": 44, "dense": 3, "zipf": 1.0,
        "categoricals": [["A", 40], ["B", 120], ["C", 60], ["D", 8],
                         ["E", 50], ["F", 17]],
        "nested": [["B", "E"]], "effects": ["A", "B", "D"],
        "effect_scale": 0.6, "weight_scale": 0.6, "interaction": 0.5,
        "noise": 0.5, "heavy_tail_every": 4, "heavy_tail_power": 1.5}
FEATURES = 3 + 40 + 120 + 60 + 8 + 50 + 17
PARAMS = {"objective": "binary", "num_leaves": LEAVES, "learning_rate": 0.1,
          "min_data_in_leaf": 0, "min_sum_hessian_in_leaf": 5.0,
          "max_bin": 255, "verbosity": -1, "metric": "auc",
          "bin_construct_sample_cnt": 1000, "tree_learner": "data",
          "mesh_shape": "data=1"}
REF_DEFAULTS = dict(lambda_l2=0.0, min_data_in_bin=3, data_random_seed=1,
                    enable_bundle=True, max_conflict_rate=0.0)
# Largest gap over the training rows' scores at this size (CPU): 2.7e-6
# after three steps, 1.7e-6 over the held-out rows (the program's CPU
# histograms add a leaf's rows one after another in float32, the
# reference's in blocks); a tree that parts from the reference moves some
# rows by a leaf value, 1e-2 or more.
SCORE_ATOL = 1e-5


def _table(rows=ROWS, hold=HOLD):
    X, y, _ = onehot_sparse.make_table(rows + hold, FEATURES, 1, DATA)
    return X[:rows], y[:rows], X[rows:], y[rows:]


def _booster(X, y, X_valid=None, y_valid=None, **changed):
    params = dict(PARAMS, **changed)
    train_set = lgb.Dataset(X, label=y, params=dict(params)).construct()
    bst = lgb.Booster(params=params, train_set=train_set)
    if X_valid is not None:
        bst.add_valid(lgb.Dataset(X_valid, label=y_valid,
                                  reference=train_set).construct(), "test")
    return bst


def _scores(bst):
    return np.asarray(bst.inner.train_score, dtype=np.float32).reshape(-1)


@pytest.fixture(scope="module")
def grown():
    X, y, X_hold, y_hold = _table()
    was = registry.timer.enabled
    registry.timer.enable()
    before = dict(registry.counters)
    try:
        bst = _booster(X, y, X_hold, y_hold)
        scores = []
        for _ in range(STEPS):
            bst.update()
            bst.eval_valid()
            scores.append(_scores(bst).copy())
    finally:
        registry.timer.enabled = was
    moved = {k: v - before.get(k, 0) for k, v in registry.counters.items()
             if v != before.get(k, 0)}
    assert type(bst.inner.learner).__name__ == "DataParallelTreeLearner"
    return bst, scores, X, y, X_hold, moved


@pytest.fixture(scope="module")
def reference():
    X, y, X_hold, _ = _table()
    ref = gbdt_onehot.Reference(X, y, gbdt_onehot.Params.from_dict(
        dict(PARAMS, **REF_DEFAULTS)))
    return ref, [ref.step() for _ in range(STEPS)], ref.predict_raw(X_hold)


def test_trees_and_scores_follow_the_one_hot_statement(grown, reference):
    bst, scores, X, y, X_hold, _ = grown
    ref, ref_scores, ref_hold = reference
    ds = bst.inner.train_data
    assert ds.bundle is not None and ds.num_features == ref.D + ref.I
    column = lambda f: (ref.dense_cols[f] if f < ref.D
                        else ref.ind_cols[f - ref.D])
    for k in range(STEPS):
        tree, want = bst.inner.models[k], ref.trees[k]
        n = len(want.leaf)
        assert tree.num_leaves == n + 1 == LEAVES
        assert list(tree.split_feature[:n]) == [column(f)
                                                for f in want.feature]
        np.testing.assert_allclose(scores[k], ref_scores[k], rtol=0,
                                   atol=SCORE_ATOL)
    # indicators are split on, not the numerical columns alone
    assert any(f >= ref.D for t in ref.trees for f in t.feature)
    got_hold = np.asarray(bst.predict(X_hold, raw_score=True,
                                      predict_on_device=False))
    np.testing.assert_allclose(got_hold, ref_hold, rtol=0, atol=SCORE_ATOL)


def test_the_validation_walk_over_bundle_bins_is_the_hosts(grown):
    bst, _, _, _, X_hold, moved = grown
    vd = bst.inner.valid_data[0]
    walked = np.asarray(vd.scores_dev, dtype=np.float64).reshape(-1)
    host = np.asarray(bst.predict(X_hold, raw_score=True,
                                  predict_on_device=False))
    np.testing.assert_allclose(walked, host, rtol=0, atol=1e-5)
    # trees over bundles take the lockstep walk, never all nodes at once
    assert moved["valid/trees_walked"] == STEPS
    assert "valid/trees_all_nodes" not in moved
    assert moved["valid/walk_hops_run"] >= moved["valid/walk_hops_needed"] > 0


def test_counters_say_what_was_bundled_and_unpacked(grown, reference):
    bst, _, _, _, _, moved = grown
    lrn, ds = bst.inner.learner, bst.inner.train_data
    lay = ds.bundle
    assert moved["efb/groups"] == lay.num_groups == ds.bins.shape[1]
    assert moved["efb/features_bundled"] == sum(
        len(g) for g in lay.groups if len(g) > 1)
    assert moved.get("efb/conflict_rows", 0) == reference[0].conflict_rows
    splits = sum(t.num_leaves - 1 for t in bst.inner.models)
    calls = STEPS + splits
    assert moved["efb/unpacks"] == calls
    # the features' own bins and the bundles' own bins, not the store's
    # padded F x B
    entries = int(ds.num_bin_per_feature.sum())
    bundle_entries = sum(
        int(ds.num_bin_per_feature[g[0]]) if len(g) == 1
        else 1 + sum(int(ds.num_bin_per_feature[f]) - 1 for f in g)
        for g in lay.groups)
    assert entries < lrn.F * lrn.B and bundle_entries < entries
    assert moved["efb/unpacked_entries"] == calls * entries
    assert moved["efb/bundle_entries"] == calls * bundle_entries


def test_unpack_is_named_on_the_device_clock(grown):
    bst = grown[0]
    lrn = bst.inner.learner
    gh = jnp.zeros((lrn.R, 4), jnp.float32)
    mask = jnp.ones(lrn.F, bool)
    text = jax.jit(lrn._root_impl).lower(
        lrn.bins, gh, mask, jnp.int32(0), lrn._qs_ones).as_text(
            debug_info=True)
    assert "obs_unpack" in text


def _no_conflict_table(seed=3):
    """Indicators of three categorical columns, mutually exclusive within a
    column, and two numerical columns: bundles hold no conflict."""
    X, y, _ = onehot_sparse.make_table(
        3000, 2 + 30 + 20 + 9, seed,
        dict(DATA, dense=2, categoricals=[["A", 30], ["B", 20], ["C", 9]],
             nested=[], effects=["A"]))
    return X, y


@pytest.mark.parametrize("bits", [8, 16])
def test_the_zero_bin_is_rebuilt_exactly_in_integers(bits):
    X, y = _no_conflict_table()
    cfg = Config.from_params({"verbosity": -1, "max_bin": 255,
                              "min_data_in_leaf": 0})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    lay = ds.bundle
    assert lay is not None and any(len(g) > 1 for g in lay.groups)
    rng = np.random.RandomState(bits)
    lim = 2 ** (bits - 1) - 1
    gh = rng.randint(-lim, lim + 1, size=(ds.num_data, 4)).astype(np.int64)
    gh[:, 2:] = 1
    Bg, B = 256, 256
    bhist = np.zeros((lay.num_groups, Bg, 4), np.int64)
    for g in range(lay.num_groups):
        np.add.at(bhist[g], ds.bins[:, g].astype(np.int64), gh)
    want = np.zeros((ds.num_features, B, 4), np.int64)
    per_feature = ds.feature_bins()
    for f in range(ds.num_features):
        np.add.at(want[f], per_feature[:, f].astype(np.int64), gh)
    btab = build_bundle_tables(ds, ds.num_features)
    zero_bins = jnp.asarray([m.default_bin for m in ds.bin_mappers],
                            jnp.int32)
    got = unpack_bundle_histogram(
        jnp.asarray(bhist, jnp.int32), btab.group_of, btab.first_bin,
        btab.num_bins, btab.zero_fix, zero_bins, None, B)
    np.testing.assert_array_equal(np.asarray(got), want)


def _sampled_masks(ds, X, sample_cnt):
    """Per used feature, its sampled rows away from its zero bin (the
    table's columns are indicators and dense columns with no zero)."""
    rows = np.sort(np.random.RandomState(1).choice(
        X.shape[0], sample_cnt, replace=False))
    held = X[rows].toarray() != 0
    return [held[:, f] for f in ds.used_feature_map]


@pytest.mark.parametrize("rate", [0.0, 1e-4])
def test_max_conflict_rate_bounds_the_sampled_conflicts(rate, monkeypatch):
    # two columns of many rare levels: bundles whose members meet; 1e-4 is
    # the former budget, planted as the benchmark's fault is
    monkeypatch.setattr(efb, "MAX_CONFLICT_RATE", rate)
    X, y, _ = onehot_sparse.make_table(30000, 3 + 600 + 900 + 5, 2, dict(
        DATA, categoricals=[["A", 600], ["B", 900], ["C", 5]], nested=[],
        effects=["A"]))
    sample = 20000
    cfg = Config.from_params({"verbosity": -1,
                              "bin_construct_sample_cnt": sample})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    masks = _sampled_masks(ds, X, sample)
    met = []
    for group in ds.bundle.groups:
        if len(group) > 1:
            count = np.sum([masks[f] for f in group], axis=0)
            met.append(int((count > 1).sum()))
    assert max(met) <= int(rate * sample)
    # the budget is the one find_groups applies to the sample's masks
    cand = [m if m.mean() <= 0.3 else None for m in masks]
    assert ds.bundle.groups == efb.find_groups(
        cand, ds.num_bin_per_feature, sample, max(ds.max_num_bin, 256))


def test_the_default_rate_is_upstreams():
    assert efb.MAX_CONFLICT_RATE == 0.0


def _conflict_table():
    """Two indicators that never meet in the bundling sample (its first
    half of rows, by ``bin_construct_sample_cnt`` and the seed) and meet
    in ``MEET`` rows outside it, beside a dense column."""
    n = 400
    sample = np.sort(np.random.RandomState(1).choice(n, 200, replace=False))
    outside = np.setdiff1d(np.arange(n), sample)
    a = np.zeros(n, bool)
    b = np.zeros(n, bool)
    a[sample[:40]] = True
    b[sample[40:80]] = True
    meet = outside[:MEET]
    a[meet] = b[meet] = True
    X = np.zeros((n, 3), np.float32)
    X[:, 0] = np.random.RandomState(0).randn(n)
    X[:, 1], X[:, 2] = a, b
    y = (X[:, 0] + a - b > 0).astype(np.float32)
    return sp.csr_matrix(X), y, meet


MEET = 7


def test_a_row_in_conflict_keeps_the_higher_column(timer_on):
    X, y, meet = _conflict_table()
    before = registry.counters.get("efb/conflict_rows", 0)
    cfg = Config.from_params({"verbosity": -1, "min_data_in_bin": 1,
                              "bin_construct_sample_cnt": 200})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    assert [sorted(g) for g in ds.bundle.groups if len(g) > 1] == [[1, 2]]
    assert registry.counters["efb/conflict_rows"] - before == MEET
    low, high = ds.feature_bin_column(1), ds.feature_bin_column(2)
    assert (low[meet] == 0).all() and (high[meet] == 1).all()
    ref = gbdt_onehot.Reference(X, y, gbdt_onehot.Params.from_dict(dict(
        PARAMS, **dict(REF_DEFAULTS, min_data_in_bin=1),
        bin_construct_sample_cnt=200)))
    assert ref.conflict_rows == MEET
    rows = np.arange(X.shape[0])
    np.testing.assert_array_equal(ref.holds(0, rows), low == 1)
    np.testing.assert_array_equal(ref.holds(1, rows), high == 1)


def test_no_program_compiles_in_a_window_of_20_iterations():
    X, y, X_hold, y_hold = _table()
    prog = program.Program(dict(PARAMS))
    prog.bin(X, y, X_hold, y_hold)
    prog.build()
    prog.update()
    prog.update()
    prog.warm_validation_walk()
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    counts = program.trace_counts()
    counter.listening = True
    for _ in range(20):
        prog.update()
    prog.wait()
    counter.listening = False
    assert counter.count == 0
    assert program.trace_counts() == counts
