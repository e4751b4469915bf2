"""Under a bag the grower orders the in-bag rows alone.

Where a tree is grown over a bag (GOSS after its unsampled iterations,
``bagging_fraction`` < 1), ``treelearner/grow.py`` ``make_root_state``
builds leaf 0's segment of ``GrowState.order`` from the in-bag rows, so
every window of ``_partition_order`` and every tile of
``_compact_child_hist`` after it visits rows that carry weight, and
``gh``'s last channel counts those rows. Held here for the learner the
benchmark's cells run (a mesh of one device, looped and in the
``tpu_batch_iterations`` scan) and the serial learner, under a fresh
GOSS bag with amplified rows and a bagging indicator kept across trees:

- the root's segment is exactly the in-bag rows, ascending, and after a
  whole tree every leaf's segment is exactly its in-bag rows;
- out-of-bag rows keep their leaf, so the scores are a host walk of the
  model over every row;
- the trees are those of the learner that histograms the masked row
  space (a mesh of two devices), to float32 rounding in leaf values;
- without a bag the trees are those of the whole-row order, bit for bit;
- over EFB bundles the unpacked zero bins are the per-feature
  histogram's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.obs.registry import registry
from lightgbm_tpu.parallel import DataParallelTreeLearner, make_mesh
from lightgbm_tpu.treelearner.serial import SerialTreeLearner

ROWS, FEATURES, LEAVES = 2048, 6, 15
BASE = {"objective": "binary", "num_leaves": LEAVES, "verbosity": -1,
        "min_data_in_leaf": 5, "bin_construct_sample_cnt": ROWS,
        "learning_rate": 0.5}
# GOSS samples from the third iteration on (1 / learning_rate); the
# bagging indicator is drawn every second iteration and kept between
SAMPLING = {
    "goss": {"data_sample_strategy": "goss", "top_rate": 0.2,
             "other_rate": 0.1},
    "bagging": {"bagging_fraction": 0.6, "bagging_freq": 2},
}
LEARNERS = {
    "mesh": {"tree_learner": "data", "mesh_shape": "data=1"},
    "serial": {"tree_learner": "serial"},
    "scan": {"tree_learner": "data", "mesh_shape": "data=1"},
    "masked": {"tree_learner": "data", "mesh_shape": "data=2"},
}
ITERS = 5


def _data(rows=ROWS, features=FEATURES, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, features)
    y = (X[:, 0] - 0.8 * X[:, 1] + 0.5 * rng.randn(rows) > 0) \
        .astype(np.float64)
    return X, y


def _spy(learner, seen):
    """Record, for every tree the learner grows, the grow state at the
    root and after the whole tree (as host arrays)."""
    def record(root, state):
        seen.append({"root_order": root.order,
                     "root_count": root.seg_count,
                     **{k: getattr(state, k) for k in (
                         "order", "seg_begin", "seg_count", "leaf_of_row",
                         "gh", "hists")}})

    if isinstance(learner, SerialTreeLearner):
        fused = learner._fused_fn

        def fused_spy():
            fn = fused()

            def run(bins, bins_fm, state, *args):
                root = jax.device_get(state)
                state, recs = fn(bins, bins_fm, state, *args)
                record(root, jax.device_get(state))
                return state, recs
            return run
        learner._fused_fn = fused_spy
        return

    # the mesh learner's whole-tree program, looped and inside the scan
    # alike: the states leave it through a host callback
    tree_impl = learner._tree_impl

    def _tree_impl(bins, bins_fm, state, *args):
        out, recs = tree_impl(bins, bins_fm, state, *args)
        jax.debug.callback(
            lambda root, out: record(*jax.device_get((root, out))),
            state, out, ordered=True)
        return out, recs
    learner._tree_impl = _tree_impl
    # programs built before the spy would not call it
    learner._root_fn = learner._many_fn = None


def _booster(learner, sampling, X, y, seen=None):
    params = dict(BASE, **SAMPLING[sampling], **LEARNERS[learner])
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y))
    if seen is not None:
        _spy(bst.inner.learner, seen)
    if learner == "scan":
        bst.update()
        assert bst.inner.can_train_batched()
        assert not bst.inner.train_batch(ITERS - 1)
    else:
        for _ in range(ITERS):
            bst.update()
    assert len(bst.inner.models) == ITERS
    return bst


def _segment(state, leaf):
    begin, count = int(state["seg_begin"][leaf]), \
        int(state["seg_count"][leaf])
    return state["order"][begin:begin + count]


@pytest.mark.parametrize("sampling", sorted(SAMPLING))
@pytest.mark.parametrize("learner", ["mesh", "serial", "scan"])
def test_segments_hold_the_in_bag_rows(learner, sampling, timer_on):
    X, y = _data()
    seen = []
    before = registry.count("grow/rows_ordered")
    bst = _booster(learner, sampling, X, y, seen)
    assert len(seen) == ITERS
    bagged = 0
    for tree, state in zip(bst.inner.models, seen):
        gh = state["gh"]
        in_bag = gh[:, 2] != 0          # pad rows carry 0 in every channel
        # the last channel counts the rows the grower orders
        np.testing.assert_array_equal(gh[:, 3], gh[:, 2] != 0)
        bagged += int(in_bag[:ROWS].sum() < ROWS)
        rows = np.flatnonzero(in_bag)
        assert int(state["root_count"][0]) == len(rows)
        np.testing.assert_array_equal(
            state["root_order"][:len(rows)], rows)
        part = state["leaf_of_row"]
        # out-of-bag rows keep a leaf; only the pad rows carry none
        assert (part[:ROWS] >= 0).all() and (part[ROWS:] < 0).all()
        assert tree.num_leaves > 1
        for leaf in range(tree.num_leaves):
            want = np.flatnonzero((part == leaf) & in_bag)
            np.testing.assert_array_equal(_segment(state, leaf), want)
            assert tree.leaf_count[leaf] == len(want)
    # GOSS leaves its first two trees and bagging none unsampled
    assert bagged == (ITERS - 2 if sampling == "goss" else ITERS)
    # the scores the partition gave every row are a host walk of the
    # model over every row
    np.testing.assert_allclose(
        np.asarray(bst.inner.train_score)[:, 0],
        bst.predict(X, raw_score=True), rtol=1e-5, atol=1e-5)
    if learner == "mesh":
        assert registry.count("grow/rows_ordered") - before == sum(
            int(s["root_count"][0]) for s in seen)


def _assert_same_splits(a, b):
    for ta, tb in zip(a.inner.models, b.inner.models):
        n = ta.num_internal
        assert ta.num_leaves == tb.num_leaves
        np.testing.assert_array_equal(ta.split_feature[:n],
                                      tb.split_feature[:n])
        np.testing.assert_array_equal(ta.threshold_in_bin[:n],
                                      tb.threshold_in_bin[:n])
        np.testing.assert_array_equal(ta.leaf_count[:ta.num_leaves],
                                      tb.leaf_count[:tb.num_leaves])
        np.testing.assert_allclose(ta.leaf_value[:ta.num_leaves],
                                   tb.leaf_value[:tb.num_leaves],
                                   rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("sampling", sorted(SAMPLING))
@pytest.mark.parametrize("learner", ["mesh", "serial"])
def test_trees_are_those_of_the_masked_row_space(learner, sampling):
    """The mesh of two devices does not compact: its histograms run over
    the masked rows, out-of-bag ones included, and its total channel
    counts every row."""
    X, y = _data()
    masked = _booster("masked", sampling, X, y)
    assert not masked.inner.learner._compacts()
    _assert_same_splits(_booster(learner, sampling, X, y), masked)


def _learner(kind, X, y):
    params = dict(BASE, **LEARNERS[kind])
    ds = BinnedDataset.from_matrix(X, Config.from_params(dict(params)),
                                   label=y)
    config = Config.from_params(params)
    if kind == "serial":
        return SerialTreeLearner(config, ds)
    return DataParallelTreeLearner(config, ds, make_mesh(1))


@pytest.mark.parametrize("learner", ["mesh", "serial"])
def test_no_bag_grows_the_whole_row_orders_trees(learner):
    """A bag of every row orders every row: the tree is the one grown
    with no bag, bit for bit."""
    X, y = _data()
    rng = np.random.RandomState(3)
    grad = jnp.asarray(rng.rand(ROWS).astype(np.float32) - y)
    hess = jnp.asarray(0.1 + rng.rand(ROWS).astype(np.float32))
    trees = []
    for bag in (None, jnp.ones(ROWS, dtype=jnp.float32)):
        tree, part = _learner(learner, X, y).train(grad, hess, bag)
        trees.append((tree.to_string(), np.asarray(part)))
    assert trees[0][0] == trees[1][0]
    np.testing.assert_array_equal(trees[0][1], trees[1][1])


def test_bundled_zero_bins_are_the_per_feature_histograms():
    """One-hot columns bundled by EFB: the unpack rebuilds a member's
    zero bin from its leaf's totals less its other bins, so the totals'
    last channel has to count the rows the tiles visit, the in-bag ones.
    Every leaf's stored histogram is the per-feature histogram of its
    in-bag rows, zero bins included, counts exactly."""
    rng = np.random.RandomState(11)
    levels = 12
    level = rng.randint(0, levels, ROWS)
    dense = rng.randn(ROWS, 2)
    X = np.concatenate([dense, np.eye(levels)[level]], axis=1)
    y = (dense[:, 0] + (level % 3 == 0) + 0.5 * rng.randn(ROWS) > 0.5) \
        .astype(np.float64)
    params = dict(BASE, **SAMPLING["bagging"], **LEARNERS["mesh"])
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y))
    lrn = bst.inner.learner
    assert lrn._bundled and lrn._compacts()
    seen = []
    _spy(lrn, seen)
    bst.update()
    state, tree = seen[0], bst.inner.models[0]
    gh = state["gh"].astype(np.float64)
    in_bag = gh[:, 2] != 0
    assert in_bag.sum() < ROWS
    bins = lrn.dataset.feature_bins()
    for leaf in range(tree.num_leaves):
        rows = np.flatnonzero((state["leaf_of_row"] == leaf) & in_bag)
        got = state["hists"][leaf][:lrn.F].astype(np.float64)
        want = np.zeros_like(got)
        for f in range(lrn.F):
            np.add.at(want[f], bins[rows, f], gh[rows])
        np.testing.assert_array_equal(got[..., 2:], want[..., 2:])
        np.testing.assert_allclose(got[..., :2], want[..., :2],
                                   rtol=1e-4, atol=1e-4)
