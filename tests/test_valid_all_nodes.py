"""The validation rows scored all nodes at once (``ops/predict.py``): every
node's decision for every row and one product with the tree's leaf-path
matrix give the leaves the lockstep walk and the host walk give, and one
program serves every depth."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.io.binning import MissingType
from lightgbm_tpu.models.tree import Tree
from lightgbm_tpu.obs import compile as obs_compile
from lightgbm_tpu.ops import predict


def _bin_meta(rng, features, bins):
    """(nan_bins, zero_bins, missing_types) with every missing type."""
    missing = rng.choice([MissingType.NONE, MissingType.ZERO,
                          MissingType.NAN], features).astype(np.int32)
    nan_bins = np.full(features, bins - 1, dtype=np.int32)
    zero_bins = rng.randint(0, bins, features).astype(np.int32)
    return nan_bins, zero_bins, missing


def _random_tree(rng, leaves, features, bins, meta, chain=False):
    """A tree grown leaf-wise by random splits (``chain``: always the
    first leaf, so the tree is ``leaves - 1`` deep), ``default_left``
    drawn both ways."""
    tree = Tree(max(leaves, 2))
    for _ in range(leaves - 1):
        leaf = 0 if chain else rng.randint(tree.num_leaves)
        f = rng.randint(features)
        tree.split(leaf=leaf, feature=f, feature_inner=f,
                   threshold_bin=rng.randint(bins - 1), threshold_real=0.0,
                   left_value=rng.randn(), right_value=rng.randn(),
                   left_count=1, right_count=1, left_weight=1.0,
                   right_weight=1.0, gain=1.0, missing_type=int(meta[2][f]),
                   default_left=bool(rng.rand() < 0.5))
    return tree


def _rows(rng, n, features, bins):
    """Bins that hit every NaN and zero bin often."""
    return rng.randint(0, bins, (n, features)).astype(np.uint8)


def _leaves_all_nodes(bins, dtree):
    # a fresh function, so that the module's row block is read at trace
    fn = jax.jit(lambda b, d: predict._traverse_body(b, d, None))
    return np.asarray(fn(jnp.asarray(bins), dtree))


def _leaves_walked(bins, dtree):
    return np.asarray(predict._traverse(jnp.asarray(bins), dtree,
                                        predict._next_pow2(dtree.depth)))


def _both_forms(tree, meta, bins, monkeypatch):
    """The tree as it is scored all nodes at once, and as it walks."""
    all_nodes = predict.build_device_tree(tree, meta, bins)
    with monkeypatch.context() as m:
        m.setattr(predict, "ALL_NODES_MAX_LEAVES", 0)
        walked = predict.build_device_tree(tree, meta, bins)
    assert all_nodes.path is not None and walked.path is None
    return all_nodes, walked


@pytest.mark.parametrize("leaves,rows,bins,chain,block", [
    (2, 1000, 16, False, None),        # one node
    (3, 777, 16, False, None),         # NI 2, NL 4: a padded leaf
    (9, 2049, 64, False, None),        # NI 8, NL 16: seven padded leaves
    (31, 3001, 256, False, None),
    (255, 2500, 256, False, None),     # NI 256 (2 padded), NL 256 (1)
    (255, 1200, 256, True, None),      # a 254-deep chain
    (40, 2501, 32, False, 1000),       # 2,501 rows in blocks of 1,000
    (255, 4099, 256, False, 512),      # the last block overlaps
])
def test_leaves_equal_the_walk_and_the_host(leaves, rows, bins, chain, block,
                                            monkeypatch):
    if block is not None:
        monkeypatch.setattr(predict, "ALL_NODES_ROW_BLOCK", block)
    rng = np.random.RandomState(leaves * 7 + rows)
    features = 12
    meta = _bin_meta(rng, features, bins)
    tree = _random_tree(rng, leaves, features, bins, meta, chain=chain)
    X = _rows(rng, rows, features, bins)
    all_nodes, walked = _both_forms(tree, meta, bins, monkeypatch)
    host = tree.predict_by_bin(X, *meta)
    got = _leaves_all_nodes(X, all_nodes)
    np.testing.assert_array_equal(got, host)
    np.testing.assert_array_equal(got, _leaves_walked(X, walked))
    # the rows reach most leaves of the smaller trees
    if leaves <= 40:
        assert len(np.unique(host)) > leaves // 2


def test_the_leaf_path_matrix_of_a_small_tree():
    """Node 0 splits leaf 0; node 1 splits its right child (leaf 1)."""
    tree = Tree(3)
    for leaf in (0, 1):
        tree.split(leaf=leaf, feature=0, feature_inner=0, threshold_bin=3,
                   threshold_real=0.0, left_value=0.0, right_value=0.0,
                   left_count=1, right_count=1, left_weight=1.0,
                   right_weight=1.0, gain=1.0, missing_type=0,
                   default_left=False)
    path, turns = predict.leaf_path_matrix(tree, 2, 4)
    # leaves 0 | 1, 2 under node 0; leaves 1 | 2 under node 1
    np.testing.assert_array_equal(path, [[1, -1, -1, 0], [0, 1, -1, 0]])
    np.testing.assert_array_equal(turns, [1, 1, 0, -1])


def test_a_stump_is_its_constant():
    from types import SimpleNamespace
    from lightgbm_tpu.boosting.gbdt import _device_tree_outputs
    tree = Tree(2)
    tree.leaf_value[0] = 0.25
    rng = np.random.RandomState(0)
    meta = _bin_meta(rng, 3, 16)
    dataset = SimpleNamespace(num_data=64, max_num_bin=16, bundle=None)
    delta, dtree, trips = _device_tree_outputs(
        tree, jnp.asarray(_rows(rng, 64, 3, 16)), dataset, meta)
    assert dtree is None and trips is None
    np.testing.assert_array_equal(np.asarray(delta), np.full(64, 0.25,
                                                             np.float32))


def test_bins_wider_than_a_byte_walk():
    """Bin 256 and up are not exact in bf16: such trees keep the walk."""
    rng = np.random.RandomState(1)
    meta = _bin_meta(rng, 4, 300)
    tree = _random_tree(rng, 15, 4, 300, meta)
    dtree = predict.build_device_tree(tree, meta, 300)
    assert dtree.path is None and dtree.left is not None
    X = rng.randint(0, 300, (500, 4)).astype(np.uint16)
    leaf, trips = predict.predict_leaf_on_device(jnp.asarray(X), dtree)
    assert trips == predict._next_pow2(dtree.depth)
    np.testing.assert_array_equal(np.asarray(leaf),
                                  tree.predict_by_bin(X, *meta))


def test_a_tree_over_the_leaf_bound_walks(monkeypatch):
    monkeypatch.setattr(predict, "ALL_NODES_MAX_LEAVES", 8)
    rng = np.random.RandomState(2)
    meta = _bin_meta(rng, 4, 16)
    assert predict.build_device_tree(
        _random_tree(rng, 8, 4, 16, meta), meta, 16).path is not None
    assert predict.build_device_tree(
        _random_tree(rng, 9, 4, 16, meta), meta, 16).path is None


def _spy(monkeypatch):
    calls, traverse = [], predict._traverse

    def spy(bins, dtree, trips):
        calls.append((bins.shape[0], trips, dtree.path is not None))
        return traverse(bins, dtree, trips)
    monkeypatch.setattr(predict, "_traverse", spy)
    return calls


def test_a_tree_with_a_categorical_node_walks(monkeypatch):
    rng = np.random.RandomState(3)
    X = rng.randn(3000, 4)
    X[:, 0] = rng.randint(0, 6, 3000)
    y = (np.isin(X[:, 0], [1, 4]) ^ (X[:, 1] > 0)).astype(float)
    Xv = X[:700].copy()
    train = lgb.Dataset(X, label=y, categorical_feature=[0])
    valid = lgb.Dataset(Xv, label=y[:700], reference=train)
    calls = _spy(monkeypatch)
    bst = lgb.train({"objective": "binary", "verbose": -1, "num_leaves": 7,
                     "metric": "auc", "min_data_per_group": 5,
                     "cat_smooth": 1}, train, num_boost_round=3,
                    valid_sets=[valid])
    trees = bst.inner.models
    has_cat = [bool(t.cat_bin_masks) for t in trees]
    assert any(has_cat)
    assert [c[2] for c in calls] == [not h for h in has_cat]
    assert all((trips is None) == all_nodes for _, trips, all_nodes in calls)
    # the incremental validation scores are those of a fresh prediction
    np.testing.assert_allclose(
        bst.inner.valid_data[0].scores[:, 0],
        bst.predict(Xv, raw_score=True), rtol=1e-5, atol=1e-5)


def test_a_bundled_dataset_walks(monkeypatch):
    from test_efb import _sparse_onehot_data
    X, y = _sparse_onehot_data()
    Xv, yv = _sparse_onehot_data(seed=7)
    train = lgb.Dataset(X, label=y)
    valid = lgb.Dataset(Xv, label=yv, reference=train)
    calls = _spy(monkeypatch)
    bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbose": -1,
                     "min_data_in_leaf": 20, "metric": "auc"}, train,
                    num_boost_round=2, valid_sets=[valid])
    assert bst.inner.train_data.bundle is not None
    assert len(calls) == 2 and all(trips is not None and not all_nodes
                                   for _, trips, all_nodes in calls)


def test_numeric_validation_scores_equal_a_fresh_prediction():
    rng = np.random.RandomState(4)
    X, Xv = rng.randn(4000, 6), rng.randn(1111, 6)
    X[rng.rand(4000, 6) < 0.05] = np.nan
    Xv[rng.rand(1111, 6) < 0.05] = np.nan
    X[rng.rand(4000, 6) < 0.1] = 0.0
    label = (np.nan_to_num(X[:, 0]) * X[:, 1] > 0).astype(float)
    train = lgb.Dataset(X, label=label)
    valid = lgb.Dataset(Xv, label=(np.nan_to_num(Xv[:, 0]) > 0)
                        .astype(float), reference=train)
    bst = lgb.train({"objective": "binary", "verbose": -1, "num_leaves": 31,
                     "metric": "auc", "zero_as_missing": False}, train,
                    num_boost_round=4, valid_sets=[valid])
    np.testing.assert_allclose(
        bst.inner.valid_data[0].scores[:, 0],
        bst.predict(Xv, raw_score=True), rtol=1e-5, atol=1e-5)


def _warm_walk(inner):
    """The benchmark's set-up walk: the last tree at every hop count from
    8 to 256, the depth a tree of 255 leaves can have."""
    import copy
    tree = copy.deepcopy(inner.models[-1])
    for depth in (8, 16, 32, 64, 128, 256):
        tree.leaf_depth[:tree.num_leaves] = depth
        inner.valid_data[0]._tree_outputs(tree, inner._bin_meta) \
            .block_until_ready()


def test_one_program_scores_every_depth():
    """The warm walk's six hop counts and a window of trees of other depths
    trace the all-nodes program once."""
    rng = np.random.RandomState(6)
    X, Xv = rng.randn(5000, 7), rng.randn(1537, 7)
    y = (X[:, 0] * X[:, 1] + X[:, 2] + 0.5 * rng.randn(5000) > 0) \
        .astype(float)
    yv = (Xv[:, 0] * Xv[:, 1] + Xv[:, 2] > 0).astype(float)
    train = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params={"objective": "binary", "verbose": -1,
                              "num_leaves": 31, "metric": "auc",
                              "min_data_in_leaf": 2},
                      train_set=train)
    bst.add_valid(lgb.Dataset(Xv, label=yv, reference=train), "test")
    before = dict(obs_compile.trace_counts())
    for _ in range(2):
        bst.update()
        bst.eval_valid()
    _warm_walk(bst.inner)
    warm = dict(obs_compile.trace_counts())
    for _ in range(8):
        bst.update()
        bst.eval_valid()
    after = dict(obs_compile.trace_counts())
    depths = {int(t.leaf_depth[:t.num_leaves].max())
              for t in bst.inner.models}
    assert len({predict._next_pow2(d) for d in depths}) > 1
    assert warm.get("predict.traverse", 0) \
        - before.get("predict.traverse", 0) == 1
    assert after.get("predict.traverse") == warm.get("predict.traverse")
