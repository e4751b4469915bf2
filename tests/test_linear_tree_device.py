"""Piecewise-linear leaves (``linear_tree``) fit on the device, as the
benchmark's cell ``bosch-train-linear`` runs them, at a small size against
the plain reference ``benchmark/reference/gbdt_linear.py``:

- the program through ``lgb.Booster.update`` grows the reference's trees
  and moves every training row's score by the reference's linear values,
  for the serial learner and the learner the cells run (a mesh of one
  device); the first tree keeps constant leaves;
- held-out rows with a NaN among their leaf's features take the leaf's
  constant value, in the validation scores and in ``Booster.predict``;
- a leaf with fewer rows than its features plus one keeps its constant;
- the model text round-trips, and ``PredictServer`` serves the training
  rows' scores;
- the fit is named on the device clock (``obs_linear_fit``,
  ``obs_linear_out``) and counted while the stage timer is on;
- with ``linear_tree`` off nothing the accepted cells run changes: the
  grower's programs lower to the same text.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark.harness import traffic
from benchmark.reference import gbdt_linear
from lightgbm_tpu.obs.registry import registry
from lightgbm_tpu.ops import linear as linear_ops

ROWS, FEATURES, HOLD, LEAVES, STEPS = 6000, 20, 1000, 15, 3
DATA = {"table_seed": 26, "informative": 8, "weight_scale": 0.6,
        "interaction": 0.5, "noise": 0.5, "heavy_tail_every": 4,
        "heavy_tail_power": 1.5}
PARAMS = {"objective": "binary", "max_bin": 255, "num_leaves": LEAVES,
          "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 5.0, "verbosity": -1,
          "linear_tree": True, "metric": "auc"}
LEARNERS = {"serial": {"tree_learner": "serial"},
            "data": {"tree_learner": "data", "mesh_shape": "data=1"}}
REF_DEFAULTS = dict(lambda_l2=0.0, min_data_in_bin=3,
                    bin_construct_sample_cnt=200000, data_random_seed=1,
                    linear_lambda=0.0)
# Largest gap over the training rows' scores at this size (CPU), against
# the reference in float32 / in float64 (``fit_dtype``): the program 4.0e-6
# / 4.0e-6 after the constant first tree, 2.5e-5 / 3.8e-6 after each
# linear one, where the float32 reference itself lies 2.5e-5 from its
# float64 twin. Both sum a leaf's products in float32 in other orders and
# solve systems whose condition numbers reach 3.8e3 here (median 140), so
# a solve moves the sums' last bits by that factor. The tolerance leaves
# four times over the largest gap; a coefficient left out or fit from the
# wrong rows moves scores by 1e-3 or more (the first test's second check).
SCORE_ATOL = 1e-4


def _table(seed, hold=HOLD):
    X, y = traffic.make_table(ROWS + hold, FEATURES, seed, DATA)
    return X[:ROWS], y[:ROWS], X[ROWS:], y[ROWS:]


def _booster(X, y, learner="data", X_valid=None, y_valid=None, **changed):
    params = dict(PARAMS, **LEARNERS[learner], **changed)
    train_set = lgb.Dataset(X, label=y, params=dict(params)).construct()
    bst = lgb.Booster(params=params, train_set=train_set)
    if X_valid is not None:
        bst.add_valid(lgb.Dataset(X_valid, label=y_valid,
                                  reference=train_set).construct(), "test")
    return bst


def _scores(bst):
    return np.asarray(bst.inner.train_score, dtype=np.float32).reshape(-1)


def _reference(X, y, **changed):
    params = gbdt_linear.Params.from_dict(dict(PARAMS, **REF_DEFAULTS,
                                               **changed))
    return gbdt_linear.Reference(X, y, params)


@pytest.fixture(scope="module", params=sorted(LEARNERS))
def grown(request):
    X, y, X_hold, y_hold = _table(11)
    bst = _booster(X, y, request.param, X_hold, y_hold)
    scores = []
    for _ in range(STEPS):
        bst.update()
        bst.eval_valid()
        scores.append(_scores(bst).copy())
    want = {"serial": "SerialTreeLearner", "data": "DataParallelTreeLearner"}
    assert type(bst.inner.learner).__name__ == want[request.param]
    return bst, scores, X, y, X_hold


@pytest.fixture(scope="module")
def reference():
    X, y, X_hold, _ = _table(11)
    ref = _reference(X, y)
    return ref, [ref.step() for _ in range(STEPS)], ref.predict_raw(X_hold)


def test_training_scores_follow_the_reference(grown, reference):
    bst, scores, X, y, X_hold = grown
    ref, ref_scores, ref_hold = reference
    for k in range(STEPS):
        tree, want = bst.inner.models[k], ref.trees[k]
        n = len(want.leaf)
        assert tree.num_leaves == n + 1 == LEAVES
        assert list(tree.split_feature[:n]) == want.feature
        np.testing.assert_allclose(scores[k], ref_scores[k], rtol=0,
                                   atol=SCORE_ATOL)
        if k:
            # the linear leaves move the scores: far more than the gap
            const = ref_scores[k - 1] + want.value[want.leaves(
                lambda f: ref.bins_t[f])].astype(np.float32)
            assert np.max(np.abs(scores[k] - const)) > 100 * SCORE_ATOL
    got_hold = np.asarray(bst.predict(X_hold, raw_score=True,
                                      predict_on_device=False))
    np.testing.assert_allclose(got_hold, ref_hold, rtol=0, atol=SCORE_ATOL)
    valid = np.asarray(bst.inner.valid_data[0].scores_dev)[:, 0]
    np.testing.assert_allclose(valid, ref_hold, rtol=0, atol=SCORE_ATOL)


def test_first_tree_keeps_constant_leaves(grown, reference):
    bst, scores, *_ = grown
    ref, ref_scores, _ = reference
    first, second = bst.inner.models[0], bst.inner.models[1]
    nl = first.num_leaves
    assert first.is_linear and first._linear_dev is None
    assert all(not f for f in first.leaf_features[:nl])
    np.testing.assert_array_equal(first.leaf_const[:nl],
                                  first.leaf_value[:nl])
    assert ref.trees[0].lin == [None] * nl
    # the constant tree's gap is the accepted cells' (4e-6 here)
    np.testing.assert_allclose(scores[0], ref_scores[0], rtol=0, atol=1e-5)
    # the second tree is fit: nearly every leaf has coefficients
    fitted = [f for f in second.leaf_features[:second.num_leaves] if f]
    assert len(fitted) >= second.num_leaves - 1
    text = bst.model_to_string()
    assert text.count("is_linear=1") == STEPS


def test_nan_among_a_leafs_features_falls_back_to_its_value():
    X, y, X_hold, y_hold = _table(12)
    X_hold = X_hold.copy()
    params = dict(PARAMS, **LEARNERS["data"])
    train_set = lgb.Dataset(X, label=y, params=dict(params)).construct()
    bst = lgb.Booster(params=params, train_set=train_set)
    for _ in range(2):
        bst.update()
    tree = bst.inner.models[1]
    split_cols = sorted(set(int(f) for f in
                            tree.split_feature[:tree.num_leaves - 1]))
    rng = np.random.RandomState(3)
    nan_rows = rng.rand(HOLD) < 0.3
    X_hold[np.ix_(nan_rows, split_cols[:2])] = np.nan
    ref = _reference(X, y)
    for _ in range(2):
        ref.step()
    want = ref.predict_raw(X_hold)
    got = np.asarray(bst.predict(X_hold, raw_score=True,
                                 predict_on_device=False))
    np.testing.assert_allclose(got, want, rtol=0, atol=SCORE_ATOL)
    # on the device: a validation set added now is scored tree by tree
    bst.add_valid(lgb.Dataset(X_hold, label=y_hold,
                              reference=train_set).construct(), "nan")
    valid = np.asarray(bst.inner.valid_data[-1].scores_dev)[:, 0]
    np.testing.assert_allclose(valid, want, rtol=0, atol=SCORE_ATOL)
    # the rows whose leaf reads a NaN take that leaf's constant value
    leaf = tree.predict_leaf_index(X_hold)
    with_nan = np.array([
        any(np.isnan(X_hold[i, f]) for f in tree.leaf_features[leaf[i]])
        for i in range(HOLD)])
    assert with_nan.sum() > HOLD // 10
    first = bst.inner.models[0].predict(X_hold)
    np.testing.assert_allclose(
        got[with_nan] - first[with_nan] - bst.inner.models[1].leaf_value[
            leaf[with_nan]], 0.0, atol=1e-12)


def test_leaf_under_its_features_plus_one_rows_keeps_its_constant():
    X, y, _, _ = _table(13)
    X, y = X[:300], y[:300]
    changed = {"num_leaves": 31, "min_sum_hessian_in_leaf": 1e-3,
               "min_data_in_leaf": 1}
    bst = _booster(X, y, "data", **changed)
    for _ in range(2):
        bst.update()
    tree = bst.inner.models[1]
    feat, valid = linear_ops.path_table(tree, tree.num_leaves)
    k = valid.sum(axis=1)
    counts = tree.leaf_count[:tree.num_leaves]
    small = counts < k + 1
    assert small.any() and (~small).any()
    for leaf in range(tree.num_leaves):
        if small[leaf]:
            assert tree.leaf_features[leaf] == []
            assert tree.leaf_const[leaf] == tree.leaf_value[leaf]
        else:
            assert tree.leaf_features[leaf]
    ref = _reference(X, y, **changed)
    ref_scores = [ref.step() for _ in range(2)]
    assert [lin is None for lin in ref.trees[1].lin] == list(small)
    np.testing.assert_allclose(_scores(bst), ref_scores[1], rtol=0,
                               atol=SCORE_ATOL)


def test_model_text_round_trip(grown):
    bst, _, X, _, X_hold = grown
    text = bst.model_to_string()
    loaded = lgb.Booster(model_str=text)
    trees = lambda t: t[:t.index("end of trees")]
    assert trees(loaded.model_to_string()) == trees(text)
    np.testing.assert_array_equal(
        loaded.predict(X_hold, raw_score=True, predict_on_device=False),
        bst.predict(X_hold, raw_score=True, predict_on_device=False))


def test_served_predictions_match_the_training_scores(grown):
    from lightgbm_tpu.serve.server import PredictServer
    bst, scores, X, _, _ = grown
    server = PredictServer(bst, max_batch=2048, output_kind="raw")
    try:
        served = np.asarray(server.predict(X[:2048], timeout=300),
                            dtype=np.float64).reshape(-1)
    finally:
        server.stop()
    # float32 training scores against float64 sums of the same model
    np.testing.assert_allclose(served, scores[-1][:2048], rtol=0,
                               atol=1e-5)


def test_scopes_are_in_the_lowered_programs():
    n, f, L, D = 64, 5, 7, 4
    vec = jax.ShapeDtypeStruct((n,), jnp.float32)
    ids = jax.ShapeDtypeStruct((n,), jnp.int32)
    table = jax.ShapeDtypeStruct((L, D), jnp.int32)
    mask = jax.ShapeDtypeStruct((L, D), jnp.bool_)
    leaf_vec = jax.ShapeDtypeStruct((L,), jnp.float32)
    raw = jax.ShapeDtypeStruct((n, f), jnp.float32)
    fit = jax.jit(linear_ops.linear_fit).lower(
        raw, vec, vec, None, ids, leaf_vec, table, mask, jnp.float32(0.1),
        jnp.float32(0.0)).as_text(debug_info=True)
    for scope in ("obs_linear_fit", "obs_linear_out"):
        assert re.search(r'[/"]%s[/"]' % scope, fit), scope
    out = jax.jit(linear_ops.linear_valid_output).lower(
        raw, ids, leaf_vec, leaf_vec, jax.ShapeDtypeStruct(
            (L, D), jnp.float32), table, mask,
        jax.ShapeDtypeStruct((L,), jnp.bool_)).as_text(debug_info=True)
    assert re.search(r'[/"]obs_linear_out[/"]', out)


def test_normal_equations_are_the_leaves_sums():
    rng = np.random.RandomState(5)
    n, p, L = 20000, 5, 9
    a = rng.randn(n, p).astype(np.float32)
    g, h = rng.randn(n).astype(np.float32), rng.rand(n).astype(np.float32)
    ok = rng.rand(n) < 0.9
    leaf = rng.randint(0, L, n).astype(np.int32)
    A, b, cnt = linear_ops._normal_equations(
        jnp.asarray(a), jnp.asarray(g), jnp.asarray(h), jnp.asarray(ok),
        jnp.asarray(leaf), L)
    for l in range(L):
        r = (leaf == l) & ok
        a64 = a[r].astype(np.float64)
        np.testing.assert_allclose(np.asarray(A[l]),
                                   (a64 * h[r, None]).T @ a64,
                                   rtol=1e-4, atol=1e-2)
        np.testing.assert_allclose(np.asarray(b[l]), a64.T @ g[r],
                                   rtol=1e-4, atol=1e-2)
        assert int(cnt[l]) == int(r.sum())


@pytest.mark.parametrize("learner", sorted(LEARNERS))
def test_counters_follow_the_fits(timer_on, learner):
    X, y, X_hold, y_hold = _table(11)
    bst = _booster(X, y, learner, X_hold, y_hold)
    before = {k: registry.counters.get(k, 0) for k in (
        "linear/trees_fit", "linear/leaves_fit", "linear/leaves_const",
        "linear/path_features", "linear/rows_fit")}
    for _ in range(STEPS):
        bst.update()
        bst.eval_valid()
    moved = {k: registry.counters.get(k, 0) - v for k, v in before.items()}
    trees = bst.inner.models[1:]
    assert moved["linear/trees_fit"] == STEPS - 1
    assert moved["linear/leaves_fit"] + moved["linear/leaves_const"] == \
        sum(t.num_leaves for t in trees)
    fitted = [(t, l) for t in trees for l in range(t.num_leaves)
              if t.leaf_features[l]]
    assert moved["linear/leaves_fit"] == len(fitted)
    assert moved["linear/path_features"] >= sum(
        len(t.leaf_features[l]) for t, l in fitted)
    assert moved["linear/rows_fit"] == sum(
        int(t.leaf_count[l]) for t, l in fitted)


def _lowered_grower(linear: bool):
    X, y, _, _ = _table(11)
    params = dict(PARAMS, **LEARNERS["data"], linear_tree=linear)
    train_set = lgb.Dataset(X, label=y, params=dict(params)).construct()
    lrn = lgb.Booster(params=params, train_set=train_set).inner.learner
    lrn._ensure_compiled()
    gh = jax.ShapeDtypeStruct((lrn.R, 4), jnp.float32)
    args = (lrn.bins, gh, lrn._sample_features(), jnp.int32(1),
            lrn._qs_ones)
    state = jax.eval_shape(lrn._root_impl, *args)[0]
    return (jax.jit(lrn._root_impl).lower(*args).as_text(),
            jax.jit(lrn._tree_impl).lower(
                lrn.bins, lrn.bins_fm, state, lrn._sample_features(),
                jnp.int32(1), lrn._qs_ones).as_text())


def test_linear_tree_leaves_the_lowered_grower_as_it_was():
    """The leaves are fit after the tree is grown, in a program of their
    own: the root and whole-tree programs with ``linear_tree`` on lower to
    the text they lower to with it off, and hold nothing of the fit."""
    plain, linear = _lowered_grower(False), _lowered_grower(True)
    assert plain == linear
    assert "obs_linear" not in "".join(plain)


def test_no_program_compiles_after_the_second_tree():
    """The benchmark's window opens after two checked steps and may compile
    nothing: every tree after the first two shares the fit, the validation
    output and the validation scores' addition (a path table of at least
    ``MIN_PATH_WIDTH`` slots; validation scores placed on the device as the
    fit's outputs are)."""
    from lightgbm_tpu.obs import compile as obs_compile
    X, y, X_hold, y_hold = _table(11)
    bst = _booster(X, y, "data", X_hold, y_hold)
    for _ in range(2):
        bst.update()
        bst.eval_valid()
    before = dict(obs_compile.trace_counts())
    for _ in range(12):
        bst.update()
        bst.eval_valid()
    after = dict(obs_compile.trace_counts())
    # the walk compiles one program a power of two of depth, which the
    # benchmark warms for every depth a tree can have
    moved = {k for k, v in after.items() if before.get(k) != v}
    assert moved <= {"predict.traverse"}
    assert {"linear.fit", "linear.valid_output", "gbdt.valid_score_add",
            "gbdt.score_add_col"} <= set(before)
    widths = {linear_ops.path_table(t, LEAVES)[0].shape[1]
              for t in bst.inner.models[1:]}
    assert widths == {linear_ops.MIN_PATH_WIDTH}
