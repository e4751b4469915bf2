"""Gradient-based one-side sampling as the benchmark's cell
``bosch-train-goss`` runs it (``data_sample_strategy=goss``, ``top_rate``
0.2, ``other_rate`` 0.1), at a small size with ``learning_rate`` 0.5 so
that the unsampled warm-up is 2 iterations.

- the program through ``lgb.Booster.update`` grows the trees of the plain
  reference (``benchmark/reference/gbdt_goss.py``) on seeded tables: scores
  after each step, the root's in-bag count, held-out scores; for the serial
  learner and for the learner the cells run (a mesh of one device);
- a sampled tree's counts in the model text are in-bag counts, and its
  ``leaf_count`` adds up to its root's ``internal_count``;
- the bag is drawn anew at the next iteration (the cell compares one
  sampled step on the chip, so the redraw and the second step are held here);
- the sampling is named on the device clock (``obs_goss``) and counted
  (``sample/goss_trees``, ``sample/rows_in_bag``,
  ``grow/hist_rows_in_bag``) while the stage timer is on, and only then.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark.harness import traffic
from benchmark.reference import gbdt_goss
from benchmark.trace import work
from lightgbm_tpu.boosting.sample_strategy import GOSSStrategy
from lightgbm_tpu.config import Config
from lightgbm_tpu.obs.registry import registry

ROWS, FEATURES, HOLD, LEAVES = 6000, 12, 1000, 15
WARM, SAMPLED = 2, 2
DATA = {"table_seed": 26, "informative": 8, "weight_scale": 0.6,
        "interaction": 0.5, "noise": 0.5, "heavy_tail_every": 4,
        "heavy_tail_power": 1.5}
PARAMS = {"objective": "binary", "max_bin": 255, "num_leaves": LEAVES,
          "learning_rate": 0.5, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 5.0, "verbosity": -1,
          "data_sample_strategy": "goss", "top_rate": 0.2,
          "other_rate": 0.1}
LEARNERS = {"serial": {"tree_learner": "serial"},
            "data": {"tree_learner": "data", "mesh_shape": "data=1"}}
REF_PARAMS = gbdt_goss.Params.from_dict(dict(
    PARAMS, lambda_l2=0.0, min_data_in_bin=3,
    bin_construct_sample_cnt=200000, data_random_seed=1, bagging_seed=3))


def _table(seed):
    X, y = traffic.make_table(ROWS + HOLD, FEATURES, seed, DATA)
    return X[:ROWS], y[:ROWS], X[ROWS:]


def _booster(X, y, learner):
    params = dict(PARAMS, **LEARNERS[learner])
    train_set = lgb.Dataset(X, label=y, params=dict(params)).construct()
    return lgb.Booster(params=params, train_set=train_set)


def _scores(bst):
    return np.asarray(bst.inner.train_score, dtype=np.float32).reshape(-1)


@pytest.fixture(scope="module", params=sorted(LEARNERS))
def grown(request):
    """A booster of each learner after the warm-up and two sampled steps,
    with the training rows' scores after every step."""
    X, y, X_hold = _table(11)
    bst = _booster(X, y, request.param)
    scores = []
    for _ in range(WARM + SAMPLED):
        bst.update()
        scores.append(_scores(bst).copy())
    want = {"serial": "SerialTreeLearner", "data": "DataParallelTreeLearner"}
    assert type(bst.inner.learner).__name__ == want[request.param]
    return bst, scores, X, y, X_hold


@pytest.mark.parametrize("start", ["from the start", "after the warm-up"])
def test_program_follows_the_reference(grown, start):
    """Same splits, same bag and the same scores, step by step: from the
    score boosting starts from, and from the program's own scores after the
    warm-up, as the benchmark's runner hands them over."""
    bst, scores, X, y, X_hold = grown
    hold = lambda n: np.asarray(bst.predict(
        X_hold, num_iteration=n, raw_score=True), dtype=np.float64)
    if start == "from the start":
        ref, first, base = gbdt_goss.Reference(X, y, REF_PARAMS), 0, None
    else:
        ref = gbdt_goss.Reference(X, y, REF_PARAMS,
                                  start_scores=scores[WARM - 1],
                                  start_iteration=WARM)
        first, base = WARM, hold(WARM)
    for k in range(first, WARM + SAMPLED):
        got = ref.step()
        tree, want = bst.inner.models[k], ref.trees[-1]
        n = len(want.leaf)
        assert tree.num_leaves == n + 1 == LEAVES
        assert list(tree.split_feature[:n]) == want.feature
        assert list(tree.threshold_in_bin[:n]) == want.thr_bin
        # the rows the tree was grown from: all of them, then the bag
        assert int(tree.internal_count[0]) == want.smaller_rows[0]
        assert (want.smaller_rows[0] == ROWS) == (k < WARM)
        # a right child's float32 sums are its parent's less the left
        # child's, so a small leaf's value carries the parent's last
        # digits (1e-5 of it), and at this rate it is five times the cells'
        np.testing.assert_allclose(scores[k], got, rtol=0, atol=3e-5)
    np.testing.assert_allclose(hold(WARM + SAMPLED),
                               ref.predict_raw(X_hold, start=base),
                               rtol=0, atol=3e-5)


def test_model_text_counts_are_in_bag(grown):
    bst = grown[0]
    counts = work.tree_counts_from_model_text(bst.model_to_string())
    assert [c[0] for c in counts[:WARM]] == [ROWS] * WARM
    for k in range(WARM, WARM + SAMPLED):
        tree, (root, smaller) = bst.inner.models[k], counts[k]
        assert abs(root - 0.3 * ROWS) < 0.02 * ROWS
        assert int(tree.leaf_count[:tree.num_leaves].sum()) == root
        # each split's smaller child by its in-bag count is under half
        assert all(2 * small <= root for small in smaller)


def _lowered():
    strategy = GOSSStrategy(Config.from_params(PARAMS), ROWS, 1)
    vec = jax.ShapeDtypeStruct((ROWS,), jnp.float32)
    key = jax.random.PRNGKey(3)
    return {
        "boost.goss": GOSSStrategy._goss.lower(strategy, vec, vec, key,
                                               jnp.int32(2)),
        "scan body": jax.jit(strategy.apply_traced).lower(
            jnp.int32(2), vec, vec),
    }


@pytest.mark.parametrize("program", ["boost.goss", "scan body"])
def test_scope_is_in_the_lowered_program(program):
    text = _lowered()[program].as_text(debug_info=True)
    assert re.search(r'[/"]obs_goss[/"]', text), (
        "%s has no operation under obs_goss" % program)


@pytest.mark.parametrize("shape", ["one score a row", "three scores a row"])
def test_bag_is_redrawn_every_iteration(shape):
    """The same gradients under the next iteration number keep the top rows
    and draw the rest anew; the benchmark's cell compares one sampled step
    on the chip, so the redraw is held here."""
    strategy = GOSSStrategy(Config.from_params(PARAMS), ROWS, 1)
    rng = np.random.RandomState(5)
    size = (ROWS,) if shape == "one score a row" else (ROWS, 3)
    grad = jnp.asarray(rng.randn(*size), dtype=jnp.float32)
    hess = jnp.asarray(rng.rand(*size) + 0.1, dtype=jnp.float32)
    weight = np.abs(np.asarray(grad * hess)).reshape(ROWS, -1).sum(axis=1)
    top = weight >= np.sort(weight)[ROWS - strategy.top_k]
    bags, scaled = [], []
    for it in (WARM, WARM + 1, WARM):
        g, h, bag = strategy.bagging(it, grad, hess)
        bags.append(np.asarray(bag) > 0)
        scaled.append(np.asarray(g))
    assert all(b[top].all() for b in bags)
    rest = [b & ~top for b in bags]
    assert (rest[0] == rest[2]).all()           # a pure function of it
    both = (rest[0] & rest[1]).sum()
    # two independent draws of 1 in 8 share 1 in 64 of the rest
    assert 0 < both < 0.25 * rest[0].sum()
    assert abs(int(rest[1].sum()) - strategy.other_k) < 0.25 * strategy.other_k
    factor = (ROWS - strategy.top_k) / strategy.other_k
    np.testing.assert_allclose(
        scaled[1][rest[1]], factor * np.asarray(grad)[rest[1]], rtol=1e-6)
    np.testing.assert_array_equal(scaled[1][top], np.asarray(grad)[top])


def test_second_sampled_tree_has_its_own_bag(grown):
    """Through ``Booster.update``: the two sampled trees' bags differ (their
    in-bag root counts are those of two draws), and the reference's second
    step, which draws under the next iteration number, has the program's."""
    bst, scores, X, y, _ = grown
    ref = gbdt_goss.Reference(X, y, REF_PARAMS,
                              start_scores=scores[WARM - 1],
                              start_iteration=WARM)
    roots = []
    for k in range(WARM, WARM + SAMPLED):
        ref.step()
        roots.append(int(bst.inner.models[k].internal_count[0]))
        assert roots[-1] == ref.trees[-1].smaller_rows[0]
    assert roots[0] != roots[1]


COUNTERS = ("sample/goss_trees", "sample/rows_in_bag",
            "grow/hist_rows_in_bag", "grow/hist_rows_needed")


def _counters():
    return [registry.count(name) for name in COUNTERS]


def _in_bag_rows_of_smaller_children(bst, X, k):
    """``(in-bag rows, rows in all)`` of the child with fewer in-bag rows
    (ties: the left), the one the grower histograms, over the splits of
    tree ``k``: the tree's own in-bag counts, the sizes in all from where
    every row lands."""
    tree = bst.inner.models[k]
    leaf_of_row = np.asarray(bst.predict(X, pred_leaf=True))[:, k]
    leaf_rows = np.bincount(leaf_of_row, minlength=tree.num_leaves)

    def sizes(child):       # (rows in all, rows in the bag)
        if child < 0:
            return leaf_rows[~child], int(tree.leaf_count[~child])
        left, right = (sizes(tree.left_child[child]),
                       sizes(tree.right_child[child]))
        assert left[1] + right[1] == int(tree.internal_count[child])
        return left[0] + right[0], left[1] + right[1]

    in_bag = in_all = 0
    for node in range(tree.num_leaves - 1):
        left, right = (sizes(tree.left_child[node]),
                       sizes(tree.right_child[node]))
        small = left if left[1] <= right[1] else right
        in_bag, in_all = in_bag + small[1], in_all + small[0]
    return in_bag, in_all


def test_counters_follow_the_sampled_trees(timer_on):
    X, y, _ = _table(12)
    before = _counters()
    bst = _booster(X, y, "data")
    for _ in range(WARM + SAMPLED):
        bst.update()
    trees, in_bag, hist_in_bag, needed = (
        a - b for a, b in zip(_counters(), before))
    sampled = range(WARM, WARM + SAMPLED)
    assert trees == SAMPLED
    assert in_bag == sum(int(bst.inner.models[k].internal_count[0])
                         for k in sampled)
    small = [_in_bag_rows_of_smaller_children(bst, X, k)
             for k in range(WARM + SAMPLED)]
    assert hist_in_bag == sum(b for b, _ in small)
    # the passes visit the smaller child's in-bag rows alone, the rows the
    # grower orders, of which its rows in all hold a fifth to four fifths
    assert hist_in_bag == needed
    assert 0.2 * sum(a for _, a in small) < hist_in_bag \
        < 0.8 * sum(a for _, a in small)


@pytest.mark.parametrize("learner", sorted(LEARNERS))
def test_counters_stay_still_while_the_timer_is_off(learner):
    assert not registry.timer.enabled
    X, y, _ = _table(12)
    before = _counters()
    bst = _booster(X, y, learner)
    for _ in range(WARM + 1):
        bst.update()
    assert _counters() == before


def test_serial_learner_counts_the_sampled_iterations(timer_on):
    X, y, _ = _table(12)
    before = registry.count("sample/goss_trees")
    bst = _booster(X, y, "serial")
    for _ in range(WARM + SAMPLED):
        bst.update()
    assert registry.count("sample/goss_trees") - before == SAMPLED
