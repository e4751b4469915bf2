"""Column and row sampling as the benchmark's cell ``bosch-train-subsample``
runs it (``feature_fraction`` 0.8, ``bagging_fraction`` 0.8,
``bagging_freq`` 5), at a small size over seven iterations, so that the bag
is drawn twice and seven column masks are.

- the program through ``lgb.Booster.update`` grows the trees of the plain
  reference (``benchmark/reference/gbdt_subsample.py``, which grows over the
  in-bag rows and the sampled columns alone): splits, scores after each
  step, in-bag counts, held-out scores; for the serial learner and for the
  learner the cells run (a mesh of one device);
- a tree's counts in the model text are in-bag counts, and every row's
  score moves;
- the serial and the mesh learner draw the same column masks (one draw,
  ``CapabilityMixin._draw_feature_mask``);
- the draw is named on the device clock (``obs_bag``), the mask's draw and
  upload on the host's (``tree::sample_features``), and both are counted
  (``sample/bag_trees``, ``sample/bag_draws``, ``sample/cols_in_mask``,
  ``sample/cols_total``) while the stage timer is on, and only then;
- the scope and the span leave the lowered grower of the accepted cells as
  it was.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark.harness import traffic
from benchmark.reference import gbdt_subsample
from benchmark.trace import work
from lightgbm_tpu.boosting import sample_strategy
from lightgbm_tpu.config import Config
from lightgbm_tpu.obs.registry import registry

ROWS, FEATURES, HOLD, LEAVES = 6000, 20, 1000, 15
STEPS, FREQ = 7, 5
SAMPLED_COLS = 16       # max(1, round(20 * 0.8))
DATA = {"table_seed": 26, "informative": 8, "weight_scale": 0.6,
        "interaction": 0.5, "noise": 0.5, "heavy_tail_every": 4,
        "heavy_tail_power": 1.5}
PARAMS = {"objective": "binary", "max_bin": 255, "num_leaves": LEAVES,
          "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 5.0, "verbosity": -1,
          "feature_fraction": 0.8, "bagging_fraction": 0.8,
          "bagging_freq": FREQ}
LEARNERS = {"serial": {"tree_learner": "serial"},
            "data": {"tree_learner": "data", "mesh_shape": "data=1"}}
REF_PARAMS = gbdt_subsample.Params.from_dict(dict(
    PARAMS, lambda_l2=0.0, min_data_in_bin=3,
    bin_construct_sample_cnt=200000, data_random_seed=1, bagging_seed=3,
    feature_fraction_seed=2))


def _table(seed):
    X, y = traffic.make_table(ROWS + HOLD, FEATURES, seed, DATA)
    return X[:ROWS], y[:ROWS], X[ROWS:]


def _booster(X, y, learner, **changed):
    params = dict(PARAMS, **LEARNERS[learner], **changed)
    train_set = lgb.Dataset(X, label=y, params=dict(params)).construct()
    return lgb.Booster(params=params, train_set=train_set)


def _scores(bst):
    return np.asarray(bst.inner.train_score, dtype=np.float32).reshape(-1)


@pytest.fixture(scope="module", params=sorted(LEARNERS))
def grown(request):
    """A booster of each learner after seven sampled steps, with the
    training rows' scores after every step."""
    X, y, X_hold = _table(11)
    bst = _booster(X, y, request.param)
    scores = []
    for _ in range(STEPS):
        bst.update()
        scores.append(_scores(bst).copy())
    want = {"serial": "SerialTreeLearner", "data": "DataParallelTreeLearner"}
    assert type(bst.inner.learner).__name__ == want[request.param]
    return bst, scores, X, y, X_hold


@pytest.fixture(scope="module")
def reference():
    X, y, X_hold = _table(11)
    ref = gbdt_subsample.Reference(X, y, REF_PARAMS)
    return ref, [ref.step() for _ in range(STEPS)], ref.predict_raw(X_hold)


def test_program_follows_the_reference(grown, reference):
    """Same splits, same bags, same masks and the same scores, step by step,
    across the bag's redraw at iteration 5."""
    bst, scores, X, y, X_hold = grown
    ref, ref_scores, ref_hold = reference
    roots = []
    for k in range(STEPS):
        tree, want = bst.inner.models[k], ref.trees[k]
        n = len(want.leaf)
        assert tree.num_leaves == n + 1 == LEAVES
        assert list(tree.split_feature[:n]) == want.feature
        assert list(tree.threshold_in_bin[:n]) == want.thr_bin
        # the rows the tree was grown from: the bag's
        assert int(tree.internal_count[0]) == want.smaller_rows[0]
        roots.append(want.smaller_rows[0])
        np.testing.assert_allclose(scores[k], ref_scores[k], rtol=0,
                                   atol=3e-5)
    # one bag lasts five iterations, the next is another draw
    assert len(set(roots[:FREQ])) == 1 and roots[FREQ] == roots[FREQ + 1]
    assert roots[0] != roots[FREQ]
    assert all(abs(r - 0.8 * ROWS) < 0.03 * ROWS for r in roots)
    got_hold = np.asarray(bst.predict(X_hold, num_iteration=STEPS,
                                      raw_score=True), dtype=np.float64)
    np.testing.assert_allclose(got_hold, ref_hold, rtol=0, atol=3e-5)


def test_reference_grows_over_the_bag_and_the_mask_alone(monkeypatch):
    """The reference's own statement, not the program's masks: its grower is
    handed a table of the sampled columns and a permutation of the in-bag
    rows, and its counts add up to the bag."""
    X, y, _ = _table(11)
    ref = gbdt_subsample.Reference(X, y, REF_PARAMS)
    seen = []
    grow_on = gbdt_subsample.gbdt_goss.Reference._grow_on

    def spy(view, gh, gh_host, rows):
        seen.append((tuple(view.bins.shape), view.bins_t.shape, len(rows)))
        return grow_on(view, gh, gh_host, rows)

    monkeypatch.setattr(gbdt_subsample.gbdt_goss.Reference, "_grow_on", spy)
    ref.step()
    (dev_shape, host_shape, bag), = seen
    assert dev_shape == (ROWS, SAMPLED_COLS)
    assert host_shape == (SAMPLED_COLS, ROWS)
    assert bag == ref.trees[0].smaller_rows[0] < ROWS
    with open(gbdt_subsample.__file__) as f:
        source = f.read()
    assert "import lightgbm_tpu" not in source
    assert "from lightgbm_tpu" not in source


def test_model_text_counts_are_in_bag_and_every_score_moves(grown):
    bst, scores, X, y, _ = grown
    counts = work.tree_counts_from_model_text(bst.model_to_string())
    assert len(counts) == STEPS
    for k, (root, smaller) in enumerate(counts):
        tree = bst.inner.models[k]
        assert abs(root - 0.8 * ROWS) < 0.03 * ROWS
        assert int(tree.leaf_count[:tree.num_leaves].sum()) == root
    before = np.full(ROWS, gbdt_subsample.init_score(y), dtype=np.float32)
    for after in scores:
        # out-of-bag rows walk the tree too: no row keeps its score
        assert np.all(after != before)
        before = after


@pytest.mark.parametrize("constrained", [False, True])
def test_serial_and_mesh_learners_draw_the_same_masks(constrained):
    X, y, _ = _table(12)
    changed = ({"interaction_constraints": [list(range(10)), [10, 11]]}
               if constrained else {})
    masks = {}
    for learner in sorted(LEARNERS):
        lrn = _booster(X, y, learner, **changed).inner.learner
        host = [lrn._draw_feature_mask() for _ in range(4)]
        masks[learner] = [m[:FEATURES] for m in host]
        assert all(not m[FEATURES:].any() for m in host)
        placed = lrn._sample_features()
        assert placed.shape == (lrn.Fp,) and placed.dtype == jnp.bool_
    for a, b in zip(masks["serial"], masks["data"]):
        np.testing.assert_array_equal(a, b)
    sizes = {int(m.sum()) for m in masks["data"]}
    assert (max(sizes) <= min(SAMPLED_COLS, 12)) if constrained \
        else sizes == {SAMPLED_COLS}
    assert any((a != b).any() for a, b in zip(masks["data"],
                                              masks["data"][1:]))


def _lowered():
    strategy = sample_strategy.BaggingStrategy(Config.from_params(PARAMS),
                                               ROWS, 1)
    vec = jax.ShapeDtypeStruct((ROWS,), jnp.float32)
    return {
        "boost.bag_draw": sample_strategy.bag_draw.lower(
            jax.random.PRNGKey(3), jnp.int32(1), jnp.float32(0.8), ROWS),
        "scan body": jax.jit(strategy.apply_traced).lower(
            jnp.int32(2), vec, vec),
    }


@pytest.mark.parametrize("program", ["boost.bag_draw", "scan body"])
def test_scope_is_in_the_lowered_program(program):
    text = _lowered()[program].as_text(debug_info=True)
    assert re.search(r'[/"]obs_bag[/"]', text), (
        "%s has no operation under obs_bag" % program)


COUNTERS = ("sample/bag_trees", "sample/bag_draws", "sample/cols_in_mask",
            "sample/cols_total", "sample/rows_in_bag",
            "grow/hist_rows_in_bag", "grow/hist_rows_needed",
            "grow/hist_rows_bucketed")


def _counters():
    return [registry.count(name) for name in COUNTERS]


@pytest.mark.parametrize("learner", sorted(LEARNERS))
def test_counters_follow_the_trees_grown(timer_on, learner):
    X, y, _ = _table(12)
    before = _counters()
    spans = registry.timer.counts["tree::sample_features"]
    bst = _booster(X, y, learner)
    for _ in range(STEPS):
        bst.update()
    moved = dict(zip(COUNTERS, (a - b for a, b in zip(_counters(), before))))
    assert moved["sample/bag_trees"] == STEPS
    assert moved["sample/bag_draws"] == 2           # iterations 0 and 5
    assert moved["sample/cols_in_mask"] == STEPS * SAMPLED_COLS
    assert moved["sample/cols_total"] == STEPS * FEATURES
    # the mask's draw and upload are a host span of their own, once a tree
    assert registry.timer.counts["tree::sample_features"] - spans == STEPS
    assert registry.timer.totals["tree::sample_features"] > 0.0
    if learner == "data":       # the learner the cells run counts its rows
        roots = [int(t.internal_count[0]) for t in bst.inner.models]
        assert moved["sample/rows_in_bag"] == sum(roots)
        # the passes visit the smaller child's rows in and out of the bag
        assert 0.6 * moved["grow/hist_rows_needed"] \
            < moved["grow/hist_rows_in_bag"] \
            < 0.95 * moved["grow/hist_rows_needed"]
        assert moved["grow/hist_rows_bucketed"] \
            >= moved["grow/hist_rows_needed"]


@pytest.mark.parametrize("learner", sorted(LEARNERS))
def test_counters_stay_still_while_the_timer_is_off(learner):
    assert not registry.timer.enabled
    X, y, _ = _table(12)
    before = _counters()
    spans = registry.timer.counts["tree::sample_features"]
    bst = _booster(X, y, learner)
    for _ in range(2):
        bst.update()
    assert _counters() == before
    assert registry.timer.counts["tree::sample_features"] == spans


def _lowered_tree(changed):
    """The lowered whole-tree program of the learner the cells run, at a
    small shape, under the accepted cells' parameters (``changed`` on top)."""
    X, y, _ = _table(12)
    params = {k: v for k, v in PARAMS.items() if k not in (
        "feature_fraction", "bagging_fraction", "bagging_freq")}
    params.update(LEARNERS["data"], **changed)
    train_set = lgb.Dataset(X, label=y, params=dict(params)).construct()
    lrn = lgb.Booster(params=params, train_set=train_set).inner.learner
    lrn._ensure_compiled()
    sds = jax.ShapeDtypeStruct
    gh = sds((lrn.R, 4), jnp.float32)
    state = jax.eval_shape(lrn._root_impl, lrn.bins, gh,
                           lrn._sample_features(), jnp.int32(1),
                           lrn._qs_ones)[0]
    return jax.jit(lrn._tree_impl).lower(
        lrn.bins, state, lrn._sample_features(), jnp.int32(1),
        lrn._qs_ones).as_text()


@pytest.mark.parametrize("cell", ["bosch-train", "bosch-train-subsample"])
def test_sampling_leaves_the_lowered_grower_as_it_was(cell):
    """The whole-tree program takes the column mask as an argument and the
    bag as a channel of ``gh``: with the sampling on it lowers to the text
    it lowers to with the sampling off, and neither holds ``obs_bag`` (the
    draw is a program of its own)."""
    plain = _lowered_tree({})
    text = plain if cell == "bosch-train" else _lowered_tree(
        {"feature_fraction": 0.8, "bagging_fraction": 0.8,
         "bagging_freq": FREQ})
    assert text == plain
    assert "obs_bag" not in text
