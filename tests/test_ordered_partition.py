"""The grower keeps the row numbers ordered by leaf.

Where a learner compacts (every learner on one device), ``GrowState``
carries ``order`` (the row numbers, each leaf's rows contiguous and
ascending), ``seg_begin`` and ``seg_count`` (each leaf's segment), and a
split reorders its parent's window of ``order`` only
(``treelearner/grow.py _partition_order``; ISSUE 34). Held here, on the
CPU:

- the window partition against numpy on seeded masks: a parent in the
  middle of ``order``, one at the end of the rows (its window runs into
  ``order``'s spare half), one that fills its window to the last entry,
  an empty side, and an invalid step, which writes nothing;
- after a whole tree of the serial and of the one-device mesh learner
  (exact, quantized, bagging): ``order`` is a permutation of the rows,
  and each leaf's segment holds exactly the rows with
  ``leaf_of_row == leaf``, ascending;
- a learner that does not compact carries no ``order`` at all.

The guard on what the chip's compiler makes of it is in
``tests/test_hist_store_inplace.py`` (one file describes the v5e).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.parallel import (DataParallelTreeLearner,
                                   VotingParallelTreeLearner, make_mesh)
from lightgbm_tpu.treelearner.grow import (_ORDER_CHUNK, GrowState,
                                           _partition_order, _window_sizes)
from lightgbm_tpu.treelearner.serial import SerialTreeLearner

R = 40_000                      # windows of 65,536, 32,768 and 16,384
LEAVES = 6


def _ordered(seed: int, counts):
    """``(order, seg_begin, seg_count)`` of ``R`` rows dealt
    at random to ``len(counts)`` leaves of the given sizes, the rest pad
    rows (leaf -1) behind them."""
    rng = np.random.RandomState(seed)
    leaf_of_row = np.full(R, -1, dtype=np.int32)
    rows = rng.permutation(R)
    at = 0
    for leaf, n in enumerate(counts):
        leaf_of_row[rows[at:at + n]] = leaf
        at += n
    key = np.where(leaf_of_row < 0, len(counts), leaf_of_row)
    order = np.argsort(key, kind="stable").astype(np.int32)
    order = np.concatenate([order, np.zeros(_window_sizes(R)[0], np.int32)])
    seg_count = np.zeros(LEAVES, dtype=np.int32)
    seg_count[:len(counts)] = counts
    seg_begin = np.zeros(LEAVES, dtype=np.int32)
    seg_begin[:len(counts)] = np.cumsum([0] + list(counts[:-1]))
    return order, seg_begin, seg_count


# (sizes of leaves 0..2, the leaf that splits, share of its rows sent left)
CASES = {
    # 9,000 rows from entry 12,000 on: the smallest window, mid-array
    "parent_in_the_middle": ((12_000, 9_000, 15_000), 1, 0.4),
    # the last real segment, then 1,000 pad rows; its 32,768-entry
    # window from entry 21,000 on passes the 40,000 rows
    "window_runs_past_the_rows": ((6_000, 15_000, 18_000), 2, 0.7),
    "parent_fills_its_window": ((5_000, _ORDER_CHUNK, 10_000), 1, 0.5),
    "parent_fills_two_chunks": ((5_000, 2 * _ORDER_CHUNK, 100), 1, 0.25),
    "parent_is_every_row": ((R,), 0, 0.6),
    "all_rows_go_left": ((12_000, 9_000, 15_000), 1, 1.0),
    "all_rows_go_right": ((12_000, 9_000, 15_000), 1, 0.0),
    "one_row_parent": ((12_000, 1, 15_000), 1, 0.0),
}


@pytest.mark.parametrize("valid", [True, False], ids=["valid", "invalid"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_window_partition_against_numpy(case, valid):
    counts, leaf, share = CASES[case]
    order, seg_begin, seg_count = _ordered(7, counts)
    rng = np.random.RandomState(11)
    gl = rng.rand(R) < share if 0.0 < share < 1.0 else np.full(R, share > 0)
    new_leaf = LEAVES - 1
    got_order, got_begin, got_count = jax.jit(_partition_order)(
        jnp.asarray(order), jnp.asarray(seg_begin), jnp.asarray(seg_count),
        jnp.asarray(gl), jnp.int32(leaf), jnp.int32(new_leaf),
        jnp.asarray(valid))
    if not valid:
        assert np.asarray(got_order).tobytes() == order.tobytes()
        assert np.asarray(got_begin).tobytes() == seg_begin.tobytes()
        assert np.asarray(got_count).tobytes() == seg_count.tobytes()
        return
    b, n = seg_begin[leaf], seg_count[leaf]
    parent = order[b:b + n]
    want = order.copy()
    # numpy's stable partition: lefts then rights, each still ascending
    want[b:b + n] = np.concatenate([parent[gl[parent]], parent[~gl[parent]]])
    np.testing.assert_array_equal(np.asarray(got_order), want)
    n_left = int(gl[parent].sum())
    want_begin, want_count = seg_begin.copy(), seg_count.copy()
    want_count[leaf], want_count[new_leaf] = n_left, n - n_left
    want_begin[new_leaf] = b + n_left
    np.testing.assert_array_equal(np.asarray(got_begin), want_begin)
    np.testing.assert_array_equal(np.asarray(got_count), want_count)
    for child in (leaf, new_leaf):
        seg = want[want_begin[child]:want_begin[child] + want_count[child]]
        assert (np.diff(seg) > 0).all()


def test_window_ladder_holds_every_parent():
    for rows in (1, _ORDER_CHUNK, _ORDER_CHUNK + 1, 400_000, 1_000_000):
        sizes = _window_sizes(rows)
        assert sizes[-1] == _ORDER_CHUNK and sizes[0] >= rows
        assert all(a == 2 * b for a, b in zip(sizes, sizes[1:]))
        assert len(sizes) == 1 or sizes[1] < rows


# --- after a whole tree ---------------------------------------------------

def _table(n=9_000, f=8, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] * X[:, 1] + X[:, 2] + 0.3 * rng.randn(n) > 0)
    return X, y.astype(np.float64)


def _grown_state(kind: str, mode: str):
    """The ``GrowState`` a learner ends its first tree with."""
    X, y = _table()
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "min_data_in_leaf": 5, "verbosity": -1}
    if mode == "quantized":
        params.update(use_quantized_grad=True, num_grad_quant_bins=4)
    cfg = Config.from_params(params)
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    states = []

    def keep_state(out):
        states.append(out if isinstance(out, GrowState) else out[0])
        return out

    if kind == "serial":
        learner = SerialTreeLearner(cfg, ds)
        grow = learner._train_fused
        learner._train_fused = lambda *a, **k: keep_state(grow(*a, **k))
    else:
        learner = DataParallelTreeLearner(cfg, ds, make_mesh(1))
        learner._ensure_compiled()
        grow = learner._tree_fn
        learner._tree_fn = lambda *a: keep_state(grow(*a))
    grad = jnp.asarray(0.5 - y, dtype=jnp.float32)
    hess = jnp.full(len(y), 0.25, dtype=jnp.float32)
    bag = None
    if mode == "bagging":
        bag = jnp.asarray(np.random.RandomState(2).rand(len(y)) < 0.6,
                          dtype=jnp.float32)
    tree, _ = learner.train(grad, hess, bag)
    assert tree.num_leaves > 8
    return learner, states[0], tree


@pytest.mark.parametrize("mode", ["exact", "quantized", "bagging"])
@pytest.mark.parametrize("kind", ["serial", "mesh"])
def test_segments_hold_each_leafs_rows_after_a_tree(kind, mode):
    learner, state, tree = _grown_state(kind, mode)
    rows = learner.R
    order = np.asarray(state.order)
    leaf_of_row = np.asarray(state.leaf_of_row)
    begin, count = np.asarray(state.seg_begin), np.asarray(state.seg_count)
    # the rows the grower orders: the in-bag ones under a bag, every real
    # row without (the pad rows carry 0 in every channel of gh)
    in_bag = np.asarray(state.gh)[:, 2] != 0
    assert (in_bag[:learner.N].all()) == (mode != "bagging")
    assert not in_bag[learner.N:].any()
    np.testing.assert_array_equal(np.sort(order[:rows]), np.arange(rows))
    assert not order[rows:].any()
    assert (count[tree.num_leaves:] == 0).all()
    at = 0
    # the segments tile the ordered rows; a split leaves its left child at
    # the parent's begin, so the leaves are not in order of their number
    for leaf in np.argsort(begin[:tree.num_leaves], kind="stable"):
        assert begin[leaf] == at
        np.testing.assert_array_equal(
            order[at:at + count[leaf]],
            np.flatnonzero((leaf_of_row == leaf) & in_bag))
        assert count[leaf] > 0
        at += count[leaf]
    assert at == in_bag.sum()
    # the rows out of the bag and the pad rows stay behind every leaf,
    # though every real row has its leaf
    assert (leaf_of_row[:learner.N] >= 0).all()
    np.testing.assert_array_equal(order[at:rows], np.flatnonzero(~in_bag))


@pytest.mark.parametrize("make", [
    lambda cfg, ds: DataParallelTreeLearner(cfg, ds, make_mesh(2)),
    lambda cfg, ds: VotingParallelTreeLearner(cfg, ds, make_mesh(1)),
], ids=["two_device_mesh", "voting_on_one_device"])
def test_a_learner_that_does_not_compact_carries_no_order(make):
    X, y = _table(n=2_000)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                              "max_bin": 15, "verbosity": -1})
    learner = make(cfg, BinnedDataset.from_matrix(X, cfg, label=y))
    learner._ensure_compiled()
    gh = jax.ShapeDtypeStruct((learner.R, 4), jnp.float32)
    state, _ = jax.eval_shape(learner._root_fn, learner.bins, gh,
                              learner._sample_features(), jnp.int32(1),
                              learner._qs_ones)
    assert state.order is None and state.seg_begin is None \
        and state.seg_count is None
