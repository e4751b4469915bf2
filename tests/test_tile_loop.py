"""The smaller child is histogrammed over the row tiles it fills.

``treelearner/grow.py`` ``_compact_child_hist`` loops over the tiles of
the child's segment of ``GrowState.order`` (trip count: the segment's
row count over the tile's rows) and carries the histogram in the layout
of the path ``build_histogram`` takes (``ops/histogram.py``
``histogram_tiles``). Whatever the count, the result is what one pass
over exactly the child's rows gives: bit for bit in float32, the
additions being the same ones in the same order, and exactly in
integers. The Pallas kernel's accumulating entry is interpreted here;
on the CPU the learners take the scatter.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.ops import histogram
from lightgbm_tpu.ops.histogram import (DEFAULT_ROW_TILE, build_histogram,
                                        histogram_tiles,
                                        unpack_bundle_histogram)
from lightgbm_tpu.treelearner.grow import (GrowState, _compact_child_hist,
                                           _record_at, _window_sizes)
from lightgbm_tpu.treelearner.serial import (SerialTreeLearner,
                                             _leaf_histogram, _split_body)

T = DEFAULT_ROW_TILE
R, F, B = 6000, 5, 32
SEGMENT = 4 * T + 301       # the whole segment of the leaf under test
BEGIN = 777                 # where it starts in ``order``


def _rows_and_state(gh, count):
    """A grow state whose leaf 3 owns ``count`` of ``SEGMENT`` ascending
    rows from ``BEGIN`` on; what lies behind them belongs to leaf 1."""
    rng = np.random.RandomState(5)
    rows = np.sort(rng.choice(R, SEGMENT, replace=False)).astype(np.int32)
    order = np.zeros(R + _window_sizes(R)[0], np.int32)
    order[:R] = rng.permutation(R)
    order[BEGIN:BEGIN + SEGMENT] = rows
    state = GrowState(**dict.fromkeys(GrowState._fields))._replace(
        gh=jnp.asarray(gh), order=jnp.asarray(order),
        seg_begin=jnp.asarray([0, BEGIN + count, 0, BEGIN], jnp.int32),
        seg_count=jnp.asarray([0, SEGMENT - count, 0, count], jnp.int32))
    return rows[:count], state


def _data(gh_dtype, columns=F, num_bins=B):
    rng = np.random.RandomState(11)
    bins = rng.randint(0, num_bins, size=(R, columns)).astype(np.uint8)
    if gh_dtype == np.int8:
        gh = rng.randint(-127, 128, size=(R, 4)).astype(np.int8)
    else:
        gh = rng.randn(R, 4).astype(np.float32)
    return bins, gh


COUNTS = [0, 1, T - 1, T, T + 1, 3 * T + 7, SEGMENT]


@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("backend,gh_dtype", [
    ("scatter", np.float32), ("scatter", np.int8), ("onehot", np.float32),
    ("onehot", np.int8)])
def test_child_histogram_is_one_pass_over_exactly_its_rows(backend,
                                                           gh_dtype, count):
    bins, gh = _data(gh_dtype)
    rows, state = _rows_and_state(gh, count)
    impl = (backend, False)

    @jax.jit
    def child(bins, state):
        tiles = histogram_tiles(bins, state.gh, B, hist_impl=impl)
        # the int8 einsum takes two steps of its scan a tile
        assert tiles.rows == (2 * T if impl == ("onehot", False)
                              and gh_dtype == np.int8 else T)
        return _compact_child_hist(bins, state, jnp.int32(3), tiles)

    got = np.asarray(child(jnp.asarray(bins), state))
    rows_bins, rows_gh = bins[rows], gh[rows]
    if backend == "onehot" and gh_dtype == np.float32:
        # a product of a tile's one-hot sums in an order of its own, so
        # the one pass is over whole tiles too (zero rows behind)
        pad = -count % T
        rows_bins = np.concatenate([rows_bins, np.zeros((pad, F), np.uint8)])
        rows_gh = np.concatenate([rows_gh, np.zeros((pad, 4), gh_dtype)])
    want = np.asarray(build_histogram(
        jnp.asarray(rows_bins), jnp.asarray(rows_gh), B, hist_impl=impl))
    assert got.dtype == want.dtype == (
        np.int32 if gh_dtype == np.int8 else np.float32)
    # equal as numbers in every bin: float32 too, no tolerance
    np.testing.assert_array_equal(got, want)
    if gh_dtype == np.float32:
        ones = np.asarray(child(jnp.asarray(bins), state._replace(
            gh=jnp.ones((R, 4), jnp.float32))))
        assert ones[0, :, 3].sum() == count


def test_a_tile_may_not_pass_the_spare_tail_of_the_order():
    bins, gh = _data(np.float32)
    _, state = _rows_and_state(gh, T)
    tiles = histogram_tiles(bins, gh, B)._replace(
        rows=_window_sizes(R)[0] + 1)
    with pytest.raises(ValueError, match="spare tail"):
        _compact_child_hist(jnp.asarray(bins), state, jnp.int32(3), tiles)


@pytest.mark.parametrize("gh_dtype", [np.float32, np.int8])
@pytest.mark.parametrize("tiles_of_rows", [1, 3])
@pytest.mark.parametrize("feature_block", [0, 8],
                         ids=["one-block", "blocks-of-8"])
def test_kernel_continues_its_accumulator_like_one_call(gh_dtype,
                                                        tiles_of_rows,
                                                        feature_block):
    """The Pallas kernel, interpreted: ``tiles_of_rows`` row tiles a
    call, one call after another into the same accumulator, against one
    call of the one-block kernel over all the rows (the root's pass):
    the same additions, whether the features go through in one block or
    in blocks of 8 (12 features: the last block is ragged)."""
    tile, calls, features = 256, 4, 12
    S = tile * tiles_of_rows * calls
    rng = np.random.RandomState(2)
    bins = jnp.asarray(
        rng.randint(0, B, size=(S, features)).astype(np.uint8))
    gh = jnp.asarray(_data(gh_dtype)[1][:S])
    acc = histogram._kernel_zeros(features, B, 4, gh.dtype)
    step = S // calls
    for k in range(calls):
        acc = histogram._pallas_accumulate(
            acc, bins[k * step:(k + 1) * step],
            gh[k * step:(k + 1) * step], tile, interpret=True,
            feature_block=feature_block)
    got = np.asarray(histogram._from_kernel_layout(acc, B))
    whole = np.asarray(histogram._pallas_histogram_body(
        bins, gh, B, tile, interpret=True))
    assert got.tobytes() == whole.tobytes()


def test_tiles_follow_the_path_build_histogram_takes(monkeypatch):
    """The tile's rows are those of one step of the path's own row
    loop: the kernel's row tile where the kernel runs, the einsum's and
    the scatter's elsewhere."""
    sds = jax.ShapeDtypeStruct
    bins = sds((65536, 28), jnp.uint8)
    f32, i8 = sds((65536, 4), jnp.float32), sds((65536, 4), jnp.int8)
    assert histogram_tiles(bins, f32, 255).rows == T
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert histogram_tiles(bins, f32, 255).rows \
        == histogram.PALLAS_ROW_TILE
    assert histogram_tiles(bins, i8, 255).rows \
        == histogram.PALLAS_ROW_TILE_INT
    # the benchmark's widths take the kernel too, in blocks of features
    # where all of them pass its VMEM bound: F = 2,000 in float32 and
    # int8 rows at F = 968
    wide, bosch = sds((65536, 2000), jnp.uint8), sds((65536, 968), jnp.uint8)
    assert histogram_tiles(wide, f32, 255).rows \
        == histogram.PALLAS_ROW_TILE
    assert histogram_tiles(bosch, f32, 255).rows \
        == histogram.PALLAS_ROW_TILE
    assert histogram_tiles(bosch, i8, 255).rows \
        == histogram.PALLAS_ROW_TILE_INT
    # what the kernel does not take: a sharded caller's rows, float64
    # sums, int16 rows, fewer rows than one row tile, a histogram so
    # wide that not even 8 features' accumulators fit
    assert histogram_tiles(bins, f32, 255, pallas_ok=False).rows == T
    assert histogram_tiles(bins, i8, 255, pallas_ok=False).rows == 2 * T
    assert histogram_tiles(wide, f32, 255, pallas_ok=False).rows == T
    assert histogram_tiles(bins, f32, 255,
                           hist_impl=("auto", True)).rows == T
    assert histogram_tiles(bins, sds((65536, 4), jnp.int16),
                           255).rows == 2 * T
    assert histogram_tiles(sds((1024, 28), jnp.uint8),
                           sds((1024, 4), jnp.float32), 255).rows == T
    assert histogram_tiles(sds((65536, 28), jnp.uint16), f32,
                           65536).rows == T


# --- bundled columns: unpacked once a split, with the child's totals -----

def _bundled_learner(quantized: bool):
    rng = np.random.RandomState(0)
    n, block = 3000, 8
    X = np.zeros((n, 2 + 2 * block))
    X[:, :2] = rng.randn(n, 2)
    for b in range(2):
        X[np.arange(n), 2 + b * block + rng.randint(0, block, n)] = \
            rng.rand(n) + 0.5
    y = (X[:, 0] + 0.8 * (X[:, 2] > 0) - 0.6 * (X[:, 10] > 0)
         + 0.3 * rng.randn(n) > 0).astype(np.float32)
    cfg = Config.from_params({"num_leaves": 7, "min_data_in_leaf": 5,
                              "verbosity": -1,
                              "use_quantized_grad": quantized})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    assert ds.bundle is not None and ds.bundle.num_groups < ds.num_features
    return SerialTreeLearner(cfg, ds), y


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["float32", "int8"])
def test_bundled_child_histogram_is_unpacked_with_its_totals(quantized):
    lrn, _ = _bundled_learner(quantized)
    rng = np.random.RandomState(9)
    if quantized:
        gh = rng.randint(-100, 101, size=(lrn.R, 4)).astype(np.int8)
        gh[:, 2:] = 1
    else:
        gh = rng.randn(lrn.R, 4).astype(np.float32)
        gh[:, 2:] = 1
    count = 2 * T + 45
    rows = np.sort(rng.choice(lrn.N, count, replace=False)).astype(np.int32)
    order = np.zeros(lrn.R + _window_sizes(lrn.R)[0], np.int32)
    order[BEGIN:BEGIN + count] = rows
    state = GrowState(**dict.fromkeys(GrowState._fields))._replace(
        gh=jnp.asarray(gh), order=jnp.asarray(order),
        seg_begin=jnp.asarray([BEGIN], jnp.int32),
        seg_count=jnp.asarray([count], jnp.int32))
    totals = jnp.asarray(gh[rows].astype(np.float32).sum(axis=0))
    kw = dict(B=lrn.B, Bg=lrn.Bg, bundled=True, hist_impl=lrn._hist_impl)

    @jax.jit
    def child(bins, state):
        bh = _compact_child_hist(
            bins, state, jnp.int32(0),
            histogram_tiles(bins, state.gh, lrn.Bg,
                            hist_impl=lrn._hist_impl))
        return unpack_bundle_histogram(
            bh, lrn._btab.group_of, lrn._btab.first_bin,
            lrn._btab.num_bins, lrn._btab.zero_fix, lrn.meta.zero_bin,
            None if quantized else totals, lrn.B)

    got = np.asarray(child(lrn.bins, state))
    want = np.asarray(_leaf_histogram(
        lrn.bins[rows], jnp.asarray(gh[rows]), lrn.meta, lrn._btab,
        totals=totals, **kw))
    np.testing.assert_array_equal(got, want)
    # every feature's bins hold every row of the child, zero-bin rows too
    assert (got[:lrn.F, :, 3].sum(axis=1) == count).all()


# --- a step that is not valid leaves the state as it was -----------------

def test_invalid_step_leaves_the_store_and_the_order_as_they_were():
    lrn, y = _bundled_learner(False)
    grad = jnp.asarray(0.5 - y)
    hess = jnp.full(lrn.N, 0.25, dtype=jnp.float32)
    gh = jnp.concatenate([
        jnp.stack([grad, hess, jnp.ones_like(grad), jnp.ones_like(grad)],
                  axis=1),
        jnp.zeros((lrn.R - lrn.N, 4), jnp.float32)])
    fmask = jnp.ones(lrn.Fp, dtype=bool)
    state, rec = lrn._root_fn(
        lrn.bins, gh, lrn._leaf_of_row0, fmask, jnp.asarray(True),
        jnp.int32(0), lrn._qs_ones, lrn.meta, lrn.params, lrn._btab)
    assert float(rec.gain) > 0
    noise = np.random.RandomState(1).randn(
        *state.hists.shape[1:]).astype(np.float32)
    state = state._replace(hists=state.hists.at[1:].set(noise))

    def step(valid):
        return jax.jit(lambda s: _split_body(
            lrn.bins, s, _record_at(s, 0), jnp.int32(0), jnp.int32(1),
            jnp.asarray(valid), fmask, fmask, lrn.meta, lrn.params,
            lrn._btab, B=lrn.B, Bg=lrn.Bg, bundled=True, max_depth=0,
            extra_trees=False, has_cat=lrn._has_cat,
            hist_impl=lrn._hist_impl, qscale=lrn._qs_ones))(state)

    after = step(False)
    for name in ("hists", "order", "seg_begin", "seg_count", "leaf_of_row",
                 "gain"):
        assert np.asarray(getattr(after, name)).tobytes() \
            == np.asarray(getattr(state, name)).tobytes(), name
    # and a valid one does move them: the two children's slots, from the
    # tile loop's histogram
    moved = step(True)
    part = np.asarray(moved.leaf_of_row)
    small = 0 if (part == 0).sum() <= (part == 1).sum() else 1
    rows = np.flatnonzero(part == small)
    want = np.asarray(_leaf_histogram(
        lrn.bins[rows], gh[rows], lrn.meta, lrn._btab, B=lrn.B, Bg=lrn.Bg,
        bundled=True, hist_impl=lrn._hist_impl,
        totals=jnp.asarray(np.asarray(gh)[rows].sum(axis=0))))
    np.testing.assert_allclose(np.asarray(moved.hists[small]), want,
                               rtol=1e-5, atol=1e-4)
    assert int(moved.seg_count[small]) == len(rows)


# --- the counter of the rows that went through the kernel ----------------

def test_kernel_rows_are_counted_where_the_tiles_are_the_kernels(
        timer_on, monkeypatch):
    """``grow/hist_rows_kernel`` beside ``grow/hist_rows_bucketed``: the
    root's rows and the smaller children's tiles of a tree whose passes
    take the Pallas kernel; still on the CPU, whose passes scatter."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.registry import registry
    rng = np.random.RandomState(3)
    X = rng.randn(3000, 6)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "min_data_in_leaf": 5, "tree_learner": "data",
              "mesh_shape": "data=1"}
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(
        X, label=y, params=dict(params)).construct())
    learner = bst.inner.learner
    names = ("grow/hist_rows_kernel", "grow/hist_rows_bucketed")

    def moved():
        before = [registry.count(n) for n in names]
        bst.update()
        return [registry.count(n) - b for n, b in zip(names, before)]

    kernel, bucketed = moved()
    assert kernel == 0 and bucketed > 0
    tiles = learner._data_tiles()
    assert not tiles.kernel
    monkeypatch.setattr(learner, "_data_tiles",
                        lambda: tiles._replace(kernel=True))
    kernel, bucketed = moved()
    assert kernel == learner.R + bucketed


@pytest.mark.parametrize("quantized", [False, True], ids=["float32", "int8"])
def test_kernel_rows_in_pieces_are_the_float32_rows(
        quantized, timer_on, monkeypatch):
    """``grow/hist_rows_kernel_pieces`` beside ``grow/hist_rows_kernel``:
    every kernel row where the rows are float32 (the kernel splits them
    into three bf16 pieces), none where they are int8."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.registry import registry
    rng = np.random.RandomState(4)
    X = rng.randn(3000, 6)
    y = (X[:, 0] - X[:, 3] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "min_data_in_leaf": 5, "tree_learner": "data",
              "mesh_shape": "data=1", "use_quantized_grad": quantized}
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(
        X, label=y, params=dict(params)).construct())
    learner = bst.inner.learner
    tiles = learner._data_tiles()
    monkeypatch.setattr(learner, "_data_tiles",
                        lambda: tiles._replace(kernel=True))
    names = ("grow/hist_rows_kernel", "grow/hist_rows_kernel_pieces")
    before = [registry.count(n) for n in names]
    bst.update()
    kernel, pieces = [registry.count(n) - b for n, b in zip(names, before)]
    assert kernel > learner.R
    assert pieces == (0 if quantized else kernel)
