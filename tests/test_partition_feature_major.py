"""The partition reads the split feature's column from the learners'
feature-major copy of the bins (``grow.bins_by_feature``).

A column of the row-major ``u8[R, C]`` table is one byte in every
128-byte tile row on the TPU, so the comparison pass read the whole
table's tile columns to get one byte a row (0.48 ms a split at 1M rows
on the v5e). Every learner that holds its bins on the device keeps a
second, feature-major copy and the pass reads one column's bytes alone.
Held here:

- (a) the column and the rows sent left, from the copy, equal the
  row-major table's for every feature: numerical bins with NaN and zero
  missing types, a categorical record, bundled (EFB) columns, and a
  row-sharded mesh;
- (b) the serial and the one-device mesh learner grow model text equal
  to the text they grow when the pass reads the row-major table by
  ``jnp.take``, as it did before the copy (plain, bundled, quantized);
- (c) the copy's pad rows never reach ``leaf_of_row``;
- (d) ``grow/partition_cols_feature_major`` counts every applied split.
"""
import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.binning import MissingType
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.efb import member_bin
from lightgbm_tpu.obs.registry import registry
from lightgbm_tpu.parallel import DataParallelTreeLearner, make_mesh
from lightgbm_tpu.parallel import data_parallel
from lightgbm_tpu.treelearner import grow, serial
from lightgbm_tpu.treelearner.grow import (SplitRecord, _go_left_by_bin,
                                           _partition_col, _rows_go_left,
                                           bins_by_feature)
from lightgbm_tpu.treelearner.serial import SerialTreeLearner

PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
          "min_data_in_leaf": 5, "verbosity": -1}


def _numeric(n=3000, seed=3):
    """Six columns: two with NaNs, two with many exact zeros."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6)
    X[rng.rand(n) < 0.15, 0] = np.nan
    X[rng.rand(n) < 0.1, 1] = np.nan
    X[rng.rand(n) < 0.4, 2] = 0.0
    X[rng.rand(n) < 0.3, 3] = 0.0
    y = (np.nan_to_num(X[:, 0]) + X[:, 2] - 0.5 * X[:, 4]
         + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y, None


def _categorical(n=3000, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 4)
    X[:, 0] = rng.randint(0, 9, n)
    effect = rng.randn(9)
    y = (effect[X[:, 0].astype(int)] + X[:, 1] > 0).astype(np.float64)
    return X, y, [0]


def _bundled(n=3000, seed=0):
    """Two dense columns and two blocks of eight one-hot columns, which
    bundling packs into two bundle columns."""
    rng = np.random.RandomState(seed)
    block = 8
    X = np.zeros((n, 2 + 2 * block))
    X[:, :2] = rng.randn(n, 2)
    for b in range(2):
        X[np.arange(n), 2 + b * block + rng.randint(0, block, n)] = \
            rng.rand(n) + 0.5
    y = (X[:, 0] + 0.8 * (X[:, 2] > 0) - 0.6 * (X[:, 10] > 0)
         + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y, None


TABLES = {"numeric": _numeric, "zero_missing": _numeric,
          "categorical": _categorical, "bundled": _bundled}
# the numeric table's NaN columns are missing by NaN; under
# ``zero_as_missing`` its columns are missing by zero
MISSING = {"numeric": MissingType.NAN, "zero_missing": MissingType.ZERO}


def _dataset(table, **extra):
    X, y, cat = TABLES[table]()
    cfg = Config.from_params(dict(
        PARAMS, zero_as_missing=table == "zero_missing", **extra))
    ds = BinnedDataset.from_matrix(X, cfg, label=y,
                                   categorical_feature=cat)
    assert (ds.bundle is not None) == (table == "bundled")
    return cfg, ds


def _learner(kind, cfg, ds):
    if kind == "serial":
        return SerialTreeLearner(cfg, ds)
    n = {"mesh1": 1, "mesh8": 8}[kind]
    return DataParallelTreeLearner(cfg, ds, make_mesh(n))


# --- (a) the column and the rows sent left --------------------------------

def _row_major_col(lrn, f):
    """The split column the pass read before the copy: a ``jnp.take``
    from the row-major table, unbundled where the columns are bundles."""
    bins = np.asarray(lrn.bins)
    if not lrn._bundled:
        return bins[:, f].astype(np.int32)
    btab = jax.tree_util.tree_map(np.asarray, lrn._btab)
    return member_bin(bins[:, btab.group_of[f]].astype(np.int32),
                      btab.first_bin[f], btab.num_bins[f],
                      int(np.asarray(lrn.meta.zero_bin)[f]),
                      btab.zero_fix[f])


def _record(lrn, f, tbin, default_left, rng):
    B = lrn.B
    is_cat = bool(np.asarray(lrn.meta.is_categorical)[f])
    return SplitRecord(
        leaf=jnp.int32(0), gain=jnp.float32(1.0), feature=jnp.int32(f),
        threshold_bin=jnp.int32(tbin),
        default_left=jnp.asarray(default_left),
        is_categorical=jnp.asarray(is_cat),
        cat_mask=jnp.asarray(rng.rand(B) < 0.5),
        **{k: jnp.float32(0) for k in SplitRecord._fields[7:]})


@pytest.mark.parametrize("table,kind", [
    ("numeric", "serial"), ("numeric", "mesh1"), ("numeric", "mesh8"),
    ("zero_missing", "serial"), ("zero_missing", "mesh1"),
    ("categorical", "serial"), ("categorical", "mesh1"),
    ("bundled", "serial"), ("bundled", "mesh1"), ("bundled", "mesh8")])
def test_every_column_from_the_copy_equals_the_row_major_take(table, kind):
    cfg, ds = _dataset(table)
    lrn = _learner(kind, cfg, ds)
    meta = jax.tree_util.tree_map(np.asarray, lrn.meta)
    if table in MISSING:
        assert MISSING[table] in meta.missing_type[:ds.num_features]
    R = lrn.R
    fm = lrn.bins_fm
    shards = 8 if kind == "mesh8" else 1
    # a block of each device's rows, padded to whole (32, 128) tiles
    assert fm.shape[:2] == (lrn.bins.shape[1], shards)
    assert fm.shape[3] == 128 and fm.shape[2] % 32 == 0
    assert fm.shape[2] * 128 - R // shards < 4096
    # ... each on its own device
    assert fm.sharding.shard_shape(fm.shape) == fm.shape[:1] + (1,) \
        + fm.shape[2:]
    rng = np.random.RandomState(1)
    col_fn = jax.jit(lambda fm, f: _partition_col(
        fm, R, f, lrn.meta, lrn._btab, lrn._bundled))
    left_fn = jax.jit(lambda fm, rec: _rows_go_left(
        fm, R, rec, lrn.meta, lrn._btab, lrn._bundled, lrn._has_cat))
    for f in range(ds.num_features):
        want = _row_major_col(lrn, f)
        got = np.asarray(col_fn(lrn.bins_fm, jnp.int32(f)))
        np.testing.assert_array_equal(got, want, err_msg="feature %d" % f)
        if not lrn._bundled:
            np.testing.assert_array_equal(
                got[:ds.num_data], ds.feature_bin_column(f))
        for tbin in (0, int(meta.num_bin[f]) // 2, int(meta.num_bin[f]) - 2):
            for dl in (False, True):
                rec = _record(lrn, f, tbin, dl, rng)
                want_gl = _go_left_by_bin(
                    jnp.asarray(want), rec.threshold_bin, rec.default_left,
                    meta.missing_type[f], meta.num_bin[f] - 1,
                    meta.zero_bin[f], rec.is_categorical, rec.cat_mask)
                np.testing.assert_array_equal(
                    np.asarray(left_fn(lrn.bins_fm, rec)),
                    np.asarray(want_gl), err_msg="feature %d" % f)


@pytest.mark.parametrize("shards", [1, 4])
def test_the_copy_holds_each_column_in_tiles_of_its_own(shards):
    bins = np.random.RandomState(2).randint(
        0, 256, size=(5000, 7)).astype(np.uint8)
    fm = np.asarray(bins_by_feature(jnp.asarray(bins), shards))
    assert fm.dtype == np.uint8 and fm.shape[:2] == (7, shards)
    assert fm.shape[3] == 128 and fm.shape[2] % 32 == 0
    blocks = fm.reshape(7, shards, -1)
    r = 5000 // shards
    for k in range(shards):
        np.testing.assert_array_equal(blocks[:, k, :r],
                                      bins[k * r:(k + 1) * r].T)
    assert not blocks[:, :, r:].any()


# --- (b) the trees the learners grow --------------------------------------

@contextlib.contextmanager
def _row_major_take():
    """The pass as it was before the copy: the learner's "copy" is the
    row-major table itself and the column a ``jnp.take`` from it."""
    def take(bins, rows, c):
        return jnp.take(bins, c, axis=1)
    with mock.patch.object(grow, "_feature_column", take), \
            mock.patch.object(serial, "stage_bins_by_feature", lambda b: b), \
            mock.patch.object(data_parallel, "stage_bins_by_feature",
                              lambda b: b):
        yield


def _model_text(kind, table, extra, row_major=False):
    X, y, cat = TABLES[table]()
    params = dict(PARAMS, **extra)
    params["tree_learner"] = "serial" if kind == "serial" else "data"
    if kind != "serial":
        params.update(num_machines=1, mesh_shape="data=1")
    ds = lgb.Dataset(X, label=y, categorical_feature=cat or "auto")
    with _row_major_take() if row_major else contextlib.nullcontext():
        bst = lgb.train(params, ds, num_boost_round=4)
    lrn = bst.inner.learner
    assert type(lrn).__name__ == ("SerialTreeLearner" if kind == "serial"
                                  else "DataParallelTreeLearner")
    assert kind == "serial" or lrn.mesh.devices.size == 1
    if row_major:
        assert lrn.bins_fm is lrn.bins
    else:
        assert lrn.bins_fm.shape[:2] == (lrn.bins.shape[1], 1)
    return bst.model_to_string()


@pytest.mark.parametrize("kind", ["serial", "mesh1"])
@pytest.mark.parametrize("table,extra", [
    ("numeric", {}), ("bundled", {}),
    ("numeric", {"use_quantized_grad": True})],
    ids=["plain", "bundled", "quantized"])
def test_learners_grow_the_trees_of_the_row_major_pass(kind, table, extra):
    text = _model_text(kind, table, extra)
    assert text.count("split_feature=") == 4
    assert text == _model_text(kind, table, extra, row_major=True)


# --- (c) the copy's pad rows ------------------------------------------------

@pytest.mark.parametrize("kind", ["serial", "mesh1"])
def test_pad_rows_of_the_copy_never_reach_leaf_of_row(kind):
    cfg, ds = _dataset("numeric")
    lrn = _learner(kind, cfg, ds)
    N, R, shape = lrn.N, lrn.R, lrn.bins_fm.shape
    fm = np.asarray(lrn.bins_fm).reshape(shape[0], -1)   # one block
    assert fm.shape[1] > N and not fm[:, N:].any()
    grad = jnp.asarray(np.random.RandomState(4).randn(N), jnp.float32)
    hess = jnp.full(N, 0.25, jnp.float32)
    states = []
    name = "_train_fused" if kind == "serial" else "_tree_fn"
    if kind != "serial":
        lrn._ensure_compiled()
    run = getattr(lrn, name)

    def keep(*a, **k):
        out = run(*a, **k)
        states.append(out if kind == "serial" else out[0])
        return out
    setattr(lrn, name, keep)
    clean, _ = lrn.train(grad, hess)
    # the pad rows at the largest bin, which sends every row right
    padded = fm.copy()
    padded[:, N:] = 255
    lrn.bins_fm = jnp.asarray(padded.reshape(shape))
    dirty, _ = lrn.train(grad, hess)
    assert clean.num_leaves > 4 and clean.to_string() == dirty.to_string()
    lor = [np.asarray(s.leaf_of_row) for s in states]
    assert lor[0].shape == (R,)
    np.testing.assert_array_equal(lor[0], lor[1])
    assert (lor[0][N:] == -1).all()


# --- (d) the counter --------------------------------------------------------

def test_every_applied_split_reads_the_copy(timer_on):
    def counts():
        return (registry.count("grow/partition_splits"),
                registry.count("grow/partition_cols_feature_major"))
    splits0, fm0 = counts()
    X, y, _ = _numeric()
    bst = lgb.train(dict(PARAMS, tree_learner="data", num_machines=1,
                         mesh_shape="data=1"),
                    lgb.Dataset(X, label=y), num_boost_round=3)
    assert type(bst.inner.learner).__name__ == "DataParallelTreeLearner"
    assert bst.inner.learner.mesh.devices.size == 1
    applied = sum(int(ln.split("=")[1]) - 1
                  for ln in bst.model_to_string().splitlines()
                  if ln.startswith("num_leaves="))
    splits, fm = counts()
    assert applied > 3 * 8 and splits - splits0 == applied
    assert fm - fm0 == applied
