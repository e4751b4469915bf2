"""The partition pays the categorical table lookup only where the data
has a categorical feature.

``_go_left_by_bin`` routes the rows of a split leaf. For a categorical
split it reads ``cat_mask[col]``: a gather from the ``[B]`` boolean table
over every row of the data. XLA keeps that gather under a
``where(False, ...)``, and on the v5e it was 7.3 ns a row a split, 29% of
an iteration at the Bosch shape (ISSUE 32). ``_partition_rec`` strips the
record's categorical fields where the learner's ``_has_cat`` is false.
Held here:

- (a) the split step of each learner family, traced from numeric data,
  holds no gather from a ``[B]`` boolean table; traced from data with a
  categorical feature it holds it, as before;
- (b) the lookup under an all-false ``is_categorical`` never changed a
  bit of the answer;
- (c) a learner made to keep the lookup grows the same trees on numeric
  data as the learner left alone;
- (d) the whole-tree program compiled for a described v5e has no
  ``obs_partition/gather`` (the host-side check of
  ``.claude/skills/verify/SKILL.md``);
- the mesh learner's counters ``grow/partition_splits`` and
  ``grow/partition_cat_splits`` say how often the lookup is needed.
"""
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.binning import MissingType
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.shards import ShardedBinnedDataset
from lightgbm_tpu.obs.registry import registry
from lightgbm_tpu.parallel import DataParallelTreeLearner, make_mesh
from lightgbm_tpu.treelearner import sharded
from lightgbm_tpu.treelearner.grow import _go_left_by_bin
from lightgbm_tpu.treelearner.serial import SerialTreeLearner, _split_body
from test_hist_store_inplace import compile_tree_program, describe_v5e

PARAMS = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
          "min_data_in_leaf": 5, "verbosity": -1,
          "bin_construct_sample_cnt": 600}


def _table(categorical: bool, n: int = 600, seed: int = 4):
    """(X, y, categorical_feature): four columns, the first an integer
    code of 6 levels that is declared categorical or left numeric."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 4)
    X[:, 0] = rng.randint(0, 6, n)
    effect = np.array([1.5, -1.0, 0.2, -0.4, 0.9, -1.3])
    y = (effect[X[:, 0].astype(int)] + X[:, 1] > 0).astype(np.float64)
    return X, y, ([0] if categorical else None)


def _binned(categorical: bool):
    X, y, cat = _table(categorical)
    cfg = Config.from_params(dict(PARAMS))
    return cfg, BinnedDataset.from_matrix(X, cfg, label=y,
                                          categorical_feature=cat)


# --- (a) what the split step lowers ---------------------------------------

def _sub_jaxprs(value):
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(value, "jaxpr"):
        yield from _sub_jaxprs(value.jaxpr)
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def bool_table_gathers(jaxpr, B: int) -> int:
    """Gathers whose operand is a ``[B]`` boolean table, in ``jaxpr``
    and every jaxpr under it (``pjit``, ``while``, ``cond``)."""
    found = 0
    for eqn in jaxpr.eqns:
        aval = eqn.invars[0].aval if eqn.invars else None
        if (eqn.primitive.name == "gather" and aval.shape == (B,)
                and aval.dtype == jnp.bool_):
            found += 1
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                found += bool_table_gathers(sub, B)
    return found


def _serial_step(categorical: bool, tmp_path):
    cfg, ds = _binned(categorical)
    ln = SerialTreeLearner(cfg, ds)
    assert ln._has_cat == categorical
    gh = jnp.zeros((ln.R, 4), jnp.float32)
    fmask = jnp.ones(ln.Fp, dtype=bool)
    state, rec = jax.eval_shape(
        ln._root_fn, ln.bins, gh, ln._leaf_of_row0, fmask,
        jnp.asarray(True), jnp.int32(0), ln._qs_ones, ln.meta, ln.params,
        ln._btab)
    step = functools.partial(
        _split_body, B=ln.B, Bg=ln.Bg, bundled=ln._bundled,
        max_depth=ln.max_depth, extra_trees=False, has_cat=ln._has_cat,
        hist_impl=ln._hist_impl)
    jaxpr = jax.make_jaxpr(step)(
        ln.bins, ln.bins_fm, state, rec, jnp.int32(0), jnp.int32(1),
        jnp.asarray(True), fmask, fmask, ln.meta, ln.params, ln._btab)
    return jaxpr.jaxpr, ln.B


def _mesh_step(categorical: bool, tmp_path):
    cfg, ds = _binned(categorical)
    ln = DataParallelTreeLearner(cfg, ds, make_mesh(1))
    assert ln._has_cat == categorical
    gh = jnp.zeros((ln.R, 4), jnp.float32)
    fmask = ln._sample_features()
    state, rec = jax.eval_shape(
        ln._root_impl, ln.bins, gh, fmask, jnp.int32(0), ln._qscale)
    jaxpr = jax.make_jaxpr(
        functools.partial(ln._mesh_split_body, qscale=ln._qscale))(
        ln.bins, ln.bins_fm, state, rec, jnp.int32(0), jnp.int32(1),
        jnp.asarray(True), fmask, fmask)
    return jaxpr.jaxpr, ln.B


def _sharded_step(categorical: bool, tmp_path, frontier: int,
                  fn_name: str, raw, static):
    """The arguments the sharded learner really hands its jitted shard
    step while it grows one tree, traced through the step's body."""
    X, y, cat = _table(categorical)
    params = dict(PARAMS, tpu_frontier_splits=frontier)

    def source():
        for lo in range(0, len(X), 250):
            yield X[lo:lo + 250], y[lo:lo + 250].astype(np.float32)

    ds = ShardedBinnedDataset.from_chunk_source(
        source, Config.from_params(dict(params)), str(tmp_path),
        shard_rows=250, total_rows=len(X), categorical_feature=cat)
    calls = []
    jitted = getattr(sharded, fn_name)

    def spy(*args):
        calls.append(args)
        return jitted(*args)

    with mock.patch.object(sharded, fn_name, spy):
        booster = create_boosting(
            Config.from_params(dict(params, num_iterations=1)), ds)
        booster.train_one_iter()
    assert booster.learner._has_cat == categorical
    assert calls, "the sharded learner never called %s" % fn_name
    jaxpr = jax.make_jaxpr(raw, static_argnums=static)(*calls[0])
    return jaxpr.jaxpr, booster.learner.B


STEPS = {
    "serial": _serial_step,
    "mesh": _mesh_step,
    "sharded": functools.partial(
        _sharded_step, frontier=1, fn_name="_shard_step_fn",
        raw=sharded._shard_step, static=(7,)),
    "sharded_kbatch": functools.partial(
        _sharded_step, frontier=4, fn_name="_shard_kstep_fn",
        raw=sharded._shard_kstep, static=(10, 11)),
}


@pytest.mark.parametrize("categorical", [False, True],
                         ids=["numeric", "categorical"])
@pytest.mark.parametrize("family", list(STEPS))
def test_split_step_looks_the_table_up_only_for_categorical_data(
        family, categorical, tmp_path):
    jaxpr, B = STEPS[family](categorical, tmp_path)
    found = bool_table_gathers(jaxpr, B)
    if categorical:
        # one lookup per routed split (the K-batch step routes K)
        assert found >= 1, "the categorical routing lost its lookup"
    else:
        assert found == 0, (
            "the split step gathers from a [%d] boolean table although "
            "the data has no categorical feature" % B)


# --- (b) the lookup under an all-false flag was always a no-op ------------

@pytest.mark.parametrize("missing", [MissingType.NONE, MissingType.NAN,
                                     MissingType.ZERO],
                         ids=["none", "nan", "zero"])
@pytest.mark.parametrize("default_left", [False, True],
                         ids=["right", "left"])
def test_all_false_is_categorical_equals_no_lookup(missing, default_left):
    rng = np.random.RandomState(int(missing) * 2 + default_left)
    B = 64
    col = jnp.asarray(rng.randint(0, B, 5000), jnp.int32)
    args = (col, jnp.int32(rng.randint(1, B - 1)),
            jnp.asarray(default_left), jnp.int32(int(missing)),
            jnp.int32(B - 1), jnp.int32(rng.randint(0, B)))
    table = jnp.asarray(rng.rand(B) < 0.5)
    plain = _go_left_by_bin(*args)
    looked_up = _go_left_by_bin(*args, jnp.asarray(False), table)
    assert plain.dtype == jnp.bool_ and 0 < int(plain.sum()) < col.size
    np.testing.assert_array_equal(np.asarray(plain),
                                  np.asarray(looked_up))
    if missing != MissingType.NONE:
        special = B - 1 if missing == MissingType.NAN else int(args[5])
        at = np.asarray(col) == special
        assert at.any() and (np.asarray(plain)[at] == default_left).all()
    # and a true flag does read the table
    np.testing.assert_array_equal(
        np.asarray(_go_left_by_bin(*args, jnp.asarray(True), table)),
        np.asarray(table)[np.asarray(col)])


# --- (c) the same trees with and without the lookup -----------------------

class _AlwaysTrue:
    """Data descriptor: the learner's ``self._has_cat = ...`` is ignored
    and every read gives True (today's program, lookup and all)."""

    def __get__(self, obj, owner):
        return True

    def __set__(self, obj, value):
        pass


def _numeric_model(learner: str, quantized: bool) -> str:
    rng = np.random.RandomState(11)
    X = rng.randn(3000, 6)
    X[rng.rand(3000) < 0.1, 2] = np.nan       # a NaN-missing feature
    X[rng.rand(3000) < 0.6, 3] = 0.0          # a sparse one
    y = (X[:, 0] * X[:, 1] + np.nan_to_num(X[:, 2]) + X[:, 3] > 0
         ).astype(np.float64)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
              "max_bin": 63, "min_data_in_leaf": 5, "zero_as_missing": False}
    if learner == "mesh":
        params.update(tree_learner="data", num_machines=1)
    if quantized:
        params.update(use_quantized_grad=True, num_grad_quant_bins=4)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4)
    assert type(bst.inner.learner).__name__ == {
        "serial": "SerialTreeLearner",
        "mesh": "DataParallelTreeLearner"}[learner]
    return bst.model_to_string()


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["exact", "quantized"])
@pytest.mark.parametrize("learner", ["serial", "mesh"])
def test_numeric_data_grows_the_same_trees_without_the_lookup(
        learner, quantized, monkeypatch):
    alone = _numeric_model(learner, quantized)
    cls = {"serial": SerialTreeLearner,
           "mesh": DataParallelTreeLearner}[learner]
    monkeypatch.setattr(cls, "_has_cat", _AlwaysTrue(), raising=False)
    forced = _numeric_model(learner, quantized)
    assert alone.count("Tree=") == 4 and "num_leaves=15" in alone
    assert forced == alone


# --- (d) the whole-tree program on a described v5e ------------------------

def test_no_partition_gather_in_the_tree_program_on_v5e():
    try:
        topo = describe_v5e()
    except Exception as e:  # no libtpu, or its lock is held elsewhere
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    hlo, _ = compile_tree_program(topo, 64, 31)
    # the scope's other operations are there, so the names are readable
    assert "obs_partition/" in hlo and "/while/body/" in hlo
    lines = [ln.strip()[:200] for ln in hlo.splitlines()
             if "obs_partition/gather" in ln]
    assert lines == [], (
        "the tree program gathers over every row in the partition of "
        "data with no categorical feature:\n" + "\n".join(lines))


# --- the counters ---------------------------------------------------------

def _partition_counts():
    return (registry.count("grow/partition_splits"),
            registry.count("grow/partition_cat_splits"))


def _train_mesh(categorical: bool):
    X, y, cat = _table(categorical, n=3000)
    bst = lgb.train(dict(PARAMS, tree_learner="data", num_machines=1),
                    lgb.Dataset(X, label=y,
                                categorical_feature=cat or "auto"),
                    num_boost_round=3)
    assert type(bst.inner.learner).__name__ == "DataParallelTreeLearner"
    text = bst.model_to_string()
    leaves = [int(ln.split("=")[1]) for ln in text.splitlines()
              if ln.startswith("num_leaves=")]
    return sum(n - 1 for n in leaves), text.count("cat_threshold=")


@pytest.mark.parametrize("categorical", [False, True],
                         ids=["numeric", "categorical"])
def test_partition_counters_follow_the_records(categorical, timer_on):
    splits0, cat0 = _partition_counts()
    applied, cat_trees = _train_mesh(categorical)
    splits, cat = _partition_counts()
    assert applied >= 3 and splits - splits0 == applied
    if categorical:
        assert 0 < cat - cat0 <= applied and cat_trees > 0
    else:
        assert cat - cat0 == 0 and cat_trees == 0


def test_partition_counters_stay_still_while_the_timer_is_off():
    assert not registry.timer.enabled
    before = _partition_counts()
    _train_mesh(False)
    assert _partition_counts() == before
