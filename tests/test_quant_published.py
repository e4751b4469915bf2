"""Quantized training at LightGBM's published settings
(``use_quantized_grad`` with ``num_grad_quant_bins``, ``stochastic_rounding``,
``quant_train_renew_leaf``).

- the discretizer follows the published levels (gradient in ``[-B/2, B/2]``
  on the scale ``max|g| / (B/2)``, hessian in ``[0, B]`` on ``max|h| / B``),
  checked against numpy, stochastic and to nearest;
- the three upstream parameters are known to ``Config`` and never silently
  ignored; without ``num_grad_quant_bins`` the ``quant_grad_bits`` scheme
  stays;
- the serial and mesh learners keep drawing identical rows;
- the discretizer and the dequantization are named on the device clock
  (``obs_quantize``, ``obs_dequantize``) and counted (``quant/trees``,
  ``quant/rows_discretized``).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.obs.registry import registry
from lightgbm_tpu.ops.quantize import (QuantLevels, dequantize_hist,
                                       published_levels, quantize_gh,
                                       symmetric_levels)
from lightgbm_tpu.parallel import DataParallelTreeLearner, make_mesh
from lightgbm_tpu.treelearner.serial import SerialTreeLearner
from lightgbm_tpu.utils import log
from lightgbm_tpu.utils.log import LightGBMError


def _rows(n=5000, seed=0):
    rng = np.random.RandomState(seed)
    g = rng.randn(n).astype(np.float32)
    h = (np.abs(rng.randn(n)) * 0.2 + 1e-3).astype(np.float32)
    ind = (rng.rand(n) < 0.8).astype(np.float32)
    return g, h, ind


# --- the discretizer against numpy ---------------------------------------

@pytest.mark.parametrize("stochastic", [True, False],
                         ids=["stochastic", "nearest"])
@pytest.mark.parametrize("bins", [4, 16])
def test_discretizer_follows_the_published_levels(bins, stochastic):
    g, h, ind = _rows()
    key = jax.random.fold_in(jax.random.PRNGKey(0), 3)
    levels = published_levels(bins, stochastic, 8, len(g))
    assert levels == QuantLevels(bins // 2, bins, 0, stochastic)
    gh, scale = quantize_gh(jnp.asarray(g), jnp.asarray(h), jnp.asarray(ind),
                            key, levels, jnp.int8)
    gh, scale = np.asarray(gh), np.asarray(scale)
    assert gh.dtype == np.int8 and gh.shape == (len(g), 4)

    gi, hi = g * ind, h * ind
    s_g = np.float32(np.abs(gi).max()) / np.float32(bins // 2)
    s_h = np.float32(np.abs(hi).max()) / np.float32(bins)
    np.testing.assert_array_equal(scale, np.array([s_g, s_h], np.float32))
    u = (np.asarray(jax.random.uniform(key, (len(g), 2))) if stochastic
         else np.full((len(g), 2), 0.5, np.float32))
    want_g = np.clip(np.floor(gi / s_g + u[:, 0]), -(bins // 2), bins // 2)
    want_h = np.clip(np.floor(hi / s_h + u[:, 1]), 0, bins)
    np.testing.assert_array_equal(gh[:, 0], want_g.astype(np.int8))
    np.testing.assert_array_equal(gh[:, 1], want_h.astype(np.int8))
    np.testing.assert_array_equal(gh[:, 2], ind.astype(np.int8))
    assert (gh[:, 3] == 1).all()
    # the largest row of each channel sits on its last level, none beyond
    assert np.abs(gh[:, 0]).max() == bins // 2
    assert gh[:, 1].min() == 0 and gh[:, 1].max() == bins
    if stochastic:      # unbiased: the dequantized mean is the mean
        assert abs((gh[:, 0] * s_g).mean() - gi.mean()) < 4 * s_g / 70
    else:               # to nearest: never more than half a level away
        assert np.abs(gh[:, 0] * s_g - gi).max() <= 0.5 * s_g * (1 + 1e-6)


@pytest.mark.parametrize("bins", [4, 16])
def test_hessian_levels_are_never_negative(bins):
    """A hessian at or below zero (a custom objective's, an out-of-bag
    row's) rounds to level 0, never under it; the ``quant_grad_bits``
    scheme keeps its symmetric range."""
    g, h, ind = _rows(seed=1)
    h[::7] = -h[::7]
    h[::11] = 0.0
    args = (jnp.asarray(g), jnp.asarray(h), jnp.asarray(ind),
            jax.random.PRNGKey(5))
    gh, _ = quantize_gh(*args, published_levels(bins, True, 8, len(g)),
                        jnp.int8)
    assert int(np.asarray(gh)[:, 1].min()) == 0
    old, _ = quantize_gh(*args, 127, jnp.int8)
    assert int(np.asarray(old)[:, 1].min()) < 0


def test_an_int_still_means_the_symmetric_scheme():
    g, h, ind = _rows(seed=2)
    args = (jnp.asarray(g), jnp.asarray(h), jnp.asarray(ind),
            jax.random.PRNGKey(9))
    a, sa = quantize_gh(*args, 127, jnp.int8)
    b, sb = quantize_gh(*args, symmetric_levels(127), jnp.int8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
    assert symmetric_levels(127) == QuantLevels(127, 127, -127, True)


def test_levels_the_accumulator_cannot_hold_are_refused():
    assert published_levels(64, True, 8, 1 << 20).hess == 64
    with pytest.raises(LightGBMError, match="num_grad_quant_bins=200"):
        published_levels(200, True, 8, 1000)            # over int8
    with pytest.raises(LightGBMError, match="num_grad_quant_bins=64"):
        published_levels(64, True, 8, 1 << 26)          # over int32 sums


# --- the parameters ------------------------------------------------------

@pytest.fixture
def warnings_logged():
    seen = []
    log.register_log_callback(seen.append)
    yield seen
    log.register_log_callback(None)
    log.set_verbosity(-1)


@pytest.mark.parametrize("name,value,default", [
    ("num_grad_quant_bins", 16, 4),
    ("stochastic_rounding", False, True),
    ("quant_train_renew_leaf", False, False),
])
def test_upstream_parameter_is_known(warnings_logged, name, value, default):
    assert getattr(Config(), name) == default       # upstream's default
    cfg = Config.from_params({"use_quantized_grad": True, name: value,
                              "verbosity": 0})
    assert getattr(cfg, name) == value
    assert not [m for m in warnings_logged if "Unknown parameter" in m]
    Config.from_params({"no_such_parameter": 1, "verbosity": 0})
    assert [m for m in warnings_logged if "Unknown parameter" in m]


def test_renew_leaf_is_refused_by_name_not_ignored():
    with pytest.raises(LightGBMError, match="quant_train_renew_leaf"):
        Config.from_params({"use_quantized_grad": True,
                            "quant_train_renew_leaf": True})
    # the exact mode has no quantized leaf values to renew
    Config.from_params({"quant_train_renew_leaf": True, "verbosity": -1})


def test_fewer_than_two_bins_is_refused():
    with pytest.raises(LightGBMError, match="num_grad_quant_bins"):
        Config.from_params({"num_grad_quant_bins": 1})


@pytest.mark.parametrize("params,want", [
    ({}, 127),
    ({"quant_grad_bits": 8}, 127),
    ({"num_grad_quant_bins": 4}, QuantLevels(2, 4, 0, True)),
    ({"num_grad_quant_bins": 4, "stochastic_rounding": False},
     QuantLevels(2, 4, 0, False)),
    ({"num_grad_quant_bins": 16}, QuantLevels(8, 16, 0, True)),
])
def test_levels_a_learner_takes_from_the_parameters(params, want):
    """Given, ``num_grad_quant_bins`` selects the published levels; not
    given, ``quant_grad_bits`` decides as before."""
    cfg = Config.from_params(dict({"use_quantized_grad": True,
                                   "num_leaves": 7, "verbosity": -1},
                                  **params))
    assert cfg.grad_quant_bins_given() == params.get("num_grad_quant_bins",
                                                     0)
    ds = BinnedDataset.from_matrix(np.random.RandomState(0).randn(300, 4),
                                   cfg)
    for learner in (SerialTreeLearner(cfg, ds),
                    DataParallelTreeLearner(cfg, ds, make_mesh(1))):
        assert learner._qmax == want and learner._qdtype == jnp.int8


# --- the learners --------------------------------------------------------

def _table(n=3000, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.3 * rng.randn(n) > 0.3)
    return X, y.astype(np.float64)


@pytest.mark.parametrize("bins", [4, 16])
def test_serial_and_mesh_learners_draw_identical_rows(bins):
    X, y = _table(777, 6)
    grad = np.where(y > 0, -0.5, 0.5).astype(np.float32)
    grad *= np.random.RandomState(1).rand(777).astype(np.float32)
    hess = np.abs(grad) * (1 - np.abs(grad))
    cfg = Config.from_params({"num_leaves": 15, "min_data_in_leaf": 5,
                              "use_quantized_grad": True,
                              "num_grad_quant_bins": bins, "verbosity": -1})
    ds = BinnedDataset.from_matrix(X, cfg)
    ts, ps = SerialTreeLearner(cfg, ds).train(jnp.asarray(grad),
                                              jnp.asarray(hess))
    td, pd = DataParallelTreeLearner(cfg, ds, make_mesh(8)).train(
        jnp.asarray(grad), jnp.asarray(hess))
    assert ts.num_leaves == td.num_leaves > 2
    n = ts.num_internal
    np.testing.assert_array_equal(ts.split_feature[:n], td.split_feature[:n])
    np.testing.assert_array_equal(ts.threshold_in_bin[:n],
                                  td.threshold_in_bin[:n])
    np.testing.assert_array_equal(np.asarray(ps), np.asarray(pd))


def test_training_follows_the_levels_it_is_given():
    """Four bins and the +-127 scheme grow different forests from the same
    table, both of them learn, and a second run repeats the first."""
    X, y = _table()
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "use_quantized_grad": True}

    def scores(**extra):
        bst = lgb.train(dict(base, **extra), lgb.Dataset(X, label=y),
                        num_boost_round=8)
        return bst.predict(X, raw_score=True)

    four, old = scores(num_grad_quant_bins=4), scores()
    assert np.abs(four - old).max() > 1e-3
    np.testing.assert_array_equal(four, scores(num_grad_quant_bins=4))
    for s in (four, old, scores(num_grad_quant_bins=4,
                                stochastic_rounding=False)):
        assert s[y > 0].mean() - s[y == 0].mean() > 0.5


def test_counters_move_with_the_trees_discretized():
    X, y = _table(2000)
    trees0 = registry.count("quant/trees")
    rows0 = registry.count("quant/rows_discretized")
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "use_quantized_grad": True, "num_grad_quant_bins": 4},
              lgb.Dataset(X, label=y), num_boost_round=3)
    assert registry.count("quant/trees") - trees0 == 3
    assert registry.count("quant/rows_discretized") - rows0 == 3 * 2000
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
              lgb.Dataset(X, label=y), num_boost_round=2)
    assert registry.count("quant/trees") - trees0 == 3


# --- the device scopes ---------------------------------------------------

@pytest.fixture(scope="module")
def lowered():
    cfg = Config.from_params({"num_leaves": 31, "max_bin": 63,
                              "verbosity": -1, "use_quantized_grad": True,
                              "num_grad_quant_bins": 4})
    ds = BinnedDataset.from_matrix(np.random.RandomState(0).randn(4000, 8),
                                   cfg)
    lrn = DataParallelTreeLearner(cfg, ds, make_mesh(1))
    lrn._ensure_compiled()
    sds = jax.ShapeDtypeStruct
    vec = sds((lrn.N,), jnp.float32)
    key = jax.random.PRNGKey(0)
    root = lrn._root_fn.lower(
        lrn.bins, sds((lrn.R, 4), jnp.int8), lrn._sample_features(),
        jnp.int32(1), lrn._qs_ones)
    return {
        "ops.quantize_gh": quantize_gh.lower(
            vec, vec, vec, key, lrn._qmax, jnp.int8),
        "scan staging": jax.jit(
            lambda g, h, k: lrn._make_gh_quantized_traced(g, h, None, k)
        ).lower(vec, vec, key),
        "mesh.root": root,
        "dequantize_hist": jax.jit(dequantize_hist).lower(
            sds((8, 64, 4), jnp.int32), sds((2,), jnp.float32)),
    }


@pytest.mark.parametrize("program,scope", [
    ("ops.quantize_gh", r"obs_quantize"),
    ("scan staging", r"obs_quantize"),
    ("mesh.root", r"obs_split_scan/obs_dequantize"),
    ("dequantize_hist", r"obs_dequantize"),
])
def test_scope_is_in_the_lowered_program(lowered, program, scope):
    text = lowered[program].as_text(debug_info=True)
    assert re.search(r'[/"]%s[/"]' % scope, text), (
        "%s has no operation under %s" % (program, scope))


def test_float_histograms_pass_the_dequantizer_untouched():
    hist = jnp.ones((2, 4, 4), jnp.float32)
    assert dequantize_hist(hist, None) is hist
