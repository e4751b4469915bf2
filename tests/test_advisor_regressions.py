"""Regression tests for the round-3/round-4 advisor findings
(ADVICE.md): Pallas selection bounds + explicit-backend downgrade
warnings (ops/histogram.py), CLI predict on narrow LibSVM test files
(application.py), and shard-averaged metric labeling (parallel/dtrain.py
— covered in tests/distributed). Each test pins the fixed behavior."""
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.histogram import (FEATURE_BLOCK_ALIGN,
                                        PALLAS_ROW_TILE,
                                        PALLAS_ROW_TILE_INT,
                                        PALLAS_VMEM_LIMIT,
                                        _pallas_feature_block,
                                        _pallas_fits, _pallas_vmem_bytes,
                                        _warn_once, build_histogram,
                                        resolve_hist_impl)


def test_pallas_vmem_bound_rejects_wide_shapes():
    """A histogram whose VMEM-resident accumulator + transients exceed
    the budget must not select the Pallas kernel (round-3 finding: a
    Mosaic compile/VMEM failure at real width killed training). Since
    the kernel's grid runs over blocks of features the width that is
    refused is a feature's own (bins, stat columns), not F."""
    assert _pallas_fits(28, 256, 4)          # Higgs shape fits
    assert _pallas_fits(8192, 256, 8)        # in blocks of features
    assert _pallas_fits(2000, 256, 4)        # epsilon
    assert _pallas_fits(968, 256, 4, PALLAS_ROW_TILE_INT, 1)
    # 65,536 bins: 8 features' accumulators are 4 x 8 x 4,096 x 128 x 4
    # bytes = 64 MiB, twice the limit
    assert not _pallas_fits(28, 65536, 4, bins_itemsize=2)
    assert _pallas_feature_block(28, 65536, 4, PALLAS_ROW_TILE, 4, 2) == 0


def _bound(F, fb, T, itemsize):
    return _pallas_vmem_bytes(fb, 256, 4, T, itemsize, 1, blocked=fb < F)


@pytest.mark.parametrize("F,T,itemsize,one_block", [
    (968, PALLAS_ROW_TILE, 4, True),         # bosch-train: today's program
    (28, PALLAS_ROW_TILE, 4, True),
    (28, PALLAS_ROW_TILE_INT, 1, True),
    (2000, PALLAS_ROW_TILE, 4, False),       # epsilon-train
    (968, PALLAS_ROW_TILE_INT, 1, False),    # bosch-train-quant
], ids=["bosch-f32", "higgs-f32", "higgs-int8", "epsilon-f32",
        "bosch-int8"])
def test_feature_block_of_the_benchmark_shapes(F, T, itemsize, one_block):
    """All of F where the bound admits it, else the fewest balanced
    blocks, each a multiple of 8, under the limit."""
    fb = _pallas_feature_block(F, 256, 4, T, itemsize, 1)
    assert _bound(F, fb, T, itemsize) <= PALLAS_VMEM_LIMIT
    if one_block:
        assert fb == F
        return
    assert 0 < fb < F and fb % FEATURE_BLOCK_ALIGN == 0
    blocks = -(-F // fb)
    # balanced: the blocks differ by less than one alignment step each
    assert blocks * fb - F < blocks * FEATURE_BLOCK_ALIGN
    # the fewest: one block less would pass the limit
    fewer = -(-F // (blocks - 1))
    assert _bound(F, fewer, T, itemsize) > PALLAS_VMEM_LIMIT


@pytest.mark.parametrize("T,itemsize", [(PALLAS_ROW_TILE, 4),
                                        (PALLAS_ROW_TILE_INT, 1)],
                         ids=["float32", "int8"])
def test_feature_block_stays_under_the_limit_for_every_width(T, itemsize):
    for F in list(range(1, 64)) + list(range(64, 8200, 37)):
        fb = _pallas_feature_block(F, 256, 4, T, itemsize, 1)
        assert 0 < fb <= F
        assert fb == F or fb % FEATURE_BLOCK_ALIGN == 0
        assert _bound(F, fb, T, itemsize) <= PALLAS_VMEM_LIMIT, F


@pytest.fixture
def log_capture():
    from lightgbm_tpu.utils import log
    lines = []
    prev_level = log._level
    log.set_verbosity(0)             # earlier tests may have set -1
    log.register_log_callback(lines.append)
    yield lines
    log.register_log_callback(None)
    log._level = prev_level


def test_explicit_pallas_request_warns_on_downgrade(log_capture):
    """hist_backend=pallas that cannot run must say why (round-3
    finding: silent einsum fallback skews kernel benchmarks)."""
    import jax.numpy as jnp
    _warn_once._seen.clear()
    b = jnp.zeros((64, 4), dtype=jnp.uint8)
    g = jnp.ones((64, 3), dtype=jnp.float32)
    build_histogram(b, g, 16, hist_impl=resolve_hist_impl("pallas"))
    assert any("pallas requested but unavailable" in m
               for m in log_capture)


def test_explicit_pallas_warning_fires_once_per_reason(log_capture):
    import jax.numpy as jnp
    _warn_once._seen.clear()
    b = jnp.zeros((64, 4), dtype=jnp.uint8)
    g = jnp.ones((64, 3), dtype=jnp.float32)
    build_histogram(b, g, 16, hist_impl=resolve_hist_impl("pallas"))
    build_histogram(b, g, 16, hist_impl=resolve_hist_impl("pallas"))
    msgs = [m for m in log_capture
            if "pallas requested but unavailable" in m]
    assert len(msgs) == 1


def test_shard_metric_logged_as_approx(log_capture):
    """Non-sum-decomposable metrics reduced as an n-weighted shard mean
    must not be labeled 'global' (round-3 finding); sum-decomposable
    ones still are."""
    from lightgbm_tpu.parallel import dtrain
    rng = np.random.RandomState(0)
    X = rng.rand(600, 5)
    y = (X[:, 0] + 0.3 * rng.randn(600) > 0.5).astype(float)
    dtrain.train({"objective": "binary", "num_leaves": 7,
                  "verbosity": 1, "metric": ["auc", "binary_logloss"],
                  "metric_freq": 1, "is_provide_training_metric": True,
                  "min_data_in_leaf": 10},
                 X, y, num_boost_round=2)
    joined = "\n".join(log_capture)
    assert "shard-avg approx auc" in joined
    assert "global binary_logloss" in joined
    assert "global auc" not in joined


def test_cli_predict_pads_narrow_libsvm(tmp_path):
    """A LibSVM test file whose max feature index is below the training
    width must predict (zero-padded), matching the reference CLI's
    by-index mapping (round-3 finding: the shape check rejected it)."""
    rng = np.random.RandomState(0)
    X = rng.rand(400, 6)
    y = (X[:, 0] + X[:, 5] > 1.0).astype(float)
    d = str(tmp_path)
    train = os.path.join(d, "train.svm")
    with open(train, "w") as f:
        for yi, row in zip(y, X):
            feats = " ".join("%d:%.6f" % (j + 1, v)
                             for j, v in enumerate(row))
            f.write("%d %s\n" % (int(yi), feats))
    # test rows never mention features 5-6 → parsed width 4 < 6
    test = os.path.join(d, "test.svm")
    with open(test, "w") as f:
        for row in X[:50]:
            feats = " ".join("%d:%.6f" % (j + 1, v)
                             for j, v in enumerate(row[:4]))
            f.write("0 %s\n" % feats)
    conf_train = os.path.join(d, "train.conf")
    model = os.path.join(d, "model.txt")
    with open(conf_train, "w") as f:
        f.write("task=train\ndata=%s\nobjective=binary\nnum_trees=5\n"
                "min_data_in_leaf=10\nverbosity=-1\noutput_model=%s\n"
                % (train, model))
    from lightgbm_tpu.application import run as app_main
    assert app_main(["config=" + conf_train]) == 0
    out = os.path.join(d, "preds.txt")
    conf_pred = os.path.join(d, "pred.conf")
    with open(conf_pred, "w") as f:
        f.write("task=predict\ndata=%s\ninput_model=%s\n"
                "output_result=%s\nverbosity=-1\n" % (test, model, out))
    assert app_main(["config=" + conf_pred]) == 0
    preds = np.loadtxt(out)
    assert preds.shape == (50,)
    assert np.isfinite(preds).all()


def test_renew_objective_rejects_monotone_constraints():
    """Leaf-output-renewing objectives (l1/quantile/mape) overwrite the
    clamped outputs, so the reference refuses the combination
    (gbdt.cpp:94) — and so do we (found by tools/fuzz_differential.py:
    the reference rejected a config we silently accepted)."""
    from lightgbm_tpu.utils.log import LightGBMError
    rng = np.random.RandomState(0)
    X = rng.rand(200, 3)
    y = X[:, 0] + 0.1 * rng.randn(200)
    for obj in ("quantile", "l1", "mape"):
        with pytest.raises(LightGBMError, match="monotone_constraints"):
            lgb.train({"objective": obj, "verbosity": -1,
                       "monotone_constraints": [1, 0, 0]},
                      lgb.Dataset(X, label=np.abs(y)),
                      num_boost_round=2)
    # l2 regression still accepts them
    lgb.train({"objective": "regression", "verbosity": -1,
               "monotone_constraints": [1, 0, 0]},
              lgb.Dataset(X, label=y), num_boost_round=2)
