"""C API (native inference library) cross-checks.

The C-ABI library (native/capi.cpp, header native/capi.h) is the
external-engine counterpart of the reference's predict-side C API
(reference: include/LightGBM/c_api.h, src/c_api.cpp; exercised by the
reference's own tests through basic.py's ctypes calls). Every test
trains with the Python runtime, then drives the C library through the
same ctypes call sequence an R/Java/C host would use and requires
agreement with the Python predictor.
"""
import os
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.native.capi import (
    C_API_PREDICT_CONTRIB,
    C_API_PREDICT_LEAF_INDEX,
    C_API_PREDICT_NORMAL,
    C_API_PREDICT_RAW_SCORE,
    NativeBooster,
    load_lib,
)

pytestmark = pytest.mark.skipif(load_lib() is None,
                                reason="no native toolchain")


def _train(params, X, y, rounds=15):
    ds = lgb.Dataset(X, label=y)
    p = {"verbosity": -1, "min_data_in_leaf": 5}
    p.update(params)
    return lgb.train(p, ds, num_boost_round=rounds)


@pytest.fixture(scope="module")
def binary_model():
    rng = np.random.RandomState(0)
    X = rng.randn(500, 6)
    y = (X[:, 0] - X[:, 1] + 0.3 * rng.randn(500) > 0).astype(float)
    bst = _train({"objective": "binary"}, X, y)
    return bst, X


@pytest.mark.parametrize("objective,extra,make_y", [
    ("binary", {}, lambda X, rng: (X[:, 0] > 0).astype(float)),
    ("regression", {}, lambda X, rng: X[:, 0] * 2 + X[:, 1]),
    ("regression", {"reg_sqrt": True},
     lambda X, rng: np.abs(X[:, 0] * 3)),
    ("poisson", {}, lambda X, rng: rng.poisson(np.exp(
        np.clip(X[:, 0], -2, 2))).astype(float)),
    ("quantile", {"alpha": 0.7}, lambda X, rng: X[:, 0] + rng.randn(
        len(X)) * 0.1),
    ("multiclass", {"num_class": 3},
     lambda X, rng: np.argmax(X[:, :3], axis=1).astype(float)),
    ("multiclassova", {"num_class": 3},
     lambda X, rng: np.argmax(X[:, :3], axis=1).astype(float)),
    ("cross_entropy", {}, lambda X, rng: 1.0 / (1 + np.exp(-X[:, 0]))),
])
def test_predict_matches_python(objective, extra, make_y):
    rng = np.random.RandomState(7)
    X = rng.randn(400, 5)
    y = make_y(X, rng)
    bst = _train(dict({"objective": objective}, **extra), X, y, rounds=12)
    nb = NativeBooster(model_str=bst.model_to_string())
    Xt = rng.randn(80, 5)
    for pt, kwargs in ((C_API_PREDICT_NORMAL, {}),
                       (C_API_PREDICT_RAW_SCORE, {"raw_score": True})):
        ours = np.asarray(bst.predict(Xt, **kwargs))
        theirs = nb.predict(Xt, predict_type=pt)
        np.testing.assert_allclose(
            theirs.reshape(ours.shape), ours, rtol=1e-12, atol=1e-12,
            err_msg="%s predict_type=%d" % (objective, pt))


def test_metadata(binary_model):
    bst, X = binary_model
    nb = NativeBooster(model_str=bst.model_to_string())
    assert nb.num_classes == 1
    assert nb.num_features == 6
    assert nb.num_iterations == 15
    assert nb.feature_names() == ["Column_%d" % i for i in range(6)]


def test_leaf_index_matches(binary_model):
    bst, X = binary_model
    nb = NativeBooster(model_str=bst.model_to_string())
    ours = np.asarray(bst.predict(X[:50], pred_leaf=True))
    theirs = nb.predict(X[:50], predict_type=C_API_PREDICT_LEAF_INDEX)
    np.testing.assert_array_equal(theirs.astype(np.int64),
                                  ours.reshape(theirs.shape))


def test_contrib_matches_python(binary_model):
    bst, X = binary_model
    nb = NativeBooster(model_str=bst.model_to_string())
    ours = np.asarray(bst.predict(X[:40], pred_contrib=True))
    theirs = nb.predict(X[:40], predict_type=C_API_PREDICT_CONTRIB)
    np.testing.assert_allclose(theirs.reshape(ours.shape), ours,
                               rtol=1e-9, atol=1e-9)
    # additivity: contribs sum to the raw score
    raw = np.asarray(bst.predict(X[:40], raw_score=True))
    np.testing.assert_allclose(theirs.sum(axis=1), raw, atol=1e-9)


def test_contrib_multiclass():
    rng = np.random.RandomState(3)
    X = rng.randn(300, 4)
    y = np.argmax(X[:, :3], axis=1).astype(float)
    bst = _train({"objective": "multiclass", "num_class": 3}, X, y, 8)
    nb = NativeBooster(model_str=bst.model_to_string())
    ours = np.asarray(bst.predict(X[:30], pred_contrib=True))
    theirs = nb.predict(X[:30], predict_type=C_API_PREDICT_CONTRIB)
    np.testing.assert_allclose(theirs.reshape(ours.shape), ours,
                               rtol=1e-9, atol=1e-9)


def test_missing_and_categorical():
    rng = np.random.RandomState(5)
    X = rng.randn(600, 5)
    X[:, 2] = rng.randint(0, 8, size=600)  # categorical
    X[rng.rand(600, 5) < 0.1] = np.nan     # missing holes
    y = ((np.nan_to_num(X[:, 0]) > 0) ^ (X[:, 2] == 3)).astype(float)
    ds = lgb.Dataset(X, label=y, categorical_feature=[2])
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "min_data_in_leaf": 5}, ds, num_boost_round=12)
    nb = NativeBooster(model_str=bst.model_to_string())
    Xt = X[rng.permutation(600)[:100]]
    ours = np.asarray(bst.predict(Xt))
    theirs = nb.predict(Xt)
    np.testing.assert_allclose(theirs.reshape(ours.shape), ours,
                               rtol=1e-12, atol=1e-12)


def test_linear_trees():
    rng = np.random.RandomState(6)
    X = rng.randn(500, 4)
    y = 3 * X[:, 0] + X[:, 1] + 0.05 * rng.randn(500)
    bst = _train({"objective": "regression", "linear_tree": True}, X, y)
    nb = NativeBooster(model_str=bst.model_to_string())
    Xt = rng.randn(60, 4)
    Xt[rng.rand(60, 4) < 0.1] = np.nan  # NaN rows fall back to constants
    ours = np.asarray(bst.predict(Xt))
    theirs = nb.predict(Xt)
    np.testing.assert_allclose(theirs.reshape(ours.shape), ours,
                               rtol=1e-12, atol=1e-12)


def test_rf_average_output():
    rng = np.random.RandomState(8)
    X = rng.randn(500, 5)
    y = (X[:, 0] + X[:, 1] > 0).astype(float)
    bst = _train({"objective": "binary", "boosting": "rf",
                  "bagging_freq": 1, "bagging_fraction": 0.7,
                  "feature_fraction": 0.8}, X, y, rounds=10)
    nb = NativeBooster(model_str=bst.model_to_string())
    ours = np.asarray(bst.predict(X[:50]))
    theirs = nb.predict(X[:50])
    np.testing.assert_allclose(theirs.reshape(ours.shape), ours,
                               rtol=1e-12, atol=1e-12)


def test_csr_matches_dense(binary_model):
    bst, X = binary_model
    import scipy.sparse as sp
    Xs = X[:50].copy()
    Xs[np.abs(Xs) < 0.5] = 0.0
    csr = sp.csr_matrix(Xs)
    nb = NativeBooster(model_str=bst.model_to_string())
    dense = nb.predict(Xs)
    sparse = nb.predict_csr(csr.indptr, csr.indices, csr.data,
                            num_col=Xs.shape[1])
    np.testing.assert_allclose(sparse, dense, rtol=1e-15)


def test_model_file_roundtrip(binary_model, tmp_path):
    bst, X = binary_model
    path = str(tmp_path / "model.txt")
    bst.save_model(path)
    nb = NativeBooster(model_file=path)
    ours = np.asarray(bst.predict(X[:20]))
    np.testing.assert_allclose(nb.predict(X[:20]).reshape(ours.shape),
                               ours, rtol=1e-12)
    # verbatim save round-trip
    out = str(tmp_path / "model2.txt")
    assert nb._lib.LGBM_BoosterSaveModel(nb._handle, 0, -1, 0,
                                         out.encode()) == 0
    with open(path) as f1, open(out) as f2:
        assert f1.read() == f2.read()
    assert nb.save_model_to_string() == open(path).read()


def test_iteration_slicing(binary_model):
    bst, X = binary_model
    nb = NativeBooster(model_str=bst.model_to_string())
    ours = np.asarray(bst.predict(X[:30], raw_score=True,
                                  start_iteration=3, num_iteration=5))
    theirs = nb.predict(X[:30], predict_type=C_API_PREDICT_RAW_SCORE,
                        start_iteration=3, num_iteration=5)
    np.testing.assert_allclose(theirs.reshape(ours.shape), ours,
                               rtol=1e-12, atol=1e-14)


def test_reference_model_loads():
    """A model file written by the REFERENCE binary predicts identically
    through the C library (when the parity binary is available)."""
    import os
    import subprocess
    import tempfile
    ref = os.environ.get("LGBM_TPU_REFERENCE_BIN")
    if not ref or not os.path.exists(ref):
        pytest.skip("reference binary not available")
    rng = np.random.RandomState(11)
    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(float)
    with tempfile.TemporaryDirectory() as d:
        train = os.path.join(d, "train.csv")
        np.savetxt(train, np.column_stack([y, X]), delimiter=",")
        conf = os.path.join(d, "train.conf")
        model = os.path.join(d, "model.txt")
        with open(conf, "w") as f:
            f.write("task=train\nobjective=binary\ndata=%s\n"
                    "label_column=0\noutput_model=%s\nnum_trees=10\n"
                    "verbosity=-1\nheader=false\n" % (train, model))
        subprocess.check_call([ref, "config=%s" % conf],
                              stdout=subprocess.DEVNULL)
        nb = NativeBooster(model_file=model)
        bst = lgb.Booster(model_file=model)
        ours = np.asarray(bst.predict(X))
        np.testing.assert_allclose(nb.predict(X).reshape(ours.shape),
                                   ours, rtol=1e-12, atol=1e-12)


def test_single_row_matches_batch(binary_model):
    bst, X = binary_model
    lib = load_lib()
    import ctypes
    nb = NativeBooster(model_str=bst.model_to_string())
    row = np.ascontiguousarray(X[7], dtype=np.float64)
    out = np.empty(1, dtype=np.float64)
    out_len = ctypes.c_int64()
    rc = lib.LGBM_BoosterPredictForMatSingleRow(
        nb._handle, row.ctypes.data_as(ctypes.c_void_p), 1,
        row.shape[0], 1, C_API_PREDICT_NORMAL, 0, -1, b"",
        ctypes.byref(out_len),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    assert rc == 0 and out_len.value == 1
    batch = nb.predict(X[7:8])
    assert out[0] == batch[0, 0]


def test_c_example_end_to_end(tmp_path):
    """The examples/c_api host compiles, loads a CLI-trained model, and
    its predictions match the Python predictor."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(root, "examples", "c_api", "run.sh")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    subprocess.check_call(["bash", script, str(tmp_path)], env=env,
                          stdout=subprocess.DEVNULL,
                          stderr=subprocess.DEVNULL)
    preds_c = np.loadtxt(tmp_path / "preds_c.txt")
    feats = np.loadtxt(tmp_path / "features.csv", delimiter=",")
    bst = lgb.Booster(model_file=str(tmp_path / "model.txt"))
    np.testing.assert_allclose(preds_c, np.asarray(bst.predict(feats)),
                               rtol=1e-10)


def test_csc_matches_dense(binary_model):
    bst, X = binary_model
    import ctypes

    import scipy.sparse as sp
    Xs = X[:40].copy()
    Xs[np.abs(Xs) < 0.5] = 0.0
    csc = sp.csc_matrix(Xs)
    nb = NativeBooster(model_str=bst.model_to_string())
    dense = nb.predict(Xs)
    out = np.empty(40, dtype=np.float64)
    out_len = ctypes.c_int64()
    col_ptr = np.ascontiguousarray(csc.indptr, dtype=np.int64)
    indices = np.ascontiguousarray(csc.indices, dtype=np.int32)
    data = np.ascontiguousarray(csc.data, dtype=np.float64)
    rc = nb._lib.LGBM_BoosterPredictForCSC(
        nb._handle, col_ptr.ctypes.data_as(ctypes.c_void_p), 3,
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        data.ctypes.data_as(ctypes.c_void_p), 1, len(col_ptr),
        len(data), 40, C_API_PREDICT_NORMAL, 0, -1, b"",
        ctypes.byref(out_len),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    assert rc == 0
    np.testing.assert_allclose(out, dense[:, 0], rtol=1e-15)


def test_leaf_value_get_set(binary_model):
    bst, X = binary_model
    import ctypes
    nb = NativeBooster(model_str=bst.model_to_string())
    v = ctypes.c_double()
    assert nb._lib.LGBM_BoosterGetLeafValue(
        nb._handle, 0, 1, ctypes.byref(v)) == 0
    assert v.value == bst.inner.models[0].leaf_value[1]
    # out-of-range errors, not crashes
    assert nb._lib.LGBM_BoosterGetLeafValue(
        nb._handle, 9999, 0, ctypes.byref(v)) != 0
    # external leaf refit: set, predict reflects it, verbatim save gone
    before = nb.predict(X[:5], predict_type=C_API_PREDICT_RAW_SCORE)
    assert nb._lib.LGBM_BoosterSetLeafValue(
        nb._handle, 0, 1, v.value + 1.0) == 0
    after = nb.predict(X[:5], predict_type=C_API_PREDICT_RAW_SCORE)
    leaf0 = nb.predict(X[:5], predict_type=C_API_PREDICT_LEAF_INDEX)[:, 0]
    delta = np.where(leaf0 == 1, 1.0, 0.0)
    np.testing.assert_allclose(after[:, 0] - before[:, 0], delta,
                               atol=1e-12)
    with pytest.raises(Exception):
        nb.save_model_to_string()


def test_predict_for_file(binary_model, tmp_path):
    """C-only deployment pipeline: predict straight from a CSV file
    (label column in front, CLI convention) and from LibSVM, no Python
    in the loop."""
    bst, X = binary_model
    nb = NativeBooster(model_str=bst.model_to_string())
    expect = np.asarray(bst.predict(X[:50]))
    # CSV with label column
    data = tmp_path / "rows.csv"
    y0 = np.zeros((50, 1))
    np.savetxt(data, np.hstack([y0, X[:50]]), delimiter=",", fmt="%.10g")
    out = tmp_path / "preds.txt"
    rc = nb._lib.LGBM_BoosterPredictForFile(
        nb._handle, str(data).encode(), 0, C_API_PREDICT_NORMAL, 0, -1,
        b"", str(out).encode())
    assert rc == 0
    got = np.loadtxt(out)
    np.testing.assert_allclose(got, expect, rtol=1e-12)
    # LibSVM (narrower than the model pads with zeros)
    svm = tmp_path / "rows.svm"
    with open(svm, "w") as f:
        for i in range(50):
            feats = " ".join("%d:%.10g" % (j, X[i, j])
                             for j in range(4) if X[i, j] != 0.0)
            f.write("0 %s\n" % feats)
    Xp = X[:50].copy()
    Xp[:, 4:] = 0.0
    expect_svm = np.asarray(bst.predict(Xp))
    out2 = tmp_path / "preds2.txt"
    assert nb._lib.LGBM_BoosterPredictForFile(
        nb._handle, str(svm).encode(), 0, C_API_PREDICT_NORMAL, 0, -1,
        b"", str(out2).encode()) == 0
    np.testing.assert_allclose(np.loadtxt(out2), expect_svm, rtol=1e-12)


def test_predict_for_file_parameters(binary_model, tmp_path):
    bst, X = binary_model
    nb = NativeBooster(model_str=bst.model_to_string())
    expect = np.asarray(bst.predict(X[:20]))
    # features-only file needs no_label=true
    data = tmp_path / "feat.csv"
    np.savetxt(data, X[:20], delimiter=",", fmt="%.10g")
    out = tmp_path / "p.txt"
    assert nb._lib.LGBM_BoosterPredictForFile(
        nb._handle, str(data).encode(), 0, C_API_PREDICT_NORMAL, 0, -1,
        b"no_label=true", str(out).encode()) == 0
    np.testing.assert_allclose(np.loadtxt(out), expect, rtol=1e-12)
    # without the parameter, the width mismatch is a loud error
    assert nb._lib.LGBM_BoosterPredictForFile(
        nb._handle, str(data).encode(), 0, C_API_PREDICT_NORMAL, 0, -1,
        b"", str(out).encode()) != 0
    # label in the last column
    data2 = tmp_path / "tail.csv"
    np.savetxt(data2, np.hstack([X[:20], np.zeros((20, 1))]),
               delimiter=",", fmt="%.10g")
    assert nb._lib.LGBM_BoosterPredictForFile(
        nb._handle, str(data2).encode(), 0, C_API_PREDICT_NORMAL, 0, -1,
        ("label_column=%d" % X.shape[1]).encode(),
        str(out).encode()) == 0
    np.testing.assert_allclose(np.loadtxt(out), expect, rtol=1e-12)
    # unsupported parameters are rejected, not silently dropped
    assert nb._lib.LGBM_BoosterPredictForFile(
        nb._handle, str(data).encode(), 0, C_API_PREDICT_NORMAL, 0, -1,
        b"two_round=true", str(out).encode()) != 0


def test_dump_model_matches_python():
    rng = np.random.RandomState(17)
    X = rng.randn(500, 6)
    X[:, 3] = rng.randint(0, 6, 500)
    y = ((X[:, 0] > 0) ^ (X[:, 3] == 2)).astype(float)
    ds = lgb.Dataset(X, label=y, categorical_feature=[3])
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "min_data_in_leaf": 5,
                     "monotone_constraints": [1, 0, 0, 0, 0, 0]},
                    ds, num_boost_round=6)
    nb = NativeBooster(model_str=bst.model_to_string())
    # identical schema and values, feature_infos included (floats
    # compare exactly: both sides write round-trip representations)
    assert nb.dump_model() == bst.dump_model()


def test_dump_model_linear_matches_python():
    rng = np.random.RandomState(19)
    X = rng.randn(500, 4)
    y = 2 * X[:, 0] + X[:, 1] + 0.05 * rng.randn(500)
    bst = lgb.train({"objective": "regression", "linear_tree": True,
                     "verbosity": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y), num_boost_round=4)
    nb = NativeBooster(model_str=bst.model_to_string())
    assert nb.dump_model() == bst.dump_model()
