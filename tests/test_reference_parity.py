"""Model interchange + accuracy parity against the REAL reference
binary (SURVEY §2.10's point: producing the text format verbatim lets
reference-LightGBM load and validate TPU-trained models).

Requires the reference CLI built via
``tools/build_reference_parity_binary.sh``; set
``LGBM_TPU_REFERENCE_BIN`` to its path (tests skip otherwise).

Round-3 measured results (committed in docs/PARITY_EVIDENCE.md):
predictions through the reference binary from OUR model files are
bit-identical (max |diff| ~1e-16), and vice versa.
"""
import os
import subprocess

import numpy as np
import pytest

import lightgbm_tpu as lgb

REF_BIN = os.environ.get("LGBM_TPU_REFERENCE_BIN", "")
pytestmark = pytest.mark.skipif(
    not (REF_BIN and os.path.exists(REF_BIN)),
    reason="reference binary not built; run "
           "tools/build_reference_parity_binary.sh and set "
           "LGBM_TPU_REFERENCE_BIN")


def _data(n=1500, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 8)
    y = (X[:, 0] + 0.6 * X[:, 1] ** 2 - 0.4 * X[:, 2]
         + 0.3 * rng.randn(n) > 0.2).astype(float)
    return X, y


def _ref(args, cwd):
    r = subprocess.run([REF_BIN] + args, cwd=cwd, capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    return r


def test_reference_predicts_our_model_bit_identically(tmp_path):
    X, y = _data()
    Xte, _ = _data(400, seed=1)
    d = str(tmp_path)
    np.savetxt(os.path.join(d, "test.tsv"),
               np.column_stack([np.zeros(len(Xte)), Xte]),
               delimiter="\t", fmt="%.10g")
    bst = lgb.train({"objective": "binary", "num_leaves": 31,
                     "min_data_in_leaf": 20, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=10)
    bst.save_model(os.path.join(d, "our_model.txt"))
    _ref(["task=predict", "data=test.tsv", "input_model=our_model.txt",
          "output_result=preds.txt"], d)
    via_ref = np.loadtxt(os.path.join(d, "preds.txt"))
    ours = bst.predict(Xte)
    np.testing.assert_allclose(via_ref, ours, rtol=0, atol=1e-12)


def test_we_predict_reference_model_bit_identically(tmp_path):
    X, y = _data()
    Xte, _ = _data(400, seed=1)
    d = str(tmp_path)
    np.savetxt(os.path.join(d, "train.tsv"),
               np.column_stack([y, X]), delimiter="\t", fmt="%.10g")
    np.savetxt(os.path.join(d, "test.tsv"),
               np.column_stack([np.zeros(len(Xte)), Xte]),
               delimiter="\t", fmt="%.10g")
    _ref(["task=train", "data=train.tsv", "objective=binary",
          "num_trees=10", "num_leaves=31", "min_data_in_leaf=20",
          "verbosity=-1", "output_model=ref_model.txt"], d)
    _ref(["task=predict", "data=test.tsv", "input_model=ref_model.txt",
          "output_result=ref_preds.txt"], d)
    ref_preds = np.loadtxt(os.path.join(d, "ref_preds.txt"))
    bst = lgb.Booster(model_file=os.path.join(d, "ref_model.txt"))
    ours = bst.predict(Xte)
    np.testing.assert_allclose(ours, ref_preds, rtol=0, atol=1e-12)


def test_training_quality_tracks_reference(tmp_path):
    """Same data, same params: AUC within a small tolerance (split
    choices may tie-break differently; gains agree to ~1e-5)."""
    X, y = _data(4000)
    Xte, yte = _data(1500, seed=2)
    d = str(tmp_path)
    np.savetxt(os.path.join(d, "train.tsv"),
               np.column_stack([y, X]), delimiter="\t", fmt="%.10g")
    np.savetxt(os.path.join(d, "test.tsv"),
               np.column_stack([yte, Xte]), delimiter="\t", fmt="%.10g")
    _ref(["task=train", "data=train.tsv", "objective=binary",
          "num_trees=20", "num_leaves=31", "min_data_in_leaf=20",
          "verbosity=-1", "output_model=ref_model.txt"], d)
    _ref(["task=predict", "data=test.tsv", "input_model=ref_model.txt",
          "output_result=ref_preds.txt"], d)
    ref_preds = np.loadtxt(os.path.join(d, "ref_preds.txt"))
    bst = lgb.train({"objective": "binary", "num_leaves": 31,
                     "min_data_in_leaf": 20, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=20)
    ours = bst.predict(Xte)

    def auc(pred, yy):
        order = np.argsort(pred)
        ys = yy[order]
        n1 = ys.sum()
        n0 = len(ys) - n1
        ranks = np.arange(1, len(ys) + 1)
        return (ranks[ys == 1].sum() - n1 * (n1 + 1) / 2) / (n0 * n1)

    a_ours, a_ref = auc(ours, yte), auc(ref_preds, yte)
    assert abs(a_ours - a_ref) < 5e-3, (a_ours, a_ref)
    assert a_ours > 0.9 and a_ref > 0.9


@pytest.mark.slow
def test_equal_bins_auc_parity_at_scale(tmp_path):
    """Round-5 verdict item 3 (CI-scale pin of tools/parity_run.py):
    equal bins (full-data binning — deterministic, bit-identical
    mappers both sides) + f64 histogram sums + equal iters must agree
    to |dAUC| <= 1e-4 on a held-out set. Runs the parity harness in a
    subprocess (f64 histograms need JAX_ENABLE_X64 before jax init).
    The full-scale (10.5M-row) result lives in docs/PARITY_EVIDENCE.md."""
    import json
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "1"
    env["PARITY_WORKDIR"] = str(tmp_path)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "parity_run.py"),
         "1000000", "10", REF_BIN],
        env=env, capture_output=True, text=True, timeout=3600)
    assert r.returncode == 0, r.stdout + r.stderr
    result = json.loads(r.stdout.strip().splitlines()[-1])
    # measured round 5: delta 0.0 at this scale (PARITY_EVIDENCE.md);
    # at <=200k rows tie-break divergence can reach ~6e-4, so the 1e-4
    # equivalence bar is asserted at the scale it's defined for
    assert result["delta"] <= 1e-4, result
