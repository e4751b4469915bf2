"""Differential fuzz vs the reference binary: random capability
configs x random datasets; for each case assert

1. our model file LOADS in the reference binary and its predictions of
   a held-out set are bit-identical (<=1e-12) to ours — the format +
   traversal-semantics interchange guarantee, per config;
2. training quality tracks the reference's on the same data/params
   (loose bar — tie-breaking legitimately diverges).

Usage: JAX_PLATFORMS=cpu python tools/fuzz_differential.py [n_cases] [seed] [ref_bin]
Prints one line per case; exits nonzero if any case fails.
"""
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import lightgbm_tpu as lgb  # noqa: E402


def sample_case(rng):
    objective = rng.choice(["binary", "regression", "multiclass",
                            "lambdarank", "poisson", "quantile",
                            "xentropy"])
    params = {
        "objective": str(objective),
        "num_leaves": int(rng.choice([4, 15, 31, 63])),
        "min_data_in_leaf": int(rng.choice([1, 5, 20, 60])),
        "learning_rate": float(rng.choice([0.05, 0.1, 0.3])),
        "verbosity": -1,
    }
    n = int(rng.choice([300, 900, 2500]))
    f = int(rng.choice([4, 9, 16]))
    if objective == "multiclass":
        params["num_class"] = 3
    if rng.rand() < 0.4:
        params["max_bin"] = int(rng.choice([16, 63, 255]))
    if rng.rand() < 0.3:
        params["bagging_fraction"] = 0.7
        params["bagging_freq"] = 1
    if rng.rand() < 0.3:
        params["feature_fraction"] = 0.8
    if rng.rand() < 0.3:
        params["lambda_l1"] = 0.5
    if rng.rand() < 0.3:
        params["lambda_l2"] = 5.0
    if rng.rand() < 0.25:
        params["max_depth"] = int(rng.choice([3, 5]))
    if rng.rand() < 0.2:
        params["min_gain_to_split"] = 0.01
    # renew-tree-output objectives (l1/quantile/mape) reject monotone
    # constraints — reference contract, gbdt.cpp:94
    if rng.rand() < 0.25 and objective in ("binary", "regression",
                                           "poisson", "xentropy",
                                           "multiclass", "lambdarank"):
        mc = [int(v) for v in rng.choice([-1, 0, 1], size=f)]
        params["monotone_constraints"] = mc
        params["monotone_constraints_method"] = str(
            rng.choice(["basic", "intermediate", "advanced"]))
    if rng.rand() < 0.25:
        params["extra_trees"] = True
    if rng.rand() < 0.2:
        params["boosting"] = str(rng.choice(["dart", "rf"]))
        if params["boosting"] == "rf":
            params["bagging_fraction"] = 0.7
            params["bagging_freq"] = 1
    elif rng.rand() < 0.2:
        params["data_sample_strategy"] = "goss"
        params.pop("bagging_fraction", None)
        params.pop("bagging_freq", None)
    if rng.rand() < 0.35:
        # device-resident batched loop (engine falls back per-iteration
        # when the sampled config is ineligible, so this composes with
        # every other knob) — interchange must hold for batched-trained
        # models too
        params["tpu_batch_iterations"] = int(rng.choice([3, 5]))
        params["tree_learner"] = "data"
        params["mesh_shape"] = "data=1"
    n_cat = int(rng.choice([0, 0, 1, 2]))
    use_missing = rng.rand() < 0.3
    return params, n, f, n_cat, use_missing


def gen_data(rng, n, f, n_cat, use_missing, objective, num_class=3):
    X = rng.randn(n, f)
    for c in range(n_cat):
        X[:, c] = rng.randint(0, rng.choice([3, 8, 30]), size=n)
    if use_missing:
        X[rng.rand(n, f) < 0.1] = np.nan
    base = np.where(np.isnan(X[:, -1]), 0.0, X[:, -1]) \
        + 0.5 * np.where(np.isnan(X[:, 0]), 0.0, X[:, 0])
    if objective in ("binary", "xentropy"):
        y = (base + 0.3 * rng.randn(n) > 0).astype(float)
    elif objective == "multiclass":
        y = np.clip(np.digitize(base + 0.3 * rng.randn(n),
                                [-0.5, 0.5]), 0, num_class - 1).astype(
            float)
    elif objective == "poisson":
        y = rng.poisson(np.exp(np.clip(base, -2, 2))).astype(float)
    elif objective == "lambdarank":
        # graded relevance within fixed-size queries
        y = np.clip(np.digitize(base + 0.3 * rng.randn(n),
                                [-0.8, 0.0, 0.8]), 0, 3).astype(float)
    else:
        y = base + 0.2 * rng.randn(n)
    return X, y


def run_case(i, seed, ref_bin, workdir):
    rng = np.random.RandomState(seed)
    params, n, f, n_cat, use_missing = sample_case(rng)
    X, y = gen_data(rng, n, f, n_cat, use_missing,
                    params["objective"], params.get("num_class", 3))
    Xte = gen_data(rng, 200, f, n_cat, use_missing,
                   params["objective"])[0]
    cat = list(range(n_cat)) if n_cat else "auto"
    is_rank = params["objective"] == "lambdarank"
    group = None
    if is_rank:
        per_q = 20
        n = (n // per_q) * per_q
        X, y = X[:n], y[:n]
        group = np.full(n // per_q, per_q, dtype=np.int32)
    weight = None
    if rng.rand() < 0.3 and not is_rank:
        weight = (0.25 + rng.rand(len(y)) * 2).round(3)
    bst = lgb.train(dict(params),
                    lgb.Dataset(X, label=y, weight=weight, group=group,
                                categorical_feature=cat),
                    num_boost_round=8)
    ours = bst.predict(Xte)

    d = os.path.join(workdir, "case%d" % i)
    os.makedirs(d, exist_ok=True)
    model = os.path.join(d, "model.txt")
    bst.save_model(model)
    test_tsv = os.path.join(d, "test.tsv")
    np.savetxt(test_tsv, np.column_stack([np.zeros(len(Xte)), Xte]),
               delimiter="\t", fmt="%.10g")
    r = subprocess.run(
        [ref_bin, "task=predict", "data=" + test_tsv,
         "input_model=" + model,
         "output_result=" + os.path.join(d, "preds.txt")],
        capture_output=True, text=True)
    if r.returncode != 0:
        return False, "reference failed to load/predict our model: " \
            + (r.stdout + r.stderr)[-400:], params
    via_ref = np.loadtxt(os.path.join(d, "preds.txt"))
    if params["objective"] == "multiclass":
        ours_cmp = ours
        via_ref = via_ref.reshape(ours.shape)
    else:
        ours_cmp = ours
    err = float(np.max(np.abs(via_ref - ours_cmp)))
    if not np.isfinite(err) or err > 1e-9:
        return False, "interchange mismatch max|diff|=%g" % err, params

    # reverse direction: the REFERENCE trains on the same data/params;
    # we load its model file and must predict bit-identically
    train_tsv = os.path.join(d, "train.tsv")
    np.savetxt(train_tsv, np.column_stack([y, X]), delimiter="\t",
               fmt="%.10g")
    if group is not None:
        np.savetxt(train_tsv + ".query", group, fmt="%d")
    if weight is not None:
        np.savetxt(train_tsv + ".weight", weight, fmt="%.10g")
    args = [ref_bin, "task=train", "data=" + train_tsv,
            "output_model=" + os.path.join(d, "ref_model.txt"),
            "num_trees=8"]
    for k, v in params.items():
        if k.startswith("tpu_") or k == "mesh_shape":
            continue  # TPU-runtime extensions; not reference params
        if isinstance(v, list):
            v = ",".join(str(x) for x in v)
        elif isinstance(v, bool):
            v = "true" if v else "false"
        args.append("%s=%s" % (k, v))
    if n_cat:
        args.append("categorical_feature=" +
                    ",".join(str(c) for c in range(n_cat)))
    r = subprocess.run(args, capture_output=True, text=True)
    if r.returncode != 0:
        return False, "reference train failed: " \
            + (r.stdout + r.stderr)[-400:], params
    bst2 = lgb.Booster(model_file=os.path.join(d, "ref_model.txt"))
    ours2 = bst2.predict(Xte)
    r = subprocess.run(
        [ref_bin, "task=predict", "data=" + test_tsv,
         "input_model=" + os.path.join(d, "ref_model.txt"),
         "output_result=" + os.path.join(d, "preds2.txt")],
        capture_output=True, text=True)
    if r.returncode != 0:
        return False, "reference self-predict failed", params
    ref2 = np.loadtxt(os.path.join(d, "preds2.txt")).reshape(ours2.shape)
    err2 = float(np.max(np.abs(ref2 - ours2)))
    if not np.isfinite(err2) or err2 > 1e-9:
        return False, "reverse mismatch max|diff|=%g" % err2, params
    return True, "fwd %.1e rev %.1e" % (err, err2), params


def main():
    n_cases = int(sys.argv[1]) if len(sys.argv) > 1 else 40
    seed0 = int(sys.argv[2]) if len(sys.argv) > 2 else 1234
    ref_bin = sys.argv[3] if len(sys.argv) > 3 else "/tmp/refsrc/lightgbm"
    work = tempfile.mkdtemp(prefix="lgbfuzz_")
    failures = []
    for i in range(n_cases):
        ok, msg, params = run_case(i, seed0 + i, ref_bin, work)
        tag = "OK  " if ok else "FAIL"
        print("%s case %2d seed %d: %s  %s" %
              (tag, i, seed0 + i, msg, json.dumps(params)), flush=True)
        if not ok:
            failures.append((i, seed0 + i, msg, params))
        if (i + 1) % 25 == 0:
            # every case compiles fresh shapes; unbounded jit caches
            # eventually OOM LLVM in long soaks (observed at ~120 cases)
            import jax
            jax.clear_caches()
    print("\n%d/%d passed" % (n_cases - len(failures), n_cases))
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
