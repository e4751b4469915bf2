"""Full-scale AUC parity vs the reference binary (round-5 verdict item
3): same Higgs-shaped data, same params, equal-bins (full-data binning),
equal iteration count; report both test AUCs and the delta.

Usage:
    JAX_PLATFORMS=cpu python tools/parity_run.py [rows] [iters] [ref_bin]

Writes a JSON line and appends a stage log to /tmp/parity_stages.log so
a late failure keeps the evidence.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def log(msg):
    line = "%s %s" % (time.strftime("%H:%M:%S"), msg)
    print(line, flush=True)
    with open("/tmp/parity_stages.log", "a") as f:
        f.write(line + "\n")


def auc(scores, labels):
    order = np.argsort(scores, kind="stable")
    ys = labels[order]
    n1 = ys.sum()
    n0 = len(ys) - n1
    ranks = np.arange(1, len(ys) + 1, dtype=np.float64)
    return float((ranks[ys == 1].sum() - n1 * (n1 + 1) / 2) / (n0 * n1))


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 10_500_000
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 50
    ref_bin = sys.argv[3] if len(sys.argv) > 3 else "/tmp/refsrc/lightgbm"
    n_test = min(500_000, rows // 4)
    work = os.environ.get("PARITY_WORKDIR",
                          "/tmp/parity_run_%d" % rows)
    os.makedirs(work, exist_ok=True)

    from bench import make_higgs_like
    log("generating %d train + %d test rows" % (rows, n_test))
    X, y = make_higgs_like(rows, seed=0)
    Xte, yte = make_higgs_like(n_test, seed=99)

    train_tsv = os.path.join(work, "train.tsv")
    test_tsv = os.path.join(work, "test.tsv")
    if not os.path.exists(train_tsv + ".done"):
        log("writing TSVs (reference input)")
        chunk = 1 << 19
        with open(train_tsv, "w") as f:
            for lo in range(0, rows, chunk):
                hi = min(lo + chunk, rows)
                np.savetxt(f, np.column_stack(
                    [y[lo:hi], X[lo:hi]]), delimiter="\t", fmt="%.10g")
        with open(test_tsv, "w") as f:
            np.savetxt(f, np.column_stack([yte, Xte]), delimiter="\t",
                       fmt="%.10g")
        open(train_tsv + ".done", "w").close()

    params_common = [
        "objective=binary", "num_leaves=255", "max_bin=255",
        "learning_rate=0.1", "min_data_in_leaf=100", "verbosity=-1",
        "bin_construct_sample_cnt=%d" % rows,   # full-data binning:
        # deterministic, so both sides build bit-identical BinMappers
        "num_trees=%d" % iters,
    ]
    ref_model = os.path.join(work, "ref_model.txt")
    log("training reference binary (%d iters)" % iters)
    t0 = time.time()
    r = subprocess.run(
        [ref_bin, "task=train", "data=" + train_tsv,
         "output_model=" + ref_model] + params_common,
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    t_ref = time.time() - t0
    log("reference trained in %.0fs" % t_ref)
    r = subprocess.run(
        [ref_bin, "task=predict", "data=" + test_tsv,
         "input_model=" + ref_model,
         "output_result=" + os.path.join(work, "ref_preds.txt")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    ref_pred = np.loadtxt(os.path.join(work, "ref_preds.txt"))
    auc_ref = auc(ref_pred, yte)
    log("reference test AUC %.6f" % auc_ref)

    import lightgbm_tpu as lgb
    params = {
        "objective": "binary", "num_leaves": 255, "max_bin": 255,
        "learning_rate": 0.1, "min_data_in_leaf": 100, "verbosity": -1,
        "bin_construct_sample_cnt": rows,
        "tpu_use_f64_hist": True,   # f32 hist sums drift ~1e-9*N at
        # this scale; f64 accumulation is the documented remedy
        # (reference gpu_use_dp analogue)
    }
    log("training lightgbm_tpu (%d iters)" % iters)
    t0 = time.time()
    ds = lgb.Dataset(X, label=np.asarray(y, dtype=np.float64))
    bst = lgb.train(params, ds, num_boost_round=iters)
    t_ours = time.time() - t0
    log("ours trained in %.0fs" % t_ours)
    ours_pred = bst.predict(Xte)
    auc_ours = auc(ours_pred, yte)
    log("our test AUC %.6f" % auc_ours)

    result = {
        "rows": rows, "iters": iters,
        "auc_ref": round(auc_ref, 7), "auc_ours": round(auc_ours, 7),
        "delta": round(abs(auc_ours - auc_ref), 7),
        "t_ref_s": round(t_ref, 1), "t_ours_s": round(t_ours, 1),
    }
    print(json.dumps(result))
    with open(os.path.join(work, "parity_result.json"), "w") as f:
        json.dump(result, f)
    bst.save_model(os.path.join(work, "our_model.txt"))


if __name__ == "__main__":
    os.environ.setdefault("JAX_ENABLE_X64", "1")
    main()
