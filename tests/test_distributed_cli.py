"""Distributed CLI: a two-rank fake cluster driven purely through conf
files (reference: tests/distributed/_test_distributed.py:53
DistributedMockup — same shape: shared machine list, per-rank
local_listen_port, rank 0's model validated by prediction)."""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env() -> dict:
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=2")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


@pytest.mark.slow
def test_cli_two_machine_train_and_predict(tmp_path):
    d = str(tmp_path)
    rng = np.random.RandomState(5)
    n = 600
    X = rng.randn(n, 5)
    y = (X[:, 0] - 0.6 * X[:, 1] + 0.25 * rng.randn(n) > 0).astype(float)
    np.savetxt(os.path.join(d, "train.tsv"),
               np.column_stack([y, X]), delimiter="\t", fmt="%.8g")
    ports = [_free_port(), _free_port()]
    with open(os.path.join(d, "mlist.txt"), "w") as f:
        for p in ports:
            f.write("127.0.0.1 %d\n" % p)
    model = os.path.join(d, "model.txt")
    base = ("task=train\ndata=%s\nobjective=binary\nnum_trees=10\n"
            "num_leaves=15\nmin_data_in_leaf=5\ntree_learner=data\n"
            "verbosity=-1\nnum_machines=2\nmachine_list_file=%s\n"
            "pre_partition=false\nbin_construct_sample_cnt=%d\n"
            "output_model=%s\n"
            % (os.path.join(d, "train.tsv"),
               os.path.join(d, "mlist.txt"), n, model))
    confs = []
    for r, p in enumerate(ports):
        cpath = os.path.join(d, "train%d.conf" % r)
        with open(cpath, "w") as f:
            f.write(base + "local_listen_port=%d\n" % p)
        confs.append(cpath)
    procs = [subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu.application",
         "config=" + c], env=_worker_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for c in confs]
    logs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        logs.append(out)
    for r, p in enumerate(procs):
        assert p.returncode == 0, "rank %d failed:\n%s" % (r, logs[r])
    assert os.path.exists(model)

    # the saved model predicts well on the full data (in-process)
    import lightgbm_tpu as lgb
    bst = lgb.Booster(model_file=model)
    pred = bst.predict(X)
    auc_sep = pred[y == 1].mean() - pred[y == 0].mean()
    assert auc_sep > 0.3, auc_sep

    # the CLI predict task reads the distributed model too
    np.savetxt(os.path.join(d, "test.tsv"),
               np.column_stack([np.zeros(100), X[:100]]),
               delimiter="\t", fmt="%.8g")
    pconf = os.path.join(d, "pred.conf")
    out_path = os.path.join(d, "preds.txt")
    with open(pconf, "w") as f:
        f.write("task=predict\ndata=%s\ninput_model=%s\n"
                "output_result=%s\nverbosity=-1\n"
                % (os.path.join(d, "test.tsv"), model, out_path))
    from lightgbm_tpu.application import run as app_run
    assert app_run(["config=" + pconf]) == 0
    np.testing.assert_allclose(np.loadtxt(out_path), pred[:100],
                               rtol=0, atol=1e-9)
