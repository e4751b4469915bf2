"""Two-process fake cluster on localhost (reference:
tests/distributed/_test_distributed.py:53 DistributedMockup): spawn two
worker processes that bootstrap ``jax.distributed`` over a loopback gRPC
coordinator, each holding half the rows, and assert the distributed tree
equals the single-process one."""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "distributed",
                       "_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(n_devices: int) -> dict:
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=%d" % n_devices)
    env["XLA_FLAGS"] = " ".join(flags)
    return env


@pytest.mark.slow
def test_two_process_tree_matches_single_process(tmp_path):
    nproc = 2
    port = _free_port()
    outs = [str(tmp_path / ("w%d.npz" % r)) for r in range(nproc)]
    procs = [subprocess.Popen(
        [sys.executable, _WORKER, str(r), str(nproc), str(port), outs[r]],
        env=_worker_env(2), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
        for r in range(nproc)]
    logs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        logs.append(out)
    for r, p in enumerate(procs):
        assert p.returncode == 0, "worker %d failed:\n%s" % (r, logs[r])

    w = [np.load(o) for o in outs]
    # both processes must have built the identical tree
    np.testing.assert_array_equal(w[0]["split_feature"],
                                  w[1]["split_feature"])
    np.testing.assert_array_equal(w[0]["threshold_in_bin"],
                                  w[1]["threshold_in_bin"])
    np.testing.assert_allclose(w[0]["leaf_value"], w[1]["leaf_value"],
                               rtol=1e-6)

    # ... and it must equal the single-process tree on the full data
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.treelearner.serial import SerialTreeLearner
    rng = np.random.RandomState(0)
    n, f = 800, 6
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 > 0.3)
    cfg = Config.from_params({"num_leaves": 15, "min_data_in_leaf": 5,
                              "bin_construct_sample_cnt": n,
                              "verbosity": -1})
    ds = BinnedDataset.from_matrix(X, cfg)
    serial = SerialTreeLearner(cfg, ds)
    grad = jnp.asarray(np.where(y, -0.5, 0.5).astype(np.float32))
    hess = jnp.full(n, 0.25, dtype=jnp.float32)
    tree, part = serial.train(grad, hess)
    assert int(w[0]["num_leaves"][0]) == tree.num_leaves
    np.testing.assert_array_equal(w[0]["split_feature"],
                                  tree.split_feature[:tree.num_internal])
    np.testing.assert_array_equal(
        w[0]["threshold_in_bin"],
        tree.threshold_in_bin[:tree.num_internal])
    np.testing.assert_allclose(w[0]["leaf_value"],
                               tree.leaf_value[:tree.num_leaves],
                               rtol=2e-3, atol=1e-5)
    # per-row leaf assignment: distributed shards == single-process rows
    full_leaf = np.asarray(part)
    np.testing.assert_array_equal(w[0]["local_leaf"], full_leaf[:400])
    np.testing.assert_array_equal(w[1]["local_leaf"], full_leaf[400:])


_DTRAIN_WORKER = os.path.join(os.path.dirname(__file__), "distributed",
                              "_dtrain_worker.py")


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["binary", "multiclass"])
def test_two_process_full_boosting_matches_single(tmp_path, mode):
    """Full distributed boosting (parallel/dtrain.py train) produces the
    same model on both processes and tracks single-process lgb.train on
    the full data (reference: test_dask.py model-equivalence pattern)."""
    nproc = 2
    port = _free_port()
    outs = [str(tmp_path / ("d%d.npz" % r)) for r in range(nproc)]
    procs = [subprocess.Popen(
        [sys.executable, _DTRAIN_WORKER, str(r), str(nproc), str(port),
         outs[r], mode],
        env=_worker_env(2), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
        for r in range(nproc)]
    logs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        logs.append(out)
    for r, p in enumerate(procs):
        assert p.returncode == 0, "worker %d failed:\n%s" % (r, logs[r])

    w = [np.load(o) for o in outs]
    # identical model text on both processes
    s0 = open(outs[0] + ".txt").read()
    s1 = open(outs[1] + ".txt").read()
    assert s0 == s1
    np.testing.assert_allclose(w[0]["pred"], w[1]["pred"], rtol=1e-12)

    # equivalence with single-process training on the same full data
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    n, f = 600, 5
    X = rng.randn(n, f)
    if mode == "binary":
        y = (X[:, 0] - 0.7 * X[:, 1]
             + 0.2 * rng.randn(n) > 0).astype(float)
        params = {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 5, "bin_construct_sample_cnt": n,
                  "verbosity": -1, "learning_rate": 0.2}
    else:
        score = np.stack([X[:, 0], X[:, 1], X[:, 2]], axis=1)
        y = np.argmax(score + 0.2 * rng.randn(n, 3),
                      axis=1).astype(float)
        params = {"objective": "multiclass", "num_class": 3,
                  "num_leaves": 15, "min_data_in_leaf": 5,
                  "bin_construct_sample_cnt": n, "verbosity": -1,
                  "learning_rate": 0.2}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    pred_single = bst.predict(X)
    np.testing.assert_allclose(w[0]["pred"], pred_single, rtol=5e-3,
                               atol=5e-3)
    if mode == "binary":
        sep = w[0]["pred"][y == 1].mean() - w[0]["pred"][y == 0].mean()
        assert sep > 0.5
    else:
        acc = (np.argmax(w[0]["pred"], axis=1) == y).mean()
        assert acc > 0.8
        assert int(w[0]["n_trees"][0]) == 24  # 8 iters x 3 classes


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["mono_intermediate", "mono_advanced", "cegb"])
def test_two_process_capabilities_match_single_process(tmp_path, mode):
    """The capability matrix holds for the MULTI-PROCESS learner too:
    host-stepwise capability drivers (monotone intermediate/advanced,
    CEGB) replicate
    deterministically across ranks and equal the single-process mesh
    learner's tree (reference contract: every feature under every
    tree_learner)."""
    nproc = 2
    port = _free_port()
    outs = [str(tmp_path / ("w%d.npz" % r)) for r in range(nproc)]
    procs = [subprocess.Popen(
        [sys.executable, _WORKER, str(r), str(nproc), str(port),
         outs[r], mode],
        env=_worker_env(2), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
        for r in range(nproc)]
    logs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        logs.append(out)
    for r, p in enumerate(procs):
        assert p.returncode == 0, "worker %d failed:\n%s" % (r, logs[r])
    w = [np.load(o) for o in outs]
    np.testing.assert_array_equal(w[0]["split_feature"],
                                  w[1]["split_feature"])
    np.testing.assert_array_equal(w[0]["threshold_in_bin"],
                                  w[1]["threshold_in_bin"])

    import sys as _sys
    _sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                     "distributed"))
    from _worker import worker_params
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.parallel import DataParallelTreeLearner, make_mesh
    rng = np.random.RandomState(0)
    n, f = 800, 6
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 > 0.3)
    cfg = Config.from_params(worker_params(mode, n))
    ds = BinnedDataset.from_matrix(X, cfg)
    single = DataParallelTreeLearner(cfg, ds, make_mesh(2))
    grad = jnp.asarray(np.where(y, -0.5, 0.5).astype(np.float32))
    hess = jnp.full(n, 0.25, dtype=jnp.float32)
    tree, _ = single.train(grad, hess)
    assert int(w[0]["num_leaves"][0]) == tree.num_leaves
    np.testing.assert_array_equal(w[0]["split_feature"],
                                  tree.split_feature[:tree.num_internal])
    np.testing.assert_array_equal(
        w[0]["threshold_in_bin"],
        tree.threshold_in_bin[:tree.num_internal])


_BINNING_WORKER = os.path.join(os.path.dirname(__file__), "distributed",
                               "_binning_worker.py")


@pytest.mark.slow
def test_two_process_distributed_binning_layout(tmp_path):
    """Regression for the PR-1 allgather shape fix: pins the gathered
    sample LAYOUT of multi-process ``distributed_binned_dataset`` —
    per-rank sorted sample rows, padded to the max take, trimmed by the
    gathered count vector, concatenated in RANK order — by replaying
    exactly that construction single-process and demanding bit-equal bin
    mappers on every rank. The shards are unequal (500/100 rows) so the
    pad/trim path actually runs."""
    from tests.distributed import _binning_worker as bw

    nproc = 2
    port = _free_port()
    outs = [str(tmp_path / ("b%d.npz" % r)) for r in range(nproc)]
    procs = [subprocess.Popen(
        [sys.executable, _BINNING_WORKER, str(r), str(nproc), str(port),
         outs[r]],
        env=_worker_env(2), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
        for r in range(nproc)]
    logs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        logs.append(out)
    for r, p in enumerate(procs):
        assert p.returncode == 0, "worker %d failed:\n%s" % (r, logs[r])
    w = [np.load(o) for o in outs]

    # every rank built IDENTICAL mappers (same gathered sample seen)
    np.testing.assert_array_equal(w[0]["sizes"], w[1]["sizes"])
    np.testing.assert_array_equal(w[0]["bounds"], w[1]["bounds"])
    np.testing.assert_array_equal(w[0]["missing"], w[1]["missing"])
    np.testing.assert_array_equal(w[0]["used"], w[1]["used"])

    # replay the pinned layout single-process: per-rank sorted sample,
    # concatenated rank-major (this is the contract the allgather must
    # reproduce bit-for-bit, f64 included)
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    X = bw.make_data()
    cfg = Config.from_params(bw.worker_params())
    per_proc = max(1, cfg.bin_construct_sample_cnt // nproc)
    parts = []
    for rank in range(nproc):
        local = bw.shard(X, rank)
        take = min(per_proc, local.shape[0])
        rng = np.random.RandomState(cfg.data_random_seed + rank)
        idx = (np.sort(rng.choice(local.shape[0], take, replace=False))
               if take < local.shape[0] else np.arange(local.shape[0]))
        parts.append(local[idx])
    assert len(parts[0]) != len(parts[1]), \
        "test must exercise the unequal-take padding path"
    full_sample = np.concatenate(parts, axis=0)
    cfg2 = Config.from_params(dict(
        cfg.raw_params, bin_construct_sample_cnt=len(full_sample)))
    template = BinnedDataset.from_matrix(full_sample, cfg2)
    exp_bounds = np.concatenate(
        [np.asarray(m.bin_upper_bound) for m in template.bin_mappers])
    np.testing.assert_array_equal(w[0]["bounds"], exp_bounds)
    np.testing.assert_array_equal(
        w[0]["sizes"],
        [len(m.bin_upper_bound) for m in template.bin_mappers])
    np.testing.assert_array_equal(w[0]["used"], template.used_feature_map)

    # local rows bin identically to reference-aligned binning
    for rank in range(nproc):
        expected = BinnedDataset.from_matrix(
            bw.shard(X, rank), cfg, reference=template).bins
        np.testing.assert_array_equal(w[rank]["bins"],
                                      expected.astype(np.int64))
