"""Distributed learner tests on a virtual 8-device CPU mesh.

Mirrors the reference's distributed test strategy
(reference: tests/distributed/_test_distributed.py — N fake ranks on one
host, asserting distributed == single-process predictions): here the fake
cluster is 8 XLA host devices and the assertion is tree-for-tree
equality between DataParallelTreeLearner and SerialTreeLearner.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.parallel import DataParallelTreeLearner, make_mesh
from lightgbm_tpu.treelearner.serial import SerialTreeLearner


def _data(n=777, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 > 0.3).astype(np.float64)
    grad = np.where(y > 0, -0.5, 0.5).astype(np.float32)
    hess = np.full(n, 0.25, dtype=np.float32)
    return X, grad, hess


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
    return make_mesh(8)


class TestDataParallel:
    def test_one_device_mesh_compaction_matches_serial(self):
        """The 1-device mesh path histograms the smaller child's rows
        alone (the tile loop of ``_compact_child_hist``); the tree must
        equal the serial learner's exactly at tie-free scale."""
        X, grad, hess = _data(n=1500)
        cfg = Config.from_params({"num_leaves": 31, "min_data_in_leaf": 5,
                                  "verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg)
        serial = SerialTreeLearner(cfg, ds)
        dist = DataParallelTreeLearner(cfg, ds, make_mesh(1))
        t1, p1 = serial.train(jnp.asarray(grad), jnp.asarray(hess))
        t2, p2 = dist.train(jnp.asarray(grad), jnp.asarray(hess))
        assert t1.num_leaves == t2.num_leaves
        np.testing.assert_array_equal(
            t1.split_feature[:t1.num_internal],
            t2.split_feature[:t2.num_internal])
        np.testing.assert_array_equal(
            t1.threshold_in_bin[:t1.num_internal],
            t2.threshold_in_bin[:t2.num_internal])
        np.testing.assert_allclose(
            t1.leaf_value[:t1.num_leaves],
            t2.leaf_value[:t2.num_leaves], rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))

    def test_matches_serial(self, mesh8):
        X, grad, hess = _data()
        cfg = Config.from_params({"num_leaves": 15, "min_data_in_leaf": 5,
                                  "verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg)
        serial = SerialTreeLearner(cfg, ds)
        dist = DataParallelTreeLearner(cfg, ds, mesh8)
        t1, part1 = serial.train(jnp.asarray(grad), jnp.asarray(hess))
        t2, part2 = dist.train(jnp.asarray(grad), jnp.asarray(hess))
        assert t1.num_leaves == t2.num_leaves
        np.testing.assert_array_equal(
            t1.split_feature[:t1.num_internal],
            t2.split_feature[:t2.num_internal])
        np.testing.assert_array_equal(
            t1.threshold_in_bin[:t1.num_internal],
            t2.threshold_in_bin[:t2.num_internal])
        np.testing.assert_allclose(
            t1.leaf_value[:t1.num_leaves], t2.leaf_value[:t2.num_leaves],
            rtol=2e-3, atol=1e-5)
        # identical row partitions
        np.testing.assert_array_equal(np.asarray(part1), np.asarray(part2))

    def test_uneven_rows(self, mesh8):
        # N not divisible by 8 exercises the pad path
        X, grad, hess = _data(n=1001)
        cfg = Config.from_params({"num_leaves": 8, "verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg)
        dist = DataParallelTreeLearner(cfg, ds, mesh8)
        tree, part = dist.train(jnp.asarray(grad), jnp.asarray(hess))
        assert tree.num_leaves > 1
        assert len(np.asarray(part)) == 1001
        # every row lands on a real leaf
        assert (np.asarray(part) >= 0).all()

    def test_bagging_mask(self, mesh8):
        X, grad, hess = _data()
        cfg = Config.from_params({"num_leaves": 8, "verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg)
        dist = DataParallelTreeLearner(cfg, ds, mesh8)
        rng = np.random.RandomState(0)
        bag = jnp.asarray((rng.rand(len(X)) < 0.7).astype(np.float32))
        tree, _ = dist.train(jnp.asarray(grad), jnp.asarray(hess), bag)
        assert tree.num_leaves > 1

    def test_max_depth_on_device(self, mesh8):
        """Depth gating runs inside the whole-tree device loop."""
        X, grad, hess = _data()
        cfg = Config.from_params({"num_leaves": 31, "max_depth": 3,
                                  "min_data_in_leaf": 5, "verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg)
        serial = SerialTreeLearner(cfg, ds)
        dist = DataParallelTreeLearner(cfg, ds, mesh8)
        t1, _ = serial.train(jnp.asarray(grad), jnp.asarray(hess))
        t2, _ = dist.train(jnp.asarray(grad), jnp.asarray(hess))
        assert t2.num_leaves <= 8  # 2^3 leaves max at depth 3
        assert t1.num_leaves == t2.num_leaves
        np.testing.assert_array_equal(
            t1.split_feature[:t1.num_internal],
            t2.split_feature[:t2.num_internal])

    def test_capability_matrix_matches_serial(self, mesh8):
        """The reference supports every feature under every tree_learner
        (col_sampler.hpp, cost_effective_gradient_boosting.hpp,
        monotone_constraints.hpp); the mesh learners must too — exact
        tree equality vs serial for each capability."""
        X, grad, hess = _data(n=900)
        mono = [1, -1, 0, 0, 0, 0]
        cases = [
            ("cegb", {"cegb_tradeoff": 0.9, "cegb_penalty_split": 1e-4},
             {}),
            ("extra_trees", {"extra_trees": True, "extra_seed": 13}, {}),
            ("monotone_basic_penalty",
             {"monotone_constraints": mono, "monotone_penalty": 1.0}, {}),
            ("monotone_intermediate",
             {"monotone_constraints": mono,
              "monotone_constraints_method": "intermediate"}, {}),
            ("monotone_advanced",
             {"monotone_constraints": mono,
              "monotone_constraints_method": "advanced"}, {}),
            ("interaction_constraints",
             {"interaction_constraints": [[0, 1, 2], [3, 4, 5]]}, {}),
            ("bynode", {"feature_fraction_bynode": 0.5}, {}),
        ]
        for name, extra, ds_kw in cases:
            cfg = Config.from_params(dict(
                {"num_leaves": 15, "min_data_in_leaf": 5,
                 "verbosity": -1}, **extra))
            ds = BinnedDataset.from_matrix(X, cfg, **ds_kw)
            serial = SerialTreeLearner(cfg, ds)
            dist = DataParallelTreeLearner(cfg, ds, mesh8)
            t1, p1 = serial.train(jnp.asarray(grad), jnp.asarray(hess))
            t2, p2 = dist.train(jnp.asarray(grad), jnp.asarray(hess))
            assert t1.num_leaves == t2.num_leaves, name
            np.testing.assert_array_equal(
                t1.split_feature[:t1.num_internal],
                t2.split_feature[:t2.num_internal], err_msg=name)
            np.testing.assert_array_equal(
                t1.threshold_in_bin[:t1.num_internal],
                t2.threshold_in_bin[:t2.num_internal], err_msg=name)
            np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2),
                                          err_msg=name)

    def test_bundled_matches_serial(self, mesh8):
        """EFB stays bundled across the mesh: the mesh learner trains on
        the [N, G] bundle matrix (comm = the bundle histogram) and must
        produce the serial learner's exact tree (reference contract:
        bundles built before ReduceScatter, data_parallel_tree_learner
        .cpp:185)."""
        from tests.test_efb import _sparse_onehot_data
        X, y = _sparse_onehot_data(n=1600)
        grad = np.where(y > 0, -0.5, 0.5).astype(np.float32)
        hess = np.full(len(y), 0.25, dtype=np.float32)
        cfg = Config.from_params({"num_leaves": 15, "min_data_in_leaf": 5,
                                  "enable_bundle": True, "verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg, label=y)
        assert ds.bundle is not None and \
            ds.bundle.num_groups < ds.num_features
        serial = SerialTreeLearner(cfg, ds)
        dist = DataParallelTreeLearner(cfg, ds, mesh8)
        assert dist._bundled  # trains on the bundle matrix, not unpacked
        assert dist.bins.shape[1] == ds.bundle.num_groups
        t1, part1 = serial.train(jnp.asarray(grad), jnp.asarray(hess))
        t2, part2 = dist.train(jnp.asarray(grad), jnp.asarray(hess))
        assert t1.num_leaves == t2.num_leaves
        np.testing.assert_array_equal(
            t1.split_feature[:t1.num_internal],
            t2.split_feature[:t2.num_internal])
        np.testing.assert_array_equal(
            t1.threshold_in_bin[:t1.num_internal],
            t2.threshold_in_bin[:t2.num_internal])
        np.testing.assert_array_equal(np.asarray(part1),
                                      np.asarray(part2))
