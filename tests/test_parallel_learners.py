"""Feature- and voting-parallel learners on the virtual 8-device mesh.

Round-2 review: these two learners had zero tests and voting did not
actually reduce its cross-device traffic. Serial-equality mirrors
tests/test_data_parallel.py; the comm claim is verified structurally by
inspecting the lowered step's all-reduce shapes.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.parallel import (FeatureParallelTreeLearner,
                                   VotingParallelTreeLearner, make_mesh)
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


def _assert_same_tree(t1, t2, value_rtol=2e-3):
    assert t1.num_leaves == t2.num_leaves
    np.testing.assert_array_equal(t1.split_feature[:t1.num_internal],
                                  t2.split_feature[:t2.num_internal])
    np.testing.assert_array_equal(t1.threshold_in_bin[:t1.num_internal],
                                  t2.threshold_in_bin[:t2.num_internal])
    np.testing.assert_allclose(t1.leaf_value[:t1.num_leaves],
                               t2.leaf_value[:t2.num_leaves],
                               rtol=value_rtol, atol=1e-5)


class TestFeatureParallel:
    def test_matches_serial(self, mesh8):
        X, grad, hess = _data()
        cfg = Config.from_params({"num_leaves": 15, "min_data_in_leaf": 5,
                                  "verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg)
        serial = SerialTreeLearner(cfg, ds)
        dist = FeatureParallelTreeLearner(cfg, ds, mesh8)
        t1, part1 = serial.train(jnp.asarray(grad), jnp.asarray(hess))
        t2, part2 = dist.train(jnp.asarray(grad), jnp.asarray(hess))
        _assert_same_tree(t1, t2)
        np.testing.assert_array_equal(np.asarray(part1), np.asarray(part2))

    def test_more_devices_than_features(self, mesh8):
        # F=5 < 8 devices exercises the feature-pad path
        X, grad, hess = _data(f=5)
        cfg = Config.from_params({"num_leaves": 8, "verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg)
        dist = FeatureParallelTreeLearner(cfg, ds, mesh8)
        tree, part = dist.train(jnp.asarray(grad), jnp.asarray(hess))
        assert tree.num_leaves > 1
        assert (np.asarray(part) >= 0).all()


class TestVotingParallel:
    def test_matches_serial_when_vote_covers_all(self, mesh8):
        """top_k >= F ⇒ every feature is voted ⇒ identical trees."""
        X, grad, hess = _data()
        cfg = Config.from_params({"num_leaves": 15, "min_data_in_leaf": 5,
                                  "top_k": 6, "verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg)
        serial = SerialTreeLearner(cfg, ds)
        dist = VotingParallelTreeLearner(cfg, ds, mesh8)
        t1, part1 = serial.train(jnp.asarray(grad), jnp.asarray(hess))
        t2, part2 = dist.train(jnp.asarray(grad), jnp.asarray(hess))
        _assert_same_tree(t1, t2)
        np.testing.assert_array_equal(np.asarray(part1), np.asarray(part2))

    def test_small_top_k_still_learns(self, mesh8):
        X, grad, hess = _data(n=900)
        cfg = Config.from_params({"num_leaves": 15, "min_data_in_leaf": 5,
                                  "top_k": 1, "verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg)
        dist = VotingParallelTreeLearner(cfg, ds, mesh8)
        tree, part = dist.train(jnp.asarray(grad), jnp.asarray(hess))
        assert tree.num_leaves > 2
        # informative features dominate the votes
        used = set(tree.split_feature[:tree.num_internal])
        assert used <= {0, 1, 2, 3, 4, 5}

    def test_step_reduces_only_voted_block(self, mesh8):
        """The step's histogram all-reduce must carry the [V, B, 4] voted
        block, not the full [F, B, 4] buffer (reference comm contract:
        CopyLocalHistogram, voting_parallel_tree_learner.cpp:184)."""
        X, grad, hess = _data(f=6)
        cfg = Config.from_params({"num_leaves": 7, "top_k": 1,
                                  "verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg)
        dist = VotingParallelTreeLearner(cfg, ds, mesh8)
        dist._ensure_compiled()
        gh_sds = jax.ShapeDtypeStruct((dist.R, 4), jnp.float32)
        bins_sds = jax.ShapeDtypeStruct(dist.bins.shape, dist.bins.dtype)
        mask_sds = jax.ShapeDtypeStruct((dist.F,), jnp.bool_)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        qs_sds = jax.ShapeDtypeStruct((2,), jnp.float32)
        state_sds, _ = jax.eval_shape(dist._root_impl, bins_sds, gh_sds,
                                      mask_sds, i32, qs_sds)
        fm_sds = jax.ShapeDtypeStruct(dist.bins_fm.shape,
                                      dist.bins_fm.dtype)
        lowered = jax.jit(dist._step_impl).lower(
            bins_sds, fm_sds, state_sds, i32, i32, mask_sds, mask_sds, i32,
            qs_sds)
        hlo = lowered.as_text()
        F, B, V = dist.F, dist.B, dist.n_voted
        # all-reduces over f32 histogram payloads: largest must be the
        # voted block, and the full per-feature buffer must not appear.
        # stablehlo all_reduce ops close with `}) : (tensor<DIMS>) -> ...`
        sizes = []
        for m in re.finditer(r"stablehlo\.all_reduce", hlo):
            seg = hlo[m.start():m.start() + 2000]
            sig = re.search(
                r"\}\) : \(tensor<([0-9x]+)xf32>\)", seg)
            if sig:
                dims = [int(d) for d in sig.group(1).split("x")]
                sizes.append(int(np.prod(dims)))
        assert sizes, "no f32 all-reduce found in the voting step HLO"
        assert max(sizes) <= V * B * 4, (
            "voting step reduces %d f32 elements; voted block is %d"
            % (max(sizes), V * B * 4))
        assert max(sizes) < F * B * 4
