"""EFB (exclusive feature bundling) + sparse input.

Mirrors the reference's behavior contract (Dataset::FindGroups,
src/io/dataset.cpp:107): bundling is a storage/compute optimization —
training results must match the unbundled run whenever the bundles are
conflict-free. Test pattern follows tests/test_data_parallel.py's
serial-equality approach.
"""
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.efb import build_layout, find_groups


def _sparse_onehot_data(n=2000, n_blocks=6, block=8, seed=0):
    """Block-one-hot matrix: within each block exactly one column is
    non-zero per row — mutually exclusive by construction — plus two
    dense informative columns."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, 2 + n_blocks * block))
    X[:, 0] = rng.randn(n)
    X[:, 1] = rng.randn(n)
    for b in range(n_blocks):
        choice = rng.randint(0, block, n)
        X[np.arange(n), 2 + b * block + choice] = rng.rand(n) + 0.5
    logit = X[:, 0] + 0.8 * (X[:, 2] > 0) - 0.6 * (X[:, 10] > 0)
    y = (logit + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


class TestFindGroups:
    def test_exclusive_features_bundle(self):
        n = 500
        masks = []
        # 4 mutually exclusive features
        for k in range(4):
            m = np.zeros(n, dtype=bool)
            m[k::4] = True
            masks.append(m)
        groups = find_groups(masks, np.full(4, 10), n, max_bundle_bins=256)
        assert len(groups) == 1 and sorted(groups[0]) == [0, 1, 2, 3]

    def test_conflicting_features_stay_apart(self):
        n = 500
        m = np.ones(n, dtype=bool)
        groups = find_groups([m.copy(), m.copy()], np.full(2, 10), n,
                             max_bundle_bins=256)
        assert len(groups) == 2

    def test_bin_budget_respected(self):
        n = 500
        masks = [np.zeros(n, dtype=bool) for _ in range(3)]
        for k, m in enumerate(masks):
            m[k::3] = True
        groups = find_groups(masks, np.full(3, 200), n, max_bundle_bins=256)
        # 1 + 199 + 199 > 256 → at most one extra member joins each group
        assert all(1 + sum(199 for _ in g) <= 256 or len(g) == 1
                   for g in groups)

    def test_dense_none_masks_are_singletons(self):
        groups = find_groups([None, None], np.full(2, 10), 100, 256)
        assert sorted(map(tuple, groups)) == [(0,), (1,)]


class TestLayout:
    def test_unbundle_roundtrip(self):
        num_bins = np.array([5, 4, 6], dtype=np.int32)
        zero_bins = np.array([0, 1, 0], dtype=np.int32)
        layout = build_layout([[0, 1, 2]], num_bins)
        from lightgbm_tpu.io.efb import bundle_columns, member_bin
        rng = np.random.RandomState(0)
        n = 300
        cols = {}
        for f in range(3):
            c = np.full(n, zero_bins[f], dtype=np.int64)
            # truly exclusive: feature f owns rows ≡ f (mod 3)
            rows = np.arange(f, n, 3)[:n // 6]
            nz = [t for t in range(num_bins[f]) if t != zero_bins[f]]
            c[rows] = rng.choice(nz, len(rows))
            cols[f] = c
        bundled, conflicts = bundle_columns(lambda f: cols[f], layout,
                                            zero_bins, n, np.uint8)
        assert bundled.shape == (n, 1) and conflicts == 0
        # unbundle each feature and compare
        for f in range(3):
            g = layout.group_of[f]
            col = bundled[:, g].astype(np.int64)
            rec = member_bin(col, layout.first_bin[f], num_bins[f],
                             zero_bins[f], layout.needs_zero_fix[f])
            np.testing.assert_array_equal(rec, cols[f])


class TestEndToEnd:
    def _train_auc(self, X, y, enable_bundle):
        import lightgbm_tpu as lgb
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "enable_bundle": enable_bundle, "min_data_in_leaf": 20,
                  "metric": "auc"}
        bst = lgb.train(params, lgb.Dataset(X, label=y),
                        num_boost_round=15)
        return bst

    def test_bundled_dataset_is_built(self):
        X, y = _sparse_onehot_data()
        cfg = Config.from_params({"verbosity": -1})
        ds = BinnedDataset.from_matrix(X, cfg, label=y)
        assert ds.bundle is not None
        assert ds.bundle.num_groups < ds.num_features
        assert ds.bins.shape[1] == ds.bundle.num_groups

    def test_bundled_matches_unbundled_predictions(self):
        X, y = _sparse_onehot_data()
        b1 = self._train_auc(X, y, True)
        b0 = self._train_auc(X, y, False)
        p1 = b1.predict(X)
        p0 = b0.predict(X)
        np.testing.assert_allclose(p1, p0, rtol=1e-4, atol=1e-5)

    def test_sparse_input_trains(self):
        sp = pytest.importorskip("scipy.sparse")
        X, y = _sparse_onehot_data()
        Xs = sp.csr_matrix(X)
        import lightgbm_tpu as lgb
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbose": -1, "min_data_in_leaf": 20},
                        lgb.Dataset(Xs, label=y), num_boost_round=10)
        pred = bst.predict(X)
        auc_sep = pred[y == 1].mean() - pred[y == 0].mean()
        assert auc_sep > 0.1

    def test_sparse_and_dense_match(self):
        sp = pytest.importorskip("scipy.sparse")
        X, y = _sparse_onehot_data()
        import lightgbm_tpu as lgb
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "min_data_in_leaf": 20}
        bd = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
        bs = lgb.train(params, lgb.Dataset(sp.csr_matrix(X), label=y),
                       num_boost_round=8)
        np.testing.assert_allclose(bd.predict(X), bs.predict(X),
                                   rtol=1e-5, atol=1e-6)

    def test_valid_set_alignment_with_bundles(self):
        import lightgbm_tpu as lgb
        X, y = _sparse_onehot_data()
        Xv, yv = _sparse_onehot_data(seed=7)
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "min_data_in_leaf": 20, "metric": "binary_logloss"}
        train = lgb.Dataset(X, label=y)
        rec = {}
        import lightgbm_tpu.callback as cb
        bst = lgb.train(params, train, num_boost_round=10,
                        valid_sets=[lgb.Dataset(Xv, label=yv,
                                                reference=train)],
                        callbacks=[cb.record_evaluation(rec)])
        # incrementally tracked valid logloss must match fresh prediction
        pv = bst.predict(Xv, raw_score=False)
        from lightgbm_tpu.metric import create_metric
        ll = -np.mean(yv * np.log(pv) + (1 - yv) * np.log(1 - pv))
        assert abs(rec["valid_0"]["binary_logloss"][-1] - ll) < 1e-3
