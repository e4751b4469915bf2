"""Serving subsystem (lightgbm_tpu/serve): StackedForest bit-identity
with the host predict path, shape-bucketed compile cache, micro-batching
PredictServer, and model-registry hot swap.

Acceptance contract (ISSUE 2): ``StackedForest.predict`` is bit-identical
to ``Booster.predict`` (host path) on dense, NaN-containing, and
categorical inputs across regression/binary/multiclass models; a second
dispatch at the same bucket shows ZERO retraces via obs/compile.py; and
N concurrent single-row requests are served in <= ceil(N/bucket)
dispatches.

Most tests share ONE module-scoped binary model (`shared`): the suite
runs on a single-core CPU budget, and reusing the model also reuses the
stacked kernels' compiled executables across tests.
"""
import math

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import compile as obs_compile
from lightgbm_tpu.obs import events
from lightgbm_tpu.obs.registry import registry
from lightgbm_tpu.serve import (BucketedPredictor, ModelRegistry,
                                PredictServer, StackedForest,
                                round_down_f32)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    yield
    events.configure(None)
    events.register_event_callback(None)
    registry.disable()


def _data(n=400, seed=0, with_nan=True, with_cat=True):
    """f32-representable rows (the serving contract; also what keeps the
    host-f64 vs device-f32 comparison meaningful bit-for-bit)."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6).astype(np.float32).astype(np.float64)
    if with_nan:
        X[rng.rand(n) < 0.15, 2] = np.nan
    if with_cat:
        X[:, 4] = rng.randint(0, 9, n)
    y = (X[:, 0] + 0.5 * np.nan_to_num(X[:, 2])
         + (X[:, 4] % 3 == 1) > 0.2).astype(float)
    return X, y


def _train(objective, X, y, rounds=6, **extra):
    params = {"objective": objective, "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5, "max_bin": 63,
              "categorical_feature": [4]}
    params.update(extra)
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=rounds)


@pytest.fixture(scope="module")
def shared():
    """(X, bst, host_pred): one 640-row 12-round binary model with NaNs
    + a categorical column, shared by every test that doesn't need its
    own objective/config."""
    X, y = _data(n=640, seed=11)
    bst = _train("binary", X, y, rounds=12)
    return X, bst, bst.predict(X, predict_on_device=False)


# ----------------------------------------------------------------------
# StackedForest: bit-identity with the host walk
# ----------------------------------------------------------------------

def test_stacked_forest_bit_identical_binary(shared):
    X, bst, host = shared
    forest = StackedForest.from_gbdt(bst)
    assert np.array_equal(host, forest.predict(X))
    assert np.array_equal(
        bst.predict(X, raw_score=True, predict_on_device=False),
        forest.predict(X, raw_score=True))
    # leaf ids match the host pred_leaf walk too
    assert np.array_equal(bst.predict(X, pred_leaf=True), forest.leaves(X))


@pytest.mark.parametrize("objective,extra", [
    ("regression", {}),
    ("multiclass", {"num_class": 3, "num_leaves": 7}),
])
def test_stacked_forest_bit_identical_other_objectives(objective, extra):
    X, y = _data()
    label = (X[:, 0] + np.nan_to_num(X[:, 2]) if objective == "regression"
             else (X[:, 4] % 3).astype(float))
    bst = _train(objective, X, label, **extra)
    forest = StackedForest.from_gbdt(bst)
    for raw in (False, True):
        host = bst.predict(X, raw_score=raw, predict_on_device=False)
        dev = forest.predict(X, raw_score=raw)
        assert np.array_equal(host, dev), (
            "%s raw=%s: max |diff| %g" % (
                objective, raw, np.abs(host - dev).max()))


def test_stacked_forest_zero_as_missing_exact():
    rng = np.random.RandomState(3)
    X, y = _data(seed=3, with_nan=False, with_cat=False)
    X = np.where(rng.rand(*X.shape) < 0.4, 0.0, X)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "min_data_in_leaf": 5,
                     "max_bin": 63, "zero_as_missing": True},
                    lgb.Dataset(X, label=y), num_boost_round=5)
    host = bst.predict(X, predict_on_device=False)
    assert np.array_equal(host, StackedForest.from_gbdt(bst).predict(X))


def test_stacked_forest_from_text_loaded_model_exact(shared):
    """Serving hot-swaps v3 model text (models/tree.py parse): the
    packed forest of a text round-tripped model must still match the
    loaded model's host walk exactly — including categorical bitsets."""
    X, bst, host = shared
    loaded = lgb.Booster(model_str=bst.model_to_string())
    assert np.array_equal(
        loaded.predict(X, predict_on_device=False),
        StackedForest.from_gbdt(loaded).predict(X))


def test_stacked_forest_start_num_iteration_slice(shared):
    X, bst, _ = shared
    host = bst.predict(X, start_iteration=3, num_iteration=5,
                       predict_on_device=False)
    forest = StackedForest.from_gbdt(bst, start_iteration=3,
                                     num_iteration=5)
    assert forest.num_trees == 5
    assert np.array_equal(host, forest.predict(X))


def test_stacked_forest_serves_linear_trees():
    """Linear-leaf models pack their leaf_const/leaf_coeff into the
    stacked arrays (ISSUE 11): the device fast path serves them with
    the bit-exact host contract (device leaf ids + host f64 linear
    accumulation) instead of declining to the host walk."""
    X, y = _data(n=200, seed=9, with_nan=False, with_cat=False)
    bst = lgb.train({"objective": "regression", "num_leaves": 7,
                     "verbosity": -1, "min_data_in_leaf": 20,
                     "max_bin": 63, "linear_tree": True},
                    lgb.Dataset(X, label=X[:, 0]), num_boost_round=2)
    host = bst.predict(X, predict_on_device=False)
    forest = StackedForest.from_gbdt(bst)
    assert forest.has_linear
    assert np.array_equal(host, forest.predict(X))
    # NaN in a fitted leaf feature falls back to the constant leaf
    # value exactly like the host (models/linear.py) does
    Xn = X.copy()
    Xn[::5, 0] = np.nan
    assert np.array_equal(bst.predict(Xn, predict_on_device=False),
                          forest.predict(Xn))
    # ... and the Booster fast path now dispatches through the cache
    base = registry.count("serve/bucket_compile") \
        + registry.count("serve/bucket_hit")
    out = bst.predict(X, predict_on_device=True)
    assert registry.count("serve/bucket_compile") \
        + registry.count("serve/bucket_hit") > base
    assert np.array_equal(out, host)


def test_round_down_f32_is_largest_f32_below():
    vals = np.array([1e-35, 0.1, -0.1, 3.5, 1e300, -1e300, 7.0])
    rd = round_down_f32(vals)
    assert rd.dtype == np.float32
    assert np.all(rd.astype(np.float64) <= vals)
    with np.errstate(over="ignore"):
        nxt = np.nextafter(rd, np.float32(np.inf))
    assert np.all(nxt.astype(np.float64) > vals)


# ----------------------------------------------------------------------
# Booster.predict fast path
# ----------------------------------------------------------------------

def test_booster_predict_fast_path_matches_host(shared):
    X, bst, host = shared
    base = registry.count("serve/bucket_compile") \
        + registry.count("serve/bucket_hit")
    fast = bst.predict(X, predict_on_device=True)
    dispatched = registry.count("serve/bucket_compile") \
        + registry.count("serve/bucket_hit")
    assert dispatched > base, \
        "fast path did not dispatch through the bucketed cache"
    assert np.array_equal(host, fast)
    # auto mode stays on the host walk on CPU backends (a device
    # dispatch only beats the vectorized host walk on accelerators) —
    # the suite runs CPU-pinned, so this predict must not dispatch
    assert np.array_equal(host, bst.predict(X))
    assert registry.count("serve/bucket_compile") \
        + registry.count("serve/bucket_hit") == dispatched


def test_booster_predict_f64_rows_take_device_dd_path(shared):
    """Rows that exceed f32 precision used to decline to the host walk;
    the double-double (hi + exact residual) encoding now serves them on
    device BIT-identically to the host's f64 compares (ISSUE 11)."""
    X, bst, _ = shared
    X64 = X + np.random.RandomState(13).randn(*X.shape) * 1e-12
    X64[:, 4] = X[:, 4]  # keep categories integral
    base = registry.count("serve/bucket_compile") \
        + registry.count("serve/bucket_hit")
    out = bst.predict(X64, predict_on_device=True)
    assert registry.count("serve/bucket_compile") \
        + registry.count("serve/bucket_hit") > base, \
        "f64 rows did not dispatch through the device dd path"
    assert np.array_equal(out, bst.predict(X64, predict_on_device=False))
    # the dd program runs under its own bucket keys
    predictor = bst._stacked_cache[1]
    assert any(len(k) == 4 and k[3] == "dd" for k in predictor.entries)


# ----------------------------------------------------------------------
# shape-bucketed compile cache
# ----------------------------------------------------------------------

def test_bucket_cache_zero_retraces_on_repeat_bucket(shared):
    X, bst, host = shared
    pred = BucketedPredictor(StackedForest.from_gbdt(bst),
                             model_version=1, min_bucket=64)
    out1 = pred.predict(X[:100])            # compiles the 128-bucket
    before = obs_compile.trace_count("serve.stacked_leaves")
    out2 = pred.predict(X[:90])             # same bucket: zero retraces
    after = obs_compile.trace_count("serve.stacked_leaves")
    assert after == before, "second dispatch at the same bucket retraced"
    assert np.array_equal(out1, host[:100])
    assert np.array_equal(out2, host[:90])
    assert pred.entries[(1, 128, "value")] == 2


def test_bucket_cache_pow2_policy_and_chunking(shared):
    X, bst, host = shared
    pred = BucketedPredictor(StackedForest.from_gbdt(bst),
                             model_version="v", min_bucket=16,
                             max_bucket=256)
    assert pred.bucket_for(1) == 16
    assert pred.bucket_for(17) == 32
    assert pred.bucket_for(256) == 256
    assert pred.bucket_for(10_000) == 256   # capped: chunked dispatches
    # 640 rows stream as 256 + 256 + 128-row chunks through two buckets
    assert np.array_equal(pred.predict(X), host)
    keys = set(pred.entries)
    assert ("v", 256, "value") in keys and ("v", 128, "value") in keys


def test_bucket_cache_output_kinds(shared):
    X, bst, _ = shared
    pred = BucketedPredictor(StackedForest.from_gbdt(bst), min_bucket=32)
    n = 50
    assert np.array_equal(pred.predict(X[:n], output_kind="raw"),
                          bst.predict(X[:n], raw_score=True,
                                      predict_on_device=False))
    assert np.array_equal(pred.predict(X[:n], output_kind="leaf"),
                          bst.predict(X[:n], pred_leaf=True))
    # the f32 device-sum throughput path tracks the f64 host sum closely
    fast = pred.predict(X[:n], output_kind="raw_device")
    host = bst.predict(X[:n], raw_score=True, predict_on_device=False)
    np.testing.assert_allclose(fast[:, 0], host, rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# PredictServer: coalescing, telemetry, hot swap, fallback event
# ----------------------------------------------------------------------

def test_predict_server_coalesces_concurrent_single_rows(shared, tmp_path):
    """Acceptance: N concurrent single-row requests served in
    <= ceil(N / max_batch) dispatches (here: exactly 3)."""
    path = str(tmp_path / "serve_events.jsonl")
    events.configure(path)
    X, bst, host = shared
    n_req, max_batch = 48, 16
    srv = PredictServer(StackedForest.from_gbdt(bst), max_batch=max_batch,
                        max_wait_ms=5, autostart=False)
    futs = [srv.submit(X[i]) for i in range(n_req)]
    srv.start()
    got = np.array([f.result(timeout=60) for f in futs])
    srv.stop()
    events.configure(None)
    assert np.array_equal(got, host[:n_req])
    assert srv.stats["dispatches"] <= math.ceil(n_req / max_batch)
    assert srv.stats["requests"] == n_req
    batches = [r for r in events.read_jsonl(path)
               if r["event"] == "predict_batch"]
    assert len(batches) == srv.stats["dispatches"]
    assert sum(b["rows"] for b in batches) == n_req
    for b in batches:
        assert b["bucket"] >= b["rows"] and b["seconds"] >= 0.0
    # latency histogram populated in the metrics registry
    lat = srv.latency_percentiles()
    assert lat["p99"] >= lat["p50"] > 0.0
    assert registry.hist_counts["serve/latency_ms"] >= n_req


def test_predict_server_multi_row_requests_and_sync_predict(shared):
    X, bst, host = shared
    srv = PredictServer(bst, max_batch=64, max_wait_ms=1)  # Booster in
    try:
        block = srv.predict(X[:10], timeout=60)
        single = srv.predict(X[0], timeout=60)
        # malformed requests fail at submit, never poisoning a batch
        with pytest.raises(ValueError, match="features"):
            srv.submit(np.zeros(X.shape[1] + 3, dtype=np.float32))
    finally:
        srv.stop()
    assert np.array_equal(block, host[:10])
    assert single == host[0]


def test_predict_server_survives_cancelled_future(shared):
    """A client-cancelled Future must drop out of its batch, not kill
    the worker thread (set_result on a cancelled Future raises)."""
    X, bst, host = shared
    srv = PredictServer(bst, max_batch=8, max_wait_ms=1, autostart=False)
    doomed = srv.submit(X[0])
    doomed.cancel()
    kept = srv.submit(X[1])
    srv.start()
    try:
        assert kept.result(timeout=60) == host[1]
        assert srv._thread.is_alive()
        assert srv.predict(X[2], timeout=60) == host[2]
    finally:
        srv.stop()


def test_model_registry_hot_swap(shared, tmp_path):
    path = str(tmp_path / "swap_events.jsonl")
    events.configure(path)
    X, bst, host = shared
    reg = ModelRegistry()
    v1 = reg.load("m", booster=bst, num_iteration=3)
    srv = PredictServer(reg, name="m", max_batch=32, max_wait_ms=1)
    try:
        got_v1 = srv.predict(X[:8], timeout=60)
        v2 = reg.load("m", model_str=bst.model_to_string())  # text path
        got_v2 = srv.predict(X[:8], timeout=60)
    finally:
        srv.stop()
    events.configure(None)
    assert (v1, v2) == (1, 2)
    assert np.array_equal(
        got_v1, bst.predict(X[:8], num_iteration=3,
                            predict_on_device=False))
    assert np.array_equal(got_v2, host[:8])
    assert not np.array_equal(got_v1, got_v2)
    swaps = [r for r in events.read_jsonl(path)
             if r["event"] == "model_swap"]
    assert [s["version"] for s in swaps] == [1, 2]
    assert swaps[0]["num_trees"] == 3 and swaps[1]["source"] == "string"


def test_predict_server_require_backend_raises(shared):
    """A required backend that jax did not resolve is an error at
    construction — never a server quietly running somewhere else."""
    X, bst, host = shared
    with pytest.raises(RuntimeError, match="require_backend='tpu'"):
        PredictServer(StackedForest.from_gbdt(bst),
                      require_backend="tpu", autostart=False)
    srv = PredictServer(StackedForest.from_gbdt(bst),
                        require_backend="cpu")
    try:
        out = srv.predict(X[0], timeout=60)
    finally:
        srv.stop()
    assert out == host[0]


def test_deep_forest_device_sum_kahan_tight():
    """ROADMAP open item: ``predict_raw_device`` accumulated plain f32
    (~1e-5 rel error at 500 trees); the per-class Kahan-compensated sum
    must land within ~1 ulp of the correctly rounded f64 total. 512
    stump trees make the sum the ONLY source of error."""
    from lightgbm_tpu.models.tree import Tree
    rng = np.random.RandomState(0)
    values = rng.rand(512).astype(np.float64)  # positive: no lucky
    #                                            cancellation hides error
    models = []
    for v in values:
        t = Tree(1)
        t.leaf_value[0] = v
        models.append(t)
    forest = StackedForest(models, num_tree_per_iteration=1,
                           num_features=1)
    X = np.zeros((4, 1), dtype=np.float32)
    dev = np.asarray(forest.predict_raw_device(X))[:, 0]
    exact = values.sum()  # f64 reference (the host predict_raw contract)
    naive = np.float32(0.0)
    for v in values.astype(np.float32):
        naive += v
    kahan_err = abs(float(dev[0]) - exact)
    # at most ~2 ulp of the f32 result (vs ~sqrt(T)/2 ulp for the
    # plain running sum)
    ulp = np.spacing(np.float32(exact))
    assert kahan_err <= 2 * float(ulp), (kahan_err, float(ulp))
    # and never worse than the plain f32 running sum it replaced
    assert kahan_err <= abs(float(naive) - exact) + 1e-12
    # all rows identical (stumps ignore features)
    np.testing.assert_array_equal(dev, dev[0])
