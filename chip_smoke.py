"""Smoke test of the Higgs train-and-serve path on a TPU.

    python chip_smoke.py [rows]

One process, normal entry points only (``lgb.Dataset`` / ``lgb.train`` /
``Booster.predict`` / ``PredictServer``), the Higgs configuration at
full width — 28 features, 255 bins, 255 leaves, ``min_data_in_leaf``
100, binary objective — on seeded ``make_higgs_like`` data. Rows are the
only dimension that shrinks (default 1,048,576; never below 262,144, so
the Pallas row-tile gates and three rungs of the smaller-child bucket
ladder stay exercised).

It checks, in order: the device is a TPU; both Pallas histogram variants
against a numpy ``np.add.at`` histogram; three training runs (default
looped, ``tpu_batch_iterations`` scan, quantized gradients) by held-out
AUC, with the Pallas program traced inside the product path and no
fallback event; a ``PredictServer`` against the host tree walk; and,
with more than one chip, the data-parallel learner over all of them.
Any failed check or exception ends the run non-zero before the result
line. The wall times it prints are information, not a metric: no speed
is claimed here.

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": n}}``
(exactly those keys; the ``summary:`` line before it carries the rest and
ends with ``"claim": null``) mean every phase passed on the chip. Without
a TPU the script prints one line saying so and exits 2; no result line is
printed on any failure.
"""
from __future__ import annotations

import contextlib
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

ROWS_DEFAULT = 1 << 20
ROWS_MIN = 1 << 18
HOLDOUT_ROWS = 1 << 16
N_FEATURES = 28
SEED = 0

PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
          "min_data_in_leaf": 100, "verbosity": -1}

# (name, extra params, boosting rounds, held-out AUC floor). Each floor
# is the lower of the held-out AUCs the CPU scatter path reached at this
# seed with 262,144 and with 1,048,576 rows, minus 0.01 (CPU runs of
# PR 21: default .918061/.916107, batched .922222/.919494, quantized
# .901095/.899879, two data-parallel rounds .885181/.885640 exact and
# .885172/.885441 quantized).
RUNS = (
    ("default", {}, 8, 0.906),
    ("batched", {"tpu_batch_iterations": 4}, 9, 0.909),
    ("quantized", {"use_quantized_grad": True}, 4, 0.889),
)
# the data-parallel repeats when several chips are visible
MULTICHIP_ROUNDS = 2
MULTICHIP_FLOOR = 0.875

SERVE_SIZES = (1, 64, 1000, 4096)
SERVE_REQUESTS = 36

# f32 kernel bound: |got - ref| <= 2^-16 * sum|gh| per (feature, bin,
# column) — f32 accumulation in any order stays far inside it, one
# operand rounded to bf16 (2^-9 relative) does not.
F32_REL_BOUND = 2.0 ** -16


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    print("phase %-34s %9.2f s" % (name, time.perf_counter() - t0),
          flush=True)


def auc(scores: np.ndarray, labels: np.ndarray) -> float:
    order = np.argsort(scores, kind="stable")
    ys = labels[order]
    n1 = float(ys.sum())
    n0 = float(len(ys)) - n1
    ranks = np.arange(1, len(ys) + 1, dtype=np.float64)
    return float((ranks[ys == 1].sum() - n1 * (n1 + 1) / 2) / (n0 * n1))


def numpy_histogram(bins: np.ndarray, gh: np.ndarray, num_bins: int,
                    dtype) -> np.ndarray:
    F, C = bins.shape[1], gh.shape[1]
    out = np.zeros((F, num_bins, C), dtype=dtype)
    for f in range(F):
        for c in range(C):
            np.add.at(out[f, :, c], bins[:, f], gh[:, c].astype(dtype))
    return out


def check_kernels() -> None:
    """Both Pallas variants, called directly, at the Higgs width and a
    ragged row count, against numpy: int8 exactly, f32 inside
    F32_REL_BOUND — and a bf16-rounded gh must fall OUTSIDE it, so the
    bound can tell a matmul that rounds its operands."""
    import jax.numpy as jnp
    import ml_dtypes

    from lightgbm_tpu.ops.histogram import (PALLAS_ROW_TILE,
                                            PALLAS_ROW_TILE_INT,
                                            _pallas_histogram)
    rng = np.random.RandomState(SEED)
    S, F, B, C = 4 * PALLAS_ROW_TILE_INT + 123, N_FEATURES, 255, 4
    bins = rng.randint(0, B, size=(S, F)).astype(np.uint8)
    gh_i = rng.randint(-127, 128, size=(S, C)).astype(np.int8)
    gh_f = rng.randn(S, C).astype(np.float32)

    got_i = np.asarray(_pallas_histogram(
        jnp.asarray(bins), jnp.asarray(gh_i), B, PALLAS_ROW_TILE_INT))
    require(got_i.dtype == np.int32 and got_i.shape == (F, B, C),
            "int8 kernel returned %s %s" % (got_i.dtype, got_i.shape))
    require(np.array_equal(got_i, numpy_histogram(bins, gh_i, B,
                                                  np.int64)),
            "int8 Pallas histogram differs from np.add.at")
    print("kernel int8: exact over %d rows" % S, flush=True)

    got_f = np.asarray(_pallas_histogram(
        jnp.asarray(bins), jnp.asarray(gh_f), B, PALLAS_ROW_TILE))
    ref = numpy_histogram(bins, gh_f, B, np.float64)
    bound = F32_REL_BOUND * numpy_histogram(bins, np.abs(gh_f), B,
                                            np.float64)
    err = np.abs(got_f.astype(np.float64) - ref)
    worst = float((err / np.maximum(bound, 1e-30)).max())
    rounded = gh_f.astype(ml_dtypes.bfloat16).astype(np.float32)
    err_bf16 = np.abs(numpy_histogram(bins, rounded, B, np.float64) - ref)
    worst_bf16 = float((err_bf16 / np.maximum(bound, 1e-30)).max())
    print("kernel f32: max err/bound %.4f (bf16-rounded operand would "
          "be %.1f)" % (worst, worst_bf16), flush=True)
    require(np.isfinite(got_f).all() and worst <= 1.0,
            "f32 Pallas histogram exceeds 2^-16 * sum|gh| "
            "(max err/bound %.3f)" % worst)
    require(worst_bf16 > 1.0, "f32 bound cannot tell a bf16-rounded "
            "operand (%.3f)" % worst_bf16)


def train_run(name: str, extra: dict, rounds: int, train_set,
              X_hold: np.ndarray, y_hold: np.ndarray, floor: float,
              expect_pallas: bool = True):
    """One ``lgb.train`` run; prints first-step (compile included) and
    steady-step wall times and checks held-out AUC."""
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import compile as obs_compile
    # an inner jit whose shapes an earlier run already traced is served
    # from jax's trace cache and would not count again
    jax.clear_caches()
    traced0 = obs_compile.trace_count("ops.pallas_histogram")
    scans0 = obs_compile.trace_count("mesh.train_many")
    marks = [(0, time.perf_counter())]

    def tick(env):
        marks.append((env.iteration + 1, time.perf_counter()))

    bst = lgb.train(dict(PARAMS, **extra), train_set,
                    num_boost_round=rounds, callbacks=[tick])
    steps = [(i1 - i0, t1 - t0)
             for (i0, t0), (i1, t1) in zip(marks, marks[1:])]
    print("run %-10s first step (compile included): %d iter %.2f s"
          % (name, steps[0][0], steps[0][1]), flush=True)
    for n, dt in steps[1:]:
        print("run %-10s step: %d iter %.3f s" % (name, n, dt),
              flush=True)
    require(bst.current_iteration == rounds,
            "%s: trained %d of %d iterations"
            % (name, bst.current_iteration, rounds))
    print("run %-10s learner %s" % (name,
                                    type(bst.inner.learner).__name__),
          flush=True)
    if "tpu_batch_iterations" in extra:
        require(bst.inner.can_train_batched(),
                "%s: can_train_batched() is false" % name)
        require(obs_compile.trace_count("mesh.train_many") > scans0,
                "%s: the train_batch scan was not taken" % name)
    traced = obs_compile.trace_count("ops.pallas_histogram") - traced0
    print("run %-10s ops.pallas_histogram traces: %d" % (name, traced),
          flush=True)
    if expect_pallas:
        require(traced > 0, "%s: the Pallas histogram program was not "
                "traced in the product path" % name)
    pred = np.asarray(bst.predict(X_hold))
    require(pred.shape == (len(y_hold),) and np.isfinite(pred).all(),
            "%s: predictions not finite of shape (%d,)"
            % (name, len(y_hold)))
    a = auc(pred, y_hold)
    print("run %-10s held-out AUC %.6f (floor %.4f)" % (name, a, floor),
          flush=True)
    require(a > floor, "%s: held-out AUC %.6f <= floor %.4f"
            % (name, a, floor))
    return bst


def serve(bst, X_hold: np.ndarray) -> None:
    """A PredictServer on the trained forest answers mixed-size
    requests; every answer must equal the host tree walk bit for bit
    (the contract tests/test_serve.py asserts on the CPU)."""
    from lightgbm_tpu.serve import PredictServer
    srv = PredictServer(bst, max_batch=max(SERVE_SIZES),
                        require_backend="tpu")
    try:
        rng = np.random.RandomState(SEED + 1)
        reqs = []
        for k in range(SERVE_REQUESTS):
            n = SERVE_SIZES[k % len(SERVE_SIZES)]
            lo = int(rng.randint(0, len(X_hold) - n))
            reqs.append((X_hold[lo:lo + n], srv.submit(X_hold[lo:lo + n])))
        rows = 0
        for x, fut in reqs:
            got = np.asarray(fut.result(timeout=600))
            want = np.asarray(bst.predict(x, predict_on_device=False))
            require(got.shape == want.shape,
                    "serve: shape %s != %s" % (got.shape, want.shape))
            require(np.array_equal(got, want),
                    "serve: %d-row answer differs from the host walk "
                    "(max |diff| %.3g)"
                    % (len(x), float(np.abs(got - want).max())))
            rows += len(x)
    finally:
        srv.stop()
    print("serve: %d requests, %d rows, bit-identical to "
          "Booster.predict(predict_on_device=False)"
          % (len(reqs), rows), flush=True)


def first_trees(bst, k: int) -> list:
    """The text blocks of the model's first ``k`` trees."""
    body = bst.model_to_string().split("\nend of trees")[0]
    return body.split("\nTree=")[1:k + 1]


def multichip(n_dev: int, train_set, X_hold, y_hold,
              quantized_one_chip) -> None:
    """tree_learner=data over every visible chip: the bin matrix must
    sit in n equal row shards on n distinct devices, and the quantized
    trees must equal the one-chip quantized trees exactly (integer psum
    is order-invariant). The mesh learner histograms through the einsum
    path (pallas_call has no partitioning rule), so no Pallas trace is
    expected."""
    mesh = {"tree_learner": "data"}
    bst = train_run("data-%d" % n_dev, mesh, MULTICHIP_ROUNDS,
                    train_set, X_hold, y_hold, MULTICHIP_FLOOR,
                    expect_pallas=False)
    learner = bst.inner.learner
    shards = learner.bins.addressable_shards
    devices = {s.device for s in shards}
    require(len(devices) == n_dev and len(shards) == n_dev,
            "bin matrix on %d device(s) in %d shard(s), expected %d"
            % (len(devices), len(shards), n_dev))
    require(all(s.data.shape[0] * n_dev == learner.R for s in shards),
            "bin shards are not R/n rows each: %s"
            % [s.data.shape for s in shards])
    print("multichip: bins %s in %d shards of %d rows on %d devices"
          % (learner.bins.shape, len(shards), learner.R // n_dev,
             len(devices)), flush=True)
    bst_q = train_run("data-%d-q" % n_dev,
                      dict(mesh, use_quantized_grad=True),
                      MULTICHIP_ROUNDS, train_set, X_hold, y_hold,
                      MULTICHIP_FLOOR, expect_pallas=False)
    got = first_trees(bst_q, MULTICHIP_ROUNDS)
    require(len(got) == MULTICHIP_ROUNDS
            and got == first_trees(quantized_one_chip, MULTICHIP_ROUNDS),
            "quantized trees over %d chips differ from the one-chip "
            "quantized trees" % n_dev)
    print("multichip: first %d quantized trees equal the one-chip "
          "quantized trees exactly" % MULTICHIP_ROUNDS, flush=True)


def main(argv) -> int:
    rows = int(argv[1]) if len(argv) > 1 else ROWS_DEFAULT
    if rows < ROWS_MIN:
        print("chip_smoke: rows=%d is below the %d minimum"
              % (rows, ROWS_MIN))
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("chip_smoke: no TPU — jax.devices()[0].platform is %r "
              "(JAX_PLATFORMS=%r); this script only runs on the chip"
              % (devs[0].platform, os.environ.get("JAX_PLATFORMS")))
        return 2
    return smoke(rows, devs)


def smoke(rows: int, devs) -> int:
    import jax
    t_start = time.perf_counter()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    import jaxlib

    import lightgbm_tpu as lgb
    from bench import make_higgs_like
    from lightgbm_tpu import native
    from lightgbm_tpu.obs import events as obs_events
    from lightgbm_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print("device: %s" % json.dumps(device))
    print("versions: jax %s jaxlib %s libtpu %s numpy %s"
          % (jax.__version__, jaxlib.__version__, libtpu,
             np.__version__))
    print("compile cache: %s" % cache_dir)
    print("rows: %d train + %d held out, %d features"
          % (rows, HOLDOUT_ROWS, N_FEATURES), flush=True)

    seen = []
    obs_events.register_event_callback(seen.append)

    with phase("kernels vs numpy"):
        check_kernels()
    with phase("generate"):
        X, y = make_higgs_like(rows + HOLDOUT_ROWS, N_FEATURES, seed=SEED)
        X_hold, y_hold = X[rows:], y[rows:]
    with phase("bin"):
        train_set = lgb.Dataset(X[:rows], label=y[:rows],
                                params=dict(PARAMS)).construct()
    print("native binning: %s" % ("used" if native.available()
                                  else "NOT used (Python fallback)"),
          flush=True)
    del X

    boosters = {}
    for name, extra, rounds, floor in RUNS:
        with phase("train %s (%d rounds)" % (name, rounds)):
            boosters[name] = train_run(name, extra, rounds, train_set,
                                       X_hold, y_hold, floor)
    with phase("serve"):
        serve(boosters["default"], X_hold)
    if len(devs) > 1:
        with phase("multichip (%d devices)" % len(devs)):
            multichip(len(devs), train_set, X_hold, y_hold,
                      boosters["quantized"])
    else:
        print("multichip: not run (1 device)", flush=True)

    obs_events.register_event_callback(None)
    bad = [e for e in seen
           if e["event"] in ("backend_fallback", "perf_warning")]
    require(not bad, "fallback/perf_warning events were emitted: %s"
            % json.dumps(bad[:5]))
    print("events: %d seen, 0 backend_fallback, 0 perf_warning"
          % len(seen))
    print("wall: %.1f s" % (time.perf_counter() - t_start))
    print("summary: %s" % json.dumps({"rows": rows,
                                      "compile_cache": cache_dir,
                                      "claim": None}))
    # the result line: exactly these keys, last on stdout
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main(sys.argv)
    except SmokeFailure as e:
        print("chip_smoke FAILED: %s" % e, file=sys.stderr)
        code = 1
    sys.exit(code)
