"""Telemetry subsystem (lightgbm_tpu/obs): stage timers, JSONL event
sink, compile/retrace tracking, backend health, end-to-end TIMETAG.

Acceptance contract (ISSUE 1): a small binary-objective train under
``LIGHTGBM_TPU_TIMETAG=1`` must print a per-stage summary covering >= 8
distinct stages spanning binning, gradient computation, histogram
build, split finding, and score update; the same run with
``LIGHTGBM_TPU_EVENT_LOG`` set must write valid JSONL containing
per-iteration events plus a backend record.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import compile as obs_compile
from lightgbm_tpu.obs import events, health
from lightgbm_tpu.obs.registry import MetricsRegistry, StageTimer, registry
from lightgbm_tpu.utils import log


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Tests share the process-wide registry/sinks; leave them clean."""
    yield
    events.configure(None)
    events.register_event_callback(None)
    log.register_log_callback(None)
    registry.disable()


def _small_problem(n=400, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6)
    y = (X[:, 0] + 0.5 * X[:, 1] + rng.randn(n) * 0.3 > 0).astype(float)
    return X, y


def _train_small(num_boost_round=5, **extra):
    X, y = _small_problem()
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "min_data_in_leaf": 5, "metric": "binary_logloss"}
    params.update(extra)
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=num_boost_round)


# ----------------------------------------------------------------------
# registry: timers, counters, gauges
# ----------------------------------------------------------------------

def test_stage_timer_aggregates_totals_and_counts():
    t = StageTimer()
    t.enable()
    for _ in range(3):
        with t.scope("stage_a"):
            pass
    with t.scope("stage_b"):
        pass
    assert t.counts["stage_a"] == 3
    assert t.counts["stage_b"] == 1
    assert t.totals["stage_a"] >= 0.0
    t.reset()
    assert not t.totals and not t.counts


def test_stage_timer_disabled_records_nothing():
    t = StageTimer()
    t.disable()
    with t.scope("nope"):
        pass
    assert "nope" not in t.counts


def test_the_process_has_one_stage_timer():
    # the scopes the package opens (obs.scope) record into the registry's
    # timer, the one the learners' obs.enabled gates and the benchmark's
    # enable_spans switch
    from lightgbm_tpu import obs
    assert not registry.enabled
    with obs.scope("test::one_timer"):
        pass
    assert "test::one_timer" not in registry.timer.counts
    registry.enable()
    try:
        assert registry.timer.enabled
        with obs.scope("test::one_timer"):
            pass
    finally:
        registry.disable()
    assert registry.timer.counts["test::one_timer"] == 1
    assert not registry.timer.enabled


def test_registry_counters_gauges_snapshot():
    r = MetricsRegistry()
    assert r.inc("c") == 1
    assert r.inc("c", 2) == 3
    r.gauge("g", 1.5)
    r.enable()
    with r.scope("s"):
        pass
    snap = r.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 1.5
    assert snap["phases"]["s"]["calls"] == 1
    r.reset()
    assert r.count("c") == 0


def test_print_summary_reaches_log_sink():
    r = MetricsRegistry()
    r.enable()
    with r.scope("my_stage"):
        pass
    lines = []
    log.register_log_callback(lines.append)
    r.print_summary()
    log.register_log_callback(None)
    text = "".join(lines)
    assert "my_stage" in text and "seconds" in text


# ----------------------------------------------------------------------
# events: JSONL sink round-trip
# ----------------------------------------------------------------------

def test_event_sink_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    events.configure(path)
    events.emit("alpha", x=1, arr=np.arange(3), f=np.float32(2.5))
    events.emit("beta", nested={"k": [1, 2]})
    events.configure(None)
    recs = events.read_jsonl(path)
    assert [r["event"] for r in recs] == ["alpha", "beta"]
    assert recs[0]["x"] == 1 and recs[0]["arr"] == [0, 1, 2]
    assert recs[0]["f"] == 2.5
    assert recs[1]["nested"] == {"k": [1, 2]}
    assert all("ts" in r for r in recs)


def test_event_env_var_sink(tmp_path, monkeypatch):
    path = str(tmp_path / "env_events.jsonl")
    monkeypatch.setenv("LIGHTGBM_TPU_EVENT_LOG", path)
    assert events.enabled()
    events.emit("from_env", ok=True)
    recs = events.read_jsonl(path)
    assert recs[0]["event"] == "from_env" and recs[0]["ok"] is True


def test_event_callback_mirrors_register_log_callback():
    seen = []
    events.register_event_callback(seen.append)
    events.emit("cb_event", n=7)
    events.register_event_callback(None)
    assert seen and seen[0]["event"] == "cb_event" and seen[0]["n"] == 7
    # unregistered: no sink -> emit returns None and records nothing
    assert events.emit("dropped") is None


# ----------------------------------------------------------------------
# compile tracking
# ----------------------------------------------------------------------

def test_compile_counter_detects_forced_retrace():
    import jax
    import jax.numpy as jnp
    name = "test.retrace_probe"
    base = obs_compile.trace_count(name)
    f = jax.jit(obs_compile.traced(name)(lambda x: x * 3.0))
    f(jnp.ones(4))
    f(jnp.ones(4))          # cached signature: no retrace
    assert obs_compile.trace_count(name) == base + 1
    f(jnp.ones(16))         # new shape: forced retrace
    assert obs_compile.trace_count(name) == base + 2
    # each trace also lands in the jit:: stage table unconditionally
    assert registry.timer.counts["jit::" + name] >= 2


def test_trace_events_emitted(tmp_path):
    import jax
    import jax.numpy as jnp
    path = str(tmp_path / "traces.jsonl")
    events.configure(path)
    f = jax.jit(obs_compile.traced("test.trace_event")(lambda x: x + 1))
    f(jnp.ones(5))
    events.configure(None)
    recs = [r for r in events.read_jsonl(path) if r["event"] == "jit_trace"]
    assert recs and recs[0]["fn"] == "test.trace_event"
    assert recs[0]["count"] >= 1


# ----------------------------------------------------------------------
# health: backend records + fallback warnings
# ----------------------------------------------------------------------

def test_backend_fallback_emits_warning_and_event(tmp_path):
    path = str(tmp_path / "health.jsonl")
    events.configure(path)
    lines = []
    log.register_log_callback(lines.append)
    health.record_backend_fallback("probe timed out (test)")
    log.register_log_callback(None)
    events.configure(None)
    assert any("Warning" in l and "fallback" in l for l in lines), lines
    recs = events.read_jsonl(path)
    fb = [r for r in recs if r["event"] == "backend_fallback"]
    assert fb and fb[0]["reason"] == "probe timed out (test)"
    assert fb[0]["requested"] == "tpu" and fb[0]["actual"] == "cpu"


def test_record_backend_event(tmp_path):
    path = str(tmp_path / "backend.jsonl")
    events.configure(path)
    platform = health.record_backend(source="test")
    events.configure(None)
    assert platform == "cpu"  # conftest pins the suite to CPU
    recs = events.read_jsonl(path)
    assert recs[0]["event"] == "backend"
    assert recs[0]["platform"] == "cpu"
    assert recs[0]["num_devices"] >= 1


# ----------------------------------------------------------------------
# log.fatal routes through the sink before raising
# ----------------------------------------------------------------------

def test_fatal_logs_through_registered_sink():
    lines = []
    log.register_log_callback(lines.append)
    with pytest.raises(log.LightGBMError, match="fatal-probe 3"):
        log.fatal("fatal-probe %d", 3)
    log.register_log_callback(None)
    assert any("[Fatal]" in l and "fatal-probe 3" in l for l in lines)


# ----------------------------------------------------------------------
# end-to-end: TIMETAG stage coverage + event-log smoke train (the
# tier-1 smoke required by the CI satellite)
# ----------------------------------------------------------------------

# one stage name per required pipeline area (acceptance criterion)
AREA_STAGES = {
    "binning": ("io::find_bins", "io::apply_bins"),
    "gradients": ("gbdt::gradients",),
    "histogram": ("tree::root_histogram",),
    "split_find": ("tree::split_batches",),
    "score_update": ("gbdt::score_update",),
}


def test_timetag_train_covers_pipeline_stages():
    registry.reset()
    registry.enable()
    _train_small()
    registry.disable()
    phases = registry.phases()
    pipeline = {k for k in phases if not k.startswith("jit::")}
    assert len(pipeline) >= 8, sorted(pipeline)
    for area, names in AREA_STAGES.items():
        assert any(n in phases for n in names), (area, sorted(phases))
    # summary table prints every stage name through the log sink
    lines = []
    log.register_log_callback(lines.append)
    registry.print_summary()
    log.register_log_callback(None)
    text = "".join(lines)
    for names in AREA_STAGES.values():
        assert any(n in text for n in names), text


def test_event_log_smoke_train(tmp_path, monkeypatch):
    """Tier-1 smoke: one small train with the event log enabled; the
    log must parse as JSONL and carry per-iteration events plus a
    backend record."""
    path = str(tmp_path / "train_events.jsonl")
    monkeypatch.setenv("LIGHTGBM_TPU_EVENT_LOG", path)
    # the process-wide backend record is once-only; reset for this test
    monkeypatch.setattr(health, "_reported", False)
    rounds = 4
    _train_small(num_boost_round=rounds)
    recs = events.read_jsonl(path)          # raises if not valid JSONL
    by_type = {}
    for r in recs:
        by_type.setdefault(r["event"], []).append(r)
    iters = by_type.get("train_iter", [])
    assert len(iters) == rounds, [r["event"] for r in recs]
    assert [r["iter"] for r in iters] == list(range(1, rounds + 1))
    for r in iters:
        assert r["seconds"] >= 0.0
        assert r["trees"] and all(
            t["num_leaves"] >= 1 and t["depth"] >= 0 for t in r["trees"])
    backend = by_type.get("backend", [])
    assert backend and backend[0]["platform"] == "cpu"
    assert len(backend) == 1, "backend event must be once-per-process"
    assert backend[0]["num_devices"] >= 1
    assert by_type.get("dataset"), "dataset construction event missing"


def test_batched_training_emits_batch_and_iter_events(tmp_path):
    path = str(tmp_path / "batch_events.jsonl")
    events.configure(path)
    # batched iterations need a mesh learner (train_many support)
    _train_small(num_boost_round=5, tpu_batch_iterations=2,
                 tree_learner="data", mesh_shape="data=1")
    events.configure(None)
    recs = events.read_jsonl(path)
    batches = [r for r in recs if r["event"] == "train_batch"]
    assert batches, [r["event"] for r in recs]
    for b in batches:
        assert b["n_iters"] == 2 and b["applied"] >= 1
        assert b["seconds"] >= 0.0
    batched_iters = [r for r in recs
                     if r["event"] == "train_iter" and r["batched"]]
    assert len(batched_iters) == sum(b["applied"] for b in batches)


def test_eval_events_carry_metric_results(tmp_path):
    path = str(tmp_path / "eval_events.jsonl")
    events.configure(path)
    X, y = _small_problem()
    Xv, yv = _small_problem(seed=1)
    ds = lgb.Dataset(X, label=y)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "min_data_in_leaf": 5, "metric": "binary_logloss"},
              ds, num_boost_round=3,
              valid_sets=[lgb.Dataset(Xv, label=yv, reference=ds)])
    events.configure(None)
    evals = [r for r in events.read_jsonl(path) if r["event"] == "eval"]
    assert evals
    res = evals[-1]["results"]
    assert any(e["metric"] == "binary_logloss" for e in res)
    assert all(np.isfinite(e["value"]) for e in res)


def test_timetag_env_var_end_to_end(tmp_path):
    """The env-var path, exactly as a user runs it: a fresh process with
    LIGHTGBM_TPU_TIMETAG=1 prints the per-stage summary at exit, and
    LIGHTGBM_TPU_EVENT_LOG captures the event stream."""
    ev_path = str(tmp_path / "e2e_events.jsonl")
    code = (
        "import numpy as np\n"
        "import lightgbm_tpu as lgb\n"
        "rng = np.random.RandomState(0)\n"
        "X = rng.randn(300, 5)\n"
        "y = (X[:, 0] + rng.randn(300) * .3 > 0).astype(float)\n"
        "lgb.train({'objective': 'binary', 'num_leaves': 7,\n"
        "           'verbosity': -1, 'min_data_in_leaf': 5},\n"
        "          lgb.Dataset(X, label=y), num_boost_round=3)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", LIGHTGBM_TPU_TIMETAG="1",
               LIGHTGBM_TPU_EVENT_LOG=ev_path)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    # the atexit summary table goes to stderr via log.info
    for names in AREA_STAGES.values():
        assert any(n in proc.stderr for n in names), proc.stderr[-2000:]
    recs = events.read_jsonl(ev_path)
    evs = {r["event"] for r in recs}
    assert "train_iter" in evs and "backend" in evs, evs


def test_bench_json_has_backend_and_phases_keys():
    """BENCH JSON schema: where it ran (platform, device kind, device
    count) and ``phases`` are first-class keys, never a substring of
    the unit field."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench
    env_keys = ("BENCH_ROWS", "BENCH_ITERS", "BENCH_WARMUP",
                "BENCH_TREE_BATCH", "BENCH_TIME_BUDGET",
                "BENCH_PREDICT_ROWS", "BENCH_PREDICT_DISPATCHES")
    saved = {k: os.environ.get(k) for k in env_keys}
    os.environ.update(BENCH_ROWS="1200", BENCH_ITERS="3",
                      BENCH_WARMUP="1", BENCH_TREE_BATCH="1",
                      BENCH_TIME_BUDGET="120",
                      BENCH_PREDICT_ROWS="8192",
                      BENCH_PREDICT_DISPATCHES="2")
    try:
        result = bench.run_bench()
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None)
            if v is not None:
                os.environ[k] = v
        registry.disable()
    assert result["backend"] == result["platform"] == "cpu"
    assert result["device_kind"] and result["device_count"] >= 1
    assert result["hist_kernel"] == "scatter"
    assert "backend_fallback" not in result
    assert isinstance(result["phases"], dict) and result["phases"]
    assert "tree::root_histogram" in result["phases"]
    # the serving predict stage is a first-class key (ISSUE 2)
    assert result["predict_rows_per_sec"] > 0.0
    assert result["predict_rows"] >= 1
    # the JSON line the driver captures must stay serializable
    json.dumps(result)


# ----------------------------------------------------------------------
# buffered JSONL writer (ISSUE 2 satellite): ordering and content are
# exactly those of the old per-emit open/append/close writer
# ----------------------------------------------------------------------

def test_event_buffer_defers_writes_until_flush(tmp_path):
    path = str(tmp_path / "buffered.jsonl")
    events.configure(path)
    for i in range(5):  # well under the default 64-line buffer
        events.emit("buffered", seq=i)
    assert not os.path.exists(path) or os.path.getsize(path) == 0, \
        "emits below the buffer limit must not touch the file"
    events.flush()
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert [r["seq"] for r in recs] == list(range(5))
    assert all(r["event"] == "buffered" and "ts" in r for r in recs)
    events.configure(None)


def test_event_buffer_overflow_flushes_in_order(tmp_path, monkeypatch):
    path = str(tmp_path / "overflow.jsonl")
    monkeypatch.setenv("LIGHTGBM_TPU_EVENT_BUFFER", "4")
    events.configure(path)
    for i in range(10):
        events.emit("ovf", seq=i, arr=np.arange(2), f=np.float32(i))
    # 10 emits with a 4-line buffer: two overflow flushes landed 8 lines
    with open(path) as f:
        on_disk = [json.loads(line) for line in f]
    assert [r["seq"] for r in on_disk] == list(range(8))
    events.configure(None)  # flushes the 2-line tail
    recs = events.read_jsonl(path)
    assert [r["seq"] for r in recs] == list(range(10))
    assert recs[3]["arr"] == [0, 1] and recs[3]["f"] == 3.0


def test_event_buffer_tracks_sink_path_changes(tmp_path):
    """Records buffered under path A must land in A even when the sink
    moved to B before the flush — per-file order is emission order."""
    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl")
    events.configure(a)
    events.emit("one", n=1)
    events.configure(b)  # flushes A's record
    events.emit("two", n=2)
    events.configure(None)
    assert [r["n"] for r in events.read_jsonl(a)] == [1]
    assert [r["n"] for r in events.read_jsonl(b)] == [2]


def test_event_buffer_flushes_at_exit(tmp_path):
    """A process that emits fewer events than the buffer limit and
    exits without calling flush() must still persist them (atexit).
    events.py is deliberately stdlib-only, so the child loads it
    standalone — no package/jax import on the single-core CI budget."""
    path = str(tmp_path / "atexit.jsonl")
    mod = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "lightgbm_tpu", "obs", "events.py")
    code = (
        "import importlib.util\n"
        "spec = importlib.util.spec_from_file_location('ev', %r)\n"
        "ev = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(ev)\n"
        "ev.configure(%r)\n"
        "ev.emit('tail', n=1)\n" % (mod, path)
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = events.read_jsonl(path)
    assert [r["event"] for r in recs] == ["tail"]


# ----------------------------------------------------------------------
# histograms (serving latency telemetry lives here)
# ----------------------------------------------------------------------

def test_registry_histogram_percentiles_and_snapshot():
    r = MetricsRegistry()
    for v in range(1, 101):
        r.observe("lat", float(v))
    assert r.percentile("lat", 50) == pytest.approx(50.5)
    assert r.percentile("lat", 99) == pytest.approx(99.01)
    assert r.percentile("missing", 50) == 0.0
    snap = r.snapshot()
    assert snap["hists"]["lat"]["count"] == 100
    assert snap["hists"]["lat"]["p99"] >= snap["hists"]["lat"]["p50"]
    r.reset()
    assert r.percentile("lat", 50) == 0.0


def test_registry_histogram_reservoir_is_bounded():
    from lightgbm_tpu.obs.registry import kHistCap
    r = MetricsRegistry()
    for v in range(kHistCap + 500):
        r.observe("big", float(v))
    assert len(r.hist_values["big"]) == kHistCap
    assert r.hist_counts["big"] == kHistCap + 500
    # old samples aged out: the reservoir holds the newest values
    assert min(r.hist_values["big"]) == 500.0


# ----------------------------------------------------------------------
# unified eval instrumentation (ISSUE 2 satellite): one eval pass ==
# one gbdt::eval_metrics scope == one `eval` event, on BOTH paths
# ----------------------------------------------------------------------

def test_eval_emits_exactly_one_scope_and_event_per_pass(tmp_path):
    path = str(tmp_path / "eval_unify.jsonl")
    registry.reset()
    registry.enable()
    events.configure(path)
    X, y = _small_problem()
    Xv, yv = _small_problem(seed=1)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 7, "verbosity": -1,
         "min_data_in_leaf": 5, "metric": "binary_logloss"},
        ds, num_boost_round=3,
        valid_sets=[lgb.Dataset(Xv, label=yv, reference=ds)])
    # the CLI-style path shares the same instrumentation point
    bst.inner.eval_metrics()
    bst.eval_valid()
    events.configure(None)
    registry.disable()
    n_events = len([r for r in events.read_jsonl(path)
                    if r["event"] == "eval"])
    n_scopes = registry.timer.counts["gbdt::eval_metrics"]
    assert n_events >= 5  # 3 training-loop passes + the 2 explicit ones
    assert n_scopes == n_events, (
        "eval double-instrumented: %d scopes vs %d events"
        % (n_scopes, n_events))
