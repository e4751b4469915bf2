"""Streaming telemetry plane (ISSUE 6).

Covers: the streaming trace spooler (size-based segment rotation under
sustained emit, zero drops below the backlog cap, drop accounting above
it, atomic always-valid segments), trace_report's segment-directory
validate / merge / tail, the env-var tier-1 smoke (short training under
``LIGHTGBM_TPU_TRACE_STREAM`` + CLI validate), the OpenMetrics snapshot
exporter (render/parse round trip, file dumps, the PredictServer
``/metrics`` endpoint under load), per-stream readiness attribution
(two concurrent watched stages land on their own spans with their own
device time), and the SLO watchdog's fire-exactly-once-per-breach
contract.
"""
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import events, export, trace
from lightgbm_tpu.obs.health import Watchdog
from lightgbm_tpu.obs.registry import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_REPORT = os.path.join(REPO, "tools", "trace_report.py")

_spec = importlib.util.spec_from_file_location("trace_report_stream",
                                               TRACE_REPORT)
trace_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_report)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Leave the process-wide registry/trace/sinks exactly as the
    suite default (timing off, no fences, no sinks, no exporter)."""
    yield
    trace.configure_stream(None)
    trace.configure(None)
    trace.set_process_index(0)
    events.configure(None)
    events.register_event_callback(None)
    export.reset_exporter()
    registry.drain_ready(timeout=10.0)
    registry.disable()
    registry.timer.sampling = False
    registry.fences = False


def _train_small(num_boost_round=2, seed=0, **extra):
    rng = np.random.RandomState(seed)
    X = rng.randn(400, 6)
    y = (X[:, 0] + 0.5 * X[:, 1] + rng.randn(400) * 0.3 > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "min_data_in_leaf": 5}
    params.update(extra)
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=num_boost_round)


def _segments(dirpath):
    return trace_report.segment_files(str(dirpath))


# ----------------------------------------------------------------------
# spool: rotation, completeness, drops
# ----------------------------------------------------------------------

def test_stream_rotation_under_sustained_emit(tmp_path):
    """Sustained scope emission rotates segments at the size cap with
    ZERO drops below the backlog cap; every emitted span lands on disk
    exactly once; every segment is standalone-valid; the directory
    validates and summarizes as one logical trace."""
    d = str(tmp_path / "segs")
    registry.reset()
    trace.configure_stream(d, segment_bytes=40_000, stage_events=128)
    n = 6000
    for _ in range(n):
        with registry.scope("probe::sustain"):
            pass
    trace.flush()
    segs = _segments(d)
    assert len(segs) >= 3, "no rotation at %d events" % n
    assert registry.count("trace/segments_written") == len(segs)
    assert registry.count("trace/dropped_events") == 0
    total = 0
    for s in segs:
        doc = trace_report.load_file(s)
        assert trace_report.validate_trace(doc, check_parents=False) \
            == [], s
        assert doc["otherData"]["segment_index"] == segs.index(s)
        total += sum(1 for e in doc["traceEvents"]
                     if e.get("ph") == "X")
    assert total == n
    errors, stats = trace_report.validate_dir(d)
    assert errors == []
    assert stats["spans"] == n and stats["dropped_events"] == 0
    table = trace_report.summarize(trace_report.load_trace(d))["phases"]
    assert table["probe::sustain"]["calls"] == n
    # no leftover tmp files: finalization is atomic
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]


def test_stream_flush_midrun_then_continue(tmp_path):
    """flush() finalizes a partial tail segment; emission continues
    into a NEW segment afterwards — the crash/fatal evidence path."""
    d = str(tmp_path / "segs")
    registry.reset()
    trace.configure_stream(d, segment_bytes=1 << 20)
    with registry.scope("probe::a"):
        pass
    trace.flush()
    assert len(_segments(d)) == 1
    with registry.scope("probe::b"):
        pass
    trace.flush()
    segs = _segments(d)
    assert len(segs) == 2
    names = set()
    for s in segs:
        doc = trace_report.load_file(s)
        names |= {e["name"] for e in doc["traceEvents"]
                  if e.get("ph") == "X"}
    assert {"probe::a", "probe::b"} <= names


def test_stream_drops_counted_when_writer_saturated(tmp_path,
                                                    monkeypatch):
    """Above the bounded backlog cap whole chunks are dropped and
    counted (trace/dropped_events) instead of growing RSS; the
    on-disk directory still validates, and the combined doc reports
    the drop count."""
    d = str(tmp_path / "segs")
    registry.reset()
    trace.configure_stream(d, segment_bytes=1 << 20, stage_events=32,
                           max_pending=2)
    sp = trace._spool
    real = sp._write_chunk

    def slow_write(chunk):
        time.sleep(0.05)
        real(chunk)

    monkeypatch.setattr(sp, "_write_chunk", slow_write)
    for _ in range(4000):
        with registry.scope("probe::flood"):
            pass
    monkeypatch.setattr(sp, "_write_chunk", real)
    trace.flush()
    dropped = registry.count("trace/dropped_events")
    assert dropped > 0
    assert dropped == sp.dropped
    assert dropped % 32 == 0  # whole chunks, never partial
    errors, stats = trace_report.validate_dir(d)
    assert errors == []
    assert stats["dropped_events"] == dropped
    # what was not dropped all made it to disk
    assert stats["spans"] == 4000 - dropped


def test_stream_env_end_to_end_and_cli_validate_tail(tmp_path):
    """Tier-1 CI smoke: a fresh process trains under
    ``LIGHTGBM_TPU_TRACE_STREAM=dir`` (exactly as a user runs it), and
    ``trace_report.py validate`` / ``tail`` pass over the produced
    segment directory."""
    d = str(tmp_path / "stream_e2e")
    code = (
        "import numpy as np\n"
        "import lightgbm_tpu as lgb\n"
        "rng = np.random.RandomState(0)\n"
        "X = rng.randn(300, 5)\n"
        "y = (X[:, 0] + rng.randn(300) * .3 > 0).astype(float)\n"
        "lgb.train({'objective': 'binary', 'num_leaves': 7,\n"
        "           'verbosity': -1, 'min_data_in_leaf': 5},\n"
        "          lgb.Dataset(X, label=y), num_boost_round=2)\n"
    )
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", LIGHTGBM_TPU_TIMETAG="sample",
               LIGHTGBM_TPU_TRACE_STREAM=d,
               LIGHTGBM_TPU_TRACE_SEGMENT_BYTES="20000")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert len(_segments(d)) >= 1
    val = subprocess.run([sys.executable, TRACE_REPORT, "validate", d],
                         capture_output=True, text=True, timeout=120)
    assert val.returncode == 0, val.stderr
    assert val.stdout.startswith("OK:"), val.stdout
    tail = subprocess.run([sys.executable, TRACE_REPORT, "tail", d],
                          capture_output=True, text=True, timeout=120)
    assert tail.returncode == 0, tail.stderr
    digests = [ln for ln in tail.stdout.splitlines() if ln.strip()]
    assert len(digests) == len(_segments(d))
    assert all("events" in ln and "spans" in ln for ln in digests)
    # the training pipeline's stages are in the streamed trace
    names = {e["name"]
             for e in trace_report.load_trace(d)["traceEvents"]
             if e.get("ph") == "X"}
    assert {"gbdt::gradients", "tree::grow"} <= names, sorted(names)


def test_stream_multirank_segments_merge_to_rank_lanes(tmp_path):
    """Two ranks' segments in ONE shared directory (the dtrain layout:
    rank tagged in the file name + otherData) merge into one Perfetto
    file with one process lane per rank — segments of the same rank
    must NOT be pid-remapped apart."""
    d = str(tmp_path / "shared")
    registry.reset()
    trace.configure_stream(d, segment_bytes=1 << 20,
                           process_index_override=0)
    for _ in range(5):
        with registry.scope("rank::work"):
            pass
    trace.flush()
    trace.configure_stream(d, segment_bytes=1 << 20,
                           process_index_override=1)
    for _ in range(7):
        with registry.scope("rank::work"):
            pass
    trace.flush()
    trace.set_process_index(0)
    assert len(_segments(d)) == 2
    out = str(tmp_path / "merged.json")
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, "merge", "-o", out, d],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    table = json.loads(proc.stdout)
    assert table["phases"]["rank::work"]["calls"] == 12
    merged = trace_report.load_file(out)
    pids = {e["pid"] for e in merged["traceEvents"]
            if e.get("ph") == "X"}
    assert pids == {0, 1}, pids


# ----------------------------------------------------------------------
# OpenMetrics: render / parse / file dump
# ----------------------------------------------------------------------

def test_openmetrics_round_trip_and_families():
    registry.reset()
    registry.inc("backend_fallback")
    registry.inc("jit_trace/test.fn_a", 3)
    registry.gauge("serve/queue_depth", 17)
    registry.gauge("backend", "cpu")
    registry.gauge("compile/test.fn_a/flops", 12345.0)
    for v in (1.0, 2.0, 3.0, 100.0):
        registry.observe("serve/latency_ms", v)
    registry.enable()
    with registry.scope("tree::grow"):
        pass
    text = export.render_openmetrics()
    assert text.rstrip().endswith("# EOF")
    parsed = export.parse_openmetrics(text)
    g = export.metric_value
    assert g(parsed, "lightgbm_tpu_backend_fallback_total") == 1
    assert g(parsed, "lightgbm_tpu_jit_traces_total", fn="test.fn_a") == 3
    assert g(parsed, "lightgbm_tpu_serve_queue_depth") == 17
    assert g(parsed, "lightgbm_tpu_backend_info", value="cpu") == 1
    assert g(parsed, "lightgbm_tpu_compile_flops", fn="test.fn_a") \
        == 12345
    p50 = g(parsed, "lightgbm_tpu_serve_latency_ms", quantile="0.5")
    p99 = g(parsed, "lightgbm_tpu_serve_latency_ms", quantile="0.99")
    assert p50 is not None and p99 is not None and p99 >= p50 > 0
    assert g(parsed, "lightgbm_tpu_serve_latency_ms_count") == 4
    assert g(parsed, "lightgbm_tpu_stage_calls_total",
             stage="tree::grow") == 1
    # strict parser: garbage raises
    with pytest.raises(ValueError):
        export.parse_openmetrics("not a metric line at all{")


def test_metrics_file_dump_atomic(tmp_path):
    registry.reset()
    registry.inc("probe_counter", 5)
    path = str(tmp_path / "metrics.prom")
    export.dump_metrics(path)
    parsed = export.parse_openmetrics(open(path).read())
    assert export.metric_value(parsed,
                               "lightgbm_tpu_probe_counter_total") == 5
    assert not os.path.exists(path + ".tmp")
    # SnapshotExporter.dump_now rewrites and runs the watchdog
    exp = export.SnapshotExporter(path, interval=0)
    exp.dump_now()
    assert "lightgbm_tpu_probe_counter_total" in open(path).read()


# ----------------------------------------------------------------------
# /metrics endpoint on PredictServer under load
# ----------------------------------------------------------------------

def test_predict_server_metrics_endpoint_under_load():
    from lightgbm_tpu.serve import PredictServer, StackedForest

    registry.reset()
    bst = _train_small(num_boost_round=3)
    srv = PredictServer(StackedForest.from_gbdt(bst), max_batch=32,
                        max_wait_ms=1, metrics_port=0)
    try:
        assert srv.metrics is not None and srv.metrics.port > 0
        rng = np.random.RandomState(1)
        futs = [srv.submit(rng.randn(6).astype(np.float32))
                for _ in range(96)]
        for f in futs:
            f.result(timeout=60)
        # compile/retrace telemetry rides the same endpoint (counted
        # deterministically — a fully-warmed suite run may cache every
        # real compile)
        from lightgbm_tpu.obs import compile as obs_compile
        obs_compile.record_trace("test.metrics_probe")
        body = urllib.request.urlopen(srv.metrics.url + "/metrics",
                                      timeout=30).read().decode()
        parsed = export.parse_openmetrics(body)
        g = export.metric_value
        # serve latency percentiles + queue depth are present and sane
        p50 = g(parsed, "lightgbm_tpu_serve_latency_ms", quantile="0.5")
        p99 = g(parsed, "lightgbm_tpu_serve_latency_ms", quantile="0.99")
        assert p50 is not None and p99 >= p50 > 0
        assert g(parsed, "lightgbm_tpu_serve_latency_ms_count") == 96
        assert g(parsed, "lightgbm_tpu_serve_queue_depth") is not None
        assert g(parsed, "lightgbm_tpu_jit_traces_total",
                 fn="test.metrics_probe") == 1
        # /healthz: JSON snapshot + watchdog state
        health = json.loads(urllib.request.urlopen(
            srv.metrics.url + "/healthz", timeout=30).read().decode())
        assert "snapshot" in health and "breached" in health
        assert health["snapshot"]["hists"]["serve/latency_ms"]["count"] \
            == 96
        # 404 for anything else
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.metrics.url + "/nope", timeout=30)
    finally:
        srv.stop()
    # endpoint is down after stop
    with pytest.raises(Exception):
        urllib.request.urlopen(srv.metrics.url + "/metrics", timeout=5)


# ----------------------------------------------------------------------
# per-stream readiness attribution
# ----------------------------------------------------------------------

def test_per_stream_attribution_concurrent_stages(tmp_path, monkeypatch):
    """Two stages watched concurrently: each ``::ready`` row measures
    ONLY its own readiness (the old single FIFO drainer folded the
    slow stage's wait into the fast one's), and each ready span
    parent-links to the exact span that submitted the watch."""
    import jax

    class FakeOut:
        def __init__(self, delay):
            self.delay = delay

    real = jax.block_until_ready

    def fake_block(x):
        if isinstance(x, FakeOut):
            time.sleep(x.delay)
            return x
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", fake_block)
    path = str(tmp_path / "attr_trace.json")
    registry.reset()
    registry.enable(sampling=True)
    trace.configure(path)

    slow, fast = FakeOut(0.5), FakeOut(0.05)
    started = threading.Barrier(2)

    def run(name, out):
        started.wait()
        with registry.scope(name):
            registry.watch_ready(name, out)

    ts = [threading.Thread(target=run, args=("probe::slow", slow)),
          threading.Thread(target=run, args=("probe::fast", fast))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert registry.drain_ready(timeout=30.0)
    monkeypatch.setattr(jax, "block_until_ready", real)

    stats = registry.timer.stats()
    slow_ready = stats["probe::slow::ready"][0]
    fast_ready = stats["probe::fast::ready"][0]
    assert slow_ready >= 0.4, stats
    # FIFO pairing would charge the fast stage the slow stage's wait
    # (>= 0.5s) whenever the slow watch was queued first
    assert fast_ready < 0.3, (
        "fast stage charged the slow stage's wait: %.3fs" % fast_ready)

    trace.flush()
    doc = trace_report.load_trace(path)
    assert trace_report.validate_trace(doc) == []
    spans = {e["name"]: e for e in doc["traceEvents"]
             if e.get("ph") == "X"}
    for name in ("probe::slow", "probe::fast"):
        ready = spans[name + "::ready"]
        # the token pins the ready span to its exact emitting span
        assert ready["args"]["parent_span_id"] \
            == spans[name]["args"]["span_id"], (name, ready["args"])
    # per-stream lanes: the two ready spans overlap in wall time, so
    # they must sit on different lanes to keep nesting valid
    assert spans["probe::slow::ready"]["tid"] \
        != spans["probe::fast::ready"]["tid"]


def test_ready_coalescing_still_bounds_inflight():
    """The at-most-one-inflight-per-stream contract survives the
    per-stream rework: floods of one stage coalesce, never queue."""
    import jax.numpy as jnp
    registry.reset()
    registry.enable(sampling=True)
    x = jnp.arange(16)
    for _ in range(64):
        registry.watch_ready("probe::coalesce", x)
    assert registry.drain_ready(timeout=30.0)
    done = registry.timer.counts.get("probe::coalesce::ready", 0)
    coalesced = registry.count("trace/ready_coalesced")
    assert done + coalesced == 64
    assert done >= 1


# ----------------------------------------------------------------------
# SLO watchdog: fires exactly once per breach
# ----------------------------------------------------------------------

def test_watchdog_fires_exactly_once_per_breach():
    registry.reset()
    seen = []
    events.register_event_callback(
        lambda rec: seen.append(rec) if rec["event"] == "health" else None)
    wd = Watchdog(registry)
    assert wd.evaluate() == []  # arms the baselines, nothing fires

    # backend fallback: one event per NEW fallback, silence in between
    registry.inc("backend_fallback")
    fired = wd.evaluate()
    assert [f["rule"] for f in fired] == ["backend_fallback"]
    assert wd.evaluate() == []          # steady state: no re-fire
    assert wd.evaluate() == []
    registry.inc("backend_fallback")    # a second distinct breach
    assert [f["rule"] for f in wd.evaluate()] == ["backend_fallback"]

    # queue saturation is level-based: fires on crossing, re-arms on
    # recovery, fires again on the next crossing
    registry.gauge("serve/queue_depth", 5000)
    assert [f["rule"] for f in wd.evaluate()] == ["queue_saturation"]
    assert wd.evaluate() == []          # still saturated: once only
    registry.gauge("serve/queue_depth", 0)
    assert wd.evaluate() == []          # recovered: re-armed
    registry.gauge("serve/queue_depth", 9999)
    assert [f["rule"] for f in wd.evaluate()] == ["queue_saturation"]
    assert wd.breached() and \
        wd.breached()[0]["rule"] == "queue_saturation"

    # retrace spike: delta per evaluation window, not absolute count
    registry.inc("jit_trace/test.spike", 20)
    assert [f["rule"] for f in wd.evaluate()] == ["retrace_spike"]
    assert wd.evaluate() == []
    registry.inc("jit_trace/test.spike", 2)   # below threshold delta
    assert wd.evaluate() == []

    # trace drops
    registry.inc("trace/dropped_events", 128)
    assert [f["rule"] for f in wd.evaluate()] == ["trace_drops"]
    assert wd.evaluate() == []

    # every firing produced exactly one structured health event + a
    # registry counter
    events.register_event_callback(None)
    rules = [r["rule"] for r in seen]
    assert rules.count("backend_fallback") == 2
    assert rules.count("queue_saturation") == 2
    assert rules.count("retrace_spike") == 1
    assert rules.count("trace_drops") == 1
    assert registry.count("health/backend_fallback") == 2
    assert all("value" in r and "threshold" in r and "severity" in r
               for r in seen)


def test_watchdog_prefetch_stall_share():
    """The out-of-core loader rule: fires when the shard prefetcher's
    stall-time share of the snapshot window crosses the threshold,
    stays quiet for sub-threshold/noise-level stalls, re-arms on
    recovery (one health event per starvation episode on a day-long
    run)."""
    registry.reset()
    seen = []
    events.register_event_callback(
        lambda rec: seen.append(rec) if rec["event"] == "health" else None)
    wd = Watchdog(registry)
    assert wd.evaluate() == []              # arms baseline + window
    # a huge stall delta over a tiny window: share >> threshold
    registry.inc("io/prefetch_stall_ms", 60_000)
    assert [f["rule"] for f in wd.evaluate()] == ["prefetch_stall"]
    assert wd.evaluate() == []              # no new stalls: re-armed
    # noise-level stall (< kMinStallMs) never fires even though the
    # evaluation window is microseconds
    registry.inc("io/prefetch_stall_ms", 10)
    assert wd.evaluate() == []
    # a second real starvation episode fires again
    registry.inc("io/prefetch_stall_ms", 120_000)
    assert [f["rule"] for f in wd.evaluate()] == ["prefetch_stall"]
    events.register_event_callback(None)
    assert [r["rule"] for r in seen] == ["prefetch_stall"] * 2
    assert all(0 < r["value"] <= 1.0 and "threshold" in r for r in seen)
    assert registry.count("health/prefetch_stall") == 2


def test_watchdog_retry_exhausted_and_fault_storm():
    """The fault-tolerance rules (utils/retry.py counters): any retry
    give-up breaches ``retry_exhausted`` immediately; a windowed burst
    of retries/injected faults past the threshold breaches
    ``fault_storm`` — both once-per-breach with re-arm, like every
    other rule."""
    registry.reset()
    seen = []
    events.register_event_callback(
        lambda rec: seen.append(rec) if rec["event"] == "health" else None)
    wd = Watchdog(registry)
    assert wd.evaluate() == []              # arms baselines

    # retry_exhausted: event-like, any new give-up fires
    registry.inc("ft/retry_exhausted")
    assert [f["rule"] for f in wd.evaluate()] == ["retry_exhausted"]
    assert wd.evaluate() == []              # once per breach
    registry.inc("ft/retry_exhausted")      # a second give-up
    assert [f["rule"] for f in wd.evaluate()] == ["retry_exhausted"]

    # fault_storm: rate rule over ft/retries + ft/faults_injected
    registry.inc("ft/retries", 10)
    registry.inc("ft/faults_injected", 10)  # 20 >= default 16
    assert [f["rule"] for f in wd.evaluate()] == ["fault_storm"]
    assert wd.evaluate() == []              # storm passed: re-armed
    registry.inc("ft/retries", 3)           # sub-threshold trickle
    assert wd.evaluate() == []
    registry.inc("ft/retries", 40)          # second storm
    assert [f["rule"] for f in wd.evaluate()] == ["fault_storm"]

    events.register_event_callback(None)
    rules = [r["rule"] for r in seen]
    assert rules.count("retry_exhausted") == 2
    assert rules.count("fault_storm") == 2
    assert registry.count("health/retry_exhausted") == 2
    assert registry.count("health/fault_storm") == 2


def test_watchdog_shed_rate_and_breaker_open():
    """The serving-plane rules (ISSUE 10): ``shed_rate`` is a windowed
    rate over serve/shed_total vs serve/requests (with a minimum-shed
    noise floor), ``breaker_open`` is level-based on the
    serve/breaker_state gauge — both once-per-breach with re-arm."""
    registry.reset()
    seen = []
    events.register_event_callback(
        lambda rec: seen.append(rec) if rec["event"] == "health" else None)
    wd = Watchdog(registry)
    assert wd.evaluate() == []              # arms baselines

    # shed_rate: 10 of 100 submissions shed in one window (>= 5%)
    registry.inc("serve/requests", 100)
    registry.inc("serve/shed_total", 10)
    assert [f["rule"] for f in wd.evaluate()] == ["shed_rate"]
    assert wd.evaluate() == []              # spike passed: re-armed
    # sub-floor trickle never fires, even at a high ratio
    registry.inc("serve/requests", 4)
    registry.inc("serve/shed_total", 3)
    assert wd.evaluate() == []
    # healthy traffic with a sub-threshold shed share stays quiet
    registry.inc("serve/requests", 1000)
    registry.inc("serve/shed_total", 9)     # above floor, < 5% share
    assert wd.evaluate() == []
    # second genuine overload episode fires again
    registry.inc("serve/requests", 50)
    registry.inc("serve/shed_total", 50)
    fired = wd.evaluate()
    assert [f["rule"] for f in fired] == ["shed_rate"]
    assert 0 < fired[0]["value"] <= 1.0

    # breaker_open: level-based on the gauge, re-arms on close
    registry.gauge("serve/breaker_state", 2)
    assert [f["rule"] for f in wd.evaluate()] == ["breaker_open"]
    assert wd.evaluate() == []              # still open: once only
    registry.gauge("serve/breaker_state", 0)
    assert wd.evaluate() == []              # closed: re-armed
    registry.gauge("serve/breaker_state", 2)
    assert [f["rule"] for f in wd.evaluate()] == ["breaker_open"]

    events.register_event_callback(None)
    rules = [r["rule"] for r in seen]
    assert rules.count("shed_rate") == 2
    assert rules.count("breaker_open") == 2
    assert registry.count("health/shed_rate") == 2
    assert registry.count("health/breaker_open") == 2


def test_watchdog_inline_tick_env(monkeypatch):
    """LIGHTGBM_TPU_WATCHDOG=1 routes per-iteration ticks through the
    default watchdog even without a metrics file exporter."""
    monkeypatch.setenv("LIGHTGBM_TPU_WATCHDOG", "1")
    export.reset_exporter()
    registry.reset()
    seen = []
    events.register_event_callback(
        lambda rec: seen.append(rec) if rec["event"] == "health" else None)
    trace.sample_iteration(0)           # arms baselines
    registry.inc("backend_fallback")
    trace.sample_iteration(1)
    trace.sample_iteration(2)
    events.register_event_callback(None)
    assert [r["rule"] for r in seen] == ["backend_fallback"]


def test_snapshot_exporter_periodic(tmp_path, monkeypatch):
    """LIGHTGBM_TPU_METRICS starts one background exporter from the
    per-iteration tick; the file refreshes with current counters."""
    path = str(tmp_path / "train_metrics.prom")
    monkeypatch.setenv("LIGHTGBM_TPU_METRICS", path)
    monkeypatch.setenv("LIGHTGBM_TPU_METRICS_INTERVAL", "0.05")
    export.reset_exporter()
    registry.reset()
    registry.inc("probe_counter", 7)
    trace.sample_iteration(0)           # starts the exporter
    deadline = time.time() + 10
    while not os.path.exists(path) and time.time() < deadline:
        time.sleep(0.02)
    assert os.path.exists(path)
    registry.inc("probe_counter", 3)
    deadline = time.time() + 10
    val = None
    while time.time() < deadline:
        parsed = export.parse_openmetrics(open(path).read())
        val = export.metric_value(parsed,
                                  "lightgbm_tpu_probe_counter_total")
        if val == 10:
            break
        time.sleep(0.02)
    assert val == 10
