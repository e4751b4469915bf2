"""Non-perturbing tracing & profiling layer (ISSUE 3).

Covers: span emission from existing stage scopes, Chrome-trace export +
round-trip through tools/trace_report.py, the sample-mode readiness
drainer (zero block_until_ready fences on the training hot path),
compile cost capture (FLOPs / bytes / HLO size on jit_trace), the
retrace budget regression guard, multi-rank trace merge, per-stage
latency percentiles, device memory gauges, and the retrace-warning
reset hook.
"""
import importlib.util
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import compile as obs_compile
from lightgbm_tpu.obs import events, trace
from lightgbm_tpu.obs.registry import (MetricsRegistry, StageTimer,
                                       registry)
from lightgbm_tpu.utils import log

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_REPORT = os.path.join(REPO, "tools", "trace_report.py")

_spec = importlib.util.spec_from_file_location("trace_report",
                                               TRACE_REPORT)
trace_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_report)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Tests share the process-wide registry/trace/sinks; leave them
    exactly as the suite default (timing off, no fences, no sinks)."""
    yield
    trace.configure(None)
    trace.set_process_index(0)
    events.configure(None)
    events.register_event_callback(None)
    log.register_log_callback(None)
    registry.drain_ready(timeout=10.0)
    registry.disable()
    registry.timer.sampling = False
    registry.fences = False


def _small_problem(n=400, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6)
    y = (X[:, 0] + 0.5 * X[:, 1] + rng.randn(n) * 0.3 > 0).astype(float)
    return X, y


def _train_small(num_boost_round=2, **extra):
    X, y = _small_problem()
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "min_data_in_leaf": 5}
    params.update(extra)
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=num_boost_round)


def _spans(doc):
    return [e for e in doc["traceEvents"] if e.get("ph") == "X"]


# ----------------------------------------------------------------------
# trace round-trip: emit → export → validate → span tree (acceptance)
# ----------------------------------------------------------------------

def test_trace_roundtrip_covers_pipeline_and_costs(tmp_path):
    """A traced 2-iteration train exports schema-valid Chrome-trace
    JSON whose spans cover binning, gradients, tree growth,
    score update, and at least one jit span carrying cost_analysis
    FLOPs; the span tree reconstructs with correct parent links."""
    path = str(tmp_path / "trace.json")
    registry.reset()
    registry.enable(sampling=True)
    trace.configure(path)
    # unique (num_leaves, max_bin) signature: earlier suite tests may
    # have compiled the common shapes already, and a fully cache-hit
    # train would (correctly) emit no jit_trace spans
    _train_small(num_boost_round=2, num_leaves=11, max_bin=21)
    trace.flush()
    doc = trace_report.load_trace(path)
    assert trace_report.validate_trace(doc) == []
    names = {e["name"] for e in _spans(doc)}
    for required in ("io::apply_bins", "gbdt::gradients", "tree::grow",
                     "tree::root_histogram", "tree::split_batches",
                     "gbdt::score_update"):
        assert required in names, sorted(names)
    # compile boundaries are costed, not just counted
    jit_spans = [e for e in _spans(doc) if e["name"].startswith("jit::")]
    assert jit_spans
    assert any(e["args"].get("flops", 0) > 0 for e in jit_spans)
    assert any(e["args"].get("hlo_bytes", 0) > 0 for e in jit_spans)
    # instant events (the JSONL stream) ride the same trace
    instants = {e["name"] for e in doc["traceEvents"]
                if e.get("ph") == "i"}
    assert "train_iter" in instants and "dataset" in instants
    # span tree: root_histogram must be a child of tree::grow
    nodes = trace_report.span_tree(doc)
    assert nodes, "no span ids in trace"
    links = {(n["name"], nodes[n["parent"]]["name"])
             for n in nodes.values() if n["parent"] in nodes}
    assert ("tree::root_histogram", "tree::grow") in links, sorted(links)
    # every span carries the process trace id
    tids = {e["args"].get("trace_id") for e in _spans(doc)}
    assert len(tids) == 1 and None not in tids
    # roofline surfacing (ISSUE 4 satellite): the summary aggregates
    # per-fn FLOPs + bytes-accessed from the compile spans into a
    # bytes/FLOP ratio — the direct evidence of a program's bandwidth
    # position (and of the quantized path moving fewer bytes)
    summary = trace_report.summarize(doc)
    roof = summary.get("roofline", {})
    assert roof, "no roofline section despite costed jit spans"
    costed = [r for r in roof.values() if r["flops"] > 0]
    assert costed
    assert any(r.get("bytes_per_flop") is not None for r in costed)


def test_trace_report_validate_cli_smoke(tmp_path):
    """Tier-1 CI smoke: a traced train's output passes
    ``trace_report.py validate`` (stdlib-only subprocess, fast)."""
    path = str(tmp_path / "cli_trace.json")
    registry.reset()
    registry.enable(sampling=True)
    trace.configure(path)
    _train_small(num_boost_round=2)
    trace.flush()
    proc = subprocess.run([sys.executable, TRACE_REPORT, "validate",
                           path], capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.startswith("OK:"), proc.stdout


def test_trace_report_validate_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"name": "x", "ph": "X", "ts": 0.0, "dur": -1.0,
         "pid": 0, "tid": 1}]}))
    proc = subprocess.run([sys.executable, TRACE_REPORT, "validate",
                           str(bad)], capture_output=True, text=True,
                          timeout=60)
    assert proc.returncode == 1
    assert "INVALID" in proc.stderr
    # partial overlap on one lane = broken nesting
    doc = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 100.0,
         "pid": 0, "tid": 1},
        {"name": "b", "ph": "X", "ts": 50.0, "dur": 100.0,
         "pid": 0, "tid": 1}]}
    errs = trace_report.validate_trace(doc)
    assert any("overlaps" in e for e in errs), errs


# ----------------------------------------------------------------------
# sample mode: zero fences on the training hot path (acceptance)
# ----------------------------------------------------------------------

def test_sample_mode_zero_hot_path_fences(tmp_path, monkeypatch):
    import jax
    calls = []
    real = jax.block_until_ready

    def spy(x):
        calls.append(threading.current_thread().name)
        return real(x)

    registry.reset()
    registry.enable(sampling=True)
    trace.configure(str(tmp_path / "sample_trace.json"))
    monkeypatch.setattr(jax, "block_until_ready", spy)
    _train_small(num_boost_round=2)
    assert registry.drain_ready(timeout=30.0)
    monkeypatch.setattr(jax, "block_until_ready", real)
    main_thread = threading.main_thread().name
    assert [c for c in calls if c == main_thread] == [], (
        "sample mode must not fence the training hot path")
    # the device time is still attributed — by the per-stream drainer
    # threads (one per watched stage name), off-thread
    assert any(c.startswith("obs-ready-drainer:") for c in calls)
    ready_stages = [k for k in registry.timer.counts
                    if k.endswith("::ready")]
    assert "tree::root_histogram::ready" in ready_stages, ready_stages
    assert registry.fence() is False


def test_fence_mode_still_fences_inline(monkeypatch):
    """LIGHTGBM_TPU_TIMETAG=1 semantics are unchanged: stage scopes
    block_until_ready on the calling thread."""
    import jax
    calls = []
    real = jax.block_until_ready

    def spy(x):
        calls.append(threading.current_thread().name)
        return real(x)

    registry.reset()
    registry.enable()
    registry.fences = True
    monkeypatch.setattr(jax, "block_until_ready", spy)
    _train_small(num_boost_round=1)
    monkeypatch.setattr(jax, "block_until_ready", real)
    assert any(c == threading.main_thread().name for c in calls)
    assert "tree::root_histogram::ready" not in registry.timer.counts


def test_timetag_sample_env_parse(monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_TIMETAG", "sample")
    t = StageTimer()
    assert t.enabled and t.sampling
    r = MetricsRegistry()
    assert r.enabled and r.sampling and not r.fence()
    monkeypatch.setenv("LIGHTGBM_TPU_TIMETAG", "1")
    t = StageTimer()
    assert t.enabled and not t.sampling
    assert MetricsRegistry().fence()


def test_watch_ready_modes():
    import jax.numpy as jnp
    # disabled: no-op
    registry.reset()
    registry.disable()
    registry.watch_ready("probe_a", jnp.arange(4))
    assert registry.drain_ready(timeout=10.0)
    assert "probe_a::ready" not in registry.timer.counts
    # sampling: async attribution under <stage>::ready
    registry.enable(sampling=True)
    registry.watch_ready("probe_b", jnp.arange(8) * 2)
    assert registry.drain_ready(timeout=30.0)
    assert registry.timer.counts["probe_b::ready"] == 1
    assert registry.timer.totals["probe_b::ready"] >= 0.0


# ----------------------------------------------------------------------
# compile cost capture
# ----------------------------------------------------------------------

def test_instrument_jit_captures_cost_once_per_signature(tmp_path,
                                                         monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setenv("LIGHTGBM_TPU_COMPILE_COST", "1")
    path = str(tmp_path / "cost.jsonl")
    events.configure(path)
    f = obs_compile.instrument_jit("test.cost_probe",
                                   lambda x: (x @ x).sum())
    before = obs_compile.trace_count("test.cost_probe")
    np.testing.assert_allclose(float(f(jnp.ones((32, 32)))), 32.0 ** 3)
    f(jnp.ones((32, 32)))          # cached signature
    events.configure(None)
    # the cost-capture lowering must NOT inflate the retrace counter
    assert obs_compile.trace_count("test.cost_probe") == before + 1
    recs = [r for r in events.read_jsonl(path)
            if r["event"] == "jit_trace" and r["fn"] == "test.cost_probe"]
    assert len(recs) == 1
    assert recs[0]["flops"] > 0
    assert recs[0]["bytes_accessed"] > 0
    assert recs[0]["hlo_bytes"] > 0
    assert registry.gauges["compile/test.cost_probe/flops"] > 0


def test_instrument_jit_without_capture_has_plain_events(tmp_path,
                                                         monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setenv("LIGHTGBM_TPU_COMPILE_COST", "0")
    path = str(tmp_path / "nocost.jsonl")
    events.configure(path)
    f = obs_compile.instrument_jit("test.nocost_probe", lambda x: x + 1)
    f(jnp.ones(3))
    events.configure(None)
    recs = [r for r in events.read_jsonl(path)
            if r["event"] == "jit_trace"
            and r["fn"] == "test.nocost_probe"]
    assert len(recs) == 1 and "flops" not in recs[0]


# ----------------------------------------------------------------------
# retrace budget regression guard (satellite)
# ----------------------------------------------------------------------

def test_retrace_budget_identical_trains_add_zero_traces():
    """Two identical 2-iteration trains on fixed shapes: the second run
    must hit every jit cache — zero new traces per instrumented
    function (guards against silent retrace regressions from
    non-weak-typed scalars / changing statics)."""
    def delta(after, before):
        # ZERO exceptions: since the objectives gained config-keyed
        # __hash__/__eq__ (ISSUE 6 satellite), config-identical
        # instances share one compiled gradient program — the former
        # "one obj.* trace per run" carve-out (the static-self jit
        # pattern compiled once per INSTANCE) is closed, and obj.*
        # must hit the cache exactly like every learner function.
        return {k: after[k] - before.get(k, 0) for k in after
                if after[k] != before.get(k, 0)}

    _train_small(num_boost_round=2)          # warm all caches
    before = dict(obs_compile.trace_counts())
    _train_small(num_boost_round=2)
    mid = dict(obs_compile.trace_counts())
    first_run = delta(mid, before)
    _train_small(num_boost_round=2)
    after = dict(obs_compile.trace_counts())
    second_run = delta(after, mid)
    assert first_run == {}, (
        "identical warmed train still traced: %r" % first_run)
    assert second_run == {}, (
        "retrace regression — identical train re-traced: %r"
        % second_run)


def test_config_identical_objectives_share_compiles():
    """Two config-identical objective instances are jit-cache-equal
    (config-keyed __hash__/__eq__), a config change is not — the direct
    unit check behind the zero-exception retrace budget above."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objective.binary import BinaryLogloss

    cfg = Config.from_params({"objective": "binary"})
    a, b = BinaryLogloss(cfg), BinaryLogloss(cfg)
    assert a == b and hash(a) == hash(b)
    cfg2 = Config.from_params({"objective": "binary", "sigmoid": 2.0})
    c = BinaryLogloss(cfg2)
    assert a != c
    # a jitted dispatch through two equal instances compiles ONCE
    import jax.numpy as jnp
    n0 = obs_compile.trace_count("obj.binary.grads")
    score = jnp.zeros(73, dtype=jnp.float32)  # unique shape for this test
    sign = jnp.ones(73, dtype=jnp.float32)
    w = jnp.ones(73, dtype=jnp.float32)
    a._grads(score, sign, w, None)
    b._grads(score, sign, w, None)
    assert obs_compile.trace_count("obj.binary.grads") == n0 + 1
    c._grads(score, sign, w, None)  # different sigmoid: new program
    assert obs_compile.trace_count("obj.binary.grads") == n0 + 2


def test_retrace_warning_resets_with_registry_reset(monkeypatch):
    """The _WARNED dedup set follows registry.reset() — repeated runs
    in one process warn again instead of at most once per process."""
    monkeypatch.setenv("LIGHTGBM_TPU_RETRACE_WARN", "2")
    name = "test.warn_reset_probe"
    log.set_verbosity(0)  # earlier verbosity=-1 trains silence warnings
    lines = []
    log.register_log_callback(lines.append)

    def n_warnings():
        return sum(1 for line in lines
                   if name in line and "traced" in line)

    registry.reset()
    for _ in range(4):
        obs_compile.record_trace(name)
    assert n_warnings() == 1, lines  # fires once past the threshold
    registry.reset()                 # clears counters AND the dedup set
    for _ in range(4):
        obs_compile.record_trace(name)
    log.register_log_callback(None)
    assert n_warnings() == 2, lines


# ----------------------------------------------------------------------
# multi-rank merge (acceptance)
# ----------------------------------------------------------------------

def test_merge_two_rank_traces_cli(tmp_path):
    """Two per-rank trace files merge into one Perfetto-loadable file
    with distinct process lanes and a correct aggregate stage table."""
    p0 = str(tmp_path / "trace.rank0.json")
    p1 = str(tmp_path / "trace.rank1.json")
    registry.reset()
    registry.enable(sampling=True)
    trace.configure(p0, process_index_override=0)
    _train_small(num_boost_round=2)
    trace.flush()
    trace.configure(p1, process_index_override=1)
    _train_small(num_boost_round=2)
    trace.flush()
    trace.configure(None)
    trace.set_process_index(0)
    per_rank_calls = []
    for p in (p0, p1):
        doc = trace_report.load_trace(p)
        assert trace_report.validate_trace(doc) == []
        per_rank_calls.append(sum(1 for e in _spans(doc)
                                  if e["name"] == "tree::grow"))
    assert all(c > 0 for c in per_rank_calls)

    out = str(tmp_path / "merged.json")
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, "merge", "-o", out, p0, p1],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    table = json.loads(proc.stdout)
    assert table["phases"]["tree::grow"]["calls"] == sum(per_rank_calls)
    assert table["phases"]["tree::grow"]["seconds"] > 0
    merged = trace_report.load_trace(out)
    assert trace_report.validate_trace(merged) == []
    pids = {e["pid"] for e in _spans(merged)}
    assert pids == {0, 1}, pids
    # per-rank process_name lanes for Perfetto
    names = {e["pid"]: e["args"]["name"]
             for e in merged["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert set(names) == {0, 1}
    # wall-clock interleave: non-metadata events sorted by ts
    ts = [e["ts"] for e in merged["traceEvents"] if e.get("ph") != "M"]
    assert ts == sorted(ts)


def test_summary_matches_bench_phase_shape(tmp_path):
    path = str(tmp_path / "sum_trace.json")
    registry.reset()
    registry.enable(sampling=True)
    trace.configure(path)
    _train_small(num_boost_round=2)
    trace.flush()
    doc = trace_report.load_trace(path)
    table = trace_report.summarize(doc)["phases"]
    entry = table["gbdt::gradients"]
    assert set(entry) == {"seconds", "calls", "p50_ms", "p99_ms"}
    assert entry["calls"] == 2
    assert entry["p99_ms"] >= entry["p50_ms"] >= 0.0


# ----------------------------------------------------------------------
# registry: latency percentiles in phases, device memory gauges
# ----------------------------------------------------------------------

def test_phases_carry_latency_percentiles():
    r = MetricsRegistry()
    r.enable()
    for _ in range(4):
        with r.scope("st"):
            pass
    entry = r.phases()["st"]
    assert entry["calls"] == 4
    assert entry["p99_ms"] >= entry["p50_ms"] >= 0.0
    # snapshot carries the same table
    assert r.snapshot()["phases"]["st"]["p50_ms"] == entry["p50_ms"]


def test_device_memory_gauges_with_cpu_fallback():
    registry.reset()
    out = trace.record_device_memory()
    # the CPU backend reports no memory_stats → live-buffer fallback
    assert out, "record_device_memory recorded nothing"
    assert any(k.startswith("device/") for k in registry.gauges)


def test_sample_iteration_is_noop_when_telemetry_off():
    registry.reset()
    registry.disable()
    trace.sample_iteration(1)
    assert not any(k.startswith("device/") for k in registry.gauges)


# ----------------------------------------------------------------------
# env-var end-to-end (exactly as a user runs it) — also the tier-1
# acceptance train: TIMETAG=sample + TRACE in a fresh process
# ----------------------------------------------------------------------

def test_trace_env_vars_end_to_end(tmp_path):
    trace_path = str(tmp_path / "e2e_trace.json")
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
               LIGHTGBM_TPU_TRACE=trace_path)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = trace_report.load_trace(trace_path)
    assert trace_report.validate_trace(doc) == []
    names = {e["name"] for e in _spans(doc)}
    assert {"io::apply_bins", "gbdt::gradients", "tree::grow",
            "gbdt::score_update"} <= names, sorted(names)
    assert any(n.startswith("jit::") for n in names)
    # sample mode: the exit summary includes async ::ready attribution
    assert "::ready" in proc.stderr, proc.stderr[-2000:]
