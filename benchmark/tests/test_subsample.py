"""The cell under column and row sampling on the CPU: the cell is found by
name from appended entries and new files alone, every seed is fed the one
table whose columns stand as the mix's ``table_seed`` draws them, the
committed runner of kind ``train`` says ``correct`` for the program at a tiny size and refuses
each fault put in its place through the mix's ``extra_params`` (which reach
the program and not the reference) and each control,
``trace/work_subsample.py`` agrees with hand counts and the eight readers
with a hand-made trace.
"""
import hashlib
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark.harness import device, spec, train
from benchmark.reference import gbdt_subsample
from benchmark.trace import work, work_subsample

CELL, CONFIG = "bosch-train-subsample", "bosch-subsample"
ROWS, FEATURES, HOLD, LEAVES = 20000, 40, 2048, 31
SEED = 2**31 + 11
# the cell's own per-layer entries, taken by name: later entries list the
# cell too
METRICS = ("subsample_train_step_mfu_pct", "device_idle_pct.train_subsample",
           "subsample_grower_ms_per_iter", "subsample_hist_ms_per_iter",
           "subsample_hist_roofline", "subsample_hist_weighted_pct",
           "subsample_draw_ms_per_iter", "subsample_mask_host_ms_per_iter")

# Readings at this size (CPU; every seed is fed the one table),
# loss1 / loss2 / step1_norm / change2_norm / holdout_loss2:
#   program as configured    2.0e-9 / 2.6e-9 / 8.5e-8 / 6.6e-8 / 1.0e-10
#   bagging_fraction 1.0     5.7e-4 / 7.0e-4 / 2.9e-3 / 9.6e-3 / 4.8e-4
#   feature_fraction 1.0     9.4e-3 / 0.012 / 0.17 / 0.30 / 9.0e-3
#   bagging_fraction 0.79    1.5e-4 / 2.8e-4 / 4.2e-3 / 1.4e-3 / 3.6e-4
#   feature_fraction 0.78    2.3e-4 / 4.1e-4 / 2.8e-3 / 8.4e-3 / 7.1e-4
#     (31 columns for 32, the first 31 of the same permutation: one column
#     fewer a tree, seen because the first tree splits on it here; with the
#     columns in the order --seed 11 draws under the accepted mix neither
#     checked tree uses it and the fault reads as the program does)
#   bagging_seed 4           6.5e-4 / 1.4e-3 / 0.020 / 0.017 / 6.7e-4
#   feature_fraction_seed 3  9.1e-3 / 2.3e-3 / 0.16 / 0.070 / 3.9e-3
#   bagging_freq 1           2.0e-9 / 2.3e-4 / 8.5e-8 / 4.0e-3 / 8.1e-4
#   ref-bf16                 4.8e-5 / 5.2e-5 / 1.9e-3 / 1.0e-3 / 5.2e-5
#   ref-half                 2.9e-5 / 1.1e-4 / 0.069 / 0.050 / 9.7e-4
#   ref-frozen               0.026 / 0.055 / 1.0 / 1.0 / 2.4e-4
LIMITS = {"loss1": 2e-6, "loss2": 2e-6, "step1_norm": 1e-5,
          "change2_norm": 1e-5, "holdout_loss2": 2e-6, "window_compiles": 0}
SECOND_STEP = {"loss2", "change2_norm", "holdout_loss2"}
FAULTS = {
    "ignores the bag": {"bagging_fraction": 1.0},
    "ignores the mask": {"feature_fraction": 1.0},
    "another fraction of the rows": {"bagging_fraction": 0.79},
    "one column fewer": {"feature_fraction": 0.78},
    "another bag": {"bagging_seed": 4},
    "other columns": {"feature_fraction_seed": 3},
    "the bag redrawn at step 2": {"bagging_freq": 1},
}


def _hashes(root):
    out = {}
    for base, _, files in os.walk(root):
        if "__pycache__" not in base:
            for name in files:
                path = os.path.join(base, name)
                with open(path, "rb") as f:
                    out[os.path.relpath(path, root)] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def small_cell(extra_params=None):
    cell = spec.Spec().cell(CELL)
    cell["config"] = dict(cell["config"], rows=ROWS, features=FEATURES,
                          valid_rows=HOLD)
    cell["config"]["params"] = dict(cell["config"]["params"],
                                    num_leaves=LEAVES,
                                    min_sum_hessian_in_leaf=20.0)
    cell["traffic"] = dict(cell["traffic"],
                           extra_params=dict(extra_params or {}))
    cell["limits"] = dict(LIMITS)
    return cell


def drive(variant=None, extra_params=None, seconds=0.0):
    import jax
    this, result, compared = train.run(
        small_cell(extra_params), SEED, seconds, False, jax.devices()[0],
        device.peaks_for("TPU v5 lite"), time.perf_counter(), variant)
    return result, this, compared


def _over(compared):
    return {k for k, c in compared.items() if c["value"] > c["limit"]}


def test_the_cell_names_the_sampled_configuration_and_the_accepted_mix():
    bench = spec.Spec()
    cell = bench.cell(CELL)
    params = cell["config"]["params"]
    assert (params["feature_fraction"], params["bagging_fraction"],
            params["bagging_freq"]) == (0.8, 0.8, 5)
    assert "bagging_seed" not in params
    assert "feature_fraction_seed" not in params
    defaults = cell["config"]["defaults_in_force"]
    assert (defaults["bagging_seed"], defaults["feature_fraction_seed"]) \
        == (3, 2)
    assert cell["config"]["reduced"] == []
    assert cell["config"]["mask_draw"] and cell["config"]["sample_draw"]
    assert bench.reference(cell) is gbdt_subsample
    assert bench.runner(cell["traffic"]["kind"]) is train
    assert set(cell["limits"]) == set(LIMITS)
    # bosch-train's shape, settings and traffic, and nothing else changed
    plain = bench.cell("bosch-train")
    for key in ("rows", "valid_rows", "features"):
        assert cell["config"][key] == plain["config"][key]
    assert {k: v for k, v in params.items()
            if k in plain["config"]["params"]} == plain["config"]["params"]
    # the accepted mix but for the order of the table's columns, which the
    # mix's table_seed draws for every --seed (the mask is drawn by position)
    mix = json.loads(json.dumps(cell["traffic"]))
    assert mix.pop("generator_why")
    assert mix["data"].pop("generator") == "table_seed_columns"
    assert mix == plain["traffic"]
    assert mix["extra_params"] == {}
    # 774 of 968 columns a tree, as upstream's ColSampler::GetCnt counts
    assert work_subsample.sampled_columns(968, 0.8) == 774


def test_every_seed_is_fed_the_table_of_the_mix_s_table_seed():
    from benchmark.harness import traffic
    cell = spec.Spec().cell(CELL)
    data = cell["traffic"]["data"]
    make_table = cell["spec"].make_table(data)
    X1, y1, extra = make_table(3000, 24, 11, data)
    X2, y2, _ = make_table(3000, 24, SEED, data)
    want_X, want_y = traffic.make_table(3000, 24, data["table_seed"], data)
    assert extra == {}
    for X, y in ((X1, y1), (X2, y2)):
        np.testing.assert_array_equal(X, want_X)
        np.testing.assert_array_equal(y, want_y)
    # the accepted mix's table under another seed: the same columns, moved
    moved, _ = traffic.make_table(3000, 24, 11, data)
    assert (moved != want_X).any()
    assert sorted(map(tuple, moved.T[:, :8])) \
        == sorted(map(tuple, want_X.T[:, :8]))


def test_reference_imports_nothing_of_the_program():
    with open(gbdt_subsample.__file__) as f:
        source = f.read()
    assert "import lightgbm_tpu" not in source
    assert "from lightgbm_tpu" not in source


def test_program_as_configured_is_correct():
    result, this, compared = drive(seconds=0.3)
    assert result["correct"] is True and result["failed"] == 0
    assert this.end_to_end["train_iter_s"] > 0
    assert set(compared) == set(LIMITS)
    assert compared["window_compiles"]["value"] == 0
    assert this.iterations == result["attempted"] >= 1


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_programs_place_is_not_correct(fault):
    result, _, compared = drive(extra_params=FAULTS[fault])
    assert result["correct"] is False
    if fault == "the bag redrawn at step 2":    # the first step is as it was
        assert _over(compared) == SECOND_STEP
    else:
        assert _over(compared) == set(LIMITS) - {"window_compiles"}


@pytest.mark.parametrize("control", ["ref-bf16", "ref-half", "ref-frozen"])
def test_control_is_not_correct(control):
    result, _, compared = drive(control)
    assert result["correct"] is False
    if control == "ref-bf16":       # the nearest precision: every number
        assert _over(compared) == set(LIMITS) - {"window_compiles"}
    else:
        assert {"loss1", "step1_norm"} <= _over(compared)


def test_cell_is_found_by_name_from_appended_entries_in_a_new_checkout(
        tmp_path):
    """The parent's benchmark with this PR's files laid over it and its
    entries appended: the cell, its reference and its eight readers are
    found, and what was there is as it was."""
    here = spec.Spec()
    new = {"configs": [e for e in here.doc["configs"]
                       if e["name"] == CONFIG],
           "workloads": [e for e in here.doc["workloads"]
                         if e["name"] == CELL],
           "per_layer": [e for e in here.doc["per_layer"]
                         if e["name"] in METRICS]}
    assert [len(new[k]) for k in ("configs", "workloads", "per_layer")] \
        == [1, 1, 8]
    added = {
        "configs/bosch-subsample.json", "limits/bosch-train-subsample.json",
        "reference/gbdt_subsample.py", "trace/work_subsample.py",
        "metrics/_subsample.py", "tests/test_subsample.py",
        "traffic/train-looped-valid-cols.json",
        "generators/table_seed_columns.py",
    } | {"metrics/%s.py" % m["name"] for m in new["per_layer"]}
    checkout = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, checkout / "benchmark",
                    ignore=lambda d, names: [
                        n for n in names if n == "__pycache__"
                        or os.path.relpath(os.path.join(d, n),
                                           spec.BENCH_DIR) in added])
    doc = json.loads(json.dumps(here.doc))
    # taken out by name, not by position: a later PR appends after them
    for k, entries in new.items():
        doc[k] = [e for e in doc[k] if e not in entries]
    (checkout / "BENCHMARK.json").write_text(json.dumps(doc))
    old = spec.Spec(str(checkout), str(checkout / "benchmark"))
    with pytest.raises(spec.SpecError, match="no workload"):
        old.cell(CELL)
    kinds = old.runner_kinds()
    before = _hashes(checkout / "benchmark")

    for rel in added:
        shutil.copy(os.path.join(spec.BENCH_DIR, rel),
                    checkout / "benchmark" / rel)
    for k, entries in new.items():
        doc[k] = doc[k] + entries
    (checkout / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Spec(str(checkout), str(checkout / "benchmark"))
    assert bench.runner_kinds() == kinds        # no new runner
    cell = bench.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"]["kind"] == "train"
    assert hasattr(bench.reference(cell), "Reference")
    assert set(bench.end_to_end(CELL)) == {"train_iter_s", "setup_s"}
    assert sorted(bench.per_layer(CELL)) == sorted(here.per_layer(CELL))
    assert set(METRICS) <= set(bench.per_layer(CELL))

    class NoTrace:      # an untraced run: nothing to read, no error
        trace = None
        iterations = window_s = busy_s = 0
        tree_counts = []
        phases = {}
    for name in METRICS:
        if name != "subsample_hist_weighted_pct":   # counters, not trace
            assert bench.reader(name)(NoTrace()) is None
    after = _hashes(checkout / "benchmark")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == added
    for other in (w["name"] for w in old.doc["workloads"]):
        assert bench.per_layer(other) == here.per_layer(other)
        assert not set(bench.per_layer(other)) & set(METRICS)


# --- trace/work_subsample.py against hand counts --------------------------

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
         "int8_ops_per_s": 393e12}


def test_sampled_columns_and_bag_draws_hand_count():
    assert work_subsample.sampled_columns(968, 0.8) == 774
    assert work_subsample.sampled_columns(968, 0.799) == 773
    assert work_subsample.sampled_columns(968, 1.0) == 968
    assert work_subsample.sampled_columns(3, 0.1) == 1
    # the cell's window: iterations 2..11 hold the redraws at 5 and 10
    assert work_subsample.bag_draws(2, 10, 5) == 2
    assert work_subsample.bag_draws(0, 7, 5) == 2
    assert work_subsample.bag_draws(2, 3, 5) == 0
    assert work_subsample.bag_draws(2, 10, 0) == 0
    assert work_subsample.bag_pass(1000) == {"bytes": 4000, "ops": 1000}


def test_least_seconds_sum_over_in_bag_rows_and_sampled_columns():
    counts = [(30, [12, 3, 7]), (31, [2])]
    hist = work_subsample.histograms_least_seconds(counts, 8, PEAKS)
    assert abs(hist - (52 + 33) * (8 + 8) / 819e9) < 1e-18
    whole = work_subsample.window_least_seconds(counts, 8, 100, 1, PEAKS)
    assert abs(whole - hist - (2 * (1600 + 800) + 400) / 819e9) < 1e-18
    # fewer columns, less to read: never more than the unsampled count
    assert hist < sum(work.least_seconds(work.histogram_pass(
        work.histogram_rows(c), 10), PEAKS)[0] for c in counts)


# --- the readers against a hand-made run ----------------------------------

class _FakeTrace:
    def __init__(self, ops, modules, host):
        self._ops, self._modules, self.host = ops, modules, host

    def ops(self, ordinal=0):
        return self._ops

    def modules(self, ordinal=0):
        return self._modules


def _fake_run(monkeypatch, features=10):
    """Five iterations, 2 to 6: the bag drawn once, at iteration 5, by a
    program of 2 ms (1.5 of them under ``obs_bag``); per iteration one
    grower program of 100 ms, 70 of them under ``obs_hist_pallas``, and one
    host range ``tree::sample_features`` of 0.4 ms."""
    from benchmark.metrics import _stages, _subsample
    from benchmark.trace import scopes, xplane
    ms = 1_000_000
    names, start, dur, tf_op, mods, spans = [], [], [], [], [], []
    for it in range(5):
        t0 = it * 200 * ms
        ops = [("%fusion.3", t0 + 10 * ms, 70 * ms,
                "jit(_tree_impl)/while/body/obs_compact/obs_hist_pallas/"
                "hist_kernel"),
               ("%fusion.4", t0 + 80 * ms, 30 * ms,
                "jit(_tree_impl)/while/body/obs_split_scan/mul")]
        mods.append(("jit__tree_impl(2)", t0 + 10 * ms, 100 * ms))
        if it == 3:
            mods.append(("jit__bag_draw(1)", t0, 2 * ms))
            ops += [("%fusion.1", t0, 1_500_000,
                     "jit(_bag_draw)/obs_bag/lt"),
                    ("%copy.2", t0 + 1_500_000, 500_000, "")]
        for name, at, d, stack in ops:
            names.append(name)
            start.append(at)
            dur.append(d)
            tf_op.append(stack)
        spans.append(("tree::sample_features", t0 + 5 * ms, 400_000))
        spans.append(("tree::stage_gh", t0 + 4 * ms, 900_000))
    line = xplane.Line(names, np.asarray(start, np.int64),
                       np.asarray(dur, np.int64))
    as_line = lambda rows: xplane.Line(
        [r[0] for r in rows], np.asarray([r[1] for r in rows], np.int64),
        np.asarray([r[2] for r in rows], np.int64))
    modules = as_line(sorted(mods, key=lambda r: r[1]))
    ops = scopes.Ops(line, tf_op, modules)
    monkeypatch.setattr(_stages, "_newest_xplane", lambda: "a.xplane.pb")
    monkeypatch.setattr(scopes, "load_ops", lambda path, ordinal=0: ops)

    def cell(self, name):
        assert name == CELL
        return {"config": {"params": {"feature_fraction": 0.8,
                                      "bagging_freq": 5}},
                "traffic": {"checked_steps": 2}}
    monkeypatch.setattr(_subsample.spec.Spec, "cell", cell)
    run = train.Run(1000, features, PEAKS)
    run.trace = _FakeTrace(line, modules, {"python": as_line(spans)})
    run.iterations, run.window_s, run.busy_s = 5, 1.0, 0.502
    run.tree_counts = [(800, [300, 100])] * 5
    return run


def test_readers_of_the_cell_s_metrics_by_hand(monkeypatch):
    from benchmark.harness import program_obs
    run = _fake_run(monkeypatch)
    monkeypatch.setattr(program_obs, "counter", {
        "grow/hist_rows_in_bag": 3000, "grow/hist_rows_bucketed": 4000,
        "sample/cols_in_mask": 56, "sample/cols_total": 70}.get)
    bench = spec.Spec()
    got = {name: bench.reader(name)(run) for name in METRICS}
    assert len(got) == 8 and None not in got.values()
    assert got["subsample_grower_ms_per_iter"] == pytest.approx(100.0)
    assert got["subsample_hist_ms_per_iter"] == pytest.approx(70.0)
    assert got["device_idle_pct.train_subsample"] == pytest.approx(49.8)
    assert got["subsample_draw_ms_per_iter"] == pytest.approx(1.5 / 5)
    assert got["subsample_mask_host_ms_per_iter"] == pytest.approx(0.4)
    # 8 of 10 columns, in-bag rows of the root and the smaller children
    hist_least = 5 * 1200 * (8 + 8) / 819e9
    assert got["subsample_hist_roofline"] == pytest.approx(
        100 * hist_least / 0.350)
    whole = hist_least + (5 * 1000 * (16 + 8) + 1000 * 4) / 819e9
    assert got["subsample_train_step_mfu_pct"] == pytest.approx(
        100 * whole / 1.0)
    assert got["subsample_hist_weighted_pct"] == pytest.approx(
        100 * 0.75 * 0.8)
    assert all(0 < got[n] < 100 for n in got if n.endswith(
        ("roofline", "_pct")))


def test_counters_are_read_since_the_window_opened_where_it_is_noted(
        monkeypatch):
    """The runners note the counters at the window's start (both kinds
    do): set-up's trees are left out."""
    from benchmark.harness import program_obs
    run = _fake_run(monkeypatch)
    monkeypatch.setattr(program_obs, "counter", {
        "grow/hist_rows_in_bag": 3000, "grow/hist_rows_bucketed": 4000,
        "sample/cols_in_mask": 56, "sample/cols_total": 70}.get)
    run.counters_at_window = {
        "grow/hist_rows_in_bag": 1000, "grow/hist_rows_bucketed": 1500,
        "sample/cols_in_mask": 16, "sample/cols_total": 20}
    assert spec.Spec().reader("subsample_hist_weighted_pct")(run) \
        == pytest.approx(100 * (2000 / 2500) * (40 / 50))


def test_readers_find_nothing_in_a_program_without_the_scope(monkeypatch):
    """The parent's programs carry no ``obs_bag``, open no
    ``tree::sample_features`` and count no ``sample/cols_*``: those metrics
    are left out, nothing raises, and the others read as they did."""
    from benchmark.harness import program_obs
    from benchmark.trace import scopes
    run = _fake_run(monkeypatch)
    ops = scopes.load_ops("a.xplane.pb")
    ops.tf_op[:] = [s.replace("obs_bag/", "") for s in ops.tf_op]
    run.trace.host = {"python": run.trace.host["python"].matching(
        "^tree::stage_gh$")}
    monkeypatch.setattr(program_obs, "counter", {
        "grow/hist_rows_in_bag": 3000, "grow/hist_rows_bucketed": 4000}.get)
    bench = spec.Spec()
    for name in ("subsample_draw_ms_per_iter",
                 "subsample_mask_host_ms_per_iter",
                 "subsample_hist_weighted_pct"):
        assert bench.reader(name)(run) is None
    assert bench.reader("subsample_grower_ms_per_iter")(run) \
        == pytest.approx(100.0)
    assert bench.reader("subsample_hist_ms_per_iter")(run) \
        == pytest.approx(70.0)
    assert 0 < bench.reader("subsample_train_step_mfu_pct")(run) < 100
