"""The cell under gradient-based one-side sampling on the CPU: the runner of
kind ``train_warm`` drives the warm-up unchecked and compares the sampled
steps with the plain reference (``reference/gbdt_goss.py``) from the
program's own scores, ``correct`` comes out false for a program that ignores
the sampling and for the controls, the cell is found from appended entries
and new files alone, and ``trace/work_goss.py`` and the readers agree with
hand counts.
"""
import hashlib
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark.harness import device, program, spec, train, train_warm
from benchmark.reference import gbdt_goss
from benchmark.trace import work, work_goss

CELL = "bosch-train-goss"
# the cell's own per-layer entries, taken by name: later entries list the
# cell too
METRICS = (
    "goss_train_step_mfu_pct", "device_idle_pct.train_goss",
    "goss_sample_ms_per_iter", "goss_sample_roofline",
    "goss_grower_ms_per_iter", "goss_hist_ms_per_iter", "goss_hist_roofline",
    "goss_hist_inbag_pct", "goss_grower_partition_ms_per_iter",
    "goss_grower_compact_ms_per_iter", "goss_grower_hist_store_ms_per_iter",
    "goss_grower_split_scan_ms_per_iter", "goss_grower_unscoped_ms_per_iter",
    "goss_hist_bucket_fill_pct", "goss_setup_bin_s", "goss_setup_compile_s",
    "goss_setup_find_bins_s", "goss_setup_apply_bins_s",
    "goss_setup_backend_compile_s", "goss_setup_cache_miss_programs")
ROWS, FEATURES, HOLD, LEAVES = 20000, 40, 2048, 31

# Readings at this size (CPU, seeds 11 and 2**31 + 11, which read alike),
# the program's and each control's, loss1 / step1_norm / holdout_loss1:
#   program as configured   1.4e-9 / 4.5e-8 / 1.3e-10
#   ref-plain               1.8e-4 / 0.21 / 5.1e-4
#   ref-noamp               2.2e-2 / 0.34 / 1.9e-2
#   ref-uniform             3.5e-4 / 0.054 / 1.8e-4
#   ref-top19               1.1e-5 / 0.020 / 4.2e-4
#   ref-bf16                3.6e-4 / 8.4e-3 / 2.8e-4
#   ref-half                3.2e-4 / 0.042 / 2.8e-4
#   ref-frozen              1.8e-2 / 1.0 / 0 (its trees are the reference's)
LIMITS = {"loss1": 2e-7, "step1_norm": 2e-6, "holdout_loss1": 2e-7,
          "window_compiles": 0}


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


def small_cell():
    cell = spec.Spec().cell(CELL)
    cell["config"] = dict(cell["config"], rows=ROWS, features=FEATURES,
                          valid_rows=HOLD)
    cell["config"]["params"] = dict(cell["config"]["params"],
                                    num_leaves=LEAVES,
                                    min_sum_hessian_in_leaf=20.0)
    cell["limits"] = dict(LIMITS)
    return cell


def drive(variant=None, seed=2**31 + 11, seconds=0.3):
    import jax
    this, result, compared = train_warm.run(
        small_cell(), seed, seconds, False, jax.devices()[0],
        device.peaks_for("TPU v5 lite"), time.perf_counter(), variant)
    return result, this, compared


def test_the_cell_names_the_sampled_configuration_and_its_runner():
    bench = spec.Spec()
    cell = bench.cell(CELL)
    params = cell["config"]["params"]
    assert params["data_sample_strategy"] == "goss"
    assert (params["top_rate"], params["other_rate"]) == (0.2, 0.1)
    assert "bagging_seed" not in params
    assert cell["config"]["defaults_in_force"]["bagging_seed"] == 3
    assert cell["config"]["reduced"] == [] and cell["config"]["sample_draw"]
    assert bench.reference(cell) is gbdt_goss
    mix = cell["traffic"]
    assert (mix["kind"], mix["warm_steps"], mix["checked_steps"]) \
        == ("train_warm", 10, 1)
    # the warm-up is the source's: int(1 / learning_rate) iterations
    assert mix["warm_steps"] == int(1 / params["learning_rate"])
    assert bench.runner(mix["kind"]) is train_warm
    # the numbers one checked step gives, each with its limit
    assert set(cell["limits"]) == set(LIMITS)
    plain, plain_mix = (spec.Spec().cell("bosch-train")[k]
                        for k in ("config", "traffic"))
    for key in ("rows", "valid_rows", "features"):
        assert cell["config"][key] == plain[key]
    assert {k: v for k, v in params.items() if k in plain["params"]} \
        == plain["params"]
    assert mix["data"] == plain_mix["data"]
    assert mix["validate"] is plain_mix["validate"] is True


def test_runner_offers_what_run_py_takes_and_needs_no_chip():
    assert "train_warm" in spec.Spec().runner_kinds()
    assert callable(train_warm.run) and train_warm.SPANS == train.SPANS
    assert set(train_warm.VARIANTS) == {
        "ref-plain", "ref-noamp", "ref-uniform", "ref-top19", "ref-bf16",
        "ref-half", "ref-frozen"}
    assert train_warm.Run is train.Run


def test_reference_imports_nothing_of_the_program():
    with open(gbdt_goss.__file__) as f:
        source = f.read()
    assert "import lightgbm_tpu" not in source
    assert "from lightgbm_tpu" not in source
    assert "top_k(" not in source       # the threshold is a full sort's


def test_program_as_configured_is_correct(monkeypatch):
    """The warm-up is driven and not compared; the step after it is."""
    calls = []
    real = program.Program.update
    monkeypatch.setattr(program.Program, "update",
                        lambda self: (calls.append(1), real(self))[1])
    result, this, compared = drive()
    assert result["correct"] is True and result["failed"] == 0
    assert this.end_to_end["train_iter_s"] > 0
    assert set(compared) == set(LIMITS)
    assert compared["window_compiles"]["value"] == 0
    assert len(calls) == 10 + 1 + result["attempted"]
    assert this.iterations == result["attempted"] >= 1


def test_a_program_that_ignores_the_sampling_is_not_correct(monkeypatch):
    from lightgbm_tpu.boosting.sample_strategy import GOSSStrategy
    monkeypatch.setattr(GOSSStrategy, "bagging",
                        lambda self, it, g, h: (g, h, None))
    result, _, compared = drive(seconds=0)
    assert result["correct"] is False
    assert compared["step1_norm"]["value"] > 1000 * LIMITS["step1_norm"]


def test_a_fault_in_the_warm_up_is_another_cells_to_find(monkeypatch):
    """The reference starts from the program's scores after the warm-up,
    whatever they are: a warm-up at another pace changes nothing compared."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    real = GBDT.train_one_iter

    def slow_start(self, *a, **k):
        self.shrinkage_rate = 0.05 if self.iter < 3 else 0.1
        return real(self, *a, **k)

    monkeypatch.setattr(GBDT, "train_one_iter", slow_start)
    result, _, _ = drive(seconds=0)
    assert result["correct"] is True


@pytest.mark.parametrize("variant", ["ref-plain", "ref-noamp"])
def test_control_is_not_correct(variant):
    result, _, compared = drive(variant, seconds=0)
    assert result["correct"] is False
    over = [k for k, c in compared.items() if c["value"] > c["limit"]]
    assert sorted(over) == ["holdout_loss1", "loss1", "step1_norm"]


def test_every_control_of_a_list_gets_its_verdict(capsys):
    """``--variant a,b``: the program and each control are judged by the
    cell's limits on a line each; the result line judges the last."""
    result, _, _ = drive("ref-plain,ref-frozen", seconds=0)
    out = capsys.readouterr().out
    assert "verdict on the program: correct True; over its limit: []" in out
    for control in ("ref-plain", "ref-frozen"):
        assert "verdict on %s: correct False; over its limit: ['loss1'" \
            % control in out
    assert result["correct"] is False


def test_reference_bag_is_the_published_one():
    """Top 20% by |g * h| whole, an eighth of the rest drawn and amplified
    eightfold, nothing sampled during the warm-up."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import traffic
    cfg = small_cell()
    X, y = traffic.make_table(4000, 8, 11, cfg["traffic"]["data"])
    params = gbdt_goss.Params.from_dict(dict(
        cfg["config"]["defaults_in_force"], **cfg["config"]["params"]))
    ref = gbdt_goss.Reference(X, y, params, start_iteration=10)
    assert (ref.top_k, ref.other_k, ref.warmup) == (800, 400, 10)
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(4000).astype(np.float32))
    h = jnp.asarray(rng.rand(4000).astype(np.float32))
    g2, h2, rows = ref.sample(g, h)
    w = np.abs(np.asarray(g) * np.asarray(h))
    top = w >= np.sort(w)[-800]
    assert top.sum() == 800
    u = np.asarray(jax.random.uniform(
        jax.random.fold_in(jax.random.PRNGKey(3), 10), (4000,)))
    drawn = ~top & (u < 400 / 3200)
    np.testing.assert_array_equal(rows, np.flatnonzero(top | drawn))
    np.testing.assert_array_equal(np.asarray(g2),
                                  np.where(drawn, 8, 1) * np.asarray(g))
    np.testing.assert_array_equal(np.asarray(h2),
                                  np.where(drawn, 8, 1) * np.asarray(h))
    ref.iteration = 9
    assert len(ref.sample(g, h)[2]) == 4000


def test_cell_is_found_from_appended_entries_in_a_new_checkout(tmp_path):
    """The parent's benchmark with this PR's files laid over it and its
    entries appended: the cell, its runner, its reference and its twenty
    readers are found, and what was there is as it was."""
    here = spec.Spec()
    added = {
        "configs/bosch-goss.json", "limits/bosch-train-goss.json",
        "traffic/train-looped-valid-warm10.json", "harness/train_warm.py",
        "reference/gbdt_goss.py", "trace/work_goss.py", "tests/test_goss.py",
    } | {"metrics/%s.py" % name for name in METRICS}
    checkout = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, checkout / "benchmark",
                    ignore=lambda d, names: [
                        n for n in names if n == "__pycache__"
                        or os.path.relpath(os.path.join(d, n),
                                           spec.BENCH_DIR) in added])
    doc = json.loads(json.dumps(here.doc))
    names = {"configs": {"bosch-goss"}, "workloads": {CELL},
             "per_layer": set(METRICS)}
    new = {k: [e for e in doc[k] if e["name"] in names[k]] for k in names}
    assert [len(new[k]) for k in ("configs", "workloads", "per_layer")] \
        == [1, 1, 20]
    # taken out by name, not by position: a later PR appends after them
    for k, entries in new.items():
        doc[k] = [e for e in doc[k] if e not in entries]
    (checkout / "BENCHMARK.json").write_text(json.dumps(doc))
    old = spec.Spec(str(checkout), str(checkout / "benchmark"))
    with pytest.raises(spec.SpecError, match="no workload"):
        old.cell(CELL)
    assert "train_warm" not in old.runner_kinds()
    kinds = old.runner_kinds()
    before = _hashes(checkout / "benchmark")

    for rel in added:
        shutil.copy(os.path.join(spec.BENCH_DIR, rel),
                    checkout / "benchmark" / rel)
    for k, entries in new.items():
        doc[k] = doc[k] + entries
    (checkout / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Spec(str(checkout), str(checkout / "benchmark"))
    assert bench.runner_kinds() == sorted(kinds + ["train_warm"])
    cell = bench.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"]["kind"] == "train_warm"
    runner = bench.runner("train_warm")
    assert callable(runner.run) and runner.VARIANTS and runner.SPANS
    assert hasattr(bench.reference(cell), "Reference")
    assert set(bench.end_to_end(CELL)) == {"train_iter_s", "setup_s"}
    assert sorted(bench.per_layer(CELL)) == sorted(here.per_layer(CELL))
    assert set(METRICS) <= set(bench.per_layer(CELL))
    for name in METRICS:
        read = bench.reader(name)

        class NoTrace:      # an untraced run: nothing to read, no error
            trace = None
            iterations = window_s = busy_s = 0
            tree_counts = []
            phases = {}
        # the program's counters and spans are there whatever the run
        if "_setup_" not in name and not name.endswith("_pct") \
                or name.startswith("device_idle") \
                or name == "goss_setup_compile_s":
            assert read(NoTrace()) is None
    after = _hashes(checkout / "benchmark")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == added
    for other in ("bosch-train", "epsilon-train", "bosch-train-quant"):
        assert bench.per_layer(other) == here.per_layer(other)
        assert not set(bench.per_layer(other)) & set(METRICS)


# --- trace/work_goss.py against hand counts ------------------------------

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
         "int8_ops_per_s": 393e12}


def test_sampling_pass_hand_count():
    # g and h read, g and h written, the indicator written: 20 bytes a row
    assert work_goss.sampling_pass(1000) == {"bytes": 20000, "ops": 6000}


def test_boosting_iteration_adds_the_sampling_to_the_plain_one():
    got = work_goss.boosting_iteration(rows=100, features=10, hist_rows=75)
    plain = work.boosting_iteration(rows=100, features=10, hist_rows=75)
    assert got == {"bytes": 75 * 18 + 1600 + 2000 + 800,
                   "ops": 2 * 75 * 10 + 800 + 600 + 100}
    assert got["bytes"] - plain["bytes"] == 2000


def test_trees_least_seconds_sums_over_the_in_bag_counts():
    counts = [(30, [12, 3, 7]), (31, [2])]
    hist_only = work_goss.trees_least_seconds(counts, 10, PEAKS)
    assert abs(hist_only - (52 + 33) * 18 / 819e9) < 1e-18
    whole = work_goss.trees_least_seconds(counts, 10, PEAKS, rows=100)
    assert abs(whole - hist_only - 2 * (1600 + 2000 + 800) / 819e9) < 1e-18


# --- the readers against a hand-made run ---------------------------------

class _FakeTrace:
    def __init__(self, ops, modules):
        self._ops, self._modules = ops, modules

    def ops(self, ordinal=0):
        return self._ops

    def modules(self, ordinal=0):
        return self._modules


def _fake_run(monkeypatch):
    """Two iterations: per iteration one sampling program of 4 ms (a sort
    of 3 ms and a fusion of 1 ms under ``obs_goss``) and one grower program
    of 100 ms, 70 of them under ``obs_hist_pallas``."""
    from benchmark.metrics import _stages
    from benchmark.trace import scopes, xplane
    ms = 1_000_000
    names, start, dur, tf_op, mods = [], [], [], [], []
    for it in range(2):
        t0 = it * 200 * ms
        mods += [("jit__goss(1)", t0, 4 * ms),
                 ("jit__tree_impl(2)", t0 + 10 * ms, 100 * ms)]
        for name, at, d, stack in (
                ("%sort.1", t0, 3 * ms, "jit(_goss)/obs_goss/top_k"),
                ("%fusion.2", t0 + 3 * ms, 1 * ms,
                 "jit(_goss)/obs_goss/select_n"),
                ("%fusion.3", t0 + 10 * ms, 70 * ms,
                 "jit(_tree_impl)/while/body/obs_compact/obs_hist_pallas/"
                 "hist_kernel"),
                ("%fusion.4", t0 + 80 * ms, 30 * ms,
                 "jit(_tree_impl)/while/body/obs_split_scan/mul")):
            names.append(name)
            start.append(at)
            dur.append(d)
            tf_op.append(stack)
    line = xplane.Line(names, np.asarray(start, np.int64),
                       np.asarray(dur, np.int64))
    modules = xplane.Line([m[0] for m in mods],
                          np.asarray([m[1] for m in mods], np.int64),
                          np.asarray([m[2] for m in mods], np.int64))
    ops = scopes.Ops(line, tf_op, modules)
    monkeypatch.setattr(_stages, "_newest_xplane", lambda: "a.xplane.pb")
    monkeypatch.setattr(scopes, "load_ops", lambda path, ordinal=0: ops)
    run = train.Run(1000, 10, PEAKS)
    run.trace = _FakeTrace(line, modules)
    run.iterations, run.window_s, run.busy_s = 2, 0.4, 0.208
    run.tree_counts = [(300, [120, 30]), (310, [100])]
    # ten unsampled trees before the window, which the reader leaves out
    run.counters_at_window = {"grow/hist_rows_in_bag": 9000,
                              "grow/hist_rows_bucketed": 20000}
    return run


def test_readers_of_the_cell_s_metrics_by_hand(monkeypatch):
    from benchmark.harness import program_obs
    run = _fake_run(monkeypatch)
    run.counters_at_window["grow/hist_rows_needed"] = 12000
    run.warm_steps = 10
    run.phases.update({"bin": 100.0, "compile + first step": 30.0,
                       "warm steps 2 to 10": 27.0})
    monkeypatch.setattr(program_obs, "counter", {
        "grow/hist_rows_in_bag": 9250, "grow/hist_rows_bucketed": 22000,
        "grow/hist_rows_needed": 12900}.get)
    bench = spec.Spec()
    got = {name: bench.reader(name)(run) for name in METRICS}
    assert len(got) == 20
    assert got["goss_sample_ms_per_iter"] == pytest.approx(4.0)
    assert got["goss_sample_roofline"] == pytest.approx(
        100 * (2 * 1000 * 20 / 819e9) / 0.008)
    assert got["goss_grower_ms_per_iter"] == pytest.approx(100.0)
    assert got["goss_hist_ms_per_iter"] == pytest.approx(70.0)
    assert got["device_idle_pct.train_goss"] == pytest.approx(48.0)
    hist_least = (450 + 410) * 18 / 819e9
    assert got["goss_hist_roofline"] == pytest.approx(
        100 * hist_least / 0.140)
    whole = hist_least + 2 * 1000 * (16 + 20 + 8) / 819e9
    assert got["goss_train_step_mfu_pct"] == pytest.approx(
        100 * whole / 0.4)
    assert got["goss_hist_inbag_pct"] == pytest.approx(12.5)
    assert all(0 < got[n] < 100 for n in got if n.endswith(
        ("roofline", "_pct")))
    # the grower's other stages, as the unsampled cells' readers read them
    assert got["goss_grower_split_scan_ms_per_iter"] == pytest.approx(30.0)
    for name in ("partition", "compact", "hist_store", "unscoped"):
        assert got["goss_grower_%s_ms_per_iter" % name] \
            == pytest.approx(0.0, abs=1e-9)
    assert got["goss_hist_bucket_fill_pct"] == pytest.approx(45.0)
    assert got["goss_setup_bin_s"] == 100.0
    assert got["goss_setup_compile_s"] == pytest.approx(27.0)
    stages = ("goss_grower_partition_ms_per_iter",
              "goss_grower_compact_ms_per_iter", "goss_hist_ms_per_iter",
              "goss_grower_hist_store_ms_per_iter",
              "goss_grower_split_scan_ms_per_iter",
              "goss_grower_unscoped_ms_per_iter")
    assert sum(got[n] for n in stages) == pytest.approx(
        got["goss_grower_ms_per_iter"])


@pytest.mark.parametrize("name,plain", [
    ("goss_grower_partition_ms_per_iter", "grower_partition_ms_per_iter"),
    ("goss_grower_compact_ms_per_iter", "grower_compact_ms_per_iter"),
    ("goss_grower_hist_store_ms_per_iter", "grower_hist_store_ms_per_iter"),
    ("goss_grower_split_scan_ms_per_iter", "grower_split_scan_ms_per_iter"),
    ("goss_grower_unscoped_ms_per_iter", "grower_unscoped_ms_per_iter"),
    ("goss_hist_ms_per_iter", "grower_hist_ms_per_iter")])
def test_stage_readers_read_what_the_unsampled_cells_readers_read(
        monkeypatch, name, plain):
    bench = spec.Spec()
    assert bench.reader(name)(_fake_run(monkeypatch)) == pytest.approx(
        bench.reader(plain)(_fake_run(monkeypatch)), abs=1e-9)


def test_readers_find_nothing_in_a_program_without_the_scope(monkeypatch):
    """The parent's programs carry no ``obs_goss`` and count no
    ``grow/hist_rows_in_bag``: those metrics are left out, nothing raises."""
    from benchmark.harness import program_obs
    from benchmark.trace import scopes
    run = _fake_run(monkeypatch)
    ops = scopes.load_ops("a.xplane.pb")
    ops.tf_op[:] = [s.replace("obs_goss/", "") for s in ops.tf_op]
    monkeypatch.setattr(program_obs, "counter", {
        "grow/hist_rows_bucketed": 22000}.get)
    run.counters_at_window["grow/hist_rows_in_bag"] = 0
    bench = spec.Spec()
    for name in ("goss_sample_ms_per_iter", "goss_sample_roofline",
                 "goss_hist_inbag_pct"):
        assert bench.reader(name)(run) is None
    assert bench.reader("goss_grower_ms_per_iter")(run) \
        == pytest.approx(100.0)
