"""The cell with piecewise-linear leaves on the CPU: the cell is found by name
from appended entries and new files alone, the committed runner of kind
``train`` says ``correct`` for the program at a tiny size and refuses the
controls, the reference refuses at once a program that cannot fit the
leaves on the device, ``trace/work_linear.py`` agrees with hand counts and
the eight readers with a hand-made trace.
"""
import hashlib
import importlib.util
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark.harness import device, spec, train
from benchmark.reference import gbdt_linear
from benchmark.trace import work, work_linear

CELL, CONFIG = "bosch-train-linear", "bosch-linear"
ROWS, FEATURES, HOLD, LEAVES = 20000, 40, 2048, 31
SEED = 2**31 + 11
METRICS = ("linear_train_step_mfu_pct", "device_idle_pct.train_linear",
           "linear_grower_ms_per_iter", "linear_fit_ms_per_iter",
           "linear_fit_roofline", "linear_out_ms_per_iter",
           "linear_out_roofline", "linear_leaf_fit_pct")
COUNTED = ("linear_leaf_fit_pct",)
# Readings at this size (CPU), loss1 / loss2 / step1_norm / change2_norm /
# holdout_loss2:
#   program as configured    1.0e-8 / 5.1e-8 / 3.0e-7 / 1.3e-6 / 4.6e-8
#   ref-bf16                 6.5e-5 / 4.3e-5 / 1.9e-3 / 6.9e-4 / 4.1e-5
#   ref-half                 3.0e-6 / 4.6e-4 / 0.024 / 0.015 / 9.7e-4
#   ref-frozen               0.036 / 0.078 / 1.0 / 1.0 / 1.7e-3
LIMITS = {"loss1": 2e-6, "loss2": 2e-6, "step1_norm": 1e-5,
          "change2_norm": 1e-5, "holdout_loss2": 2e-6, "window_compiles": 0}


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


def drive(variant=None, seconds=0.0):
    import jax
    this, result, compared = train.run(
        small_cell(), SEED, seconds, False, jax.devices()[0],
        device.peaks_for("TPU v5 lite"), time.perf_counter(), variant)
    return result, this, compared


def _over(compared):
    return {k for k, c in compared.items() if c["value"] > c["limit"]}


def test_the_cell_names_the_linear_configuration_and_the_accepted_mix():
    bench = spec.Spec()
    cell = bench.cell(CELL)
    params = cell["config"]["params"]
    assert params["linear_tree"] is True and "linear_lambda" not in params
    assert cell["config"]["defaults_in_force"]["linear_lambda"] == 0.0
    assert cell["config"]["reduced"] == []
    assert bench.reference(cell) is gbdt_linear
    assert bench.runner(cell["traffic"]["kind"]) is train
    assert set(cell["limits"]) == {"loss1", "loss2", "step1_norm",
                                   "change2_norm", "holdout_loss2",
                                   "window_compiles"}
    # bosch-train's shape, settings, defaults and traffic, and nothing else
    plain = bench.cell("bosch-train")
    for key in ("rows", "valid_rows", "features", "bin_sample"):
        assert cell["config"][key] == plain["config"][key]
    assert {k: v for k, v in params.items() if k != "linear_tree"} \
        == plain["config"]["params"]
    assert {k: v for k, v in cell["config"]["defaults_in_force"].items()
            if k != "linear_lambda"} == plain["config"]["defaults_in_force"]
    assert cell["traffic"] == plain["traffic"]
    assert cell["traffic"]["checked_steps"] == 2


def test_reference_imports_nothing_of_the_program():
    with open(gbdt_linear.__file__) as f:
        source = f.read()
    assert "import lightgbm_tpu" not in source
    assert "from lightgbm_tpu" not in source


def test_reference_refuses_a_program_without_the_device_fit(
        tmp_path, monkeypatch):
    """A program whose package has no ``ops/linear.py`` is refused when
    the harness asks for the starting score, before it builds anything."""
    y = np.array([0.0, 1.0, 1.0, 0.0])
    assert gbdt_linear.program_lacks() == ""
    assert gbdt_linear.init_score(y) == 0.0
    (tmp_path / "lightgbm_tpu" / "ops").mkdir(parents=True)
    real = importlib.util.find_spec

    def older(name, *a):
        if name == "lightgbm_tpu":
            return importlib.util.spec_from_file_location(
                name, str(tmp_path / "lightgbm_tpu" / "__init__.py"),
                submodule_search_locations=[str(tmp_path / "lightgbm_tpu")])
        return real(name, *a)
    monkeypatch.setattr(gbdt_linear.importlib.util, "find_spec", older)
    assert "no device fit of linear leaves" in gbdt_linear.program_lacks()
    with pytest.raises(RuntimeError, match="bosch-linear: the program has "
                                           "no device fit"):
        gbdt_linear.init_score(y)


def test_program_as_configured_is_correct():
    result, this, compared = drive(seconds=0.3)
    assert result["correct"] is True and result["failed"] == 0
    assert this.end_to_end["train_iter_s"] > 0
    assert set(compared) == set(LIMITS)
    assert compared["window_compiles"]["value"] == 0
    assert this.iterations == result["attempted"] >= 1


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
        "configs/bosch-linear.json", "limits/bosch-train-linear.json",
        "reference/gbdt_linear.py", "trace/work_linear.py",
        "metrics/_linear.py", "tests/test_linear.py",
    } | {"metrics/%s.py" % m["name"] for m in new["per_layer"]}
    checkout = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, checkout / "benchmark",
                    ignore=lambda d, names: [
                        n for n in names if n == "__pycache__"
                        or os.path.relpath(os.path.join(d, n),
                                           spec.BENCH_DIR) in added])
    doc = json.loads(json.dumps(here.doc))
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
    assert cell["traffic"] == old.cell("bosch-train")["traffic"]
    assert hasattr(bench.reference(cell), "Reference")
    assert set(bench.end_to_end(CELL)) == {"train_iter_s", "setup_s"}
    assert sorted(bench.per_layer(CELL)) == sorted(METRICS)

    class NoTrace:      # an untraced run: nothing to read, no error
        trace = None
        iterations = window_s = busy_s = 0
        tree_counts = []
        phases = {}
    for name in METRICS:
        if name not in COUNTED:
            assert bench.reader(name)(NoTrace()) is None
    after = _hashes(checkout / "benchmark")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == added
    for other in (w["name"] for w in old.doc["workloads"]):
        assert bench.per_layer(other) == here.per_layer(other)
        assert not set(bench.per_layer(other)) & set(METRICS)


# --- trace/work_linear.py against hand counts -----------------------------

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
         "int8_ops_per_s": 393e12}

TWO_LEAVES = """tree
version=v3

Tree=0
num_leaves=2
num_cat=0
split_feature=3
leaf_value=0.1 -0.2
leaf_count=30 70
is_linear=1
leaf_const=0.1 -0.2
num_features=0 0
leaf_features=
leaf_coeff=
shrinkage=0.1


Tree=1
num_leaves=2
num_cat=0
split_feature=5
leaf_value=0.1 -0.2
leaf_count=40 60
is_linear=1
leaf_const=0.3 -0.4
num_features=1 0
leaf_features=5
leaf_coeff=0.25
shrinkage=0.1


Tree=2
num_leaves=2
num_cat=0
split_feature=2
leaf_value=0.1 -0.2
leaf_count=25 75
is_linear=0
shrinkage=0.1


end of trees
"""


def test_work_of_two_leaf_models_by_hand():
    trees = work_linear.leaves_from_model_text(TWO_LEAVES)
    assert trees == [[(30, 0), (70, 0)], [(40, 1), (60, 0)]]
    fit, out = work_linear.tree_passes(trees[1], valid_rows=50)
    # one leaf of 40 rows fit over one feature: 40 x (4 + 12) bytes,
    # 40 x 2 x (1 + 1)^2 operations
    assert fit == {"bytes": 640, "ops": 320}
    # 100 training rows, 40 read one value: 4 x 40 + 12 x 100 bytes, and
    # the 50 validation rows alike: x 1.5
    assert out == {"bytes": int(1.5 * 1360), "ops": int(1.5 * 80)}
    # a tree that kept its constants fits nothing and reads no value
    fit0, out0 = work_linear.tree_passes(trees[0])
    assert fit0 == {"bytes": 0, "ops": 0}
    assert out0 == {"bytes": 1200, "ops": 0}
    s = work_linear.sums([(40, 3), (10, 0), (50, 2)])
    assert s == {"rows": 90, "row_features": 220, "row_features_sq": 560,
                 "all_rows": 100}
    assert work_linear.fit_pass(90, 220, 560) == {
        "bytes": 4 * 220 + 12 * 90,
        "ops": 2 * (40 * 16 + 50 * 9)}


# --- the readers against a hand-made run ----------------------------------

class _FakeTrace:
    def __init__(self, ops, modules):
        self._ops, self._modules = ops, modules

    def ops(self, ordinal=0):
        return self._ops

    def modules(self, ordinal=0):
        return self._modules


COUNTERS = {"linear/trees_fit": 5, "linear/leaves_fit": 900,
            "linear/leaves_const": 100, "linear/rows_fit": 4000,
            "linear/row_features": 20000, "linear/row_features_sq": 90000,
            "linear/valid_rows": 2500}


def _fake_run(monkeypatch, scoped=True):
    """Five iterations: per iteration one grower program of 100 ms and
    one fit program of 10 ms, 6 of them under ``obs_linear_fit`` and 3
    under ``obs_linear_out``, and a validation output of 1 ms under
    ``obs_linear_out``."""
    from benchmark.metrics import _stages
    from benchmark.trace import scopes, xplane
    ms = 1_000_000
    names, start, dur, tf_op, mods = [], [], [], [], []
    fit = "obs_linear_fit/" if scoped else ""
    out = "obs_linear_out/" if scoped else ""
    for it in range(5):
        t0 = it * 200 * ms
        mods += [("jit__tree_impl(2)", t0, 100 * ms),
                 ("jit_linear_fit(3)", t0 + 100 * ms, 10 * ms),
                 ("jit_linear_valid_output(4)", t0 + 120 * ms, 1 * ms)]
        for name, at, d, stack in (
                ("%fusion.3", t0, 100 * ms,
                 "jit(_tree_impl)/while/body/obs_compact/hist_kernel"),
                ("%gather.1", t0 + 100 * ms, 4 * ms,
                 "jit(linear_fit)/%sgather" % fit),
                ("%while.2", t0 + 104 * ms, 2 * ms,
                 "jit(linear_fit)/%swhile" % fit),
                ("%fusion.5", t0 + 106 * ms, 3 * ms,
                 "jit(linear_fit)/%sselect_n" % out),
                ("%copy.6", t0 + 109 * ms, 1 * ms, ""),
                ("%fusion.7", t0 + 120 * ms, 1 * ms,
                 "jit(linear_valid_output)/%sadd" % out)):
            names.append(name)
            start.append(at)
            dur.append(d)
            tf_op.append(stack)
    as_line = lambda rows: xplane.Line(
        [r[0] for r in rows], np.asarray([r[1] for r in rows], np.int64),
        np.asarray([r[2] for r in rows], np.int64))
    line = xplane.Line(names, np.asarray(start, np.int64),
                       np.asarray(dur, np.int64))
    modules = as_line(sorted(mods, key=lambda r: r[1]))
    ops = scopes.Ops(line, tf_op, modules)
    monkeypatch.setattr(_stages, "_newest_xplane", lambda: "a.xplane.pb")
    monkeypatch.setattr(scopes, "load_ops", lambda path, ordinal=0: ops)
    run = train.Run(1000, 10, PEAKS)
    run.trace = _FakeTrace(line, modules)
    run.iterations, run.window_s, run.busy_s = 5, 1.0, 0.555
    run.tree_counts = [(1000, [300, 100])] * 5
    run.counters_at_window = {}
    return run


def test_readers_of_the_cell_s_metrics_by_hand(monkeypatch):
    from benchmark.harness import program_obs
    run = _fake_run(monkeypatch)
    monkeypatch.setattr(program_obs, "counter", COUNTERS.get)
    bench = spec.Spec()
    got = {name: bench.reader(name)(run) for name in METRICS}
    assert len(got) == 8 and None not in got.values()
    assert got["linear_grower_ms_per_iter"] == pytest.approx(100.0)
    assert got["linear_fit_ms_per_iter"] == pytest.approx(6.0)
    assert got["linear_out_ms_per_iter"] == pytest.approx(4.0)
    assert got["device_idle_pct.train_linear"] == pytest.approx(44.5)
    assert got["linear_leaf_fit_pct"] == pytest.approx(90.0)
    fit = work_linear.fit_pass(4000, 20000, 90000)
    out = work_linear.output_pass(5000, 20000, 2500)
    fit_least = work.least_seconds(fit, PEAKS)[0]
    out_least = work.least_seconds(out, PEAKS)[0]
    assert got["linear_fit_roofline"] == pytest.approx(
        100 * fit_least / 0.030)
    assert got["linear_out_roofline"] == pytest.approx(
        100 * out_least / 0.020)
    whole = fit_least + out_least + 5 * (
        1400 * (10 + 8) / 819e9 + 1000 * 16 / 819e9)
    assert got["linear_train_step_mfu_pct"] == pytest.approx(100 * whole)
    assert all(0 < got[n] < 100 for n in got if n.endswith(
        ("roofline", "_pct")))


def test_counters_are_read_since_the_window_opened(monkeypatch):
    from benchmark.harness import program_obs
    run = _fake_run(monkeypatch)
    monkeypatch.setattr(program_obs, "counter", COUNTERS.get)
    run.counters_at_window = {"linear/leaves_fit": 500,
                              "linear/leaves_const": 50}
    assert spec.Spec().reader("linear_leaf_fit_pct")(run) \
        == pytest.approx(100 * 400 / 450)


def test_readers_find_nothing_in_a_program_without_linear_leaves(
        monkeypatch):
    """The parent's programs carry no ``obs_linear_*`` and count no
    ``linear/*``: those metrics are left out and nothing raises; the
    grower and the idle share read as they do."""
    from benchmark.harness import program_obs
    run = _fake_run(monkeypatch, scoped=False)
    monkeypatch.setattr(program_obs, "counter", {}.get)
    bench = spec.Spec()
    for name in ("linear_fit_ms_per_iter", "linear_out_ms_per_iter",
                 "linear_fit_roofline", "linear_out_roofline",
                 "linear_leaf_fit_pct", "linear_train_step_mfu_pct"):
        assert bench.reader(name)(run) is None
    assert bench.reader("linear_grower_ms_per_iter")(run) \
        == pytest.approx(100.0)
    assert bench.reader("device_idle_pct.train_linear")(run) \
        == pytest.approx(44.5)


def test_readers_read_the_committed_trace(monkeypatch):
    """``data/small.xplane.pb``, a real trace of a program with no linear
    leaves: what it lacks reads None, the rest reads a number."""
    from benchmark.metrics import _stages
    from benchmark.trace import xplane
    path = os.path.join(os.path.dirname(__file__), "data",
                        "small.xplane.pb")
    monkeypatch.setattr(_stages, "_newest_xplane", lambda: path)
    run = train.Run(1000, 10, PEAKS)
    run.trace = xplane.load(path)
    run.iterations, run.window_s = 1, 1.0
    run.busy_s = xplane.busy_s(run.trace)
    run.tree_counts = [(1000, [300])]
    run.counters_at_window = {}
    from benchmark.harness import program_obs
    monkeypatch.setattr(program_obs, "counter", {}.get)
    bench = spec.Spec()
    got = {name: bench.reader(name)(run) for name in METRICS}
    assert got["linear_fit_ms_per_iter"] is None
    assert got["linear_out_ms_per_iter"] is None
    assert got["device_idle_pct.train_linear"] is not None
