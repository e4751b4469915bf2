"""The quantized training cell on the CPU: the program grows the trees of
its plain reference (``reference/gbdt_quant.py``) on a small seeded table,
``correct`` comes out false for each control and fault the cell can have,
the cell is found from appended entries and new files alone, and
``trace/work_quant.py`` agrees with hand counts.
"""
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark.harness import check, device, program, spec, train
from benchmark.harness import traffic as table
from benchmark.reference import gbdt_quant
from benchmark.trace import work, work_quant

CELL = "bosch-train-quant"
# the cell's own per-layer entries, taken by name: later entries list the
# cell too
METRICS = ("quant_train_step_mfu_pct", "device_idle_pct.train_quant",
           "quant_discretize_ms_per_iter", "quant_grower_ms_per_iter",
           "quant_hist_ms_per_iter", "quant_hist_roofline",
           "quant_grower_rest_ms_per_iter")
ROWS, FEATURES, HOLD, LEAVES, STEPS = 4096, 24, 1024, 15, 3

# Readings at this size (CPU, seeds 11 and 2**31 + 12, which read alike),
# largest of the program's, smallest of each control's over the numbers:
#   program as configured   3e-8 (step1_norm), losses under 3e-9
#   4 -> 2 bins (ref-bf16)  1.1e-3 loss1, 1.4e-2 step1_norm, 1.6e-3 at least
#   +-127 levels            7e-4 at least (loss2), 1.4e-2 step1_norm
#   to nearest              1.4e-3 at least (loss1), 2e-2 step1_norm
#   ref-half                6e-4 at least (loss2), 0.1 step1_norm; frozen 1.0
LIMITS = {"loss1": 2e-6, "loss2": 2e-6, "loss3": 2e-6, "step1_norm": 1e-5,
          "change3_norm": 1e-5, "holdout_loss3": 2e-6, "window_compiles": 0}


def small_cell():
    cell = spec.Spec().cell(CELL)
    cell["config"] = dict(cell["config"], rows=ROWS, features=FEATURES,
                          valid_rows=HOLD)
    cell["config"]["params"] = dict(cell["config"]["params"],
                                    num_leaves=LEAVES,
                                    min_sum_hessian_in_leaf=5.0)
    cell["traffic"] = dict(cell["traffic"], checked_steps=STEPS)
    cell["limits"] = dict(LIMITS)
    return cell


def drive(variant=None, seed=11, seconds=0.3):
    import jax
    this, result, compared = train.run(
        small_cell(), seed, seconds, False, jax.devices()[0],
        device.peaks_for("TPU v5 lite"), time.perf_counter(), variant)
    return result, this, compared


@pytest.fixture(scope="module")
def small_table():
    cell = small_cell()
    X, y = table.make_table(ROWS + HOLD, FEATURES, 11,
                            cell["traffic"]["data"])
    cfg = cell["config"]
    return (X[:ROWS], y[:ROWS], X[ROWS:], y[ROWS:], dict(cfg["params"]),
            gbdt_quant.Params.from_dict(dict(cfg["defaults_in_force"],
                                             **cfg["params"])))


@pytest.fixture(scope="module")
def reference_run(small_table):
    X, y, X_hold, _, _, params = small_table
    ref = gbdt_quant.Reference(X, y, params)
    return ref, [ref.step() for _ in range(STEPS)], ref.predict_raw(X_hold)


def test_the_cell_names_the_quantized_configuration_and_reference():
    cell = spec.Spec().cell(CELL)
    params = cell["config"]["params"]
    assert params["use_quantized_grad"] is True
    assert params["num_grad_quant_bins"] == 4
    assert params["stochastic_rounding"] is True
    assert params["quant_train_renew_leaf"] is False
    assert "seed" not in params
    assert cell["spec"].reference(cell) is gbdt_quant
    plain = spec.Spec().cell("bosch-train")["config"]
    for key in ("rows", "valid_rows", "features", "defaults_in_force"):
        assert cell["config"][key] == plain[key]
    assert {k: v for k, v in params.items() if k in plain["params"]} \
        == plain["params"]


def test_reference_imports_nothing_of_the_program():
    with open(gbdt_quant.__file__) as f:
        source = f.read()
    assert "import lightgbm_tpu" not in source
    assert "from lightgbm_tpu" not in source


def test_program_grows_the_reference_trees(small_table, reference_run):
    """Same features and thresholds, split by split, and the training
    rows' scores after every step within the float32 cells' tolerance."""
    X, y, _, _, params, _ = small_table
    ref, ref_scores, _ = reference_run
    prog = program.Program(params)
    prog.bin(X, y)
    prog.build()
    for k in range(STEPS):
        prog.update()
        tree, want = prog.booster.inner.models[k], ref.trees[k]
        n = len(want.leaf)
        assert n == LEAVES - 1 and tree.num_leaves == LEAVES
        assert list(tree.split_feature[:n]) == want.feature
        assert list(tree.threshold_in_bin[:n]) == want.thr_bin
        # the program folds the score boosting starts from into tree 1,
        # and takes a right child's float32 sums from its parent's less
        # the left child's, where the reference scales integer sums
        folded = ref.init if k == 0 else 0.0
        np.testing.assert_allclose(tree.leaf_value[:LEAVES] - folded,
                                   want.value, rtol=2e-5, atol=1e-8)
        np.testing.assert_allclose(prog.scores(), ref_scores[k], rtol=0,
                                   atol=2e-6)


def test_reference_rows_are_integers_of_the_published_levels(small_table):
    X, y, _, _, _, params = small_table
    ref = gbdt_quant.Reference(X, y, params)
    g = (0.5 - y).astype(np.float32)
    h = np.full(len(y), 0.25, np.float32)
    q, scales = ref.discretize(g, h)
    q = np.asarray(q)
    assert q.dtype == np.int8
    assert set(np.unique(q[:, 0])) <= {-2, -1, 0, 1, 2}
    assert set(np.unique(q[:, 1])) <= {0, 1, 2, 3, 4}
    np.testing.assert_allclose(np.asarray(scales), [0.25, 0.0625])
    # the draw of tree 1 is the configuration's rounding_draw
    import jax
    u = np.asarray(jax.random.uniform(
        jax.random.fold_in(jax.random.PRNGKey(0), 1), (len(y), 2)))
    np.testing.assert_array_equal(
        q[:, 0], np.floor(g / np.float32(0.25) + u[:, 0]).astype(np.int8))


def test_program_as_configured_is_correct():
    result, this, compared = drive(seed=2**31 + 12)
    assert result["correct"] is True and result["failed"] == 0
    assert this.end_to_end["train_iter_s"] > 0
    assert set(compared) == set(LIMITS)
    assert compared["window_compiles"]["value"] == 0


@pytest.mark.parametrize("variant", ["ref-bf16", "ref-half", "ref-frozen"])
def test_runner_control_is_not_correct(variant):
    """``ref-bf16`` in this cell is the reference with half the levels
    (2 bins): the nearest precision below the configuration's."""
    result, _, compared = drive(variant, seconds=0)
    assert result["correct"] is False
    over = [k for k, c in compared.items() if c["value"] > c["limit"]]
    assert len(over) >= 2, compared


@pytest.mark.parametrize("control", [
    {"halve_levels": True}, {"symmetric_qmax": 127}, {"nearest": True}],
    ids=["2-bins", "pm127", "nearest"])
def test_reference_control_is_not_correct(small_table, reference_run,
                                          control):
    """The reference's own controls in the program's place: other levels
    and another rounding are other models, which the limits refuse."""
    X, y, X_hold, y_hold, _, params = small_table
    _, ref_scores, ref_hold = reference_run
    other = gbdt_quant.Reference(X, y, params, **control)
    got = [other.step() for _ in range(STEPS)]
    numbers = check.compare(gbdt_quant.loss, y, other.init, got, ref_scores,
                            y_hold, other.predict_raw(X_hold), ref_hold)
    numbers["window_compiles"] = 0
    correct, compared = check.judge(numbers, LIMITS)
    assert correct is False
    assert numbers["loss1"] > 10 * LIMITS["loss1"], compared
    assert numbers["step1_norm"] > 10 * LIMITS["step1_norm"], compared


def test_lower_precision_control_is_the_halved_levels(small_table):
    import jax.numpy as jnp
    X, y, _, _, _, params = small_table
    ref = gbdt_quant.Reference(X[:256], y[:256], params,
                               gh_dtype=jnp.bfloat16)
    assert ref.levels == (1, 2, 0)
    assert gbdt_quant.Reference(X[:256], y[:256], params).levels == (2, 4, 0)


def test_a_compilation_inside_the_window_is_a_failed_run(monkeypatch):
    import jax
    import jax.numpy as jnp
    real = program.Program.update
    calls = []

    def update(self):
        calls.append(1)
        if len(calls) > STEPS:  # inside the window: a shape never seen
            jax.jit(lambda v: v * 3)(jnp.ones(len(calls) + 11)) \
                .block_until_ready()
        real(self)

    monkeypatch.setattr(program.Program, "update", update)
    result, _, compared = drive(seconds=0.2)
    assert compared["window_compiles"]["value"] >= 1
    assert result["correct"] is False


def test_cell_is_found_from_appended_entries_in_a_new_checkout(tmp_path):
    """The parent's benchmark with this PR's files laid over it and its
    entries appended: the cell, its reference and its seven readers are
    found, and what was there is as it was."""
    here = spec.Spec()
    added = {
        "configs/bosch-quant.json", "limits/bosch-train-quant.json",
        "reference/gbdt_quant.py", "trace/work_quant.py",
        "metrics/_quant.py", "tests/test_quant.py",
    } | {"metrics/%s.py" % name for name in METRICS}
    checkout = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, checkout / "benchmark",
                    ignore=lambda d, names: [
                        n for n in names if n == "__pycache__"
                        or os.path.relpath(os.path.join(d, n),
                                           spec.BENCH_DIR) in added])
    doc = json.loads(json.dumps(here.doc))
    names = {"configs": {"bosch-quant"}, "workloads": {CELL},
             "per_layer": set(METRICS)}
    new = {k: [e for e in doc[k] if e["name"] in names[k]] for k in names}
    assert [len(new[k]) for k in ("configs", "workloads", "per_layer")] \
        == [1, 1, 7]
    # taken out by name, not by position: later PRs appended after them
    for k, entries in new.items():
        doc[k] = [e for e in doc[k] if e not in entries]
    (checkout / "BENCHMARK.json").write_text(json.dumps(doc))
    old = spec.Spec(str(checkout), str(checkout / "benchmark"))
    with pytest.raises(spec.SpecError, match="no workload"):
        old.cell(CELL)

    for rel in added:
        shutil.copy(os.path.join(spec.BENCH_DIR, rel),
                    checkout / "benchmark" / rel)
    for k, entries in new.items():
        doc[k] = doc[k] + entries
    (checkout / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Spec(str(checkout), str(checkout / "benchmark"))
    cell = bench.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"]["kind"] == "train"
    assert cell["config"]["reference"] == "gbdt_quant"
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
        assert read(NoTrace()) is None
    assert bench.per_layer("bosch-train") == here.per_layer("bosch-train")


# --- trace/work_quant.py against hand counts -----------------------------

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
         "int8_ops_per_s": 393e12}


def test_histogram_pass_hand_count():
    # 1000 rows x 10 features: 10 bin bytes + 2 gradient bytes a row, 2
    # integer adds per row and feature
    assert work_quant.histogram_pass(1000, 10) == {"bytes": 12000,
                                                   "int8_ops": 20000}
    assert work.histogram_pass(1000, 10)["bytes"] - 12000 == 6 * 1000


def test_gradient_pass_writes_two_bytes_a_row():
    assert work_quant.gradient_pass(100) == {"bytes": 1000, "ops": 1400}
    assert work.gradient_pass(100)["bytes"] - 1000 == 6 * 100


def test_boosting_iteration_adds_its_parts():
    w = work_quant.boosting_iteration(rows=100, features=10, hist_rows=250)
    assert w == {"bytes": 250 * 12 + 1000 + 800, "ops": 1400 + 100,
                 "int8_ops": 2 * 250 * 10}


def test_least_seconds_holds_integer_work_against_the_int8_peak():
    s, bound = work_quant.least_seconds({"bytes": 819e9, "int8_ops": 1e9},
                                        PEAKS)
    assert bound == "bytes" and abs(s - 1.0) < 1e-12
    s, bound = work_quant.least_seconds({"bytes": 1, "int8_ops": 393e12},
                                        PEAKS)
    assert bound == "ops" and abs(s - 1.0) < 1e-12
    s, _ = work_quant.least_seconds(
        {"bytes": 1, "int8_ops": 393e12, "ops": 197e12}, PEAKS)
    assert abs(s - 2.0) < 1e-12


def test_trees_least_seconds_sums_over_the_trees():
    counts = [(100, [40, 10, 25]), (100, [7])]
    hist_only = work_quant.trees_least_seconds(counts, 10, PEAKS)
    assert abs(hist_only - (175 + 107) * 12 / 819e9) < 1e-18
    whole = work_quant.trees_least_seconds(counts, 10, PEAKS, rows=100)
    assert abs(whole - hist_only - 2 * 1800 / 819e9) < 1e-18


# --- the readers against a hand-made run ---------------------------------

class _FakeTrace:
    def __init__(self, ops, modules):
        self._ops, self._modules = ops, modules

    def ops(self, ordinal=0):
        return self._ops

    def modules(self, ordinal=0):
        return self._modules


def _fake_run(monkeypatch):
    """Two iterations: per iteration one discretizer program of 3 ms (a
    while of 3 ms with 2 ms of body under ``obs_quantize``) and one grower
    program of 100 ms, 40 of them under ``obs_hist_einsum``."""
    from benchmark.metrics import _stages
    from benchmark.trace import scopes, xplane
    ms = 1_000_000
    names, start, dur, tf_op, mods = [], [], [], [], []
    for it in range(2):
        t0 = it * 200 * ms
        mods += [("jit__quantize_gh(1)", t0, 3 * ms),
                 ("jit__tree_impl(2)", t0 + 10 * ms, 100 * ms)]
        for name, at, d, stack in (
                ("%while.1", t0, 3 * ms, "jit(_quantize_gh)/obs_quantize/while"),
                ("%fusion.2", t0, 2 * ms,
                 "jit(_quantize_gh)/obs_quantize/while/body/floor"),
                ("%fusion.3", t0 + 10 * ms, 40 * ms,
                 "jit(_tree_impl)/while/body/obs_compact/obs_hist_einsum/dot"),
                ("%fusion.4", t0 + 50 * ms, 60 * ms,
                 "jit(_tree_impl)/while/body/obs_split_scan/obs_dequantize/"
                 "mul")):
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
    run.iterations, run.window_s, run.busy_s = 2, 0.4, 0.206
    run.tree_counts = [(1000, [400, 100]), (1000, [300])]
    return run


def test_readers_of_the_seven_metrics_by_hand(monkeypatch):
    run = _fake_run(monkeypatch)
    bench = spec.Spec()
    got = {name: bench.reader(name)(run) for name in METRICS}
    assert got["quant_discretize_ms_per_iter"] == pytest.approx(3.0)
    assert got["quant_grower_ms_per_iter"] == pytest.approx(100.0)
    assert got["quant_hist_ms_per_iter"] == pytest.approx(40.0)
    # the dequantization counts with the scan around it, in the rest
    assert got["quant_grower_rest_ms_per_iter"] == pytest.approx(60.0)
    assert got["quant_hist_ms_per_iter"] \
        + got["quant_grower_rest_ms_per_iter"] \
        == pytest.approx(got["quant_grower_ms_per_iter"])
    assert got["device_idle_pct.train_quant"] == pytest.approx(48.5)
    hist_least = (1500 + 1300) * 12 / 819e9
    assert got["quant_hist_roofline"] == pytest.approx(
        100 * hist_least / 0.080)
    whole = hist_least + 2 * (1000 * 10 + 1000 * 8) / 819e9
    assert got["quant_train_step_mfu_pct"] == pytest.approx(
        100 * whole / 0.4)


def test_readers_find_nothing_in_a_program_without_the_scope(monkeypatch):
    """The parent's programs carry no ``obs_quantize``: the metric is left
    out, nothing raises."""
    from benchmark.trace import scopes
    run = _fake_run(monkeypatch)
    ops = scopes.load_ops("a.xplane.pb")
    ops.tf_op[:] = [s.replace("obs_quantize/", "") for s in ops.tf_op]
    assert spec.Spec().reader("quant_discretize_ms_per_iter")(run) is None
    assert spec.Spec().reader("quant_grower_ms_per_iter")(run) \
        == pytest.approx(100.0)
