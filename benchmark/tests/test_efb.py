"""The one-hot cell over EFB bundles on the CPU: the cell is found by name
from appended entries and new files alone, its generator lays out the
columns the mix names, the committed runner of kind ``train`` says
``correct`` for the program at a tiny size and refuses the controls and
the two EFB faults that reach the program alone (the zero-bin fix left
out; a bundling budget of 1e-4 where the configuration states 0.0),
``trace/work_efb.py`` agrees with hand counts and the readers with a
hand-made run.
"""
import hashlib
import json
import os
import shutil
import time

import numpy as np
import pytest
import scipy.sparse as sp

from benchmark.generators import onehot_sparse
from benchmark.harness import device, spec, train
from benchmark.reference import gbdt_onehot
from benchmark.trace import scopes, work, work_efb, xplane

CELL, CONFIG = "allstate-train", "allstate-onehot"
ROWS, HOLD, LEAVES = 6000, 1024, 15
SEED = 2**31 + 13
SMALL = {"dense": 3, "categoricals": [["A", 40], ["B", 120], ["C", 60],
                                      ["D", 8], ["E", 50], ["F", 20]],
         "nested": [["B", "E"]], "effects": ["A", "B", "D"]}
FEATURES = 3 + 40 + 120 + 60 + 8 + 50 + 20
METRICS = ("efb_unpack_ms_per_iter", "efb_unpack_roofline",
           "efb_train_step_mfu_pct", "efb_setup_bundle_s")
# Readings at this size (CPU), loss1 / loss2 / step1_norm / change2_norm /
# holdout_loss2; the program's histograms are scatters that add a leaf's
# rows one after another in float32 here (a leaf's hessian sum 1e-5 off,
# bundled or not), where the reference sums in blocks:
#   program as configured    7.6e-7 / 5.7e-7 / 1.4e-5 / 5.6e-6 / 5.1e-7
#   ref-bf16                 1.1e-4 / 1.3e-4 / 2.0e-3 / 1.3e-3 / 1.4e-4
#   ref-half                 1.3e-3 / 3.1e-3 / 6.0e-3 / 1.6e-3 / 5.1e-4
#   ref-frozen               0.057 / 0.11 / 1.0 / 1.0 / 2.7e-3
LIMITS = {"loss1": 1e-5, "loss2": 1e-5, "step1_norm": 2e-4,
          "change2_norm": 2e-4, "holdout_loss2": 1e-5, "window_compiles": 0}


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
    cell["traffic"] = dict(
        cell["traffic"], data=dict(cell["traffic"]["data"], **SMALL),
        extra_params={"tree_learner": "data", "mesh_shape": "data=1"})
    cell["config"] = dict(cell["config"], rows=ROWS, features=FEATURES,
                          valid_rows=HOLD)
    cell["config"]["params"] = dict(cell["config"]["params"],
                                    num_leaves=LEAVES,
                                    min_sum_hessian_in_leaf=5.0,
                                    bin_construct_sample_cnt=2000)
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


def test_the_cell_names_the_configuration_its_mix_and_limits():
    bench = spec.Spec()
    cell = bench.cell(CELL)
    cfg = cell["config"]
    assert (cfg["rows"], cfg["valid_rows"], cfg["features"]) \
        == (1015358, 83334, 4228)
    assert cfg["params"]["num_leaves"] == 255
    assert cfg["params"]["max_bin"] == 255
    assert cfg["params"]["min_data_in_leaf"] == 0
    assert cfg["defaults_in_force"]["max_conflict_rate"] == 0.0
    assert cfg["defaults_in_force"]["enable_bundle"] is True
    assert cfg["reduced"] == ["rows", "valid_rows"]
    entry = [c for c in bench.doc["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"]
    assert bench.reference(cell) is gbdt_onehot
    assert bench.runner(cell["traffic"]["kind"]) is train
    assert cell["traffic"]["checked_steps"] == 2 and cell["traffic"][
        "validate"]
    dense, cats, total = onehot_sparse.layout(cell["traffic"]["data"])
    assert (dense, len(cats), total) == (12, 19, 4228)
    assert set(cell["limits"]) == set(LIMITS)


def test_generator_lays_out_one_level_of_every_column_a_row():
    data = dict(spec.Spec().cell(CELL)["traffic"]["data"], **SMALL)
    X, y, extra = onehot_sparse.make_table(5000, FEATURES, 1, data)
    assert extra == {} and X.shape == (5000, FEATURES)
    assert (np.diff(X.indptr) == 3 + 6).all()
    X2, y2, _ = onehot_sparse.make_table(5000, FEATURES, 2**31 + 5, data)
    assert (X != X2).nnz == 0 and (y == y2).all()   # the seed moves nothing
    assert 0.45 < y.mean() < 0.55
    _, cats, _ = onehot_sparse.layout(data)
    first = {name: (levels, col) for name, levels, col in cats}
    level = {}
    dense = X[:, 3:].tocsr()
    for name, (levels, col) in first.items():
        block = X[:, col:col + levels]
        assert (np.diff(block.tocsr().indptr) == 1).all()
        level[name] = block.tocsr().indices
        counts = np.bincount(level[name], minlength=levels)
        assert counts[0] == counts.max()        # Zipf: the first is first
    assert (level["E"] == level["B"] % 50).all()
    assert dense.nnz == 5000 * 6
    with pytest.raises(ValueError, match="lays out"):
        onehot_sparse.make_table(10, FEATURES + 1, 1, data)


def test_reference_imports_nothing_of_the_program():
    for module in (gbdt_onehot, onehot_sparse):
        with open(module.__file__) as f:
            source = f.read()
        assert "import lightgbm_tpu" not in source
        assert "from lightgbm_tpu" not in source


def test_find_bundles_by_hand():
    # candidates 0 and 1 meet in row 3, 2 meets nobody; budget 0 then 1
    rows = [np.array([0, 1, 3]), np.array([3, 4]), np.array([5])]
    assert gbdt_onehot.find_bundles(rows, np.full(3, 2), 10, 256, 0.0) \
        == [[0, 2], [1]]
    assert gbdt_onehot.find_bundles(rows, np.full(3, 2), 10, 256, 0.1) \
        == [[0, 1, 2]]
    # the bins: 1 + one a member, at most 2 here
    assert gbdt_onehot.find_bundles(rows, np.full(3, 2), 10, 2, 0.0) \
        == [[0], [1], [2]]


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
    assert _over(compared) == set(LIMITS) - {"window_compiles"}


def test_the_zero_bin_fix_left_out_is_refused(monkeypatch):
    """The other EFB fault that reaches the program alone: a bundled
    feature's zero bin left at zero where it is the leaf's total less its
    other bins."""
    import jax.numpy as jnp
    from lightgbm_tpu.parallel import data_parallel
    unpack = data_parallel.unpack_bundle_histogram

    def without_fix(bh, group_of, first_bin, num_bins, zero_fix, zero_bins,
                    totals, B):
        hist = unpack(bh, group_of, first_bin, num_bins, zero_fix,
                      zero_bins, totals, B)
        at_zero = zero_fix[:, None] & (
            jnp.arange(B, dtype=jnp.int32)[None, :] == zero_bins[:, None])
        return jnp.where(at_zero[..., None], jnp.zeros((), hist.dtype), hist)

    monkeypatch.setattr(data_parallel, "unpack_bundle_histogram",
                        without_fix)
    result, _, compared = drive()
    assert result["correct"] is False
    assert _over(compared) == set(LIMITS) - {"window_compiles"}


def _met_table(meet: int, n: int = 20000, hold: int = 2000):
    """Two dense columns and two indicators, each held in 3,000 of ``n``
    training rows, that meet in ``meet`` of them; every row is in the
    bundling sample. Two dense columns: with one, ``reference/binning.py``
    sorts a sample that is the whole table in place (``np.ascontiguousarray``
    of a one-column transpose is a view)."""
    rng = np.random.RandomState(7)
    N = n + hold
    X = np.zeros((N, 4), np.float32)
    X[:, 0], X[:, 3] = rng.randn(N), rng.randn(N)
    a, b = np.zeros(N, bool), np.zeros(N, bool)
    rows = rng.permutation(n)
    a[rows[:3000]] = True
    b[rows[3000:6000]] = True
    b[rows[:meet]] = True
    held = n + rng.permutation(hold)
    a[held[:300]] = True
    b[held[300:600]] = True
    X[:, 1], X[:, 2] = a, b
    y = (X[:, 0] + 1.5 * a - 1.5 * b + 0.5 * rng.randn(N) > 0).astype(
        np.float32)
    X = sp.csr_matrix(X)
    return X[:n], y[:n], X[n:], y[n:]


@pytest.mark.parametrize("rate, correct", [(0.0, True), (1e-4, False)])
def test_a_conflict_budget_the_configuration_does_not_state_is_refused(
        rate, correct, monkeypatch):
    """The EFB fault that reaches the program alone: the bundling budget
    (``lightgbm_tpu.io.efb.MAX_CONFLICT_RATE``) patched to 1e-4, the
    former hard-coded one, lets the two indicators share a
    bundle over the 2 sampled rows in which they meet, 1e-4 of 20,000,
    and those rows lose the lower one; the reference holds the stated
    0.0. Readings here (CPU), 0.0 / 1e-4: loss1 6.1e-6 / 2.7e-5, loss2
    4.4e-6 / 5.5e-5, holdout_loss2 3.2e-6 / 2.2e-5."""
    from benchmark.harness import check, program, program_obs
    from lightgbm_tpu.io import efb
    X, y, X_hold, y_hold = _met_table(meet=2)
    cell = small_cell()
    cfg = cell["config"]
    params = dict(cfg["params"], **cell["traffic"]["extra_params"])
    params["bin_construct_sample_cnt"] = X.shape[0]
    monkeypatch.setattr(efb, "MAX_CONFLICT_RATE", rate)
    prog = program.Program(params)
    before = program_obs.counter("efb/conflict_rows") or 0
    prog.bin(X, y, X_hold, y_hold)
    assert (program_obs.counter("efb/conflict_rows") or 0) - before \
        == (2 if rate else 0)
    prog.build()
    got = []
    for _ in range(2):
        prog.update()
        got.append(prog.scores())
    got_hold = prog.predict_raw(X_hold, 2)
    prog.free()
    ref_params = dict(cfg["defaults_in_force"], **cfg["params"])
    ref_params["bin_construct_sample_cnt"] = X.shape[0]
    ref_scores, ref_hold, ref = train._reference_scores(
        gbdt_onehot, None, X, y, ref_params, 2, X_hold)
    assert ref.conflict_rows == 0
    numbers = check.compare(gbdt_onehot.loss, y, gbdt_onehot.init_score(y),
                            got, ref_scores, y_hold, got_hold, ref_hold)
    numbers["window_compiles"] = 0
    assert check.judge(numbers, LIMITS)[0] is correct


def test_cell_is_found_by_name_from_appended_entries_in_a_new_checkout(
        tmp_path):
    """The parent's benchmark with this PR's files laid over it and its
    entries appended: the cell, its reference and its readers are found,
    and what was there is as it was."""
    here = spec.Spec()
    new = {"configs": [e for e in here.doc["configs"]
                       if e["name"] == CONFIG],
           "workloads": [e for e in here.doc["workloads"]
                         if e["name"] == CELL],
           "per_layer": [e for e in here.doc["per_layer"]
                         if e["name"] in METRICS]}
    assert [len(new[k]) for k in ("configs", "workloads", "per_layer")] \
        == [1, 1, len(METRICS)]
    added = {
        "configs/allstate-onehot.json", "limits/allstate-train.json",
        "traffic/train-looped-valid-onehot.json",
        "generators/onehot_sparse.py", "reference/gbdt_onehot.py",
        "trace/work_efb.py", "metrics/_efb.py", "tests/test_efb.py",
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
    assert hasattr(bench.reference(cell), "Reference")
    assert hasattr(bench.generator(cell["traffic"]["data"]), "make_table")
    assert set(bench.end_to_end(CELL)) == {"train_iter_s", "setup_s"}
    assert sorted(bench.per_layer(CELL)) == sorted(METRICS)

    class NoTrace:      # an untraced run: nothing to read, no error
        trace = None
        iterations = window_s = busy_s = 0
        tree_counts = []
        phases = {}
    for name in METRICS:
        if name != "efb_setup_bundle_s":
            assert bench.reader(name)(NoTrace()) is None
    after = _hashes(checkout / "benchmark")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == added
    for other in (w["name"] for w in old.doc["workloads"]):
        assert bench.per_layer(other) == here.per_layer(other)
        assert not set(bench.per_layer(other)) & set(METRICS)


# --- trace/work_efb.py against hand counts --------------------------------

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
         "int8_ops_per_s": 393e12}


def test_work_of_a_window_by_hand():
    # two trees of 1,000 rows, each two splits whose smaller children hold
    # 300 and 100 rows, in 10 bundle columns; the six unpacks made 6 x 640
    # (feature, bin) pairs from 6 x 320 (bundle, bin) pairs
    counts = [(1000, [300, 100])] * 2
    unpack, hist, grads, score, *second = work_efb.step(
        counts, 1000, 10, 6 * 640, 6 * 320)
    assert unpack == {"bytes": 16 * 6 * (640 + 320), "ops": 4 * 6 * 640}
    assert hist == {"bytes": 1400 * (10 + 8), "ops": 2 * 1400 * 10}
    assert grads == work.gradient_pass(1000)
    assert score == work.score_pass(1000)
    assert second == [hist, grads, score]


# --- the readers against a hand-made run ----------------------------------

class _FakeTrace:
    def __init__(self, ops):
        self._ops = ops

    def ops(self, ordinal=0):
        return self._ops


# the bundles bundling found, at set-up; moved in the window: 15 unpacks
# (5 trees of the root and two splits), each making 640 (feature, bin)
# pairs from 320 (bundle, bin) pairs
AT_WINDOW = {"efb/groups": 10, "efb/unpacks": 255,
             "efb/unpacked_entries": 255 * 640,
             "efb/bundle_entries": 255 * 320}
COUNTERS = {"efb/groups": 10, "efb/unpacks": 270,
            "efb/unpacked_entries": 270 * 640,
            "efb/bundle_entries": 270 * 320}


def _fake_run(monkeypatch, scoped=True):
    """Five iterations of 200 ms: per iteration the histogram kernel
    (40 ms), the unpack (6 ms: a row gather of 4 and selects of 2), the
    split scan (10 ms) and the compaction (44 ms)."""
    from benchmark.metrics import _stages
    ms = 1_000_000
    names, start, dur, tf_op = [], [], [], []
    unpack = "obs_unpack/" if scoped else ""
    for it in range(5):
        t0 = it * 200 * ms
        for name, at, d, stack in (
                ("%hist_kernel.3", t0, 40 * ms, "jit(_tree_impl)/while/"
                 "body/obs_compact/obs_hist_pallas/hist_kernel"),
                ("%gather.1", t0 + 40 * ms, 4 * ms,
                 "jit(_tree_impl)/while/body/%sjit(_take)/gather" % unpack),
                ("%fusion.2", t0 + 44 * ms, 2 * ms,
                 "jit(_tree_impl)/while/body/%sselect_n" % unpack),
                ("%fusion.4", t0 + 46 * ms, 10 * ms,
                 "jit(_tree_impl)/while/body/obs_split_scan/reduce"),
                ("%fusion.5", t0 + 56 * ms, 44 * ms,
                 "jit(_tree_impl)/while/body/obs_compact/scatter")):
            names.append(name)
            start.append(at)
            dur.append(d)
            tf_op.append(stack)
    line = xplane.Line(names, np.asarray(start, np.int64),
                       np.asarray(dur, np.int64))
    modules = xplane.Line(["jit__tree_impl(2)"] * 5,
                          np.arange(5, dtype=np.int64) * 200 * ms,
                          np.full(5, 100 * ms, np.int64))
    ops = scopes.Ops(line, tf_op, modules)
    monkeypatch.setattr(_stages, "_newest_xplane", lambda: "a.xplane.pb")
    monkeypatch.setattr(scopes, "load_ops", lambda path, ordinal=0: ops)
    run = train.Run(1000, 40, PEAKS)
    run.trace = _FakeTrace(line)
    run.iterations, run.window_s = 5, 1.0
    run.tree_counts = [(1000, [300, 100])] * 5
    run.counters_at_window = dict(AT_WINDOW)
    return run


def test_readers_of_the_cell_s_metrics_by_hand(monkeypatch):
    from benchmark.harness import program_obs
    run = _fake_run(monkeypatch)
    monkeypatch.setattr(program_obs, "counter", COUNTERS.get)
    monkeypatch.setattr(program_obs, "stage_total", {
        "io::efb_bundle": 1.5, "io::find_bins": 2.5}.get)
    bench = spec.Spec()
    got = {name: bench.reader(name)(run) for name in METRICS}
    assert got["efb_unpack_ms_per_iter"] == pytest.approx(6.0)
    assert got["efb_setup_bundle_s"] == 1.5
    unpack = work.least_seconds(work_efb.unpack_pass(15 * 640, 15 * 320),
                                PEAKS)[0]
    assert got["efb_unpack_roofline"] == pytest.approx(100 * unpack / 0.03)
    whole = sum(work.least_seconds(p, PEAKS)[0] for p in work_efb.step(
        [(1000, [300, 100])] * 5, 1000, 10, 15 * 640, 15 * 320))
    assert got["efb_train_step_mfu_pct"] == pytest.approx(100 * whole)
    assert all(0 < got[n] < 100 for n in ("efb_unpack_roofline",
                                          "efb_train_step_mfu_pct"))


def test_readers_find_nothing_in_a_program_without_the_scope_or_counters(
        monkeypatch):
    """The parent's programs carry no ``obs_unpack`` and count no
    ``efb/*``: those metrics are left out and nothing raises."""
    from benchmark.harness import program_obs
    run = _fake_run(monkeypatch, scoped=False)
    run.counters_at_window = {}
    monkeypatch.setattr(program_obs, "counter", {}.get)
    bench = spec.Spec()
    for name in ("efb_unpack_ms_per_iter", "efb_unpack_roofline",
                 "efb_train_step_mfu_pct"):
        assert bench.reader(name)(run) is None
