"""The scoring cell: its reference against a model text written by hand and
against ``Booster.predict``, the work functions by hand, and ``correct``
true for the program as configured and false for the control, for the
fault and for the timed path broken underneath, at a size a test run can
hold.

The cell is queued (``queued/bosch-score-bulk.json`` says why): the tests
append its entries to a copy of ``BENCHMARK.json`` in a temporary checkout,
as the PR that adds it will, and nothing else differs. The runs skip the
harness's look for a chip and drive the rest of a run on the CPU with the
cell's own limits: the program reads 2e-7 there (``score_max_gap``), the
bfloat16 control 2e-3 and the short forest 0.4.
"""
import json
import os
import time

import numpy as np
import pytest

from benchmark.generators import synth_forest
from benchmark.harness import device, spec
from benchmark.reference import forest_walk
from benchmark.trace import work, work_score

THREE_TREES = """tree
version=v3
num_class=1
num_tree_per_iteration=1
label_index=0
max_feature_idx=2
objective=binary sigmoid:1
feature_names=a b c
feature_infos=[-8:8] [-8:8] [-8:8]
tree_sizes=1 1 1

Tree=0
num_leaves=3
num_cat=0
split_feature=0 1
threshold=0.5 -1.0
decision_type=2 0
left_child=1 -1
right_child=-2 -3
leaf_value=1.0 2.0 4.0
is_linear=0
shrinkage=1

Tree=1
num_leaves=2
num_cat=0
split_feature=2
threshold=0.0
decision_type=2
left_child=-1
right_child=-2
leaf_value=0.25 -0.25
is_linear=0
shrinkage=1

Tree=2
num_leaves=1
num_cat=0
leaf_value=0.125
is_linear=0
shrinkage=1

end of trees
"""
ROWS = np.array([[0.0, -2.0, -1.0],     # left, left; left
                 [0.5, 0.0, 0.0],       # left (equal), right; left (equal)
                 [1.0, 5.0, 3.0]],      # right; right
                dtype=np.float32)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    os.symlink(spec.BENCH_DIR, root / "benchmark")
    with open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(spec.BENCH_DIR, "queued",
                           "bosch-score-bulk.json")) as f:
        queued = json.load(f)
    training = [w["name"] for w in doc["workloads"]]
    for m in doc["end_to_end"]:
        if m["name"] == "train_iter_s":
            m["workloads"] = training
    for key in ("workloads", "end_to_end", "per_layer"):
        doc[key] = doc[key] + queued[key]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return spec.Spec(str(root), spec.BENCH_DIR)


def test_the_queued_entries_make_the_cell(bench):
    assert bench.end_to_end("bosch-score-bulk") == ["setup_s",
                                                    "score_rows_per_s"]
    assert bench.end_to_end("bosch-train") == ["train_iter_s", "setup_s"]
    layers = bench.per_layer("bosch-score-bulk")
    assert "device_idle_pct.score" in layers and "score_step_mfu_pct" in layers
    assert not set(layers) & set(bench.per_layer("bosch-train"))
    for name in layers:
        assert callable(bench.reader(name))
    cell = bench.cell("bosch-score-bulk")
    assert bench.runner(cell["traffic"]["kind"]).__name__ == (
        "benchmark.harness.score")
    assert bench.reference(cell).__name__ == "benchmark.reference.forest_walk"


def test_the_walk_of_a_model_text_written_by_hand():
    forest = forest_walk.Forest.from_model_text(THREE_TREES)
    assert (forest.trees, forest.leaves, forest.features) == (3, 3, 3)
    assert forest.predict_raw(ROWS).tolist() == [1.375, 4.375, 1.875]
    assert forest.hops == 3 + 3 + 2     # the stump has no node to visit
    short = forest_walk.Forest.from_model_text(THREE_TREES,
                                               drop_last_trees=1)
    assert short.predict_raw(ROWS).tolist() == [1.25, 4.25, 1.75]
    import jax.numpy as jnp
    rounded = forest_walk.Forest.from_model_text(
        THREE_TREES.replace("0.125", "0.1"), leaf_dtype=jnp.bfloat16)
    assert rounded.predict_raw(ROWS)[0] == 1.25 + float(jnp.bfloat16(0.1))
    with pytest.raises(ValueError, match="numerical"):
        forest_walk.Forest.from_model_text(
            THREE_TREES.replace("decision_type=2 0", "decision_type=2 1"))


def test_the_synthesised_forest_loads_as_a_model_and_predicts_as_the_walk(
        bench):
    import lightgbm_tpu as lgb
    model = dict(bench.cell("bosch-score-bulk")["traffic"]["model"],
                 trees=12, leaves=31)
    text = synth_forest.make_model_text(10, 5000, 2**31 + 9, model)
    booster = lgb.Booster(model_str=text)
    assert booster.num_trees() == 12
    X = np.random.default_rng(3).standard_normal((512, 10), dtype=np.float32)
    forest = forest_walk.Forest.from_model_text(text)
    np.testing.assert_allclose(
        booster.predict(X, raw_score=True, predict_on_device=False),
        forest.predict_raw(X), rtol=0, atol=1e-15)
    # the structure is the mix's, whatever the seed; the rest is the seed's
    other = synth_forest.make_forest(10, 5000, 5, model)
    again = synth_forest.make_forest(10, 5000, 2**31 + 9, model)
    for a, b in zip(other, again):
        assert a["left"].tolist() == b["left"].tolist()
        assert a["leaf_count"].tolist() == b["leaf_count"].tolist()
    assert any(a["split_feature"].tolist() != b["split_feature"].tolist()
               for a, b in zip(other, again))
    # every feature as often as any other, to within one, and a feature's
    # thresholds all distinct: the quantiser's table has one shape
    feature = np.concatenate([t["split_feature"] for t in again])
    threshold = np.concatenate([t["threshold"] for t in again])
    counts = np.bincount(feature, minlength=10)
    assert counts.max() - counts.min() <= 1
    assert all(len(set(threshold[feature == f])) == counts[f]
               for f in range(10))


def test_the_work_of_a_block_counted_by_hand():
    w = work_score.block(rows=4, features=3, trees=2, leaves=3,
                         hops_per_row=2.5)
    # 4 rows x 3 values x 4 bytes in, 2 trees x (2 nodes x 16 + 3 leaves x
    # 4) bytes of tables, 4 scores x 4 bytes out
    assert w["bytes"] == 48 + 88 + 16
    # 4 rows x 2.5 nodes x (compare + choose), 4 rows x 2 trees additions
    assert w["ops"] == 20 + 8
    peaks = device.peaks_for("TPU v5 lite")
    assert work.least_seconds(w, peaks) == (152 / 819e9, "bytes")


def tiny_cell(bench):
    cell = bench.cell("bosch-score-bulk")
    cell["config"] = dict(cell["config"], rows=20000, features=40)
    mix = cell["traffic"]
    cell["traffic"] = dict(
        mix, block_rows=2048, checked_rows_per_block=1024,
        server=dict(mix["server"], max_batch=2048),
        model=dict(mix["model"], trees=20, leaves=15))
    return cell


@pytest.fixture
def drive(bench):
    return lambda *a, **kw: _drive(bench, *a, **kw)


def _drive(bench, variant=None, seed=2**31 + 11, seconds=0.3):
    import jax
    cell = tiny_cell(bench)
    this, result, compared = cell["spec"].runner("score").run(
        cell, seed, seconds, False, jax.devices()[0],
        device.peaks_for("TPU v5 lite"), time.perf_counter(), variant)
    return this, result, compared


def test_program_as_configured_is_correct(drive):
    this, result, compared = drive()
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == len(this.blocks) >= 2
    assert {b.client for b in this.blocks} == {0, 1}
    rows = sum(b.rows for b in this.blocks)
    assert this.end_to_end["score_rows_per_s"] == rows / this.window_s
    assert this.window_s == max(b.t_answer for b in this.blocks)
    assert set(compared) == {"score_max_gap", "score_rms_gap",
                             "answers_malformed", "window_compiles"}
    assert 0 < compared["score_max_gap"]["value"] < 1e-6


@pytest.mark.parametrize("variant", ["ref-bf16", "ref-short"])
def test_control_and_short_forest_are_not_correct(drive, variant):
    _, result, compared = drive(variant)
    assert result["correct"] is False
    assert compared["score_rms_gap"]["value"] > 10 * compared[
        "score_rms_gap"]["limit"]


def _patched_scores(monkeypatch, alter):
    from lightgbm_tpu.serve.forest import StackedForest
    real = StackedForest.predict_raw_device
    monkeypatch.setattr(StackedForest, "predict_raw_device",
                        lambda self, X, dd=None: alter(real(self, X, dd)))


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        drive, monkeypatch):
    # one row in 16 answered 0.01 too high
    _patched_scores(monkeypatch, lambda out: out.at[::16].add(0.01))
    _, result, compared = drive()
    assert result["correct"] is False and result["failed"] == 0
    assert compared["score_max_gap"]["value"] > compared[
        "score_max_gap"]["limit"]


def test_half_of_a_block_left_out_is_not_correct(drive, monkeypatch):
    _patched_scores(monkeypatch, lambda out: out.at[1::2].set(0.0))
    _, result, compared = drive()
    assert result["correct"] is False
    assert compared["score_rms_gap"]["value"] > 0.1


def test_an_answer_that_is_not_finite_is_malformed(drive, monkeypatch):
    _patched_scores(monkeypatch, lambda out: out.at[3].set(np.nan))
    _, result, compared = drive()
    assert result["correct"] is False
    assert compared["answers_malformed"]["value"] == result["attempted"]


def test_a_block_that_raises_is_a_failed_block(drive, monkeypatch):
    def boom(out):
        raise RuntimeError("no such device")
    from benchmark.harness import program
    real = program.Scorer.submit
    calls = []

    def submit(self, block):
        calls.append(1)
        if len(calls) == 4:     # the second block of the window
            _patched_scores(monkeypatch, boom)
        return real(self, block)

    monkeypatch.setattr(program.Scorer, "submit", submit)
    this, result, _ = drive()
    assert result["failed"] >= 1 and result["correct"] is False
    assert any("no such device" in b.error for b in this.blocks)


def test_a_compilation_inside_the_window_is_a_failed_run(drive, monkeypatch):
    import jax
    import jax.numpy as jnp
    from benchmark.harness import program
    real = program.Scorer.submit
    calls = []

    def submit(self, block):
        calls.append(1)
        if len(calls) == 4:
            jax.jit(lambda v: v * 3)(jnp.ones(13)).block_until_ready()
        return real(self, block)

    monkeypatch.setattr(program.Scorer, "submit", submit)
    _, result, compared = drive()
    assert compared["window_compiles"]["value"] >= 1
    assert result["correct"] is False


def _line(events):
    from benchmark.trace import xplane
    names, start, dur = zip(*events)
    return xplane.Line(list(names), np.asarray(start, np.int64) * 10**6,
                       np.asarray(dur, np.int64) * 10**6)


def test_the_readers_of_the_scoring_layers_on_a_trace_made_by_hand(bench):
    """Two clients, one worker, two blocks answered; times in milliseconds.
    The device runs 300 of each dispatch's 400 ms."""
    from benchmark.harness import score
    from benchmark.trace import xplane
    this = score.Run(1000, 10, device.peaks_for("TPU v5 lite"))
    this.trees, this.leaves, this.hops_per_row = 4, 8, 12.0
    this.window_s = 0.9
    this.blocks = [score.Block(0, 0.0, 0.45, 1000, answer=np.zeros(1000)),
                   score.Block(1, 0.0, 0.9, 1000, answer=np.zeros(1000))]
    program = "jit__stacked_raw_body(123)"
    this.trace = xplane.Trace(
        devices={0: {
            xplane.MODULES_LINE: _line([(program, 100, 300),
                                        (program, 550, 300)]),
            xplane.OPS_LINE: _line([("%while.1", 100, 300),
                                    ("%gather.2", 100, 100),
                                    ("%while.1", 550, 300)])}},
        host={"python": _line([("bench::block", 0, 450)]),
              "python#2": _line([("bench::block", 5, 895)]),
              "python#3": _line([("serve::predict_batch", 50, 400),
                                 ("serve::predict_batch", 500, 400)])})
    this.busy_s = xplane.busy_s(this.trace)
    read = lambda name: bench.reader(name)(this)
    assert this.busy_s == pytest.approx(0.6)
    assert read("device_idle_pct.score") == pytest.approx(100 * 0.3 / 0.9)
    assert read("score_walk_ms_per_block") == pytest.approx(300.0)
    assert read("score_dispatch_ms") == pytest.approx(100.0)
    # submitted at 0 and 5, dispatched at 50 and 500
    assert read("score_queue_wait_ms") == pytest.approx((50 + 495) / 2)
    assert read("score_block_p50_ms") == pytest.approx((450 + 900) / 2)
    least = 2 * work.least_seconds(work_score.block(1000, 10, 4, 8, 12.0),
                                   this.peaks)[0]
    assert read("score_step_mfu_pct") == pytest.approx(100 * least / 0.9)
    assert read("score_walk_roofline") == pytest.approx(100 * least / 0.6)
    assert xplane.idle_gaps(this.trace, score.SPANS) == [
        ["bench::block", pytest.approx(0.15)]]
    # nothing to read, nothing returned: no trace, no answered block
    this.trace, this.blocks = None, []
    for name in bench.per_layer("bosch-score-bulk"):
        if name != "device_idle_pct.score":
            assert read(name) is None, name
