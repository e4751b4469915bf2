"""A later PR adds a configuration, a traffic mix, a cell, a per-layer
metric, the runner of a new kind of mix, a plain reference and a generator
as new files and appended entries, and edits no file that is there."""
import hashlib
import json
import os
import shutil

import pytest

from benchmark.harness import spec


def _hashes(root):
    out = {}
    for base, _, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_every_listed_name_has_its_files():
    bench = spec.Spec()
    for w in bench.doc["workloads"]:
        cell = bench.cell(w["name"])
        assert cell["config"]["rows"] > 0 and cell["limits"]
        assert "setup_s" in bench.end_to_end(w["name"])
        for metric in bench.per_layer(w["name"]):
            assert callable(bench.reader(metric))
    for c in bench.doc["configs"]:
        listed = json.load(open(os.path.join(spec.CHECKOUT, c["file"])))
        assert listed["reduced"] == c["reduced"]


def test_additions_are_new_files_and_appended_entries(tmp_path):
    checkout = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, checkout / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(checkout / "benchmark")
    doc = json.load(open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")))

    bench_dir = checkout / "benchmark"
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps({
        "name": "tiny", "rows": 4096, "features": 8, "reduced": [],
        "reference": "ranker",
        "params": {"objective": "binary"}, "defaults_in_force": {}}))
    (bench_dir / "reference" / "ranker.py").write_text(
        "def init_score(y):\n    return 0.5\n")
    (bench_dir / "generators" / "flat.py").write_text(
        "import numpy as np\n"
        "def make_table(rows, features, seed, data):\n"
        "    return (np.full((rows, features), data['value'], np.float32),\n"
        "            np.zeros(rows, np.float32), {'group': [rows]})\n")
    (bench_dir / "harness" / "echo.py").write_text(
        "VARIANTS = ()\nSPANS = r'^bench::'\n"
        "def run(cell, seed, seconds, trace, device, peaks, t_process,\n"
        "        variant=None):\n"
        "    X, y, extra = cell['spec'].make_table(cell['traffic']['data'])(\n"
        "        4, cell['config']['features'], seed, cell['traffic']['data'])\n"
        "    ref = cell['spec'].reference(cell)\n"
        "    return X, {'init': ref.init_score(y), 'extra': extra}, {}\n")
    (bench_dir / "traffic" / "echo-flat.json").write_text(json.dumps({
        "kind": "echo", "data": {"generator": "flat", "value": 3.0}}))
    (bench_dir / "limits" / "tiny-echo.json").write_text(json.dumps({
        "limits": {}}))
    (bench_dir / "traffic" / "train-other.json").write_text(json.dumps({
        "validate": False,
        "kind": "train", "checked_steps": 2, "holdout_rows": 128,
        "extra_params": {"feature_fraction": 0.5}, "data": {}}))
    (bench_dir / "limits" / "tiny-other.json").write_text(json.dumps({
        "limits": {"loss1": 1e-6}}))
    (bench_dir / "metrics" / "iterations_count.py").write_text(
        "def read(run):\n    return float(run.iterations) or None\n")
    doc["configs"].append({"name": "tiny", "source": "a test",
                           "file": "benchmark/configs/tiny.json",
                           "reduced": [], "why": "a test"})
    doc["workloads"].append({"name": "tiny-other", "config": "tiny",
                             "traffic": "train-other", "chips": 1,
                             "why": "a test"})
    doc["workloads"].append({"name": "tiny-echo", "config": "tiny",
                             "traffic": "echo-flat", "chips": 1,
                             "why": "a test"})
    doc["per_layer"].append({
        "name": "iterations_count", "unit": "iter", "better": "higher",
        "source": "program_counter", "layer": "boosting iteration",
        "moves": "train_iter_s", "workloads": ["tiny-other"]})
    for m in doc["per_layer"]:
        if m["name"] == "device_idle_pct.train":
            m["workloads"].append("tiny-other")
    for m in doc["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny-other")
    (checkout / "BENCHMARK.json").write_text(json.dumps(doc))

    bench = spec.Spec(str(checkout), str(bench_dir))
    cell = bench.cell("tiny-other")
    assert cell["config"]["features"] == 8
    assert cell["traffic"]["extra_params"] == {"feature_fraction": 0.5}
    assert cell["limits"] == {"loss1": 1e-6}
    assert bench.per_layer("tiny-other") == ["device_idle_pct.train",
                                             "iterations_count"]
    assert set(bench.end_to_end("tiny-other")) == {"train_iter_s", "setup_s"}

    class FakeRun:
        iterations = 5
    assert bench.reader("iterations_count")(FakeRun()) == 5.0
    # the runner of the new kind, the reference its configuration names and
    # the generator its mix names, found by those names
    assert bench.runner_kinds() == sorted(
        spec.Spec().runner_kinds() + ["echo"])
    echo = bench.cell("tiny-echo")
    X, result, _ = bench.runner("echo").run(echo, 7, 0, False, None, {}, 0.0)
    assert X.shape == (4, 8) and float(X[0, 0]) == 3.0
    assert result == {"init": 0.5, "extra": {"group": [4]}}
    # a mix that names no generator and a configuration that names no
    # reference keep the ones that were there
    train_cell = bench.cell("bosch-train")
    assert bench.reference(train_cell).__name__.startswith(
        "benchmark.reference.gbdt")
    X, y, extra = bench.make_table(train_cell["traffic"]["data"])(
        64, 8, 7, train_cell["traffic"]["data"])
    assert X.shape == (64, 8) and y.shape == (64,) and extra == {}
    from benchmark.harness import traffic
    X0, y0 = traffic.make_table(64, 8, 7, train_cell["traffic"]["data"])
    assert X.tobytes() == X0.tobytes() and y.tobytes() == y0.tobytes()
    with pytest.raises(spec.SpecError, match="echo.*score.*train"):
        bench.runner("serve-online")
    # the cells that were there see none of it
    assert "iterations_count" not in bench.per_layer("bosch-train")
    after = _hashes(checkout / "benchmark")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/tiny.json", "generators/flat.py", "harness/echo.py",
        "limits/tiny-echo.json", "limits/tiny-other.json",
        "metrics/iterations_count.py", "reference/ranker.py",
        "traffic/echo-flat.json", "traffic/train-other.json"]
