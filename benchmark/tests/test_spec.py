"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as new files and appended entries, and edits no file that is there."""
import hashlib
import json
import os
import shutil

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
        "params": {"objective": "binary"}, "defaults_in_force": {}}))
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
    # the cells that were there see none of it
    assert "iterations_count" not in bench.per_layer("bosch-train")
    after = _hashes(checkout / "benchmark")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/tiny.json", "limits/tiny-other.json",
        "metrics/iterations_count.py", "traffic/train-other.json"]
