"""Where the benchmark's data files are, found by name.

A later PR adds a configuration (``configs/<name>.json``), a traffic mix
(``traffic/<name>.json``), a cell's limits (``limits/<cell>.json``), a
per-layer metric (``metrics/<name>.py``), the runner of a new kind of mix
(``harness/<kind>.py``), a plain reference (``reference/<name>.py``) or a
generator (``generators/<name>.py``) as a new file and appends an entry to
``BENCHMARK.json``; nothing here names any of them.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
import zlib

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """``BENCHMARK.json`` or a file it names is missing or inconsistent."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError("cannot read %s: %s" % (path, e)) from e


class Spec:
    """``BENCHMARK.json`` of one checkout and the files under ``bench_dir``."""

    def __init__(self, checkout: str = CHECKOUT, bench_dir: str = BENCH_DIR):
        self.checkout = checkout
        self.bench_dir = bench_dir
        self.doc = _load_json(os.path.join(checkout, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        """The cell's entry with its configuration and traffic mix loaded."""
        for w in self.doc["workloads"]:
            if w["name"] == name:
                break
        else:
            raise SpecError("no workload %r in BENCHMARK.json (has: %s)" % (
                name, ", ".join(w["name"] for w in self.doc["workloads"])))
        for c in self.doc["configs"]:
            if c["name"] == w["config"]:
                break
        else:
            raise SpecError("workload %r names configuration %r, which "
                            "BENCHMARK.json lacks" % (name, w["config"]))
        return {
            "name": name,
            "spec": self,       # where a runner finds what the cell names
            "chips": int(w["chips"]),
            "config": _load_json(os.path.join(self.checkout, c["file"])),
            "traffic": _load_json(os.path.join(
                self.bench_dir, "traffic", w["traffic"] + ".json")),
            "limits": _load_json(os.path.join(
                self.bench_dir, "limits", name + ".json"))["limits"],
        }

    def _reports(self, metric: dict, cell: str, moves=None) -> bool:
        if "workloads" in metric:
            return cell in metric["workloads"]
        return moves is None or moves in self.end_to_end(cell)

    def end_to_end(self, cell: str) -> list:
        return [m["name"] for m in self.doc["end_to_end"]
                if self._reports(m, cell)]

    def per_layer(self, cell: str) -> list:
        return [m["name"] for m in self.doc["per_layer"]
                if self._reports(m, cell, m["moves"])]

    def unit(self, metric: str) -> str:
        for m in self.doc["end_to_end"] + self.doc["per_layer"]:
            if m["name"] == metric:
                return m["unit"]
        raise SpecError("no metric %r in BENCHMARK.json" % metric)

    def reader(self, metric: str):
        """``read(run)`` of ``metrics/<metric>.py``."""
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        if mod_spec is None or not os.path.exists(path):
            raise SpecError("metric %r has no reader at %s" % (metric, path))
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read

    def _module(self, folder: str, name: str, what: str):
        """``<folder>/<name>.py`` of this benchmark as a module of the
        package ``benchmark.<folder>``; of another checkout's benchmark
        (the tests make one) under a name of its own beside it."""
        path = os.path.join(self.bench_dir, folder, str(name) + ".py")
        if not isinstance(name, str) or not os.path.exists(path):
            raise SpecError("%s %r has no file at %s" % (what, name, path))
        full = "benchmark.%s.%s" % (folder, name)
        if os.path.abspath(self.bench_dir) == BENCH_DIR:
            return importlib.import_module(full)
        full += "_at_%08x" % zlib.crc32(os.path.abspath(path).encode())
        if full not in sys.modules:
            mod_spec = importlib.util.spec_from_file_location(full, path)
            mod = importlib.util.module_from_spec(mod_spec)
            sys.modules[full] = mod
            try:
                mod_spec.loader.exec_module(mod)
            except BaseException:
                del sys.modules[full]
                raise
        return sys.modules[full]

    def runner_kinds(self) -> list:
        """The kinds of traffic mix that have a runner: the modules of
        ``harness/`` that offer ``run``."""
        kinds = []
        for entry in sorted(os.listdir(os.path.join(self.bench_dir,
                                                    "harness"))):
            name, ext = os.path.splitext(entry)
            if ext == ".py" and not name.startswith("_") and callable(getattr(
                    self._module("harness", name, "harness module"), "run",
                    None)):
                kinds.append(name)
        return kinds

    def runner(self, kind):
        """``harness/<kind>.py``, the runner of the mixes of that kind: its
        ``run(cell, seed, seconds, trace, device, peaks, t_process,
        variant)``, ``VARIANTS`` and ``SPANS``."""
        kinds = self.runner_kinds()
        if kind not in kinds:
            raise SpecError("traffic mix of kind %r, the harness runs %s" % (
                kind, kinds))
        return self._module("harness", kind, "runner")

    def reference(self, cell: dict):
        """The plain reference of a cell, ``reference/<name>.py``: the one
        its mix names, else its configuration's, else ``gbdt``."""
        name = cell["traffic"].get("reference") or cell["config"].get(
            "reference", "gbdt")
        return self._module("reference", name, "reference")

    def generator(self, part: dict):
        """``generators/<name>.py``, which a mix's ``data`` or ``model``
        group names as its ``generator``."""
        return self._module("generators", part.get("generator"), "generator")

    def make_table(self, data: dict):
        """``make_table(rows, features, seed, data) -> (X, y, extra)`` of
        the generator a mix's ``data`` names; of ``harness/traffic.py``,
        with nothing extra, where it names none."""
        if "generator" in data:
            return self.generator(data).make_table
        from . import traffic
        return lambda *a: traffic.make_table(*a) + ({},)
