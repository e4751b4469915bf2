"""The readers that account for the whole boosting iteration, on a hand-made
trace: the programs of two iterations on the ``XLA Modules`` line (grower,
validation walk, gradients, score update, one of jax's own naming), one
idle gap under the metrics' host arithmetic, and the program's ranges on a
host line. They return the per-iteration times, nothing without a trace or
from a program that lacks the names, and their terms add up to the window.
"""
import json
import os
import re

import numpy as np
import pytest

from benchmark.harness import program_obs, spec, train
from benchmark.metrics import _iteration
from benchmark.trace import xplane

READERS = ("valid_walk_ms_per_iter", "valid_walk_hop_fill_pct",
           "valid_eval_host_ms_per_iter", "boost_gradients_ms_per_iter",
           "boost_score_update_ms_per_iter", "grower_root_ms_per_iter",
           "iter_other_device_ms_per_iter")
# the cells appended to the lists once their own tests took their entries
# by name (PR 40)
APPENDED = ["bosch-train-quant", "bosch-train-goss", "bosch-train-subsample"]
PEAKS = {"hbm_bytes_per_s": 819e9}
US = 1000       # ns

# one iteration, in us from its start: name, start, duration
ITERATION = (
    ("jit_gbdt_take_col(11)", 0, 1),
    ("jit__grads(12)", 1, 2),
    ("jit_concatenate(13)", 3, 4),          # staging of gh: jax's own name
    ("jit__root_impl(14)", 10, 300),
    ("jit__tree_impl(15)", 310, 1400),
    ("jit_gbdt_score_delta(16)", 1710, 8),
    ("jit__traverse_body(17)", 1718, 270),
    ("jit__gather_leaf_values_body(18)", 1988, 1),
    ("jit_gbdt_valid_score_add(19)", 1989, 1),
    # [1990, 2000): nothing runs, the host computes the AUC
)
PERIOD = 2000
SPANS = (("gbdt::eval_metrics", 1700, 299), ("gbdt::eval_fetch", 1701, 288),
         ("gbdt::eval_compute", 1990, 9))


def _line(events):
    return xplane.Line([e[0] for e in events],
                       np.asarray([e[1] * US for e in events], np.int64),
                       np.asarray([e[2] * US for e in events], np.int64))


def _run(rename=lambda name: name, spans=SPANS, iterations=2):
    modules = _line([(rename(n), s + k * PERIOD, d)
                     for k in range(iterations) for n, s, d in ITERATION])
    host = _line([("bench::iteration", k * PERIOD, PERIOD)
                  for k in range(iterations)]
                 + [(n, s + k * PERIOD, d)
                    for k in range(iterations) for n, s, d in spans])
    run = train.Run(1000, 10, PEAKS)
    run.trace = xplane.Trace({0: {xplane.MODULES_LINE: modules}},
                             {"python": host})
    run.iterations = iterations
    run.window_s = iterations * PERIOD * US * 1e-9
    return run


@pytest.fixture
def counters(monkeypatch):
    monkeypatch.setattr(program_obs, "counter", {
        "valid/walk_hops_needed": 6600, "valid/walk_hops_run": 16000}.get)


def test_the_entries_are_appended_with_their_cells():
    bench = spec.Spec()
    names = [m["name"] for m in bench.doc["per_layer"]]
    assert tuple(names[-len(READERS):]) == READERS
    for cell in ["bosch-train"] + APPENDED:
        assert set(READERS) <= set(bench.per_layer(cell))
    assert set(READERS) & set(bench.per_layer("epsilon-train")) == {
        "boost_gradients_ms_per_iter", "boost_score_update_ms_per_iter",
        "grower_root_ms_per_iter", "iter_other_device_ms_per_iter"}
    with open(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert {entries[n]["layer"] for n in READERS} == {
        "validation", "boosting iteration", "grower"}
    assert {entries[n]["moves"] for n in READERS} == {"train_iter_s"}


def test_readers_by_hand(counters, capsys):
    run, bench = _run(), spec.Spec()
    got = {name: bench.reader(name)(run) for name in READERS}
    assert got == pytest.approx({
        "valid_walk_ms_per_iter": 0.272,
        "valid_walk_hop_fill_pct": 41.25,
        "valid_eval_host_ms_per_iter": 0.009,
        "boost_gradients_ms_per_iter": 0.003,
        "boost_score_update_ms_per_iter": 0.008,
        "grower_root_ms_per_iter": 0.300,
        "iter_other_device_ms_per_iter": 0.004})
    printed = capsys.readouterr().out
    assert "other programs: [['jit_concatenate', " in printed
    assert "jit__lambda" not in printed
    assert "train_iter_s 2.000 ms (+0.000%)" in printed


def test_the_terms_add_up_to_the_window():
    run = _run()
    terms = _iteration.terms(run)
    assert terms == pytest.approx({
        "grower": 1.700, "validation walk": 0.272, "gradients": 0.003,
        "score update": 0.008, "other": 0.004, "idle": 0.013})
    assert sum(terms.values()) == pytest.approx(
        1e3 * run.window_s / run.iterations, rel=1e-9)
    # the root's program is a part of the first term, not a term
    assert spec.Spec().reader("grower_ms_per_iter")(run) \
        == pytest.approx(terms["grower"])


def test_no_program_is_read_by_two_patterns():
    names = [n for n, _, _ in ITERATION]
    for name in names:
        hits = [term for term, pattern in _iteration.NAMED.items()
                if xplane.Line([name], np.zeros(1, np.int64),
                               np.ones(1, np.int64)).matching(pattern).names]
        assert len(hits) == (0 if name.startswith("jit_concatenate") else 1)


@pytest.mark.parametrize("name", READERS)
def test_nothing_is_read_without_a_trace(name, monkeypatch):
    monkeypatch.setattr(program_obs, "counter", {}.get)
    run = train.Run(1000, 10, PEAKS)
    assert spec.Spec().reader(name)(run) is None


def test_a_program_from_before_the_names_leaves_its_metrics_out(
        monkeypatch, capsys):
    """The parent's programs: the score plumbing runs as ``jit__lambda``,
    the validation scores are added by a program of jax's own naming, no
    range splits ``gbdt::eval_metrics`` and nothing counts the hops. What
    has a name reads as it does, the rest falls to ``other``, and the
    terms still add up."""
    old = {"jit_gbdt_take_col(11)": "jit__lambda(11)",
           "jit_gbdt_score_delta(16)": "jit__lambda(16)",
           "jit_gbdt_valid_score_add(19)": "jit_scatter-add(19)"}
    monkeypatch.setattr(program_obs, "counter", {}.get)
    run = _run(rename=lambda n: old.get(n, n), spans=SPANS[:1])
    bench = spec.Spec()
    got = {name: bench.reader(name)(run) for name in READERS}
    assert got["valid_walk_hop_fill_pct"] is None
    assert got["valid_eval_host_ms_per_iter"] is None
    assert got["boost_score_update_ms_per_iter"] is None
    assert got["valid_walk_ms_per_iter"] == pytest.approx(0.271)
    assert got["boost_gradients_ms_per_iter"] == pytest.approx(0.002)
    assert got["grower_root_ms_per_iter"] == pytest.approx(0.300)
    assert got["iter_other_device_ms_per_iter"] == pytest.approx(0.014)
    assert "['jit__lambda', " in capsys.readouterr().out
    assert sum(_iteration.terms(run).values()) == pytest.approx(2.0)


def test_a_cell_without_a_validation_set_reads_no_walk(counters):
    modules = _line([e for e in ITERATION
                     if not re.search(_iteration.WALK, e[0])])
    run = train.Run(1000, 10, PEAKS)
    run.trace = xplane.Trace({0: {xplane.MODULES_LINE: modules}}, {})
    run.iterations, run.window_s = 1, PERIOD * US * 1e-9
    bench = spec.Spec()
    assert bench.reader("valid_walk_ms_per_iter")(run) is None
    assert bench.reader("valid_eval_host_ms_per_iter")(run) is None
    terms = _iteration.terms(run)
    assert terms["validation walk"] == 0.0
    assert terms["idle"] == pytest.approx(0.013 + 0.272)
    assert sum(terms.values()) == pytest.approx(2.0)
