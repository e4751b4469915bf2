"""The measured window on the CPU: a traced run's window is
``train.TRACED_ITERATIONS`` iterations, or fewer where ``--seconds`` runs
out first, so that its trace holds the same trees whatever the program's
speed; the untraced window, which gives the end-to-end metrics, runs by the
clock alone. A stub stands in for the program where only the loop is at
stake; a tiny traced run of the runner of kind ``train`` shows the window's
count and the counters noted when it opened.
"""
import time

import pytest

from benchmark.harness import device, spec, train


class Stub:
    """``update`` and ``wait`` as the window calls them, each update
    taking ``seconds``."""

    def __init__(self, seconds=0.0):
        self.seconds = seconds
        self.updates = 0
        self.waited_after = None

    def update(self):
        self.updates += 1
        time.sleep(self.seconds)

    def wait(self):
        self.waited_after = self.updates


def test_traced_window_issues_the_fixed_count_when_time_allows():
    prog = Stub()
    attempted, seconds = train.drive_window(prog, 30.0, True)
    assert attempted == prog.updates == train.TRACED_ITERATIONS == 6
    assert prog.waited_after == 6 and seconds < 30.0


def test_traced_window_stops_at_seconds_when_they_run_out_first():
    prog = Stub(0.03)
    attempted, seconds = train.drive_window(prog, 0.07, True)
    assert 1 <= attempted == prog.updates < train.TRACED_ITERATIONS
    assert seconds >= 0.07 and prog.waited_after == attempted


@pytest.mark.parametrize("count", [6, 0])
def test_untraced_window_runs_by_the_clock(monkeypatch, count):
    """Whatever the traced count is, an untraced window runs until
    ``seconds`` have passed."""
    monkeypatch.setattr(train, "TRACED_ITERATIONS", count)
    prog = Stub(0.002)
    attempted, seconds = train.drive_window(prog, 0.15, False)
    assert attempted == prog.updates > 6 and seconds >= 0.15
    assert prog.waited_after == attempted
    assert train.drive_window(Stub(), 30.0, True)[0] == count


def test_traced_run_covers_the_fixed_count_and_notes_counters(
        monkeypatch, tmp_path):
    """The runner of kind ``train``, traced at a tiny size: six iterations
    in the window, six trees read from the model, and the counters of
    set-up's trees noted so that a reader leaves them out."""
    import jax
    from benchmark.harness import program_obs
    monkeypatch.setattr(train, "TRACE_DIR", str(tmp_path))
    cell = spec.Spec().cell("bosch-train")
    cell["config"] = dict(cell["config"], rows=4096, features=16,
                          valid_rows=512)
    cell["config"]["params"] = dict(cell["config"]["params"], num_leaves=7,
                                    min_sum_hessian_in_leaf=5.0)
    this, result, _ = train.run(
        cell, 2**31 + 40, 60.0, True, jax.devices()[0],
        device.peaks_for("TPU v5 lite"), time.perf_counter())
    assert this.iterations == result["attempted"] == 6
    assert result["failed"] == 0 and len(this.tree_counts) == 6
    assert this.trace is not None and this.window_s < 60.0
    at, now = this.counters_at_window, program_obs.counters()
    # set-up's two trees (the learner on the CPU counts no grow/*): 16
    # columns a tree, and the validation rows' walk
    assert at["sample/cols_total"] == 2 * 16
    assert now["sample/cols_total"] == 8 * 16
    moved = {n: now[n] - at.get(n, 0)
             for n in ("valid/walk_hops_needed", "valid/walk_hops_run")}
    assert at["valid/walk_hops_run"] > 0 and all(moved.values())
    fill = spec.Spec().reader("valid_walk_hop_fill_pct")(this)
    assert fill == pytest.approx(100.0 * moved["valid/walk_hops_needed"]
                                 / moved["valid/walk_hops_run"])
