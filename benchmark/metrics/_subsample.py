"""The trace of a run under column and row sampling, read once for the
readers of the ``subsample_*`` metrics (not a metric itself): the grower's
stage times as ``_stages.stage_times`` reads them and the device time under
``obs_bag``, which sits in a program of its own (``jit(_bag_draw)``), from
one pass over the file; the cell's sampling settings, from its own files;
the host span ``tree::sample_features``, from the trace's host lines."""
import time

from benchmark.harness import spec
from benchmark.metrics import _goss, _stages
from benchmark.metrics.grower_ms_per_iter import PROGRAMS
from benchmark.trace import scopes, work_subsample, xplane

CELL = "bosch-train-subsample"
SCOPE = "obs_bag"
SPAN = "tree::sample_features"


def _read(run) -> tuple:
    if run.trace is None or not run.iterations:
        return None, None
    if not hasattr(run, "_subsample_read"):
        run._subsample_read = (None, None)
        t0 = time.perf_counter()
        path = _stages._newest_xplane()
        ops = scopes.load_ops(path) if path else None
        if ops is not None and len(ops.line) == len(run.trace.ops()):
            times = scopes.stage_times(ops, PROGRAMS)
            if not set(times.stages) - {scopes.UNSCOPED}:
                times = None
            keys = [SCOPE in stack.rstrip(":").split("/")
                    for stack in ops.tf_op]
            draw = xplane.self_times(xplane.Line(
                keys, ops.line.start, ops.line.dur)).get(True) \
                if any(keys) else None
            run._subsample_read = (times, draw)
            print("stages: %s; %s: %s; read in %.3f s" % (
                times and {k: round(v, 6) for k, v in
                           sorted(times.stages.items())},
                SCOPE, draw, time.perf_counter() - t0), flush=True)
    return run._subsample_read


def stage_seconds(run, stages=_stages.HIST):
    """Device time of the grower's programs under the named stages; None
    where the run has no trace or no operation of the grower carries a
    stage."""
    times = _read(run)[0]
    if times is None:
        return None
    return sum(times.stages.get(s, 0.0) for s in stages)


def draw_seconds(run):
    """Self time of the operations under ``obs_bag``, in whichever program;
    None where the run has no trace or no operation carries the scope (a
    program from before it)."""
    return _read(run)[1]


def span_seconds(run):
    """Host time of the window's ``tree::sample_features`` ranges; None
    where the run has no trace or the program opens no such range."""
    if run.trace is None:
        return None
    found = [line.matching("^%s$" % SPAN) for line in run.trace.host.values()]
    return sum(line.total_s() for line in found) \
        if any(len(line) for line in found) else None


def settings(run) -> tuple:
    """``(sampled columns a tree, bags drawn in the window)`` of the cell
    as its configuration and its mix state them."""
    cell = spec.Spec().cell(CELL)
    params = cell["config"]["params"]
    return (work_subsample.sampled_columns(
                run.features, float(params.get("feature_fraction", 1.0))),
            work_subsample.bag_draws(
                int(cell["traffic"]["checked_steps"]), run.iterations,
                int(params.get("bagging_freq", 0))))


def weighted_share(run):
    """In-bag rows over visited rows times sampled columns over all columns,
    in percent, each pair as the program's counters moved since the runner
    noted them (at the window's start where it notes them, else since the
    stage timer went on: set-up's two trees are sampled like the window's);
    None where a counter stood still."""
    rows = _goss.counters_share(run, "grow/hist_rows_in_bag",
                                "grow/hist_rows_bucketed")
    cols = _goss.counters_share(run, "sample/cols_in_mask",
                                "sample/cols_total")
    return None if rows is None or cols is None else rows * cols / 100.0
