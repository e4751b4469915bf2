"""The trace and the counters of a run with piecewise-linear leaves, read
once for the readers of the ``linear_*`` metrics (not a metric itself): the
device time under ``obs_linear_fit`` (the leaves' normal equations and
solves) and under ``obs_linear_out`` (the training rows' linear values and
the validation rows'), in whichever program, from one pass over the file,
and the program's ``linear/*`` counters as they moved since the window
opened."""
import time

from benchmark.harness import program_obs
from benchmark.metrics import _stages
from benchmark.trace import scopes, work, work_linear, xplane

FIT, OUT = "obs_linear_fit", "obs_linear_out"


def _scope(stack: str):
    parts = stack.rstrip(":").split("/")
    return FIT if FIT in parts else OUT if OUT in parts else None


def scope_seconds(run) -> dict:
    """``{FIT: s, OUT: s}``: self time of the operations under each scope;
    None where the run has no trace or no operation carries either (a
    program from before them)."""
    if run.trace is None or not run.iterations:
        return None
    if not hasattr(run, "_linear_read"):
        run._linear_read = None
        t0 = time.perf_counter()
        path = _stages._newest_xplane()
        ops = scopes.load_ops(path) if path else None
        if ops is not None and len(ops.line) == len(run.trace.ops()):
            keys = [_scope(stack) for stack in ops.tf_op]
            if any(keys):
                times = xplane.self_times(xplane.Line(
                    keys, ops.line.start, ops.line.dur))
                run._linear_read = {FIT: times.get(FIT, 0.0),
                                    OUT: times.get(OUT, 0.0)}
            print("%s / %s: %s; read in %.3f s" % (
                FIT, OUT, run._linear_read, time.perf_counter() - t0),
                flush=True)
    return run._linear_read


def ms_per_iter(run, scope: str):
    seconds = scope_seconds(run)
    return None if seconds is None else 1e3 * seconds[scope] / run.iterations


def moved(run, name: str):
    """What the program's counter ``name`` moved by since the window
    opened; None where it never moved (a program that does not count it,
    a run with the stage timer off)."""
    value = program_obs.counter(name)
    if value is None:
        return None
    delta = value - getattr(run, "counters_at_window", {}).get(name, 0)
    return delta or None


def passes(run):
    """``(fit_pass, output_pass)`` of ``trace/work_linear.py`` over the
    trees fit in the window, from the counters that describe them; None
    where the program counted none."""
    trees = moved(run, "linear/trees_fit")
    rows = moved(run, "linear/rows_fit")
    if not trees or not rows:
        return None
    features = moved(run, "linear/row_features") or 0
    squares = moved(run, "linear/row_features_sq") or 0
    return (work_linear.fit_pass(rows, features, squares),
            work_linear.output_pass(run.rows * trees, features,
                                    moved(run, "linear/valid_rows") or 0))


def roofline(run, which: int, scope: str):
    """The least time of a pass over the device time under its scope, in
    percent."""
    seconds, work_done = scope_seconds(run), passes(run)
    if not seconds or not seconds[scope] or work_done is None:
        return None
    least, _ = work.least_seconds(work_done[which], run.peaks)
    return 100.0 * least / seconds[scope]
