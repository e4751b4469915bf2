"""The trace of a run under gradient-based sampling, read once for the
readers of the ``goss_*`` metrics (not a metric itself): the grower's stage
times as ``_stages.stage_times`` reads them and the device time under
``obs_goss``, which sits in a program of its own (``jit(_goss)``), from one
pass over the file (a pass takes 8-10 s at seven iterations a window)."""
import time

from benchmark.harness import program_obs
from benchmark.metrics import _stages
from benchmark.metrics.grower_ms_per_iter import PROGRAMS
from benchmark.trace import scopes, xplane

SCOPE = "obs_goss"


def _read(run) -> tuple:
    if run.trace is None or not run.iterations:
        return None, None
    if not hasattr(run, "_goss_read"):
        run._goss_read = (None, None)
        t0 = time.perf_counter()
        path = _stages._newest_xplane()
        ops = scopes.load_ops(path) if path else None
        if ops is not None and len(ops.line) == len(run.trace.ops()):
            times = scopes.stage_times(ops, PROGRAMS)
            if not set(times.stages) - {scopes.UNSCOPED}:
                times = None
            keys = [SCOPE in stack.rstrip(":").split("/")
                    for stack in ops.tf_op]
            sampling = xplane.self_times(xplane.Line(
                keys, ops.line.start, ops.line.dur)).get(True) \
                if any(keys) else None
            run._goss_read = (times, sampling)
            print("stages: %s; %s: %s; read in %.3f s" % (
                times and {k: round(v, 6) for k, v in
                           sorted(times.stages.items())},
                SCOPE, sampling, time.perf_counter() - t0), flush=True)
    return run._goss_read


def stage_seconds(run, stages):
    """Device time of the grower's programs under the named stages; None
    where the run has no trace or no operation of the grower carries a
    stage."""
    times = _read(run)[0]
    if times is None:
        return None
    return sum(times.stages.get(s, 0.0) for s in stages)


def stage_ms_per_iter(run, stages):
    seconds = stage_seconds(run, stages)
    return None if seconds is None else 1e3 * seconds / run.iterations


def counters_share(run, part, whole):
    """What the program's counter ``part`` moved by over what ``whole``
    moved by, in percent, since the runner noted both at the window's
    start; None where either stood still (a program that does not count
    it, a run with the stage timer off)."""
    at_window = getattr(run, "counters_at_window", {})
    moved = [(program_obs.counter(name) or 0) - at_window.get(name, 0)
             for name in (part, whole)]
    return 100.0 * moved[0] / moved[1] if all(moved) else None


def sampling_seconds(run):
    """Self time of the operations under ``obs_goss``, in whichever
    program; None where the run has no trace or no operation carries the
    scope (a program from before it)."""
    return _read(run)[1]
