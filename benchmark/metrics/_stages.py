"""The grower's stage times of a traced run, shared by the readers of the
``grower_*`` metrics (not a metric itself)."""
import glob
import os
import time

from benchmark.harness import train
from benchmark.metrics.grower_ms_per_iter import PROGRAMS
from benchmark.trace import scopes

HIST = ("obs_hist_pallas", "obs_hist_einsum", "obs_hist_scatter")


def _newest_xplane():
    """The trace this run wrote: a run traces once, into
    ``train.TRACE_DIR/<cell>``, after clearing that directory."""
    found = glob.glob(os.path.join(train.TRACE_DIR, "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def stage_times(run):
    """``scopes.StageTimes`` of the grower's programs in the run's trace,
    read once a run; None where the run has no trace, the file is not the
    one ``run.trace`` was read from, or no operation of the grower carries
    a stage (a program from before the scopes)."""
    if run.trace is None or not run.iterations:
        return None
    if not hasattr(run, "_stage_times"):
        run._stage_times = None
        t0 = time.perf_counter()
        path = _newest_xplane()
        ops = scopes.load_ops(path) if path else None
        if ops is not None and len(ops.line) == len(run.trace.ops()):
            times = scopes.stage_times(ops, PROGRAMS)
            if set(times.stages) - {scopes.UNSCOPED}:
                run._stage_times = times
                print("stages: %s" % {k: round(v, 6) for k, v in
                                      sorted(times.stages.items())})
                print("stages by bucket: %s" % {
                    b: {k: round(v, 6) for k, v in sorted(per.items())}
                    for b, per in sorted(times.buckets.items())})
                print("unscoped: %s" % times.unscoped_ops)
                print("stages read in %.3f s" % (time.perf_counter() - t0),
                      flush=True)
    return run._stage_times


def ms_per_iter(run, *stages):
    """Device milliseconds per iteration under the named stages."""
    times = stage_times(run)
    if times is None:
        return None
    return 1e3 * sum(times.stages.get(s, 0.0) for s in stages) \
        / run.iterations
