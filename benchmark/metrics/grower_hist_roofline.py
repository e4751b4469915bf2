"""The histogram passes' least time (``trace/work.py``, from the window's
own trees; bound by bytes at these shapes) over the device time under the
``obs_hist_pallas``/``einsum``/``scatter`` scopes: the histogram's share of
its roofline whatever implements it."""
from benchmark.metrics import _stages
from benchmark.trace import work


def read(run):
    times = _stages.stage_times(run)
    if times is None or not run.tree_counts:
        return None
    spent = sum(times.stages.get(s, 0.0) for s in _stages.HIST)
    if not spent:
        return None
    least = sum(work.least_seconds(work.histogram_pass(
        work.histogram_rows(c), run.features), run.peaks)[0]
        for c in run.tree_counts)
    return 100.0 * least / spent
