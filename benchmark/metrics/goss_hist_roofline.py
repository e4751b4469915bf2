"""The histogram passes' least time over the in-bag rows
(``trace/work_goss.py``: the rows that carry weight, from the model text's
in-bag counts; bound by bytes at these shapes) over the device time under
the ``obs_hist_pallas``/``einsum``/``scatter`` scopes, whatever the passes
visited."""
from benchmark.metrics import _goss, _stages
from benchmark.trace import work_goss


def read(run):
    spent = _goss.stage_seconds(run, _stages.HIST)
    if not spent or not run.tree_counts:
        return None
    return 100.0 * work_goss.trees_least_seconds(
        run.tree_counts, run.features, run.peaks) / spent
