"""The histogram passes' least time over the in-bag rows and the sampled
columns (``trace/work_subsample.py``: the row-features that carry weight,
from the model text's in-bag counts and the configuration's column count;
bound by bytes at these shapes) over the device time under the
``obs_hist_pallas``/``einsum``/``scatter`` scopes, whatever the passes
visited."""
from benchmark.metrics import _subsample
from benchmark.trace import work_subsample


def read(run):
    spent = _subsample.stage_seconds(run)
    if not spent or not run.tree_counts:
        return None
    columns, _ = _subsample.settings(run)
    return 100.0 * work_subsample.histograms_least_seconds(
        run.tree_counts, columns, run.peaks) / spent
