"""The least time the chip could take for the sampled boosting iterations
the window grew (``trace/work_subsample.py``: the histogram passes over the
in-bag rows of the root and of every smaller child of the trees themselves,
each over the sampled columns; the gradient pass and the score pass over
every row; the bag's pass once a draw), over the window's own time: the
share of the whole step."""
from benchmark.metrics import _subsample
from benchmark.trace import work_subsample


def read(run):
    if run.trace is None or not run.tree_counts or not run.window_s:
        return None
    columns, draws = _subsample.settings(run)
    return 100.0 * work_subsample.window_least_seconds(
        run.tree_counts, columns, run.rows, draws, run.peaks) / run.window_s
