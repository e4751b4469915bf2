"""The least time the chip could take for the sampled boosting iterations
the window grew (``trace/work_goss.py``: the histogram passes over the
in-bag rows of the root and of every smaller child of the trees themselves,
the gradient pass, the sampling pass, the score pass over every row), over
the window's own time: the share of the whole step."""
from benchmark.trace import work_goss


def read(run):
    if run.trace is None or not run.tree_counts or not run.window_s:
        return None
    least = work_goss.trees_least_seconds(run.tree_counts, run.features,
                                          run.peaks, rows=run.rows)
    return 100.0 * least / run.window_s
