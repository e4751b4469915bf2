"""The least time the chip could take for the iterations the window grew
(``trace/work.py``: the histogram passes over the root's and every smaller
child's rows of the trees themselves, the gradient and the score pass), over
the window's own time."""
from benchmark.trace import work


def read(run):
    if run.trace is None or not run.tree_counts or not run.window_s:
        return None
    least = 0.0
    for counts in run.tree_counts:
        seconds, _ = work.least_seconds(work.boosting_iteration(
            run.rows, run.features, work.histogram_rows(counts)), run.peaks)
        least += seconds
    return 100.0 * least / run.window_s
