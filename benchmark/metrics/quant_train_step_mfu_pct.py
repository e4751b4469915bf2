"""The least time the chip could take for the quantized boosting iterations
the window grew (``trace/work_quant.py``: the int8 histogram passes over the
root's and every smaller child's rows of the trees themselves, the gradient
pass with its 2 bytes a row, the score pass), over the window's own time:
the share of the whole step."""
from benchmark.trace import work_quant


def read(run):
    if run.trace is None or not run.tree_counts or not run.window_s:
        return None
    least = work_quant.trees_least_seconds(run.tree_counts, run.features,
                                           run.peaks, rows=run.rows)
    return 100.0 * least / run.window_s
