"""The least time the chip could take for the iterations the window grew
with linear leaves (``trace/work.py``: the histogram passes over the root's
and every smaller child's rows of the trees themselves and the gradient
pass; ``trace/work_linear.py``: the fit and the linear outputs, which move
every training and validation row's score), over the window's own time:
the share of the whole step."""
from benchmark.metrics import _linear
from benchmark.trace import work


def read(run):
    if run.trace is None or not run.tree_counts or not run.window_s:
        return None
    linear = _linear.passes(run)
    if linear is None:
        return None
    parts = list(linear)
    for counts in run.tree_counts:
        parts += [work.histogram_pass(work.histogram_rows(counts),
                                      run.features),
                  work.gradient_pass(run.rows)]
    return 100.0 * sum(work.least_seconds(p, run.peaks)[0]
                       for p in parts) / run.window_s
