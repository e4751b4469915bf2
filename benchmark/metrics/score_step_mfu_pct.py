"""The least time the chip could take for the blocks the window answered
(``trace/work_score.py``: each block read once, the node tables, a
comparison per node visited; bound by bytes at these shapes) over the whole
window's own time."""
from benchmark.metrics._score import least_seconds


def read(run):
    least = least_seconds(run)
    if least is None or run.trace is None or not run.window_s:
        return None
    return 100.0 * least / run.window_s
