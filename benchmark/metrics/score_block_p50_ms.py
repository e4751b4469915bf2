"""Submit to answer of a client's block on the host's clock, median over
the window's answered blocks (the sample count is on the run's ``window:``
line; a window of 20 blocks or more would carry a 95th percentile)."""
import numpy as np

from benchmark.metrics._score import answered


def read(run):
    blocks = answered(run)
    if not blocks or run.trace is None:
        return None
    return float(np.median([1e3 * (b.t_answer - b.t_submit) for b in blocks]))
