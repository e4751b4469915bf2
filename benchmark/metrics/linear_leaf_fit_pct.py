"""Leaves given a linear fit over all the leaves of the trees fit in the
window (what the program's ``linear/leaves_fit`` and
``linear/leaves_const`` moved by since the window opened; counted while its
stage timer is on): the rest kept their constant, for too few rows or a
solve that was not finite."""
from benchmark.metrics import _linear


def read(run):
    fit = _linear.moved(run, "linear/leaves_fit") or 0
    const = _linear.moved(run, "linear/leaves_const") or 0
    return 100.0 * fit / (fit + const) if fit + const else None
