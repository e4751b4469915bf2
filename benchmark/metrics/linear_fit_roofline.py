"""The fit passes' least time (``trace/work_linear.py``: each fitted row's
kept values, gradient, hessian and leaf read once, ``2 (k + 1)^2``
operations a row) over the device time under ``obs_linear_fit``, whatever
implements the fit."""
from benchmark.metrics import _linear


def read(run):
    return _linear.roofline(run, 0, _linear.FIT)
