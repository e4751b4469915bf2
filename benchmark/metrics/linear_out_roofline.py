"""The output passes' least time (``trace/work_linear.py``: a row's kept
values and leaf read, its score read and written, over the training and the
validation rows) over the device time under ``obs_linear_out``, whatever
implements the outputs."""
from benchmark.metrics import _linear


def read(run):
    return _linear.roofline(run, 1, _linear.OUT)
