"""Device time per iteration of the linear outputs (self time under
``obs_linear_out``: the training rows' linear values and the validation
rows'), in whichever program."""
from benchmark.metrics import _linear


def read(run):
    return _linear.ms_per_iter(run, _linear.OUT)
