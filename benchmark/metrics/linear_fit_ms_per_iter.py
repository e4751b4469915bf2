"""Device time per iteration of the linear leaves' fit (self time under
``obs_linear_fit``: each row's path values, the per-leaf normal equations,
their solves), in whichever program."""
from benchmark.metrics import _linear


def read(run):
    return _linear.ms_per_iter(run, _linear.FIT)
