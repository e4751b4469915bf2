"""Device time per iteration of the best-split scans and the stores of
their candidates (``obs_split_scan``)."""
from benchmark.metrics import _stages


def read(run):
    return _stages.ms_per_iter(run, "obs_split_scan")
