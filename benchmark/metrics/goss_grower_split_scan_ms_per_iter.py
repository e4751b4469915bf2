"""Device time per iteration of the best-split scans (``obs_split_scan``),
in a training cell under gradient-based sampling: what
``grower_split_scan_ms_per_iter`` reads, under this cell's own name, from
the pass over the trace that the cell's other readers share."""
from benchmark.metrics import _goss


def read(run):
    return _goss.stage_ms_per_iter(run, ("obs_split_scan",))
