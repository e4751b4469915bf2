"""Device time per iteration of choosing the leaf and moving its rows
(``obs_pick_leaf`` and ``obs_partition``), in a training cell under
gradient-based sampling: what ``grower_partition_ms_per_iter`` reads, under
this cell's own name, from the pass over the trace that the cell's other
readers share."""
from benchmark.metrics import _goss


def read(run):
    return _goss.stage_ms_per_iter(run, ("obs_partition", "obs_pick_leaf"))
