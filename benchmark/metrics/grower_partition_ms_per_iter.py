"""Device time per iteration of choosing the leaf and moving its rows:
the ``obs_pick_leaf`` and ``obs_partition`` scopes of the grower's programs."""
from benchmark.metrics import _stages


def read(run):
    return _stages.ms_per_iter(run, "obs_partition", "obs_pick_leaf")
