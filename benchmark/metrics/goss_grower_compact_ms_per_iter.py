"""Device time per iteration of gathering the smaller child's rows, in the
bag or not, into a bucket (``obs_compact`` less the histogram inside it),
in a training cell under gradient-based sampling: what
``grower_compact_ms_per_iter`` reads, under this cell's own name, from the
pass over the trace that the cell's other readers share."""
from benchmark.metrics import _goss


def read(run):
    return _goss.stage_ms_per_iter(run, ("obs_compact",))
