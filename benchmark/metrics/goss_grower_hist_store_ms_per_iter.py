"""Device time per iteration of updating the per-leaf histogram store
(``obs_hist_store``), in a training cell under gradient-based sampling:
what ``grower_hist_store_ms_per_iter`` reads, under this cell's own name,
from the pass over the trace that the cell's other readers share."""
from benchmark.metrics import _goss


def read(run):
    return _goss.stage_ms_per_iter(run, ("obs_hist_store",))
