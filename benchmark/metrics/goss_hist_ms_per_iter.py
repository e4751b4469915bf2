"""Device time per iteration of building the histograms, whatever builds
them, and of the sibling's by subtraction: what ``grower_hist_ms_per_iter``
reads, under this cell's own name."""
from benchmark.metrics import _goss, _stages


def read(run):
    return _goss.stage_ms_per_iter(run,
                                   ("obs_hist_subtract",) + _stages.HIST)
