"""Device time per iteration of building histograms, whatever builds them
(``obs_hist_pallas``, ``obs_hist_einsum``, ``obs_hist_scatter``: pad,
transposes and reshape included), and of the sibling's by subtraction
(``obs_hist_subtract``)."""
from benchmark.metrics import _stages


def read(run):
    return _stages.ms_per_iter(run, "obs_hist_subtract", *_stages.HIST)
