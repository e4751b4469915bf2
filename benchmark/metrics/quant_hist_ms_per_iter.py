"""Device time per iteration of building the integer histograms, whatever
builds them (``obs_hist_pallas``, ``obs_hist_einsum``, ``obs_hist_scatter``),
and of the sibling's by integer subtraction (``obs_hist_subtract``):
``grower_hist_ms_per_iter``'s reading, under this cell's own name."""
from benchmark.metrics.grower_hist_ms_per_iter import read  # noqa: F401
