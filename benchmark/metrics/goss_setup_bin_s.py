"""``setup_bin_s``'s reading in a training cell under gradient-based
sampling, under this cell's own name."""
from benchmark.metrics.setup_bin_s import read  # noqa: F401
