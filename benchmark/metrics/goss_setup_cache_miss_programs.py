"""``setup_cache_miss_programs``'s reading in a training cell under gradient-based
sampling, under this cell's own name."""
from benchmark.metrics.setup_cache_miss_programs import read  # noqa: F401
