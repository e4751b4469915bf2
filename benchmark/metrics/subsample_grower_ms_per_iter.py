"""Device time of the grower's programs per boosting iteration of a
training cell under column and row sampling: ``grower_ms_per_iter``'s
reading (their events on ``XLA Modules``), under this cell's own name."""
from benchmark.metrics.grower_ms_per_iter import read  # noqa: F401
