"""Device time of the grower's programs per boosting iteration of a
training cell with linear leaves: ``grower_ms_per_iter``'s reading (their
events on ``XLA Modules``), under this cell's own name; the fit runs in a
program of its own, so this reads what ``bosch-train`` reads."""
from benchmark.metrics.grower_ms_per_iter import read  # noqa: F401
