"""Device time per iteration that XLA attributes to the update of the
per-leaf histogram store (``obs_hist_store``); the copies of the store it
adds itself carry no scope and are under ``grower_unscoped_ms_per_iter``."""
from benchmark.metrics import _stages


def read(run):
    return _stages.ms_per_iter(run, "obs_hist_store")
