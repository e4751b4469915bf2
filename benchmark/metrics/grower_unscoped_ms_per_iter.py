"""Device time per iteration of the grower's programs under no stage:
operations XLA added itself, which carry no name stack, operations outside
every ``obs_`` stage, and what of the programs' intervals no operation
covers. With the five stage metrics it adds up to ``grower_ms_per_iter``."""
from benchmark.metrics import _stages
from benchmark.trace import scopes


def read(run):
    return _stages.ms_per_iter(run, scopes.UNSCOPED)
