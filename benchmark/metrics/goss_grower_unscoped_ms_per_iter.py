"""Device time per iteration of the grower's programs under no stage, in a
training cell under gradient-based sampling: what
``grower_unscoped_ms_per_iter`` reads, under this cell's own name, from the
pass over the trace that the cell's other readers share."""
from benchmark.metrics import _goss
from benchmark.trace import scopes


def read(run):
    return _goss.stage_ms_per_iter(run, (scopes.UNSCOPED,))
