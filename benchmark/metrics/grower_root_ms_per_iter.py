"""Device time per boosting iteration of the root's program alone
(``jit__root_impl``: the histogram pass over every row and the first scan);
``grower_ms_per_iter`` less this is ``jit__tree_impl``."""
from benchmark.metrics import _iteration


def read(run):
    return _iteration.program_ms_per_iter(run, _iteration.ROOT)
