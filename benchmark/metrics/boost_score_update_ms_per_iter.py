"""Device time per boosting iteration of the training rows' score update
(``jit_gbdt_score_delta``: ``leaf_values[leaf_of_row]`` added over every
row), on ``XLA Modules``."""
from benchmark.metrics import _iteration


def read(run):
    return _iteration.program_ms_per_iter(run, _iteration.SCORE_UPDATE)
