"""Device time per boosting iteration of the validation rows' walk of the
new tree: the lockstep traversal (``jit__traverse_body``), the leaf values'
gather (``jit__gather_leaf_values_body``) and their addition to the
validation scores (``jit_gbdt_valid_score_add``), on ``XLA Modules``."""
from benchmark.metrics import _iteration


def read(run):
    return _iteration.program_ms_per_iter(run, _iteration.WALK)
