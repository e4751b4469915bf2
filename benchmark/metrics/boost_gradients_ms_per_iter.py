"""Device time per boosting iteration of the objective's gradient program
(``jit__grads``) and of the score column it reads (``jit_gbdt_take_col``),
on ``XLA Modules``."""
from benchmark.metrics import _iteration


def read(run):
    return _iteration.program_ms_per_iter(run, _iteration.GRADIENTS)
