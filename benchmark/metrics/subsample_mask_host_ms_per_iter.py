"""Host time per iteration of the column mask's draw and upload (the
program's span ``tree::sample_features``, once a tree), over the window."""
from benchmark.metrics import _subsample


def read(run):
    seconds = _subsample.span_seconds(run)
    if seconds is None or not run.iterations:
        return None
    return 1e3 * seconds / run.iterations
