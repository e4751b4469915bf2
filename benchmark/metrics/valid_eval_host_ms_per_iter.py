"""Host time per iteration of the validation metrics' arithmetic (the
program's span ``gbdt::eval_compute``, inside ``gbdt::eval_metrics`` after
the scores are read back), during which the device idles."""
from benchmark.metrics import _iteration


def read(run):
    return _iteration.span_ms_per_iter(run, "gbdt::eval_compute")
