"""Device time per boosting iteration of every program that neither
``grower_ms_per_iter`` nor a reader of ``_iteration.NAMED`` reads. Prints its
largest programs by name and the iteration's account: the named programs,
these and the window's remainder add up to ``train_iter_s`` where no program
of the iteration is counted twice or left out."""
from benchmark.metrics import _iteration
from benchmark.trace import xplane


def read(run):
    terms = _iteration.terms(run)
    if terms is None:
        return None
    print("other programs: %s" % xplane.top(_iteration.by_program(
        _iteration.other_programs(run))))
    total, iteration = sum(terms.values()), 1e3 * run.window_s / run.iterations
    print("iteration: %s = %.3f ms; train_iter_s %.3f ms (%+.3f%%)" % (
        " + ".join("%s %.3f" % kv for kv in terms.items()), total, iteration,
        100.0 * (total - iteration) / iteration), flush=True)
    return terms[_iteration.OTHER]
