"""Device time per iteration of unpacking bundle histograms per feature
(self time under ``obs_unpack``: the root's and one a split), in whichever
program."""
from benchmark.metrics import _efb


def read(run):
    seconds = _efb.unpack_seconds(run)
    return None if seconds is None else 1e3 * seconds / run.iterations
