"""Device time per iteration of building the histograms, whatever builds
them, and of the sibling's by subtraction: what ``grower_hist_ms_per_iter``
reads, under this cell's own name."""
from benchmark.metrics import _stages, _subsample


def read(run):
    seconds = _subsample.stage_seconds(
        run, ("obs_hist_subtract",) + _stages.HIST)
    return None if seconds is None else 1e3 * seconds / run.iterations
