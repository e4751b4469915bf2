"""Device time per iteration of the bag's draw (``obs_bag``: the key's
fold-in, the uniform draw, the comparison with the fraction), in whichever
program it was traced; it runs once every ``bagging_freq`` iterations."""
from benchmark.metrics import _subsample


def read(run):
    seconds = _subsample.draw_seconds(run)
    return None if seconds is None else 1e3 * seconds / run.iterations
