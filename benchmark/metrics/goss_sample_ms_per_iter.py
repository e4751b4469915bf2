"""Device time per iteration of the one-side sampling (``obs_goss``: the
weight ``|g * h|``, the top-k for the threshold, the draw, the
amplification), in whichever program it was traced."""
from benchmark.metrics import _goss


def read(run):
    seconds = _goss.sampling_seconds(run)
    return None if seconds is None else 1e3 * seconds / run.iterations
