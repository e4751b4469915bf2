"""Device time per iteration of gathering the smaller child's rows into a
bucket: ``obs_compact`` less the histogram built inside it, which is under
its own scope."""
from benchmark.metrics import _stages


def read(run):
    return _stages.ms_per_iter(run, "obs_compact")
