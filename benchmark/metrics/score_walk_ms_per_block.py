"""Device time of the programs ``serve.stacked_raw`` launches (quantise,
walk, accumulate) per answered block."""
from benchmark.metrics._score import answered, walk_events
from benchmark.trace import xplane


def read(run):
    found = walk_events(run)
    if found is None or not answered(run):
        return None
    return 1e3 * xplane.union_s(found) / len(answered(run))
