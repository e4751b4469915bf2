"""The answered blocks' least time (``trace/work_score.py``) over the
device time of the programs that scored them."""
from benchmark.metrics._score import least_seconds, walk_events
from benchmark.trace import xplane


def read(run):
    found, least = walk_events(run), least_seconds(run)
    if found is None or least is None:
        return None
    return 100.0 * least / xplane.union_s(found)
