"""The program's ``serve::predict_batch`` range per dispatch, less the time
the device ran inside it: the upload and the host's work of a dispatch."""
from benchmark.metrics._score import DISPATCH, host_ranges
from benchmark.trace import xplane


def read(run):
    found = host_ranges(run, DISPATCH)
    if found is None:
        return None
    host_s = sum((hi - lo) * 1e-9 - xplane.union_s(run.trace.ops(), lo, hi)
                 for lo, hi in found)
    return 1e3 * host_s / len(found)
