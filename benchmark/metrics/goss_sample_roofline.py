"""The sampling passes' least time (``trace/work_goss.py``: 20 bytes a row
an iteration, bound by bytes) over the device time under ``obs_goss``,
whatever implements the selection."""
from benchmark.metrics import _goss
from benchmark.trace import work_goss


def read(run):
    spent = _goss.sampling_seconds(run)
    if not spent:
        return None
    least, _ = work_goss.least_seconds(work_goss.sampling_pass(run.rows),
                                       run.peaks)
    return 100.0 * least * run.iterations / spent
