"""The int8 histogram passes' least time (``trace/work_quant.py``: 1 byte a
bin and 2 bytes of gradient a row, integer additions against the int8 peak;
bound by bytes at these shapes) over the device time under the
``obs_hist_pallas``/``einsum``/``scatter`` scopes, whatever implements it."""
from benchmark.metrics import _stages
from benchmark.trace import work_quant


def read(run):
    times = _stages.stage_times(run)
    if times is None or not run.tree_counts:
        return None
    spent = sum(times.stages.get(s, 0.0) for s in _stages.HIST)
    if not spent:
        return None
    return 100.0 * work_quant.trees_least_seconds(
        run.tree_counts, run.features, run.peaks) / spent
