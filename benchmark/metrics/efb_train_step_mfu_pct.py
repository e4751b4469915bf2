"""The least time the chip could take for the iterations the window grew
over EFB bundles (``trace/work_efb.py``: the histogram passes over the
root's and every smaller child's rows of the bundle columns, the bundle
histograms unpacked per feature, the gradient and score passes), over the
window's own time: the share of the whole step."""
from benchmark.metrics import _efb


def read(run):
    if run.trace is None or not run.window_s:
        return None
    parts = _efb.step_passes(run)
    if parts is None:
        return None
    return 100.0 * _efb.least_seconds(parts, run.peaks) / run.window_s
