"""The histogram passes' least time (``trace/work.py``, from the window's
own trees; bound by bytes at these shapes) over the kernel's device time."""
from benchmark.metrics._hist_kernel import kernel_events
from benchmark.trace import work, xplane


def read(run):
    found = kernel_events(run)
    if found is None or not run.tree_counts:
        return None
    least = sum(work.least_seconds(work.histogram_pass(
        work.histogram_rows(c), run.features), run.peaks)[0]
        for c in run.tree_counts)
    return 100.0 * least / xplane.union_s(found)
