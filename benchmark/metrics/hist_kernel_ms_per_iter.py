"""Device time of the Pallas histogram kernel's events per iteration."""
from benchmark.metrics._hist_kernel import kernel_events
from benchmark.trace import xplane


def read(run):
    found = kernel_events(run)
    if found is None or not run.iterations:
        return None
    return 1e3 * xplane.union_s(found) / run.iterations
