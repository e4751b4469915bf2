"""The Pallas histogram kernel's events in a trace, shared by the metrics
of the histogram layer (not a metric itself)."""

KERNEL = r"hist_kernel|pallas_histogram"


def kernel_events(run):
    """The kernel's op events, or None where the run has none to read."""
    if run.trace is None:
        return None
    found = run.trace.ops().matching(KERNEL)
    return found if len(found) else None
