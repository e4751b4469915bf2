"""Share of the traced window of a training cell with linear leaves in which
no operation ran on the device (as ``device_idle_pct.train``)."""


def read(run):
    if not run.window_s or not run.busy_s:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
