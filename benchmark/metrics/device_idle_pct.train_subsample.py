"""Share of the traced window of a training cell under column and row
sampling in which no operation ran on the device (as
``device_idle_pct.train``; the result line's ``idle_gaps`` name the gaps by
the host spans, ``tree::sample_features`` and ``gbdt::bagging`` among
them)."""


def read(run):
    if not run.window_s or not run.busy_s:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
