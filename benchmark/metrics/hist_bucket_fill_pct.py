"""Rows the smaller children's histograms had to visit over the rows of
the buckets they were padded to (the program's ``grow/hist_rows_needed``
over ``grow/hist_rows_bucketed``, counted while its stage timer is on), as
they moved since the runner noted them at the window's start."""
from benchmark.metrics import _goss


def read(run):
    return _goss.counters_share(run, "grow/hist_rows_needed",
                                "grow/hist_rows_bucketed")
