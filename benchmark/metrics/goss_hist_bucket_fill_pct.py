"""Rows the smaller children's histograms had to visit, in the bag or not,
over the rows of the buckets they were padded to, over the window's trees
(what the program's ``grow/hist_rows_needed`` and ``grow/hist_rows_bucketed``
moved by since the runner noted them at the window's start):
``hist_bucket_fill_pct`` of the sampled iterations alone."""
from benchmark.metrics import _goss


def read(run):
    return _goss.counters_share(run, "grow/hist_rows_needed",
                                "grow/hist_rows_bucketed")
