"""In-bag rows of the smaller children over the rows the histogram passes
visited for them, over the window's trees (what the program's
``grow/hist_rows_in_bag`` and ``grow/hist_rows_bucketed`` moved by since the
runner noted them at the window's start; counted while its stage timer is
on): the share of the visited rows that carry weight."""
from benchmark.metrics import _goss


def read(run):
    return _goss.counters_share(run, "grow/hist_rows_in_bag",
                                "grow/hist_rows_bucketed")
