"""The share of the row-features the smaller children's histogram passes
visit that carry weight: in-bag rows over visited rows (the program's
``grow/hist_rows_in_bag`` over ``grow/hist_rows_bucketed``) times sampled
columns over all columns (``sample/cols_in_mask`` over
``sample/cols_total``), counted while its stage timer is on."""
from benchmark.metrics import _subsample


def read(run):
    return _subsample.weighted_share(run)
