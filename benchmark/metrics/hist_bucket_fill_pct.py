"""Rows the smaller children's histograms had to visit over the rows of
the buckets they were padded to (the program's ``grow/hist_rows_needed``
over ``grow/hist_rows_bucketed``, counted while its stage timer is on)."""
from benchmark.harness import program_obs


def read(run):
    needed = program_obs.counter("grow/hist_rows_needed")
    bucketed = program_obs.counter("grow/hist_rows_bucketed")
    if not needed or not bucketed:
        return None
    return 100.0 * needed / bucketed
