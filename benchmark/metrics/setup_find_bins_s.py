"""The program's ``io::find_bins`` span: sampling and finding every
column's bin boundaries, summed over the data sets built in set-up."""
from benchmark.harness import program_obs


def read(run):
    return program_obs.stage_total("io::find_bins")
