"""The program's ``io::apply_bins`` span: binning every row of the data
sets built in set-up."""
from benchmark.harness import program_obs


def read(run):
    return program_obs.stage_total("io::apply_bins")
