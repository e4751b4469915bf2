"""The program's ``io::efb_bundle`` span: finding the bundles from the
sampled rows (Algorithm 3), summed over the data sets built in set-up."""
from benchmark.harness import program_obs


def read(run):
    return program_obs.stage_total("io::efb_bundle")
