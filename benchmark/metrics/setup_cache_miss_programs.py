"""Programs compiled in set-up that the persistent cache did not hold and
was given (the program's ``jit_cache_misses``)."""
from benchmark.harness import program_obs


def read(run):
    if not program_obs.counts_cache_outcomes():
        return None
    misses = program_obs.counter("jit_cache_misses") or 0
    print("persistent cache: %d hits, %d entries written" % (
        program_obs.counter("jit_cache_hits") or 0, misses), flush=True)
    return misses
