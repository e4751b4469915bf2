"""What the scoring layers' metrics share (not a metric itself): the least
time for the window's answered blocks, the device programs a dispatch
launches, and the program's and the benchmark's host ranges in the trace."""
from benchmark.trace import work, work_score

# the programs ``serve.stacked_raw`` launches (quantise, walk, accumulate)
PROGRAMS = r"^jit_?(_stacked_raw_body|serve[._]stacked_raw)\b"
DISPATCH = r"^serve::predict_batch$"
BLOCK = r"^bench::block$"


def answered(run) -> list:
    """The window's answered blocks; none for a run of another kind."""
    return run.answered() if hasattr(run, "answered") else []


def least_seconds(run):
    """Least seconds the chip could take for the answered blocks, or None
    where the run answered none or walked no reference."""
    blocks = answered(run)
    if not blocks or not run.hops_per_row:
        return None
    return sum(work.least_seconds(work_score.block(
        b.rows, run.features, run.trees, run.leaves, run.hops_per_row),
        run.peaks)[0] for b in blocks)


def walk_events(run):
    """The scoring programs' events on the device, or None."""
    if run.trace is None:
        return None
    found = run.trace.modules().matching(PROGRAMS)
    return found if len(found) else None


def host_ranges(run, pattern):
    """``(start, end)`` in nanoseconds of the host ranges that match, over
    all threads, in order of their start; None where there is none."""
    if run.trace is None:
        return None
    found = []
    for line in run.trace.host.values():
        sel = line.matching(pattern)
        found.extend(zip(sel.start.tolist(), (sel.start + sel.dur).tolist()))
    return sorted(found) or None
