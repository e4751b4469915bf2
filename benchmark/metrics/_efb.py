"""The trace and the counters of a run over EFB bundles, read once for the
readers of the ``efb_*`` metrics (not a metric itself): the device time
under ``obs_unpack`` (a bundle histogram unpacked per feature), in whichever
program, from one pass over the file; the program's ``efb/*`` counters as
they moved since the window opened, and ``efb/groups`` as bundling left
it."""
import time

from benchmark.harness import program_obs
from benchmark.metrics import _stages
from benchmark.trace import scopes, work, work_efb, xplane

UNPACK = "obs_unpack"


def unpack_seconds(run):
    """Self time of the operations under ``obs_unpack``; None where the
    run has no trace or no operation carries the scope (a program from
    before it)."""
    if run.trace is None or not run.iterations:
        return None
    if not hasattr(run, "_efb_unpack_s"):
        run._efb_unpack_s = None
        t0 = time.perf_counter()
        path = _stages._newest_xplane()
        ops = scopes.load_ops(path) if path else None
        if ops is not None and len(ops.line) == len(run.trace.ops()):
            keys = [UNPACK in stack.rstrip(":").split("/") or None
                    for stack in ops.tf_op]
            if any(keys):
                run._efb_unpack_s = xplane.self_times(xplane.Line(
                    keys, ops.line.start, ops.line.dur)).get(True, 0.0)
            print("%s: %s s; read in %.3f s" % (
                UNPACK, run._efb_unpack_s, time.perf_counter() - t0),
                flush=True)
    return run._efb_unpack_s


def moved(run, name: str):
    """What the program's counter ``name`` moved by since the window
    opened; None where it never moved (a program that does not count it,
    a run with the stage timer off)."""
    value = program_obs.counter(name)
    if value is None:
        return None
    delta = value - getattr(run, "counters_at_window", {}).get(name, 0)
    return delta or None


def unpack_work(run):
    """``work_efb.unpack_pass`` of the window's unpacks, from the entries
    the program counted; None where it counted none."""
    entries = moved(run, "efb/unpacked_entries")
    bundle_entries = moved(run, "efb/bundle_entries")
    if not entries or not bundle_entries:
        return None
    return work_efb.unpack_pass(entries, bundle_entries)


def least_seconds(parts, peaks: dict) -> float:
    return sum(work.least_seconds(p, peaks)[0] for p in parts)


def step_passes(run):
    """Every pass of ``work_efb.step`` over the window's trees; None where
    the program counted no bundles or unpacks, or the run grew no tree."""
    groups = program_obs.counter("efb/groups")
    entries = moved(run, "efb/unpacked_entries")
    bundle_entries = moved(run, "efb/bundle_entries")
    if not groups or not entries or not bundle_entries \
            or not run.tree_counts:
        return None
    return work_efb.step(run.tree_counts, run.rows, groups, entries,
                         bundle_entries)
