"""The programs of a boosting iteration on the device clock, shared by the
readers that account for what of an iteration is not the grower (not a
metric itself). Everything here reads ``run.trace``, which the runner
loaded already: the programs' events on the ``XLA Modules`` line, each named
``jit_<function>(<fingerprint>)``, and the program's ranges on the host
lines. Programs of one device do not overlap, so the terms of ``terms``
add up to the window."""
import re

from benchmark.metrics.grower_ms_per_iter import PROGRAMS as GROWER
from benchmark.trace import xplane

# a program runs under its function's name (obs/compile.py instrument_jit)
ROOT = r"^jit__root_impl\b"
# the validation rows' walk of the new tree, its leaf values, their addition
WALK = (r"^jit_(_traverse_body|_gather_leaf_values_body"
        r"|gbdt_valid_score_add)\b")
# the objective's gradient program and the score column it reads
GRADIENTS = r"^jit_(_grads|gbdt_take_col)\b"
SCORE_UPDATE = r"^jit_gbdt_score_delta\b"
NAMED = {"grower": GROWER, "validation walk": WALK, "gradients": GRADIENTS,
         "score update": SCORE_UPDATE}
OTHER, IDLE = "other", "idle"


def program_ms_per_iter(run, pattern):
    """Device milliseconds per iteration of the window's programs whose
    name matches; None where the run has no trace or ran no such program
    (a cell without a validation set, a program from before the name)."""
    if run.trace is None or not run.iterations:
        return None
    found = run.trace.modules().matching(pattern)
    if not len(found):
        return None
    return 1e3 * xplane.union_s(found) / run.iterations


def span_ms_per_iter(run, span):
    """Host milliseconds per iteration of the window's ranges named
    ``span``; None where the run has no trace or the program opens no such
    range."""
    if run.trace is None or not run.iterations:
        return None
    found = [line.matching("^%s$" % re.escape(span))
             for line in run.trace.host.values()]
    if not any(len(line) for line in found):
        return None
    return 1e3 * sum(line.total_s() for line in found) / run.iterations


def other_programs(run):
    """The events of every program that no pattern of ``NAMED`` reads:
    staging of ``gh``, sampling, the discretizer, jax's own eager
    programs, anything unnamed. None where the run has no trace."""
    if run.trace is None:
        return None
    return run.trace.modules().matching(
        "^(?!%s)" % "|".join("(?:%s)" % p for p in NAMED.values()))


def by_program(line) -> dict:
    """Seconds per program name, the fingerprint taken off."""
    return xplane.self_times(line, lambda name: name.split("(")[0])


def terms(run):
    """``{term: ms per iteration}``: the programs of ``NAMED``, every other
    program, and the window's remainder, in which no program ran. None
    where the run has no trace or no window."""
    if run.trace is None or not run.iterations or not run.window_s:
        return None
    out = {term: program_ms_per_iter(run, pattern) or 0.0
           for term, pattern in NAMED.items()}
    out[OTHER] = 1e3 * xplane.union_s(other_programs(run)) / run.iterations
    out[IDLE] = 1e3 * (run.window_s - xplane.union_s(run.trace.modules())) \
        / run.iterations
    return out
