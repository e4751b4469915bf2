"""Hops the validation rows need over the hops the lockstep walk runs for
them (the program's ``valid/walk_hops_needed`` over ``valid/walk_hops_run``:
rows times the tree's mean leaf depth weighted by its leaf counts, over rows
times ``next_pow2(depth)``), as they moved since the runner noted them,
else since its stage timer went on: set-up's trees are walked like the
window's."""
from benchmark.metrics import _goss


def read(run):
    return _goss.counters_share(run, "valid/walk_hops_needed",
                                "valid/walk_hops_run")
