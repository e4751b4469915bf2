"""Device time of the grower's programs (``mesh.root``, ``mesh.tree``: the
jitted ``_root_impl`` and ``_tree_impl``) per boosting iteration."""
from benchmark.trace import xplane

PROGRAMS = r"^jit_?(_root_impl|_tree_impl|mesh[._]root|mesh[._]tree)\b"


def read(run):
    if run.trace is None or not run.iterations:
        return None
    found = run.trace.modules().matching(PROGRAMS)
    if not len(found):
        return None
    return 1e3 * xplane.union_s(found) / run.iterations
