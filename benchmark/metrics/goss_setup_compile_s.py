"""Host clock around the first ``Booster.update`` of a training cell under
gradient-based sampling, which traces, compiles (or reads the persistent
cache) and runs one iteration, less one warm iteration (the mean of the
warm-up's others): ``setup_compile_s`` for a runner that warms up. The
sampling's own program compiles later, in the first checked step."""


def read(run):
    warm = getattr(run, "warm_steps", 0)
    first = run.phases.get("compile + first step")
    others = run.phases.get("warm steps 2 to %d" % warm)
    if first is None or others is None or warm < 2:
        return None
    return max(first - others / (warm - 1), 0.0)
