"""Host clock around the first ``Booster.update``, which traces, compiles
(or reads the persistent cache) and runs one iteration, less one steady
warm iteration."""


def read(run):
    first = run.phases.get("compile + first step")
    steady = run.phases.get("steady warm step 2")
    if first is None or steady is None:
        return None
    return max(first - steady, 0.0)
