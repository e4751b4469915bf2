"""Device time under a scope that is no stage of the grower
(``obs_quantize`` sits in a program of its own, ``jit(_quantize_gh)``),
shared by the readers of the ``quant_*`` metrics (not a metric itself)."""
from benchmark.metrics import _stages
from benchmark.trace import scopes, xplane


def scope_seconds(run, scope: str):
    """Self time of the operations whose name stack has the segment
    ``scope``, anywhere on the device's ``XLA Ops`` line; None where the run
    has no trace or no operation carries the scope (a program from before
    it)."""
    if run.trace is None or not run.iterations:
        return None
    path = _stages._newest_xplane()
    ops = scopes.load_ops(path) if path else None
    if ops is None or len(ops.line) != len(run.trace.ops()):
        return None
    keys = [scope in stack.rstrip(":").split("/") for stack in ops.tf_op]
    if not any(keys):
        return None
    own = xplane.self_times(xplane.Line(keys, ops.line.start, ops.line.dur))
    return own.get(True, 0.0)
