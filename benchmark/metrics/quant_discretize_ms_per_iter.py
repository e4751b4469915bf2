"""Device time per iteration of the gradient discretizer (``obs_quantize``:
the two maxima, the draw, the rounding and the stack into int8 rows), in
whichever program it was traced."""
from benchmark.metrics import _quant


def read(run):
    seconds = _quant.scope_seconds(run, "obs_quantize")
    return None if seconds is None else 1e3 * seconds / run.iterations
