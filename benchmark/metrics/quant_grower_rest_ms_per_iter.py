"""Device time per iteration of the grower's programs outside the
histogram: partition, compaction, the store, the split scan (with
``obs_dequantize`` inside it) and what carries no stage. With
``quant_hist_ms_per_iter`` it adds up to ``quant_grower_ms_per_iter``."""
from benchmark.metrics import _stages

HIST = ("obs_hist_subtract",) + _stages.HIST


def read(run):
    times = _stages.stage_times(run)
    if times is None:
        return None
    rest = times.total_s - sum(times.stages.get(s, 0.0) for s in HIST)
    return 1e3 * rest / run.iterations
