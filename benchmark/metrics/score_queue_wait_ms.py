"""From a client's submit (the start of its ``bench::block`` range) to the
start of the ``serve::predict_batch`` that scored the block, median over the
window's blocks. The queue is first in, first out with one worker, so the
k-th range of the one kind belongs to the k-th of the other."""
import numpy as np

from benchmark.metrics._score import BLOCK, DISPATCH, host_ranges


def read(run):
    blocks, dispatches = host_ranges(run, BLOCK), host_ranges(run, DISPATCH)
    if blocks is None or dispatches is None or len(blocks) != len(dispatches):
        return None
    return float(np.median([(d[0] - b[0]) * 1e-6
                            for b, d in zip(blocks, dispatches)]))
