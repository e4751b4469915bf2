"""The unpacks' least time (``trace/work_efb.py``: each bundle histogram
read once and each per-feature histogram written once, of the bundles' and
the features' own bins, as the program counted them over the window's
unpacks) over the device time under ``obs_unpack``, whatever implements
them."""
from benchmark.metrics import _efb


def read(run):
    seconds, unpack = _efb.unpack_seconds(run), _efb.unpack_work(run)
    if not seconds or unpack is None:
        return None
    return 100.0 * _efb.least_seconds([unpack], run.peaks) / seconds
