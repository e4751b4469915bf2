"""From a profiler trace to numbers: the reduction, the peaks, the work."""
