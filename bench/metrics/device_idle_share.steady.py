"""Share of the traced window in which no operation ran on the device, in
%, from the profiler trace (``bench/trace_reduce.py``)."""

from bench import trace_reduce


def read(run):
    if not run.planes or not trace_reduce.device_planes(run.planes):
        return None
    window = (run.hi_ns - run.lo_ns) / 1e9
    if window <= 0:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_seconds(run.planes, run.lo_ns, run.hi_ns) / window)
