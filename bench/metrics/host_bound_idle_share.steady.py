"""Share of the traced window in which the device runs no operation while
the service is inside a batch phase on some host thread, in %
(``bench/span_reduce.py``): the part of ``device_idle_share.steady`` the
host's batch work holds the device back. The rest of the idle share is the
device waiting for requests. Moves ``latency_p50_ms``."""

from bench import span_reduce


def read(run):
    if not run.planes:
        return None
    window = (run.hi_ns - run.lo_ns) / 1e9
    s = span_reduce.host_bound_idle_seconds(run.planes, run.lo_ns, run.hi_ns)
    if s is None or window <= 0:
        return None
    return 100.0 * s / window
