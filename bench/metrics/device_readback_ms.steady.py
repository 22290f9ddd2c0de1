"""Mean time the host waited for a batch's results, in ms: the
``serving.device_readback`` host spans (the blocking copies of ids, scores,
path scores and expanded counts, after the traversal was enqueued) that
start in the traced window (``bench/span_reduce.py``). Moves
``latency_p50_ms``."""

from bench import span_reduce


def read(run):
    if not run.planes:
        return None
    return span_reduce.mean_ms(run.planes, "device_readback", run.lo_ns, run.hi_ns)
