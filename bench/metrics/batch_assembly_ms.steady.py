"""Mean time the service took to pack a batch, in ms: the
``serving.batch_assembly`` host spans that start in the traced window
(``bench/span_reduce.py``). Moves ``latency_p50_ms``."""

from bench import span_reduce


def read(run):
    if not run.planes:
        return None
    return span_reduce.mean_ms(run.planes, "batch_assembly", run.lo_ns, run.hi_ns)
