"""Mean time a request waited in the batcher's queue, in ms: the service's
``allanpoe_serving_queue_wait_seconds`` histogram (enqueue to batch start)
over the traced window. Moves ``latency_p50_ms``."""


def read(run):
    c = run.counters
    if not c.get("queue_wait_count"):
        return None
    return 1e3 * c["queue_wait_s_sum"] / c["queue_wait_count"]
