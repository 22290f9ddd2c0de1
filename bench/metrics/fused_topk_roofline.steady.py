"""The fused distance + top-k kernel's share of its roofline on the search
path with keywords on, in %: the least time the HBM bytes the traversal
needs take at the chip's peak bandwidth (``bench/kernel_bytes.py``: each request's
``iters`` rounds of ``degree`` neighbour rows at stored widths), over the
device time of the kernel's operations in the traced window, both calls of
each round (the primary pool and the keyword twin pool), so the share is
low by the twin pool's rows. The kernel is bound by HBM bandwidth (about
0.4 flop per byte against a ridge of 240). Moves ``latency_p50_ms``."""

from bench import kernel_bytes, trace_reduce
from bench.peaks import chip_peaks

KERNELS = ("fused_topk",)  # device operations fused_topk.<n>


def read(run):
    if not run.planes:
        return None
    t = trace_reduce.kernel_seconds(run.planes, KERNELS, run.lo_ns, run.hi_ns)
    n = run.counters.get("requests", 0)
    if t <= 0 or not n:
        return None
    need = kernel_bytes.search_fused_topk_bytes(run.cfg, n)
    return 100.0 * need / chip_peaks(run.device_kind).hbm_bw / t
