"""One run of one cell: set-up, a measured window, the check, one line.

Everything that belongs to a cell is found by name from ``BENCHMARK.json``:
its configuration file (widths, scale, build, search and service
settings), its traffic file under ``bench/traffic/`` and a reader file per
per-layer metric under ``bench/metrics/``. Nothing here names a cell.

Set-up (``setup_s``, from process start): the corpus is made on the device
from the seed; the sealed index is loaded from ``bench/.cache/index`` or
built there with ``build_pool_segment``, served through
``HybridSearchService`` over a ``SegmentPool`` fronted by a
``SegmentRouter``, and one batch of every shape bucket the traffic can fill
is run, so that nothing compiles in the window. The window then offers the
cell's open-loop traffic for ``--seconds``. Once it has closed and every
answer due has come back (or a minute has passed), the device's peak memory
is read, the program's state is freed, and the answers are compared with
the plain reference (``bench/check.py``).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import queue
import shutil
import sys
import threading
import time
from pathlib import Path

import numpy as np

import jax

from bench import check, corpus as corpus_mod, index_cache, reference, trace_reduce
from bench import traffic as traffic_mod

from repro.core import BuildConfig, KnnConfig, PruneConfig
from repro.core.fusion import FusionSpec
from repro.core.search import SearchParams
from repro.core.segment_pool import SegmentPool, build_pool_segment, place_pool
from repro.core.usms import FusedVectors, SparseVec
from repro.serving.batcher import BatcherConfig, SearchRequest
from repro.serving.hybrid_service import HybridSearchService, ServiceConfig
from repro.serving.segment_router import RouterConfig, SegmentRouter

ROOT = Path(__file__).resolve().parents[1]
CACHE = Path("bench") / ".cache"
K = 10
DRAIN_S = 60.0  # an answer later than this past the window's close never came
POLL_S = 0.0005  # how often a client looks for its answers
TRACE_S = 4.0  # seconds of a traced run's window that the profiler records


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# the cell, by name
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config_file: Path
    cfg: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT, rehearse: bool = False) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config_file = root / conf["file"]
    cfg = json.loads(config_file.read_text())
    if rehearse:
        cfg = {**cfg, **cfg.get("rehearse", {})}
    traffic = traffic_mod.load(root / "bench" / "traffic" / f"{w['traffic']}.json")
    if rehearse:
        traffic = {**traffic, **traffic.get("rehearse", {})}
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
    ]
    return Cell(name, w["config"], config_file, cfg, traffic, int(w["chips"]), e2e, per_layer)


def metric_reader(root: Path, name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{abs(hash(name))}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class RunRecord:
    """What a per-layer reader may read: the cell's settings, the profiler
    planes and bounds of the traced part of the window (``Tracer``), and the
    program's counters over that part."""

    cfg: dict
    traffic: dict
    device_kind: str
    planes: list
    lo_ns: float
    hi_ns: float
    counters: dict


# --------------------------------------------------------------------------
# the program's objects
# --------------------------------------------------------------------------


def fused(arrays) -> FusedVectors:
    d, si, sv, fi, fv = arrays
    return FusedVectors(d, SparseVec(si, sv), SparseVec(fi, fv))


def build_config(cfg: dict) -> BuildConfig:
    b = cfg["build"]
    return BuildConfig(
        knn=KnnConfig(**b["knn"]),
        prune=PruneConfig(**b["prune"]),
        n_entry=b["n_entry"],
        path_refine_iters=b["path_refine_iters"],
    )


def corpus_dtype(cfg: dict) -> str:
    """How the sealed corpus is stored: ``float32`` unless the
    configuration's search settings say ``int8``."""
    return cfg["search"].get("corpus_dtype", "float32")


def make_service(pool, cfg: dict, mix: dict):
    s = cfg["service"]
    dev = jax.devices()[0]
    mesh = jax.sharding.Mesh(np.asarray([dev]), ("data",))
    service = HybridSearchService(
        place_pool(pool, mesh),
        SearchParams(
            k=K,
            iters=cfg["search"]["iters"],
            pool_size=cfg["search"]["pool_size"],
            use_keywords=bool(mix.get("use_keywords", False)),
            corpus_dtype=corpus_dtype(cfg),
        ),
        ServiceConfig(
            batcher=BatcherConfig(
                max_batch=s["max_batch"],
                flush_size=s["max_batch"],
                flush_deadline_s=s["flush_deadline_s"],
            )
        ),
        mesh=mesh,
    )
    SegmentRouter(service, build_config(cfg), RouterConfig(seal_threshold=s["seal_threshold"]))
    return service


def make_requests(entries, host_queries) -> list:
    qd, qsi, qsv, qfi, qfv = host_queries
    specs = {}
    out = []
    for e in entries:
        key = (e.mode, e.weights)
        if key not in specs:
            specs[key] = FusionSpec.make(e.mode, *e.weights)
        i = e.qrow
        out.append(
            SearchRequest(
                query=FusedVectors(qd[i], SparseVec(qsi[i], qsv[i]), SparseVec(qfi[i], qfv[i])),
                fusion=specs[key],
                k=K,
                keywords=np.asarray(e.keywords, np.int32) if e.keywords else None,
            )
        )
    return out


class CompileClock:
    """Compilations and their seconds, from JAX's own compile events."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


# --------------------------------------------------------------------------
# clients
# --------------------------------------------------------------------------


class Answers:
    """Each request's answer and the client-clock time it was seen."""

    def __init__(self):
        self.lock = threading.Lock()
        self.entry: dict = {}
        self.due: dict = {}
        self.done: dict = {}
        self.result: dict = {}
        self.failed: set = set()

    def sent(self, r: int, entry: int, due: float) -> None:
        with self.lock:
            self.entry[r] = entry
            self.due[r] = due

    def finish(self, r: int, pending) -> None:
        t = time.perf_counter()
        try:
            ids, scores = pending.result(timeout=DRAIN_S)
            res = (np.asarray(ids), np.asarray(scores), np.asarray(pending.path_scores))
        except Exception as err:  # the request failed: a missing answer
            log(f"request {r} failed: {err!r}")
            res = None
        with self.lock:
            self.done[r] = t
            if res is None:
                self.failed.add(r)
            else:
                self.result[r] = res

    def fail(self, r: int, err) -> None:
        log(f"request {r} refused: {err!r}")
        with self.lock:
            self.done[r] = time.perf_counter()
            self.failed.add(r)

    def answered(self) -> list:
        return sorted(r for r in self.result)

    def unanswered(self) -> int:
        return sum(1 for r in self.entry if r not in self.result)


def drive_open(service, requests, order, due_s, t0: float, clients: int) -> tuple:
    """Send request r at ``t0 + due_s[r]`` whatever came back. ``clients``
    threads take the requests in turn, each sleeping until its request is
    due; ``submit`` runs a batch it fills on the caller's thread, and the
    other clients keep sending meanwhile, as the users of a front end do.
    Returns the answers and how late each request was sent (seconds)."""
    ans = Answers()
    handed = queue.SimpleQueue()
    late = np.zeros(len(due_s))
    turn = iter(range(len(due_s)))
    take = threading.Lock()

    def client():
        while True:
            with take:
                r = next(turn, None)
            if r is None:
                return
            wait = t0 + due_s[r] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[r] = time.perf_counter() - (t0 + due_s[r])
            ans.sent(r, int(order[r]), t0 + due_s[r])
            try:
                handed.put((r, service.submit(requests[order[r]])))
            except Exception as err:
                ans.fail(r, err)

    threads = [
        threading.Thread(target=client, name=f"bench-client-{i}", daemon=True)
        for i in range(max(int(clients), 1))
    ]
    for th in threads:
        th.start()
    deadline = t0 + (due_s[-1] if len(due_s) else 0.0) + DRAIN_S
    waiting = []
    while True:
        sending = any(th.is_alive() for th in threads)
        while True:
            try:
                waiting.append(handed.get_nowait())
            except queue.Empty:
                break
        keep = []
        for r, p in waiting:
            if p.done:
                ans.finish(r, p)
            else:
                keep.append((r, p))
        waiting = keep
        if not sending and not waiting and handed.empty():
            break
        if time.perf_counter() > deadline:
            break
        time.sleep(POLL_S)
    for th in threads:
        th.join(timeout=DRAIN_S)
    return ans, late


def warm_up(service, requests, entries, max_batch: int) -> int:
    """Compile, or load from the persistent cache, everything the window
    can run: the batcher's eager stacking and padding ops, one set per batch
    size (1 to ``max_batch``, at the narrowest keyword width), and the
    serving executable of every bucket, one per power-of-two batch size and
    keyword width. The keyword list's width changes only the executable:
    the batcher copies keywords to the device without compiling."""
    by_width: dict = {}
    for i, e in enumerate(entries):
        w = 1 << max(len(e.keywords) - 1, 0).bit_length()
        by_width.setdefault(w, []).append(i)
    narrow = min(by_width)
    batches = 0
    for w in traffic_mod.bucket_widths(entries):
        # a batch's widest keyword list sets its bucket's width
        head = by_width[w][:1] if w != narrow else []
        sizes = range(1, max_batch + 1) if w == narrow else [
            1 << j for j in range(max_batch.bit_length()) if 1 << j <= max_batch
        ]
        for m in sizes:
            pick = (head + by_width[narrow])[:m]
            pend = [service.submit(requests[i]) for i in pick]
            service.flush()
            for p in pend:
                p.result(timeout=DRAIN_S)
            batches += 1
    return batches


# --------------------------------------------------------------------------
# reading the answers
# --------------------------------------------------------------------------


def answer_arrays(ans: Answers, entries) -> dict:
    rs = ans.answered()
    kw = np.full((len(rs), 2), -1, np.int32)
    for j, r in enumerate(rs):
        e = entries[ans.entry[r]]
        kw[j, : len(e.keywords)] = e.keywords
    if rs:
        ids = np.stack([ans.result[r][0] for r in rs]).astype(np.int64)
        scores = np.stack([ans.result[r][1] for r in rs]).astype(np.float64)
        path = np.stack([ans.result[r][2] for r in rs]).astype(np.float64)
    else:
        ids, scores, path = np.zeros((0, K), np.int64), np.zeros((0, K)), np.zeros((0, K, 3))
    return {
        "r": np.asarray(rs, np.int64),
        "entry": np.asarray([ans.entry[r] for r in rs], np.int64),
        "qrow": np.asarray([entries[ans.entry[r]].qrow for r in rs], np.int64),
        "mode": np.asarray([entries[ans.entry[r]].mode for r in rs]),
        "weights": np.asarray([entries[ans.entry[r]].weights for r in rs], np.float64).reshape(-1, 3),
        "keywords": kw,
        "ids": ids,
        "scores": scores,
        "path": path,
    }


def weighted_answers(arr: dict, entries) -> np.ndarray:
    """Positions in ``arr`` of the first answer to each distinct
    weighted-sum pool entry."""
    first: dict = {}
    for j, e in enumerate(arr["entry"]):
        if entries[e].mode == reference.WEIGHTED_SUM and int(e) not in first:
            first[int(e)] = j
    return np.asarray(list(first.values()), np.int64)


def recall_at_k(arr: dict, entries, host_queries, passages, cfg: dict) -> float:
    """Recall@10 over the distinct weighted-sum pool entries answered,
    against the exact reference over ``passages``.
    The reference scores every weighted-sum entry of the pool, a count the
    seed does not change, so its programs have the same shapes in every
    run."""
    js = weighted_answers(arr, entries)
    if not len(js):
        return float("nan")
    pool = [i for i, e in enumerate(entries) if e.mode == reference.WEIGHTED_SUM]
    at = {e: j for j, e in enumerate(pool)}
    kw = np.full((len(pool), 2), -1, np.int32)
    for j, i in enumerate(pool):
        kw[j, : len(entries[i].keywords)] = entries[i].keywords
    rows = np.asarray([entries[i].qrow for i in pool])
    _, truth = reference.exact_topk(
        tuple(a[rows] for a in host_queries),
        np.asarray([entries[i].weights for i in pool]), kw, passages, K,
        vocab_sparse=cfg["vocab_sparse"], vocab_lexical=cfg["vocab_lexical"],
    )
    hits = total = 0
    for j in js:
        want = truth[at[int(arr["entry"][j])]]
        want = want[want >= 0]
        hits += len(set(arr["ids"][j].tolist()) & set(want.tolist()))
        total += len(want)
    return hits / max(total, 1)


def percentile(values: np.ndarray, q: float) -> float:
    """The q-th percentile by the nearest-rank rule (a missing answer is
    +inf, so it lands in the tail)."""
    v = np.sort(values)
    return float(v[min(len(v) - 1, max(0, math.ceil(q / 100 * len(v)) - 1))])


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


def _device_check(cell: Cell, rehearse: bool):
    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        raise NoDevice(f"no TPU found (platform {devs[0].platform})")
    if len(devs) < cell.chips:
        raise NoDevice(f"the cell asks for {cell.chips} chips, JAX sees {len(devs)}")
    return devs


def _peak_bytes(devs) -> int | None:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _index(cell: Cell, root: Path, seed: int, dev_corpus, device_kind: str, clock):
    key = index_cache.cache_key(root, cell.config_file, seed, device_kind)
    cache_root = root / CACHE / "index"
    t = time.perf_counter()
    pool = index_cache.load(cache_root, cell.config_name, key)
    if pool is not None:
        log(f"index: loaded from the cache in {time.perf_counter() - t:.2f} s")
        return pool
    c0 = clock.seconds
    seg = build_pool_segment(
        fused(dev_corpus.passages()),
        np.arange(dev_corpus.n),
        build_config(cell.cfg),
        key=jax.random.fold_in(corpus_mod.seed_key(seed), 1),
        corpus_dtype=corpus_dtype(cell.cfg),
    )
    jax.block_until_ready(seg)
    pool = SegmentPool.from_segmented(seg)
    log(
        f"index: built {dev_corpus.n} passages in {time.perf_counter() - t:.2f} s "
        f"(compile {clock.seconds - c0:.2f} s)"
    )
    t = time.perf_counter()
    index_cache.save(cache_root, cell.config_name, key, pool)
    log(f"index: saved in {time.perf_counter() - t:.2f} s")
    return pool


def _counters(service) -> dict:
    m = service.metrics
    qw = m.get("allanpoe_serving_queue_wait_seconds").snapshot()
    return {
        "requests": service.stats.requests,
        "padded_slots": service.stats.padded_slots,
        "batches": service.stats.batches,
        "queue_wait_s_sum": qw.sum,
        "queue_wait_count": qw.count,
    }


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


class Tracer:
    """The profiler over the middle ``TRACE_S`` seconds of the window, for
    ``--trace 1``: a timer thread starts and stops it while the clients
    send, and reads the program's counters at both ends. A whole window of
    small batches holds millions of device and host events, which took
    minutes to read back on a TPU v5e."""

    def __init__(self, trace_dir: Path, counters):
        self.dir = trace_dir
        self.counters = counters
        self.counted: dict = {}
        self.planes = None
        self.lo = self.hi = 0.0

    def start(self, t0: float, seconds: float) -> None:
        span = min(TRACE_S, seconds)
        begin = t0 + (seconds - span) / 2
        shutil.rmtree(self.dir, ignore_errors=True)
        self._thread = threading.Thread(
            target=self._trace, args=(begin, begin + span), name="bench-tracer", daemon=True
        )
        self._thread.start()

    def _trace(self, begin: float, end: float) -> None:
        time.sleep(max(begin - time.perf_counter(), 0.0))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        before = self.counters()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT):
            time.sleep(max(end - time.perf_counter(), 0.0))
        self.counted = _delta(before, self.counters())
        jax.profiler.stop_trace()

    def finish(self) -> str:
        """Read the trace back; returns a line on what it held."""
        self._thread.join()
        t = time.perf_counter()
        self.planes = trace_reduce.load(str(self.dir))
        self.lo, self.hi = trace_reduce.window(self.planes)
        shutil.rmtree(self.dir, ignore_errors=True)
        ops = [e for d in trace_reduce.device_planes(self.planes) for e in trace_reduce.op_events(d)
               if self.lo <= e.start_ns <= self.hi]
        n = sum(len(ln.events) for p in self.planes for ln in p.lines)
        span = (self.hi - self.lo) / 1e9
        first = (min(e.start_ns for e in ops) - self.lo) / 1e9 if ops else None
        last = (max(e.end_ns for e in ops) - self.lo) / 1e9 if ops else None
        return (f"{n} events read in {time.perf_counter() - t:.2f} s; device operations "
                f"from {first} s to {last} s of the {span:.3f} s traced")


@dataclasses.dataclass
class Measured:
    """A run up to the close of its window: the cell, the data it made, and
    what the window produced (``result``, see ``_run_window``)."""

    cell: Cell
    devs: list
    corpus: corpus_mod.Corpus
    entries: list
    host_queries: tuple
    result: dict
    peak_bytes: int | None


def make_data(cell: Cell, seed: int) -> tuple:
    """The seed's corpus on the device, the request pool of the cell's
    traffic, the queries on the host, and one request per pool entry."""
    cfg, mix = cell.cfg, cell.traffic
    n_pool = int(mix["query_pool"])
    t = time.perf_counter()
    widths = corpus_mod.Widths.from_config(cfg)
    corpus = corpus_mod.generate(corpus_mod.seed_key(seed), widths, cfg["n_docs"], n_pool)
    jax.block_until_ready(corpus)
    log(f"corpus: {cfg['n_docs']} passages, {n_pool} queries in {time.perf_counter() - t:.2f} s")
    entries = traffic_mod.pool_entries(mix, np.asarray(corpus.q_shared_kw), seed)
    host_queries = tuple(np.asarray(a) for a in corpus.queries())
    return corpus, entries, host_queries, make_requests(entries, host_queries)


def ready_service(cell: Cell, root: Path, seed: int, corpus, entries, requests, clock,
                  device_kind: str):
    """The cell's index (from the cache, or built and cached) behind a
    warmed-up service with its deadline pump running."""
    cfg = cell.cfg
    pool = _index(cell, root, seed, corpus, device_kind, clock)
    service = make_service(pool, cfg, cell.traffic)
    del pool
    t = time.perf_counter()
    c0 = clock.seconds
    n_warm = warm_up(service, requests, entries, cfg["service"]["max_batch"])
    service.start_pump(cfg["service"]["pump_interval_s"])
    log(
        f"warm-up: {n_warm} batches, {len(service.executable_cache)} executables in "
        f"{time.perf_counter() - t:.2f} s (compile {clock.seconds - c0:.2f} s)"
    )
    return service


def measure(name: str, seed: int, seconds: float, trace: bool, rehearse: bool,
            t_start: float, root: Path = ROOT) -> Measured:
    """Set up cell ``name``, run its window, and read the device's peak
    memory once the program's state is freed."""
    cell = load_cell(name, root, rehearse)
    devs = _device_check(cell, rehearse)
    clock = CompileClock()
    corpus, entries, host_queries, requests = make_data(cell, seed)
    result = _run_window(cell, seed, seconds, trace, corpus, entries, requests, clock,
                         devs[0].device_kind, root, f"{name}-{seed}", t_start)
    gc.collect()
    return Measured(cell, devs, corpus, entries, host_queries, result,
                    _peak_bytes(devs[: cell.chips]))


def host_data(m: Measured) -> tuple:
    """The plain passages and queries on the host, and the per-path stats
    of the served passages."""
    host = {"queries": m.host_queries, "passages": tuple(np.asarray(a) for a in m.corpus.passages())}
    return host, reference.path_stats(host["passages"])


def compared(m: Measured) -> dict:
    """Every number ``correct`` is decided by: the served scores, keywords
    and ids against the plain reference (``check.compare``) and recall@10
    against the exact reference's top-10."""
    t = time.perf_counter()
    host, stats = host_data(m)
    arr = m.result["answers"]
    t1 = time.perf_counter()
    numbers = check.compare(arr, host, (0, m.corpus.n), stats, m.result["unanswered"])
    t2 = time.perf_counter()
    numbers["recall_at_10"] = recall_at_k(arr, m.entries, m.host_queries, m.corpus.passages(),
                                          m.cell.cfg)
    log(
        f"reference: {time.perf_counter() - t:.2f} s (host copy {t1 - t:.2f} s, "
        f"answers {t2 - t1:.2f} s, recall {time.perf_counter() - t2:.2f} s)"
    )
    return numbers


def run(name: str, seed: int, seconds: float, trace: bool, rehearse: bool,
        t_start: float, root: Path = ROOT) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    m = measure(name, seed, seconds, trace, rehearse, t_start, root)
    cell, result, dev = m.cell, m.result, m.devs[0]
    cfg, mix = cell.cfg, cell.traffic
    numbers = compared(m)
    correct, checks = check.verdict(numbers, cfg["check"])

    values = dict(result["e2e"], recall_at_10=numbers["recall_at_10"], setup_s=result["setup_s"])
    metrics = {}
    if trace:
        run_rec = RunRecord(cfg, mix, dev.device_kind, result["planes"], result["lo"],
                            result["hi"], result["counters"])
        for spec in cell.per_layer:
            v = metric_reader(root, spec["name"])(run_rec)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    else:
        for spec in cell.end_to_end:
            metrics[spec["name"]] = {"value": values.get(spec["name"]), "unit": spec["unit"]}
    if rehearse:  # a CPU rehearsal measures nothing of the device
        metrics = {k: {"value": None, "unit": v["unit"]} for k, v in metrics.items()}

    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(m.devs),
        "memory_peak_bytes": None if rehearse else m.peak_bytes,
    }
    out = {
        "correct": bool(correct),
        "attempted": int(result["attempted"]),
        "failed": int(result["unanswered"]),
        "metrics": metrics,
        "device": device,
    }
    if trace and result["planes"] is not None:
        planes, lo, hi = result["planes"], result["lo"], result["hi"]
        device["busy_s"] = None if rehearse else trace_reduce.busy_seconds(planes, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {
            "device_ops": [list(x) for x in trace_reduce.top_ops(planes, lo, hi)],
            "idle_gaps": [list(x) for x in trace_reduce.idle_gaps(planes, lo, hi)],
        }
    for k, v in result["log"].items():
        log(f"{k}: {v}")
    log(f"peak bytes: {m.peak_bytes}  compiles in window: {result['window_compiles']}  "
        f"setup_s: {result['setup_s']}")
    for k, v in checks.items():
        log(f"check {k}: {v['value']} ({v['fails']} limit {v['limit']})")
    out["checks"] = checks
    return out


def _run_window(cell, seed, seconds, trace, corpus, entries, requests, clock, device_kind,
                root, tag, t_start) -> dict:
    mix = cell.traffic
    service = ready_service(cell, root, seed, corpus, entries, requests, clock, device_kind)
    setup_s = time.time() - t_start

    compiles0 = clock.count
    before = _counters(service)
    due = traffic_mod.arrivals(float(mix["rate_qps"]), seconds, seed)
    order = traffic_mod.request_order(len(entries), len(due), seed)
    tracer = Tracer(root / CACHE / "trace" / tag, lambda: _counters(service)) if trace else None
    t0 = time.perf_counter()
    if tracer:
        tracer.start(t0, seconds)
    ans, late = drive_open(service, requests, order, due, t0, int(mix["clients"]))
    counters = _delta(before, _counters(service))
    traced = tracer.finish() if tracer else None
    window_compiles = clock.count - compiles0
    service.stop_pump()
    service = None
    gc.collect()

    due_abs = np.asarray([ans.due[r] for r in sorted(ans.due)])
    done = np.asarray([
        ans.done[r] if (r in ans.done and r not in ans.failed) else np.inf
        for r in sorted(ans.due)
    ])
    lat = done - due_abs
    return {
        "e2e": {"latency_p50_ms": percentile(lat, 50) * 1e3},
        "answers": answer_arrays(ans, entries),
        "attempted": len(ans.entry),
        "unanswered": ans.unanswered(),
        "counters": tracer.counted if tracer else counters,
        "planes": tracer.planes if tracer else None,
        "lo": tracer.lo if tracer else 0.0,
        "hi": tracer.hi if tracer else 0.0,
        "window_compiles": window_compiles,
        "log": {
            "batches": counters["batches"],
            "requests": counters["requests"],
            "latency_p99_ms": percentile(lat, 99) * 1e3,
            "sender_late_ms_p99_max": (percentile(late, 99) * 1e3, float(late.max()) * 1e3),
            "offered_qps": len(due_abs) / seconds,
            "trace": traced,
        },
        "setup_s": setup_s,
    }


def main(args, t_start: float, root: Path = ROOT) -> int:
    try:
        out = run(args.workload, args.seed, float(args.seconds), bool(args.trace),
                  args.rehearse, t_start, root)
    except NoDevice as err:
        log(f"bench: {err}")
        return 2
    print(json.dumps(_plain(out), allow_nan=False), flush=True)
    return 0


def _plain(x):
    """JSON-ready copy: numpy scalars to Python numbers, and a number that
    is not finite (a missing answer's latency) to null."""
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if isinstance(x, (bool, np.bool_)):
        return bool(x)
    if isinstance(x, (int, np.integer)):
        return int(x)
    if isinstance(x, (float, np.floating)):
        return float(x) if math.isfinite(x) else None
    return x
