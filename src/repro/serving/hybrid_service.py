"""Batched hybrid-search serving engine with a compiled-executable cache.

``HybridSearchService`` is the online request path the paper's throughput
claims (§5) presume but the one-shot ``search()`` API does not provide:

  * heterogeneous requests (any ``PathWeights``, optional keywords/entities,
    any ``k <= params.k``) are micro-batched into fixed shape-buckets by
    ``serving.batcher`` — batch padded to a power of two, keyword/entity
    widths padded to bucket caps;
  * every bucket hits an AOT-compiled executable cached on
    ``(index shape, bucket shape, SearchParams)``. Path weights enter as
    (B,) traced arrays per Theorem 1, so one executable serves every weight
    combination with zero retrace — the whole point of the paper's dynamic
    fusion framework (§4.2);
  * streaming updates go through ``insert()``/``mark_deleted()`` behind a
    copy-on-write snapshot swap: writers build the next immutable index off
    to the side and publish it atomically, so in-flight searches never
    observe a half-updated index;
  * the same service fronts a single-device ``HybridIndex`` and a sharded
    ``SegmentedIndex`` (via ``make_distributed_search_padded``) — the
    request path is identical, only the executable factory differs;
  * a segmented snapshot may carry a *grow segment* (a small mutable
    ``HybridIndex`` absorbing streaming inserts, managed by
    ``serving.segment_router.SegmentRouter``): reads fan out to the sealed
    executable AND an exact ``scan_padded`` pass over the grow segment (a
    graph search could miss a fresh passage even by its own vector; with
    KG params the grow segment is traversed by ``search_padded``, whose
    logical reward is defined by hops), then merge per-row top-k in
    global-id space. The grow pass deliberately uses its entry point's own
    jit cache, NOT the AOT ``executable_cache``, so sealed-segment
    executables survive every insert (the grow segment changes shape per
    insert; the sealed one does not);
  * token-bucket admission control (``BatcherConfig``-level queue bound is
    backpressure; ``AdmissionConfig`` buckets are rate policy) runs in front
    of ``MicroBatcher.enqueue``, with per-tenant quotas keyed on
    ``SearchRequest.tenant``.

Deadlines are evaluated on ``submit``/``poll``; a background pump thread
(``start_pump``/``ServiceConfig.pump_interval_s``) drives ``poll`` so
flush-on-deadline no longer depends on the submit path.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.distributed import (
    SegmentedIndex,
    make_distributed_search_padded,
    make_local_group_search,
    mesh_segment_count,
)
from repro.core.segment_pool import SegmentPool, group_shape_key
from repro.core.build_pipeline import insert as index_insert
from repro.core.index import BuildConfig, HybridIndex
from repro.core.index import mark_deleted as index_mark_deleted
from repro.core.fusion import (
    FUSION_MODE_NAMES,
    FusionSpec,
    PathStats,
    as_fusion_spec,
    merge_fused_host,
    stack_specs,
)
from repro.obs.export import write_metrics_snapshot
from repro.obs.metrics import GLOBAL as GLOBAL_METRICS
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracer import TraceContext, Tracer, phase
from repro.core.search import (
    SearchParams,
    SearchResult,
    resolve_params,
    scan_padded,
    search_padded,
    search_padded_trace_count,
)
from repro.core.usms import (
    PAD_IDX,
    FusedVectors,
    PathWeights,
    QuantizedFusedVectors,
    SparseVec,
    corpus_nbytes_by_leaf,
)
from repro.serving.batcher import (
    AdmissionConfig,
    AdmissionController,
    AdmissionError,
    BatcherConfig,
    Bucket,
    MicroBatcher,
    PendingResult,
    QueueFullError,
    SearchRequest,
)


# process-wide storage-footprint gauges, ticked at every snapshot publish
# (and once at service construction — the initial snapshot never passes
# through _publish). Labels: leaf kind x storage dtype, so the quantized
# compression ratio is a scraped metric, not just a checkpoint-manifest
# field. With several services in one process the most recent publisher
# wins — the bench snapshot reads one serving index at a time.
_INDEX_BYTES = GLOBAL_METRICS.gauge(
    "allanpoe_index_bytes_total",
    "served index storage bytes by leaf kind and dtype",
    labels=("leaf", "dtype"),
)


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    batcher: BatcherConfig = BatcherConfig()
    keep_stale_executables: bool = False  # keep executables for old index shapes
    admission: Optional[AdmissionConfig] = None  # token buckets before enqueue
    pump_interval_s: Optional[float] = None  # auto-start a poll() pump thread
    # observability (DESIGN.md §12): share a registry/tracer across services
    # by passing them in; None gives the service its own private ones
    metrics: Optional[MetricsRegistry] = None
    tracer: Optional[Tracer] = None
    # periodic JSON snapshot flush from the pump thread (service registry +
    # the process-global one); None disables
    metrics_dump_path: Optional[str] = None
    metrics_dump_interval_s: float = 10.0


def _bucket_label(bucket: Bucket) -> str:
    return f"{bucket.batch}x{bucket.kw_width}x{bucket.ent_width}"


def _fusion_mode_label(spec) -> str:
    """Host-side fusion-mode label of a request spec ("batched" for (B,)
    leaf specs — per-row modes are traced data the host never unpacks)."""
    try:
        mode = spec.mode
        if np.ndim(mode) >= 1:
            return "batched"
        return FUSION_MODE_NAMES.get(int(mode), str(int(mode)))
    except Exception:
        return "unknown"


class ServiceStats:
    """Thread-safe service counters, backed by the metrics registry: every
    increment goes through the registry's single lock (previously these
    were bare ``+=`` from multiple submitter threads), and the legacy field
    names read the live series. Labeled dimensions (fusion mode on
    requests, bucket shape on batches, reject reason) are visible through
    ``HybridSearchService.metrics``; the properties here report totals."""

    def __init__(self, metrics: MetricsRegistry):
        self._requests = metrics.counter(
            "allanpoe_serving_requests_total",
            "requests admitted and enqueued (rejects counted separately)",
            labels=("mode",),
        )
        self._batches = metrics.counter(
            "allanpoe_serving_batches_total",
            "batches executed",
            labels=("bucket",),
        )
        self._compiles = metrics.counter(
            "allanpoe_serving_compiles_total",
            "AOT executable compiles (cache misses that won the publish race)",
        )
        self._padded_slots = metrics.counter(
            "allanpoe_serving_padded_slots_total",
            "wasted batch slots (padding overhead measure)",
        )
        self._rejected = metrics.counter(
            "allanpoe_serving_rejected_total",
            "rejected submits by reason (admission = rate policy, "
            "queue_full = backpressure)",
            labels=("reason",),
        )

    @property
    def requests(self) -> int:
        return int(self._requests.total())

    @property
    def batches(self) -> int:
        return int(self._batches.total())

    @property
    def compiles(self) -> int:
        return int(self._compiles.total())

    @property
    def padded_slots(self) -> int:
        return int(self._padded_slots.total())

    @property
    def rejected_queue_full(self) -> int:
        return int(self._rejected.value(reason="queue_full"))

    @property
    def rejected_admission(self) -> int:
        return int(self._rejected.value(reason="admission"))

    @property
    def rejected(self) -> int:
        return self.rejected_queue_full + self.rejected_admission

    def __repr__(self) -> str:
        return (
            f"ServiceStats(requests={self.requests}, batches={self.batches}, "
            f"compiles={self.compiles}, padded_slots={self.padded_slots}, "
            f"rejected_queue_full={self.rejected_queue_full}, "
            f"rejected_admission={self.rejected_admission})"
        )


@dataclasses.dataclass(frozen=True)
class _Snapshot:
    """An immutable, fully-materialized index the read path can hold across
    a whole batch — the copy-on-write unit. ``grow``/``grow_gids`` are the
    optional grow segment of a segmented deployment: a small mutable-by-
    replacement HybridIndex plus its local-row -> global-id map."""

    index: Union[HybridIndex, SegmentedIndex, SegmentPool]
    version: int
    grow: Optional[HybridIndex] = None
    grow_gids: Optional[jax.Array] = None  # (n_grow,) int32


class HybridSearchService:
    """Micro-batched serving front-end over a hybrid index snapshot."""

    def __init__(
        self,
        index: Union[HybridIndex, SegmentedIndex, SegmentPool],
        params: SearchParams,
        config: Optional[ServiceConfig] = None,
        *,
        mesh=None,
        build_cfg: Optional[BuildConfig] = None,
    ):
        # pin backend-auto fields (use_kernel=None) to concrete values up
        # front: self.params is a component of every AOT executable-cache
        # key, so kernel mode must be resolved — not deferred to the op
        # layer — or a backend/flag change could alias a stale executable
        self.params = resolve_params(params)
        # declared storage mode must match what the index actually holds:
        # serving quantized segments under corpus_dtype="float32" would hand
        # the executables a pytree the cache key does not describe. The
        # reverse — "int8" over (still-)fp32 segments — is allowed: during a
        # migration old fp32 seals coexist with new int8 ones, and the
        # per-group dispatch handles each by its own treedef.
        if self.params.corpus_dtype == "float32":
            quantized = [
                type(c).__name__
                for c, _ in self._norm_parts(_Snapshot(index, version=0))
                if isinstance(c, QuantizedFusedVectors)
            ]
            if quantized:
                raise ValueError(
                    "index holds quantized corpus storage but "
                    'SearchParams.corpus_dtype is "float32"; construct the '
                    'service with corpus_dtype="int8"'
                )
        self.config = config or ServiceConfig()
        self.metrics = self.config.metrics or MetricsRegistry()
        self.tracer = self.config.tracer or Tracer()
        self.stats = ServiceStats(self.metrics)
        # instruments beyond the legacy counters (naming: DESIGN.md §12)
        self._m_exec_cache = self.metrics.counter(
            "allanpoe_serving_executable_cache_total",
            "AOT executable-cache lookups by outcome",
            labels=("outcome",),
        )
        self._m_group_dispatch = self.metrics.counter(
            "allanpoe_serving_group_dispatches_total",
            "pool-read dispatches per segment shape group",
            labels=("group",),
        )
        self._m_queue_depth = self.metrics.gauge(
            "allanpoe_serving_queue_depth", "pending requests in the batcher"
        )
        self._m_queue_wait = self.metrics.histogram(
            "allanpoe_serving_queue_wait_seconds",
            "enqueue -> batch start per request",
        )
        self._m_latency = self.metrics.histogram(
            "allanpoe_serving_request_latency_seconds",
            "enqueue -> result delivery per request (the bench p50/p99 source)",
        )
        self._m_phase = self.metrics.histogram(
            "allanpoe_serving_batch_phase_seconds",
            "time per batch phase (the serving.<phase> profiler spans)",
            labels=("phase",),
        )
        self._m_nodes_expanded = self.metrics.counter(
            "allanpoe_serving_nodes_expanded_total",
            "graph nodes the traversal expanded, over real (unpadded) rows",
        )
        self._snap = _Snapshot(index, version=0)
        self._index_bytes_keys: set = set()
        self._tick_index_bytes(self._snap)
        self._write_lock = threading.Lock()  # serializes snapshot writers
        # queue lock: enqueue/take_ready only, never held across a batch run,
        # so a timer thread pumping poll() can coexist with request threads
        # without submit() stalling behind a compile or device execution
        self._queue_lock = threading.Lock()
        # cache lock: every _exec_cache read/write/prune plus batch stats
        self._cache_lock = threading.Lock()
        self._batcher = MicroBatcher(self.config.batcher)
        self._exec_cache: dict = {}
        self._pool = isinstance(index, SegmentPool)
        self._segmented = isinstance(index, SegmentedIndex) or self._pool
        self._mesh = mesh
        self._dist_fn = None
        if isinstance(index, SegmentedIndex):
            # a plain stacked index is served through the sharded executable
            if mesh is None:
                raise ValueError("a SegmentedIndex service requires a mesh")
        if self._segmented and mesh is not None:
            self._dist_fn = make_distributed_search_padded(mesh, self.params)
        # pool groups off the mesh's segment axes (or the whole pool of an
        # off-mesh deployment) are served by the collective-free local pass;
        # any segmented service can become pool-fronted after an incremental
        # compaction, so the local factory is always on hand
        self._local_fn = (
            make_local_group_search(self.params) if self._segmented else None
        )
        self._build_cfg = build_cfg
        self._router = None  # set by serving.segment_router.SegmentRouter
        # running per-path normalization stats: recomputed lazily when the
        # snapshot version moves, EMA-blended across publishes so normalized
        # fusion scores stay stable under streaming churn (DESIGN.md §11)
        self._stats_cache: Optional[PathStats] = None
        self._stats_version = -1
        self._admission = (
            AdmissionController(self.config.admission)
            if self.config.admission is not None
            else None
        )
        self._pump_lock = threading.Lock()  # guards pump start/stop
        self._pump_thread: Optional[threading.Thread] = None
        self._pump_stop = threading.Event()
        if self.config.pump_interval_s is not None:
            self.start_pump()

    # -- background pump (flush-on-deadline without a submit) ---------------

    def start_pump(self, interval_s: Optional[float] = None) -> None:
        """Start the daemon thread that drives ``poll()`` every
        ``interval_s`` (default: ``config.pump_interval_s``), so deadline
        flushes happen even when no new submit arrives. Idempotent."""
        interval = (
            self.config.pump_interval_s if interval_s is None else interval_s
        )
        if interval is None:
            raise ValueError("pump interval required (arg or config)")
        with self._pump_lock:  # check-then-start must be atomic: exactly
            # one pump thread, and _pump_stop always refers to ITS event
            if self._pump_thread is not None and self._pump_thread.is_alive():
                return
            self._pump_stop = threading.Event()
            stop = self._pump_stop

            def loop():
                last_dump = time.monotonic()
                while not stop.wait(interval):
                    try:
                        self.poll()
                    except Exception:
                        # the failing batch already failed its own waiters
                        # (_run_batch); the pump must keep pumping for the rest
                        pass
                    # periodic exposition flush rides the pump thread: the
                    # snapshot is the same registry the benches read
                    if (
                        self.config.metrics_dump_path is not None
                        and time.monotonic() - last_dump
                        >= self.config.metrics_dump_interval_s
                    ):
                        last_dump = time.monotonic()
                        try:
                            self.dump_metrics()
                        except Exception:
                            pass  # a full disk must not kill the pump

            self._pump_thread = threading.Thread(
                target=loop, name="hybrid-service-pump", daemon=True
            )
            self._pump_thread.start()

    def dump_metrics(self, path=None) -> dict:
        """Write the merged (service + process-global) metrics snapshot to
        ``path`` (default ``config.metrics_dump_path``); returns the dict."""
        path = self.config.metrics_dump_path if path is None else path
        if path is None:
            raise ValueError("no metrics dump path (arg or config)")
        return write_metrics_snapshot(path, self.metrics, GLOBAL_METRICS)

    def stop_pump(self, timeout_s: float = 5.0) -> None:
        with self._pump_lock:
            thread = self._pump_thread
            if thread is not None:
                self._pump_stop.set()
                thread.join(timeout=timeout_s)
                self._pump_thread = None
                if self.config.metrics_dump_path is not None:
                    try:
                        self.dump_metrics()  # final flush on clean shutdown
                    except Exception:
                        pass
        # clean shutdown extends to the attached router's background merge
        # worker: an in-flight merge finishes its atomic publish, then the
        # worker exits before this returns
        router = getattr(self, "_router", None)
        if router is not None and hasattr(router, "stop_merge_worker"):
            router.stop_merge_worker()

    def __enter__(self) -> "HybridSearchService":
        return self

    def __exit__(self, *exc) -> None:
        self.stop_pump()

    # -- snapshot management (copy-on-write swap) ---------------------------

    @property
    def snapshot_version(self) -> int:
        return self._snap.version

    @property
    def index(self) -> Union[HybridIndex, SegmentedIndex]:
        return self._snap.index

    @property
    def grow_index(self) -> Optional[HybridIndex]:
        """The current grow segment (None when sealed-only)."""
        return self._snap.grow

    # EMA weight of FRESH stats at each snapshot publish; extremes still
    # widen monotonically (PathStats.ema), so minmax stays in-range for
    # every row both snapshots contained
    _STATS_EMA = 0.3

    @staticmethod
    def _norm_parts(snap: _Snapshot):
        """(corpus, alive) pairs covering every live row of a snapshot —
        the input to ``PathStats.from_corpus_parts``."""
        idx = snap.index
        if isinstance(idx, SegmentPool):
            parts = [(g.index.corpus, g.index.alive) for g in idx.groups]
        elif isinstance(idx, SegmentedIndex):
            parts = [(idx.index.corpus, idx.index.alive)]
        else:
            parts = [(idx.corpus, idx.alive)]
        if snap.grow is not None:
            parts.append((snap.grow.corpus, snap.grow.alive))
        return parts

    @property
    def path_stats(self) -> PathStats:
        """Running per-path normalization stats of the served corpus
        ((3,) leaves). Lazily refreshed when the snapshot version moves;
        successive publishes EMA-blend rather than jump."""
        snap = self._snap
        if self._stats_cache is None or self._stats_version != snap.version:
            fresh = PathStats.from_corpus_parts(self._norm_parts(snap))
            stats = (
                fresh
                if self._stats_cache is None
                else PathStats.ema(self._stats_cache, fresh, self._STATS_EMA)
            )
            self._stats_cache, self._stats_version = stats, snap.version
        return self._stats_cache

    def _resolve_spec(self, spec: FusionSpec) -> FusionSpec:
        """Pin unresolved (stats=None) specs to the service's running
        stats — the downstream resolution the ``FusionSpec`` contract
        promises. Already-resolved specs pass through untouched."""
        if spec.stats is not None:
            return spec
        return dataclasses.replace(spec, stats=self.path_stats)

    def _tick_index_bytes(self, snap: _Snapshot) -> None:
        """Set the ``allanpoe_index_bytes_total{leaf,dtype}`` gauges to this
        snapshot's storage footprint. Corpus leaves are broken out by kind
        (dense / dense_scale / sparse_idx / sparse_val); everything else —
        edges, entry points, liveness, entity tables — rolls up as "graph"
        by dtype. Label pairs that vanished (e.g. float32 dense after a
        fully quantized compaction) are zeroed, not left stale."""
        totals: dict = {}

        def add(leaf: str, arr) -> None:
            key = (leaf, str(arr.dtype))
            totals[key] = totals.get(key, 0) + arr.size * arr.dtype.itemsize

        def add_index(hidx) -> None:
            for key, v in corpus_nbytes_by_leaf(hidx.corpus).items():
                totals[key] = totals.get(key, 0) + v
            corpus_ids = {id(l) for l in jax.tree.leaves(hidx.corpus)}
            for leaf in jax.tree.leaves(hidx):
                if id(leaf) not in corpus_ids:
                    add("graph", leaf)

        idx = snap.index
        if isinstance(idx, SegmentPool):
            for g in idx.groups:
                add_index(g.index)
                add("graph", g.global_ids)
        elif isinstance(idx, SegmentedIndex):
            add_index(idx.index)
            add("graph", idx.global_ids)
        else:
            add_index(idx)
        if snap.grow is not None:
            add_index(snap.grow)
        for leaf, dtype in self._index_bytes_keys - set(totals):
            _INDEX_BYTES.set(0, leaf=leaf, dtype=dtype)
        for (leaf, dtype), v in totals.items():
            _INDEX_BYTES.set(v, leaf=leaf, dtype=dtype)
        self._index_bytes_keys = set(totals)

    def _publish(self, new_index, *, grow=None, grow_gids=None) -> None:
        # materialize before publishing so readers never block on (or fail
        # inside) a half-computed donor buffer
        leaves = jax.tree.leaves(new_index)
        if grow is not None:
            if grow_gids is None:
                raise ValueError("a grow segment needs its global-id map")
            grow_gids = jnp.asarray(grow_gids, jnp.int32)
            leaves = leaves + jax.tree.leaves(grow) + [grow_gids]
        jax.block_until_ready(leaves)
        self._snap = _Snapshot(
            new_index, self._snap.version + 1, grow=grow, grow_gids=grow_gids
        )
        self._tick_index_bytes(self._snap)
        if not self.config.keep_stale_executables:
            # prune on the SEALED index keys only: the grow segment is read
            # through search_padded's own jit cache, so grow churn neither
            # adds nor evicts AOT entries — sealed executables stay warm
            # across every streaming insert (the cache-key invariant the
            # grow-segment scheme exists to provide; DESIGN.md §6). A pool
            # publish keeps every executable whose shape group SURVIVED the
            # mutation: compacting into one group never evicts the others
            # (the cache-survival guarantee, DESIGN.md §8)
            valid = self._valid_index_keys(new_index)
            with self._cache_lock:
                self._exec_cache = {
                    k: v for k, v in self._exec_cache.items() if k[0] in valid
                }

    def insert(
        self,
        new_docs: FusedVectors,
        *,
        key: Optional[jax.Array] = None,
        new_doc_entities: Optional[np.ndarray] = None,
    ) -> int:
        """Absorb streaming inserts; returns the new snapshot version.
        In-flight searches keep the snapshot they started with. A segmented
        service routes inserts to its grow segment via the attached
        ``SegmentRouter``."""
        if self._segmented:
            if self._router is None:
                raise NotImplementedError(
                    "streaming insert into a SegmentedIndex needs a grow "
                    "segment: attach a serving.segment_router.SegmentRouter"
                )
            return self._router.insert(
                new_docs, key=key, new_doc_entities=new_doc_entities
            )
        if self._build_cfg is None:
            raise ValueError("insert requires build_cfg at service construction")
        with self._write_lock:
            new_index = index_insert(
                self._snap.index,
                new_docs,
                self._build_cfg,
                key=key,
                new_doc_entities=new_doc_entities,
            )
            self._publish(new_index)
            return self._snap.version  # read under the lock: OUR version

    def mark_deleted(self, ids) -> int:
        """Mark-delete docs; returns the new snapshot version. The index
        shape is unchanged, so cached executables keep serving. A segmented
        service resolves global ids to (segment, local row) tombstones via
        the attached ``SegmentRouter``."""
        if self._segmented:
            if self._router is None:
                raise NotImplementedError(
                    "deletion on a SegmentedIndex needs global->segment id "
                    "routing: attach a serving.segment_router.SegmentRouter"
                )
            return self._router.delete(ids)
        with self._write_lock:
            new_index = index_mark_deleted(
                self._snap.index, jnp.asarray(ids, jnp.int32)
            )
            self._publish(new_index)
            return self._snap.version  # read under the lock: OUR version

    # -- executable cache ---------------------------------------------------

    @staticmethod
    def _index_key(index) -> tuple:
        if isinstance(index, SegmentedIndex):
            # the full shape signature: a stacked index serving as a pool
            # group keeps the SAME key either way, so wrapping it into a
            # SegmentPool never invalidates its cached executable
            return group_shape_key(index)
        return ("single", index.n)

    def _valid_index_keys(self, index) -> set:
        """Executable-cache keys the given snapshot index can serve."""
        if isinstance(index, SegmentPool):
            return {group_shape_key(g) for g in index.groups}
        return {self._index_key(index)}

    @property
    def executable_cache(self) -> dict:
        """(index/group key, Bucket, SearchParams, query widths) -> AOT
        executable."""
        return self._exec_cache

    def _compile_cached(self, key: tuple, lower, phases):
        """(executable, cache_hit) for a cache key, compiling on miss (the
        ``compile`` phase). Every lookup lands in the
        ``executable_cache_total{outcome}`` counter — the hit-rate series
        the CI obs gate tracks."""
        with self._cache_lock:
            exe = self._exec_cache.get(key)
        if exe is not None:
            self._m_exec_cache.inc(outcome="hit")
            return exe, True
        self._m_exec_cache.inc(outcome="miss")
        # compile outside the lock: a cold bucket must not stall warm-bucket
        # batches or snapshot publishes behind a multi-second XLA compile
        with phase("compile", self._m_phase, phases):
            exe = lower().compile()
        with self._cache_lock:
            winner = self._exec_cache.get(key)
            if winner is not None:
                return winner, False  # another thread compiled the bucket first
            # a writer may have swapped the snapshot while we compiled;
            # don't re-add an executable its prune already evicted
            if (
                self.config.keep_stale_executables
                or key[0] in self._valid_index_keys(self._snap.index)
            ):
                self._exec_cache[key] = exe
        self.stats._compiles.inc()
        return exe, False

    @staticmethod
    def _query_widths(args) -> tuple:
        """The assembled query batch's nnz caps: part of every cache key,
        since the bucket fixes batch and keyword/entity widths but not the
        query's (a 128-nnz read-back query must not hit a 32-nnz executable)."""
        return tuple(a.shape[1:] for a in jax.tree.leaves(args[0]))

    def _get_executable(self, snap: _Snapshot, bucket: Bucket, args, phases):
        key = (
            self._index_key(snap.index), bucket, self.params,
            self._query_widths(args),
        )
        if self._segmented:
            lower = lambda: self._dist_fn.lower(snap.index, *args)
        else:
            lower = lambda: search_padded.lower(snap.index, *args, self.params)
        return self._compile_cached(key, lower, phases)

    def _group_runner(self, group: SegmentedIndex):
        """Pick the executable factory for one pool group per the placement
        map: the sharded pass when the group divides over the mesh's segment
        devices, else the collective-free local pass."""
        if self._dist_fn is not None and self._mesh is not None:
            msc = mesh_segment_count(self._mesh)
            if msc > 1 and group.n_segments % msc == 0:
                return self._dist_fn
        return self._local_fn

    def _get_group_executable(self, group: SegmentedIndex, bucket: Bucket, args, phases):
        """(executable, cache_hit) for one pool shape group."""
        key = (
            group_shape_key(group), bucket, self.params,
            self._query_widths(args),
        )
        fn = self._group_runner(group)
        return self._compile_cached(key, lambda: fn.lower(group, *args), phases)

    # -- request path -------------------------------------------------------

    def _validate(self, request: SearchRequest) -> None:
        bcfg = self.config.batcher
        if request.fusion is None:
            raise ValueError(
                "SearchRequest needs fusion=FusionSpec(...) "
                "(or the deprecated weights=PathWeights form)"
            )
        if request.k > self.params.k:
            raise ValueError(
                f"request.k={request.k} exceeds the service cap params.k={self.params.k}"
            )
        if request.keywords is not None:
            if not self.params.use_keywords:
                raise ValueError("service params have use_keywords=False")
            if len(request.keywords) > bcfg.kw_cap:
                raise ValueError(
                    f"{len(request.keywords)} keywords exceed kw_cap={bcfg.kw_cap}"
                )
        if request.entities is not None:
            if not self.params.use_kg:
                raise ValueError("service params have use_kg=False")
            if len(request.entities) > bcfg.ent_cap:
                raise ValueError(
                    f"{len(request.entities)} entities exceed ent_cap={bcfg.ent_cap}"
                )

    def submit(self, request: SearchRequest) -> PendingResult:
        """Enqueue one request; runs any batch whose flush trigger fired.

        Raises ``AdmissionError`` on a token-bucket reject (rate policy) and
        ``QueueFullError`` on a bounded-queue reject (backpressure) — the
        two are counted separately in ``stats``."""
        self._validate(request)
        ctx = request.trace
        t_sub = time.perf_counter()
        pending = PendingResult(service=self)
        with self._queue_lock:
            if self._admission is not None and not self._admission.try_admit(
                request.tenant
            ):
                self.stats._rejected.inc(reason="admission")
                if ctx is not None:
                    ctx.add_span(
                        "admission", t_sub, time.perf_counter(),
                        outcome="rejected_admission", tenant=request.tenant,
                    )
                raise AdmissionError(
                    f"token-bucket admission rejected request "
                    f"(tenant={request.tenant!r}); shed load or retry later"
                )
            try:
                self._batcher.enqueue(request, pending)
            except QueueFullError:
                # the request was admitted but never served: hand the
                # tokens back so backpressure rejects don't drain quota
                if self._admission is not None:
                    self._admission.refund(request.tenant)
                self.stats._rejected.inc(reason="queue_full")
                if ctx is not None:
                    ctx.add_span(
                        "admission", t_sub, time.perf_counter(),
                        outcome="rejected_queue_full", tenant=request.tenant,
                    )
                raise
            self.stats._requests.inc(mode=_fusion_mode_label(request.fusion))
            self._m_queue_depth.set(len(self._batcher))
        if ctx is not None:
            ctx.add_span(
                "admission", t_sub, time.perf_counter(),
                outcome="admitted", tenant=request.tenant,
            )
        try:
            self._drain()
        except Exception:
            # a failing batch (ours or a sibling's) has already failed its
            # own waiters; the returned handle is the error channel here —
            # raising would discard it while the request may still be queued
            pass
        return pending

    def poll(self) -> int:
        """Run deadline-due batches (call from a timer loop); returns the
        number of batches executed. A failing batch raises here after its
        waiters have been failed — timer loops should catch and keep
        pumping; every affected result() re-raises the real error."""
        return self._drain()

    def flush(self) -> int:
        """Force-run every pending batch; returns the number executed."""
        return self._drain(force=True)

    def _drain(self, force: bool = False) -> int:
        with self._queue_lock:
            ready = self._batcher.take_ready(force=force)
            self._m_queue_depth.set(len(self._batcher))
        # entries are dequeued: run each batch outside the queue lock so
        # concurrent submits only wait for the enqueue, not the execution.
        # Every dequeued batch must resolve its waiters even if an earlier
        # sibling batch failed, so run them all before re-raising.
        first_err: Optional[BaseException] = None
        for bucket, entries in ready:
            try:
                self._run_batch(bucket, entries)
            except Exception as err:  # waiters already failed by _run_batch
                first_err = first_err or err
        if first_err is not None:
            raise first_err
        return len(ready)

    # large-negative fill for merged pad slots (matches distributed NEG_FILL)
    _NEG_FILL = np.float32(-1e30)

    @staticmethod
    def _merge_host(ids_parts, score_parts, k, path_parts=None, spec=None):
        """Per-row top-k merge of several result blocks in global-id space
        (every global id lives in exactly one segment, so merged rows are
        duplicate-free). Fusion-aware: non-RRF rows merge by score, RRF rows
        recompute ranks over the union from ``path_parts`` — merging local
        RRF scores by value is a contract violation (DESIGN.md §11) and
        raises inside ``merge_fused_host``."""
        return merge_fused_host(ids_parts, score_parts, path_parts, spec, k)

    def _merge_grow(
        self, snap: _Snapshot, args, ids, scores, ps, expanded, phases=None
    ):
        """Phase two of a segmented read: search the grow segment and merge
        per-row top-k with the sealed results in global-id space.

        The grow pass scores every live grow row (``scan_padded``), so a
        passage is found as soon as its insert returns; KG params traverse
        it with ``search_padded`` instead. Either jit cache retraces as the
        grow segment changes shape, while the AOT ``executable_cache``
        (sealed segments) is never touched. Tombstones need no extra
        filtering here: both phases already filter on their own ``alive``
        masks."""
        with phase(
            "grow_merge", self._m_phase, phases, grow_rows=int(snap.grow.n)
        ) as attrs:
            traces0 = search_padded_trace_count()
            grow_pass = search_padded if self.params.use_kg else scan_padded
            gres = grow_pass(snap.grow, *args, self.params)
            g_local = np.asarray(gres.ids)
            gids_map = np.asarray(snap.grow_gids)
            g_ids = np.where(
                g_local >= 0,
                gids_map[np.clip(g_local, 0, gids_map.shape[0] - 1)],
                PAD_IDX,
            )
            g_scores = np.where(g_local >= 0, np.asarray(gres.scores), -np.inf)
            g_ps = np.where(
                (g_local >= 0)[:, :, None], np.asarray(gres.path_scores), 0.0
            )
            m_ids, m_scores, m_ps = self._merge_host(
                [ids, g_ids],
                [scores, g_scores],
                ids.shape[1],
                path_parts=[ps, g_ps],
                spec=args[1],
            )
            attrs["retraced"] = search_padded_trace_count() > traces0
        return m_ids, m_scores, m_ps, expanded + np.asarray(gres.expanded)

    @staticmethod
    def _read_back(results):
        """Host copies of the results' ids, scores, path scores and
        ``expanded``, one list per field (blocks on the device)."""
        fields = ("ids", "scores", "path_scores", "expanded")
        return [[np.asarray(getattr(r, f)) for r in results] for f in fields]

    def _run_pool(self, pool: SegmentPool, bucket: Bucket, args, phases=None):
        """Pool read: one cached executable per shape group, merged per-row
        in global-id space. Groups untouched by a compaction keep hitting
        their existing executables."""
        with phase(
            "executable_lookup", self._m_phase, phases, groups=len(pool.groups)
        ) as attrs:
            pairs = [
                self._get_group_executable(group, bucket, args, phases)
                for group in pool.groups
            ]
            attrs["hit"] = all(h for _, h in pairs)
        # dispatch EVERY group before blocking on any result: jax executes
        # asynchronously, so the groups' device work overlaps instead of
        # paying the sum of per-group latencies
        with phase("device_dispatch", self._m_phase, phases, groups=len(pairs)):
            results = []
            for gi, (group, (exe, _)) in enumerate(zip(pool.groups, pairs)):
                self._m_group_dispatch.inc(group=gi)
                results.append(exe(group, *args))
        with phase("device_readback", self._m_phase, phases, groups=len(pairs)):
            ids_parts, score_parts, ps_parts, exp_parts = self._read_back(results)
        expanded = sum(exp_parts)
        if len(ids_parts) == 1:
            return ids_parts[0], score_parts[0], ps_parts[0], expanded
        k = ids_parts[0].shape[1]
        with phase(
            "fusion_rescore", self._m_phase, phases,
            parts=len(ids_parts), site="pool_merge",
        ):
            m_ids, m_scores, m_ps = self._merge_host(
                ids_parts, score_parts, k, path_parts=ps_parts, spec=args[1]
            )
        return m_ids, m_scores, m_ps, expanded

    def _run_batch(self, bucket: Bucket, entries) -> None:
        # batch phases are timed once and attributed to every query in the
        # batch: (name, t0, t1, attrs) tuples become spans on each request's
        # TraceContext, and each phase is a serving.<name> profiler span
        # (DESIGN.md §12 span taxonomy)
        t_batch0 = time.perf_counter()
        blabel = _bucket_label(bucket)
        phases: list[tuple[str, float, float, dict]] = []
        try:
            snap = self._snap  # one snapshot for the whole batch
            with phase(
                "batch_assembly", self._m_phase, phases,
                bucket=blabel, requests=len(entries),
            ):
                args = self._assemble(bucket, entries)
            if isinstance(snap.index, SegmentPool):
                ids, scores, ps, expanded = self._run_pool(
                    snap.index, bucket, args, phases
                )
            else:
                with phase("executable_lookup", self._m_phase, phases) as attrs:
                    exe, attrs["hit"] = self._get_executable(
                        snap, bucket, args, phases
                    )
                with phase("device_dispatch", self._m_phase, phases):
                    res = exe(snap.index, *args)
                with phase("device_readback", self._m_phase, phases):
                    (ids,), (scores,), (ps,), (expanded,) = self._read_back([res])
            if snap.grow is not None:
                ids, scores, ps, expanded = self._merge_grow(
                    snap, args, ids, scores, ps, expanded, phases
                )
        except Exception as err:
            # entries are already dequeued: propagate to every waiter so no
            # result() blocks forever, then surface to the driving thread
            for e in entries:
                e.pending._fail(err)
            raise
        with phase("deliver", self._m_phase, phases, requests=len(entries)):
            for i, e in enumerate(entries):
                e.pending._fulfill(
                    ids[i, : e.request.k],
                    scores[i, : e.request.k],
                    int(expanded[i]),
                    path_scores=ps[i, : e.request.k],
                )
            t_done = time.perf_counter()
            for e in entries:
                self._m_queue_wait.observe(t_batch0 - e.arrival_perf)
                self._m_latency.observe(t_done - e.arrival_perf)
        for e in entries:
            ctx = e.request.trace
            if ctx is not None:
                ctx.add_span(
                    "queue_wait", e.arrival_perf, t_batch0, bucket=blabel
                )
                for name, p0, p1, attrs in phases:
                    ctx.add_span(name, p0, p1, **attrs)
        self._m_nodes_expanded.inc(int(expanded[: len(entries)].sum()))
        self.stats._batches.inc(bucket=blabel)
        self.stats._padded_slots.inc(bucket.batch - len(entries))

    def _assemble(self, bucket: Bucket, entries):
        """Pack requests into the bucket's fixed shapes. Pad rows carry the
        all-zero fusion spec and PAD ids; their results are discarded on
        delivery. Every request spec is resolved against the service's
        running stats here, so the stacked spec has a FIXED pytree
        structure — fusion mode/weights/stats remain traced data, never part
        of the executable-cache key."""
        m = len(entries)
        b = bucket.batch
        queries = jax.tree.map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
            *[e.request.query for e in entries],
        )
        if m < b:
            padn = b - m
            grow = lambda a, fill: jnp.concatenate(
                [a, jnp.full((padn,) + a.shape[1:], fill, a.dtype)]
            )
            queries = FusedVectors(
                grow(queries.dense, 0),
                SparseVec(grow(queries.learned.idx, PAD_IDX), grow(queries.learned.val, 0)),
                SparseVec(grow(queries.lexical.idx, PAD_IDX), grow(queries.lexical.val, 0)),
            )
        pad_spec = self._resolve_spec(FusionSpec.zero())
        fusion = stack_specs(
            [self._resolve_spec(e.request.fusion) for e in entries]
            + [pad_spec] * (b - m)
        )
        kw = np.full((b, bucket.kw_width), PAD_IDX, np.int32)
        en = np.full((b, bucket.ent_width), PAD_IDX, np.int32)
        for i, e in enumerate(entries):
            if e.request.keywords is not None and len(e.request.keywords):
                kws = np.asarray(e.request.keywords, np.int32)
                kw[i, : len(kws)] = kws
            if e.request.entities is not None and len(e.request.entities):
                ens = np.asarray(e.request.entities, np.int32)
                en[i, : len(ens)] = ens
        return queries, fusion, jnp.asarray(kw), jnp.asarray(en)

    # -- synchronous convenience -------------------------------------------

    def search(
        self,
        queries: FusedVectors,
        fusion: Union[FusionSpec, PathWeights, Sequence, None] = None,
        *,
        weights: Union[PathWeights, Sequence[PathWeights], None] = None,
        keywords: Optional[np.ndarray] = None,
        entities: Optional[np.ndarray] = None,
        k: Optional[int] = None,
        trace: Optional[TraceContext] = None,
    ) -> SearchResult:
        """Submit a whole batch and flush: per-row requests (row i of
        ``queries`` with fusion[i] if a sequence / batched-leaf spec was
        given), results reassembled into a SearchResult. Mirrors
        core.search.search but runs through the batched request path.
        ``weights=`` is the deprecated ``PathWeights`` spelling (converts to
        a weighted-sum spec with a warning). 2-D keyword/entity arrays may
        be PAD_IDX padded (the core search() convention); pad slots are
        stripped per row before the requests are formed."""

        def row_ids(arr, i):
            if arr is None:
                return None
            row = np.asarray(arr)[i]
            row = row[row >= 0]
            return row if len(row) else None

        if fusion is not None and weights is not None:
            raise ValueError("pass fusion= or (deprecated) weights=, not both")
        if fusion is None:
            if weights is None:
                raise TypeError("search() requires fusion=FusionSpec(...)")
            fusion = weights  # deprecated form; as_fusion_spec warns below
        b = queries.dense.shape[0]
        k = self.params.k if k is None else k
        if isinstance(fusion, (FusionSpec, PathWeights)):
            spec = as_fusion_spec(fusion)
            if np.ndim(spec.mode) >= 1:  # batched (B,)-leaf form
                get_f = lambda i: jax.tree.map(lambda x: x[i], spec)
            else:
                get_f = lambda i: spec
        else:  # per-row sequence of FusionSpec / deprecated PathWeights
            rows = [as_fusion_spec(f) for f in fusion]
            get_f = lambda i: rows[i]
        reqs = [
            SearchRequest(
                query=queries[i],
                fusion=get_f(i),
                k=k,
                keywords=row_ids(keywords, i),
                entities=row_ids(entities, i),
                trace=trace,
            )
            for i in range(b)
        ]
        # validate the whole batch before enqueuing anything: one bad row
        # must not strand its predecessors as orphaned queue entries
        for req in reqs:
            self._validate(req)
        pendings = []
        for req in reqs:
            try:
                pendings.append(self.submit(req))
            except QueueFullError:
                # drain to make room rather than stranding the rows already
                # queued; force-flush empties the bounded queue entirely
                self.flush()
                pendings.append(self.submit(req))
        try:
            self.flush()
        except Exception:
            pass  # per-row errors surface from each result() below
        ids = np.stack([p.result()[0] for p in pendings])
        scores = np.stack([p.result()[1] for p in pendings])
        ps = np.stack([p.path_scores for p in pendings])
        return SearchResult(
            ids=jnp.asarray(ids),
            scores=jnp.asarray(scores),
            expanded=jnp.asarray([p.expanded for p in pendings], jnp.int32),
            path_scores=jnp.asarray(ps),
        )
