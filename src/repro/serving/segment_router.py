"""Grow-segment streaming router for the segmented serving layer.

The paper's index supports incremental insertion without reconstruction
(§4.1 "Updates"), and PR 2 gave every segment a device-resident
``insert()`` program — but a sharded ``SegmentedIndex`` had no service-level
way to absorb writes: any change to the stacked sealed segments would
change their shapes and evict every AOT-compiled search executable. This
module closes that gap with the classic vector-DB grow-segment scheme
(Milvus growing segments, GRAB-ANNS bucketed incremental indexing):

  * **growing** — streaming ``insert()`` batches land in one small mutable
    ``HybridIndex`` (the *grow segment*), built on first insert via
    ``build_index`` and extended by ``core.build_pipeline.insert`` (the
    pipelined per-segment insert program). Sealed segments are never
    touched, so their compiled executables stay warm; the read path merges
    sealed + grow per-row top-k in global-id space
    (``HybridSearchService._merge_grow``), reading the grow segment by an
    exact scan (``core.search.scan_padded``), as Milvus reads a growing
    segment by brute force. The published grow segment is padded to
    power-of-two capacity by default (``RouterConfig.grow_pow2``) so the
    read path's grow pass retraces O(log growth) times between compactions
    instead of once per insert batch;
  * **sealed** — the immutable stacked segments served through
    ``make_distributed_search_padded``'s cached executable. Deletions
    resolve global ids to (segment, local row) tombstones
    (``core.distributed.mark_deleted_segmented``) — shape-preserving, so no
    recompiles;
  * **compacted** — when the grow segment's live docs cross
    ``RouterConfig.seal_threshold``, ``compact()`` runs the configured
    compaction. ``compact_incremental`` (the default for pool-fronted
    services) seals the grow segment into ONE new pooled segment — O(grow)
    build work, tombstoned grow rows dropped, entity rows carried — and
    appends it to the ``core.segment_pool.SegmentPool`` at pow2 capacity;
    untouched shape groups keep their compiled executables (DESIGN.md §8),
    and a size-tiered ``merge_segments`` policy (``maybe_merge_segments``)
    bounds fragmentation LSM-style. ``seal_and_compact`` remains the full
    rebuild: ALL surviving docs (sealed minus tombstones, plus live grow
    docs) rebuilt into a fresh stacked index via ``build_index_sharded``
    (or the sequential ``build_segmented_index`` off-mesh), preserving
    global ids — O(corpus), total tombstone reclamation, every sealed
    executable recompiles.

Every mutation happens under the service's write lock and lands as one
atomic ``_Snapshot`` publish: readers either see (old sealed, old grow) or
(new sealed, new grow), never a half-updated pair. See DESIGN.md §6.

Knowledge-graph scope: give the router the triplets
(``SegmentRouter(..., kg_triplets=..., n_entities=...)``) and entity paths
survive compaction (logical edges are rebuilt over the surviving docs'
entities); a grow segment born from an entity-carrying insert gets its own
logical edges too, though docs from LATER inserts into the same grow
segment only gain logical edges at compaction. Constructing a router
without triplets over a KG-bearing sealed index fails fast unless
``RouterConfig.allow_kg_loss_on_compact`` is set.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.build_pipeline import (
    build_index,
    insert as index_insert,
    map_index_rows,
    pad_index_rows,
    slice_index_rows,
)
from repro.core.distributed import (
    alive_docs,
    compact_segmented_index,
    mark_deleted_segmented,
    mesh_segment_count,
    place_segmented_index,
    resolve_global_ids,
)
from repro.core.index import (
    BuildConfig,
    HybridIndex,
    mark_deleted as index_mark_deleted,
)
from repro.core.logical_edges import build_logical_edges
from repro.core.search import SearchParams
from repro.core.segment_pool import (
    SegmentPool,
    alive_docs_pool,
    append_segment,
    build_pool_segment,
    extract_segment_docs,
    live_counts,
    mark_deleted_pool,
    place_pool,
    remove_segments,
    resolve_global_ids_pool,
    widen_entities,
)
from repro.core.usms import PAD_IDX, FusedVectors, quantize_corpus
from repro.obs.metrics import MetricsRegistry
from repro.serving.batcher import _next_pow2
from repro.serving.hybrid_service import HybridSearchService


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    seal_threshold: int = 256  # live grow docs that trigger compaction
    auto_compact: bool = True  # compact from insert() when over threshold
    # optional override for the insert probe's search breadth (k and the
    # edge paths are forced by the build config; see build_pipeline.insert)
    insert_search: Optional[SearchParams] = None
    # opt-in acknowledgement that compacting a KG-bearing index WITHOUT
    # giving the router the triplets permanently drops the entity paths
    allow_kg_loss_on_compact: bool = False
    # shape-bucket the PUBLISHED grow segment: pad its capacity to the next
    # power of two so the read path's grow pass retraces O(log growth)
    # times between compactions instead of once per insert batch (pad rows
    # are dead — alive=False, PAD edges — and unreachable: no entry point or
    # edge ever references them)
    grow_pow2: bool = True
    # compaction mode: "incremental" seals the grow segment into ONE pooled
    # segment (O(grow) build work); "full" rebuilds every surviving doc into
    # a fresh stacked index (O(corpus), reclaims all tombstones). None =
    # auto: incremental when the service fronts a SegmentPool, full for a
    # plain SegmentedIndex (back-compat with pre-pool deployments)
    compaction: Optional[str] = None
    # quantize sealed pool-segment capacity to the next power of two, so
    # segments land in O(log corpus) shape groups and executables are reused
    seal_pow2: bool = True
    # size-tiered (LSM-style) merge invariant: at most tier_fanout segments
    # per pow2-capacity tier; maybe_merge_segments() coalesces the smallest
    # two of an offending tier. auto_merge runs it after each incremental
    # compaction
    tier_fanout: int = 4
    auto_merge: bool = True
    # run auto merges on a background worker thread (each merge still takes
    # the service write lock): compact_incremental returns as soon as the
    # new segment publishes instead of paying the merge cascade inline.
    # stop_pump()/stop_merge_worker() joins the worker; wait_merges() blocks
    # until the policy is quiescent (tests use it for determinism)
    background_merge: bool = True
    # auto-checkpoint: every N compactions persist the sealed pool (and the
    # paired ingest manifest) via checkpoint.index_io.save_pool, so a crash
    # can never lose more than the current grow segment. 0 = off.
    autocheckpoint_every: int = 0
    autocheckpoint_dir: Optional[str] = None


# retained names: the row pad/slice helpers moved to core.build_pipeline so
# the segment pool can share them (pool segments are shape-bucketed the same
# way the published grow segment is)
_map_grow_rows = map_index_rows
pad_grow_to_capacity = pad_index_rows
slice_grow_rows = slice_index_rows


class RouterStats:
    """Registry-backed view of the router's write-path counters.

    Every field is a ``allanpoe_router_*`` series in the owning service's
    metrics registry, so increments are atomic under the registry lock and
    the numbers in ``MetricsRegistry.render()`` are the numbers these
    properties report — there is no second bookkeeping path."""

    def __init__(self, metrics: MetricsRegistry):
        self._inserts = metrics.counter(
            "allanpoe_router_inserts_total",
            "insert() calls absorbed by the grow segment",
        )
        self._inserted_docs = metrics.counter(
            "allanpoe_router_inserted_docs_total",
            "documents appended to the grow segment",
        )
        self._deletes = metrics.counter(
            "allanpoe_router_deletes_total", "delete() calls"
        )
        self._deleted_docs = metrics.counter(
            "allanpoe_router_deleted_docs_total",
            "ids tombstoned, by where they lived "
            "(unknown = found nowhere, already compacted away?)",
            labels=("target",),
        )
        self._compactions = metrics.counter(
            "allanpoe_router_compactions_total",
            "grow-segment seals, full rebuilds vs incremental pool appends",
            labels=("mode",),
        )
        self._merges = metrics.counter(
            "allanpoe_router_merges_total", "background segment merges"
        )
        self._autocheckpoints = metrics.counter(
            "allanpoe_router_autocheckpoints_total",
            "pool checkpoints written by the router",
        )

    @property
    def inserts(self) -> int:
        return int(self._inserts.total())

    @property
    def inserted_docs(self) -> int:
        return int(self._inserted_docs.total())

    @property
    def deletes(self) -> int:
        return int(self._deletes.total())

    @property
    def deleted_sealed(self) -> int:
        return int(self._deleted_docs.value(target="sealed"))

    @property
    def deleted_grow(self) -> int:
        return int(self._deleted_docs.value(target="grow"))

    @property
    def unknown_deletes(self) -> int:
        return int(self._deleted_docs.value(target="unknown"))

    @property
    def compactions(self) -> int:
        return int(self._compactions.total())

    @property
    def incremental_compactions(self) -> int:
        return int(self._compactions.value(mode="incremental"))

    @property
    def merges(self) -> int:
        return int(self._merges.total())

    @property
    def autocheckpoints(self) -> int:
        return int(self._autocheckpoints.total())

    def __repr__(self) -> str:
        return (
            f"RouterStats(inserts={self.inserts}, "
            f"inserted_docs={self.inserted_docs}, deletes={self.deletes}, "
            f"deleted_sealed={self.deleted_sealed}, "
            f"deleted_grow={self.deleted_grow}, "
            f"unknown_deletes={self.unknown_deletes}, "
            f"compactions={self.compactions}, "
            f"incremental_compactions={self.incremental_compactions}, "
            f"merges={self.merges}, autocheckpoints={self.autocheckpoints})"
        )


class SegmentRouter:
    """Fronts a segmented ``HybridSearchService`` with a grow segment.

    Constructing a router attaches it to the service: ``service.insert`` /
    ``service.mark_deleted`` delegate here, and the service's read path
    starts merging the grow segment automatically once one exists."""

    def __init__(
        self,
        service: HybridSearchService,
        build_cfg: BuildConfig,
        config: Optional[RouterConfig] = None,
        *,
        kg_triplets: Optional[np.ndarray] = None,
        n_entities: int = 0,
        ingest=None,
    ):
        if not getattr(service, "_segmented", False):
            raise ValueError(
                "SegmentRouter fronts a SegmentedIndex service; a single "
                "HybridIndex already supports insert()/mark_deleted() directly"
            )
        self.service = service
        self.build_cfg = build_cfg
        self.config = config or RouterConfig()
        self.stats = RouterStats(service.metrics)
        # fitted IngestPipeline paired with auto-checkpoints (an index
        # restored without its frozen stats is silently wrong; DESIGN.md §7)
        self._ingest = ingest
        self._ckpt_lock = threading.Lock()  # serializes checkpoint writes
        self._last_ckpt_compactions = 0
        self._merge_lock = threading.Lock()  # merge-worker start/stop
        self._merge_thread: Optional[threading.Thread] = None
        self._merge_wake = threading.Event()
        self._merge_idle = threading.Event()
        self._merge_idle.set()
        self._merge_stop = threading.Event()
        self._kg_triplets = (
            None if kg_triplets is None else np.asarray(kg_triplets, np.int32)
        )
        self._n_entities = int(n_entities)
        # entity_adj is (1, 1) for a KG-less build (LogicalEdges.empty):
        # anything wider means the sealed index carries entity paths that a
        # triplet-less compaction would silently destroy — fail fast unless
        # the caller explicitly opted into that loss
        sealed = service._snap.index
        if isinstance(sealed, SegmentPool):
            sealed_has_kg = sealed.has_kg
            self._next_gid = sealed.max_global_id() + 1
        else:
            sealed_has_kg = sealed.index.entity_adj.shape[-1] > 1
            gids = np.asarray(sealed.global_ids)
            self._next_gid = int(gids.max()) + 1 if (gids >= 0).any() else 0
        if (
            sealed_has_kg
            and self._kg_triplets is None
            and not self.config.allow_kg_loss_on_compact
        ):
            raise ValueError(
                "the sealed index carries knowledge-graph data but the "
                "router has no kg_triplets: compaction would drop every "
                "entity path. Pass kg_triplets/n_entities, or set "
                "RouterConfig(allow_kg_loss_on_compact=True) to accept it."
            )
        self._grow_raw: Optional[HybridIndex] = None
        if service._snap.grow_gids is not None:
            # re-attaching over a live grow segment: its ids are allocated
            # past the sealed ones and must never be handed out again
            self._next_gid = max(
                self._next_gid, int(np.asarray(service._snap.grow_gids).max()) + 1
            )
            # recover the raw (unpadded) grow segment inserts extend — the
            # published one may carry a pow2 dead-row tail
            self._grow_raw = slice_grow_rows(
                service._snap.grow, int(service._snap.grow_gids.shape[0])
            )
        service._router = self

    # -- introspection ------------------------------------------------------

    @property
    def grow_size(self) -> int:
        """Real rows in the grow segment (including tombstoned ones,
        excluding pow2 shape-bucket padding)."""
        gids = self.service._snap.grow_gids
        return 0 if gids is None else int(gids.shape[0])

    @property
    def grow_capacity(self) -> int:
        """Published grow-segment capacity (= grow_size rounded up to a
        power of two when ``RouterConfig.grow_pow2`` is on)."""
        grow = self.service._snap.grow
        return 0 if grow is None else int(grow.n)

    @property
    def live_grow_size(self) -> int:
        """Non-tombstoned grow docs — the seal-threshold measure (pad rows
        are dead and never count)."""
        grow = self.service._snap.grow
        return 0 if grow is None else int(np.asarray(grow.alive).sum())

    @property
    def pool(self) -> Optional[SegmentPool]:
        """The sealed segment pool (None while fronting a plain stacked
        index that has never compacted incrementally)."""
        idx = self.service._snap.index
        return idx if isinstance(idx, SegmentPool) else None

    @property
    def compaction_mode(self) -> str:
        """Resolved ``RouterConfig.compaction``: explicit setting, else
        incremental for pool-fronted services and full otherwise."""
        if self.config.compaction is not None:
            return self.config.compaction
        return "incremental" if self.pool is not None else "full"

    @staticmethod
    def _entity_width(index) -> int:
        if isinstance(index, SegmentPool):
            return index.entity_width
        return int(index.index.doc_entities.shape[-1])

    @staticmethod
    def _as_pool(index) -> SegmentPool:
        """Wrap a stacked index as a single-group pool (no copy: its cached
        executable keeps serving — the keys are shape-identical)."""
        return (
            index
            if isinstance(index, SegmentPool)
            else SegmentPool.from_segmented(index)
        )

    def _corpus_dtype(self) -> str:
        """Sealed-segment storage dtype: follows the service's resolved
        SearchParams, so the AOT cache key and the storage always agree."""
        return self.service.params.corpus_dtype

    def _kg_kwargs(self, doc_entities: Optional[np.ndarray]) -> dict:
        if self._kg_triplets is None or self._n_entities <= 0:
            return {}
        return dict(
            kg_triplets=self._kg_triplets,
            doc_entities=doc_entities,
            n_entities=self._n_entities,
        )

    # -- writes (all under the service write lock, atomic publishes) --------

    def insert(
        self,
        new_docs: FusedVectors,
        *,
        key: Optional[jax.Array] = None,
        new_doc_entities: Optional[np.ndarray] = None,
        global_ids: Optional[np.ndarray] = None,
    ) -> int:
        """Absorb a batch of new docs into the grow segment; returns the new
        snapshot version. Never touches sealed segments (their executables
        stay cached). May trigger seal-and-compact when the grow segment
        crosses the threshold and ``auto_compact`` is on.

        ``global_ids`` pins the docs' ids instead of allocating them here —
        the replica-tier path (``serving.replica_router``), where placement
        is a function of the id and the TIER allocates: ids must be fresh
        (>= this router's next id) and strictly increasing, preserving the
        sorted-gid-map invariant the delete path relies on."""
        svc = self.service
        n_new = int(new_docs.n)
        if n_new == 0:
            return svc.snapshot_version
        if global_ids is not None:
            global_ids = np.asarray(global_ids, np.int64)
            if global_ids.shape != (n_new,):
                raise ValueError(
                    f"global_ids must be ({n_new},) to map every new doc"
                )
            if global_ids.size and (
                int(global_ids[0]) < self._next_gid
                or (np.diff(global_ids) <= 0).any()
            ):
                raise ValueError(
                    "pinned global_ids must be strictly increasing and >= "
                    f"the router's next id ({self._next_gid}): grow gids "
                    "stay sorted so deletes resolve by searchsorted"
                )
        if new_doc_entities is not None:
            if self._kg_triplets is None:
                raise ValueError(
                    "new_doc_entities given but the router has no knowledge "
                    "graph: pass kg_triplets/n_entities at construction"
                )
            new_doc_entities = np.asarray(new_doc_entities, np.int32)
            ent_width = self._entity_width(svc._snap.index)
            if new_doc_entities.shape != (n_new, ent_width):
                raise ValueError(
                    f"new_doc_entities must be ({n_new}, {ent_width}) to "
                    "match the sealed index's entity width"
                )
        with svc._write_lock:
            snap = svc._snap
            if key is None:
                key = jax.random.fold_in(jax.random.key(17), snap.version)
            new_gids = (
                np.arange(self._next_gid, self._next_gid + n_new, dtype=np.int32)
                if global_ids is None
                else global_ids.astype(np.int32)
            )
            if snap.grow is None:
                kg_kwargs = {}
                if self._kg_triplets is not None:
                    # a KG router ALWAYS births the grow segment with the
                    # sealed entity width (all-PAD rows when the batch has
                    # no entities), so later entity-carrying inserts never
                    # hit build_pipeline.insert's width check
                    ents = new_doc_entities
                    if ents is None:
                        width = self._entity_width(snap.index)
                        ents = np.full((n_new, width), PAD_IDX, np.int32)
                    kg_kwargs = dict(
                        kg_triplets=self._kg_triplets,
                        doc_entities=ents,
                        n_entities=self._n_entities,
                    )
                grow = build_index(new_docs, self.build_cfg, key=key, **kg_kwargs)
                gids = jnp.asarray(new_gids)
            else:
                # inserts always extend the RAW grow segment; the published
                # one may carry a pow2 dead-row tail that must not become
                # real neighbors
                grow = index_insert(
                    self._grow_raw,
                    new_docs,
                    self.build_cfg,
                    key=key,
                    new_doc_entities=new_doc_entities,
                    search_params=self.config.insert_search,
                )
                if new_doc_entities is not None:
                    # logical edges append INCREMENTALLY: docs inserted into
                    # an already-born grow segment get their entity paths
                    # now, not at the next compaction (host-side numpy over
                    # the small grow segment — O(grow))
                    grow = self._rebuild_grow_logical_edges(grow)
                gids = jnp.concatenate([snap.grow_gids, jnp.asarray(new_gids)])
            self._next_gid = int(new_gids[-1]) + 1
            self._grow_raw = grow
            if self.config.grow_pow2:
                grow = pad_grow_to_capacity(grow, _next_pow2(grow.n))
            svc._publish(snap.index, grow=grow, grow_gids=gids)
            self.stats._inserts.inc()
            self.stats._inserted_docs.inc(n_new)
            version = svc._snap.version
        if (
            self.config.auto_compact
            and self.live_grow_size >= self.config.seal_threshold
        ):
            return self.compact()
        return version

    def _rebuild_grow_logical_edges(self, grow: HybridIndex) -> HybridIndex:
        """Recompute the grow segment's logical edges over its FULL entity
        table (``build_pipeline.insert`` only appends PAD logical rows).
        Shape-stable: the caps and entity-table dims come from the build
        config and the frozen entity vocab."""
        if self._kg_triplets is None or self._n_entities <= 0:
            return grow
        log = build_logical_edges(
            self._kg_triplets,
            np.asarray(grow.doc_entities),
            self._n_entities,
            l_cap=self.build_cfg.logical_cap,
            m_cap=self.build_cfg.entity_doc_cap,
        )
        return dataclasses.replace(
            grow,
            logical_edges=jnp.asarray(log.edges),
            doc_entities=jnp.asarray(log.doc_entities),
            entity_to_docs=jnp.asarray(log.entity_to_docs),
            entity_adj=jnp.asarray(log.entity_adj),
        )

    def compact(self, *, key: Optional[jax.Array] = None) -> int:
        """Run the configured compaction: ``compact_incremental`` seals the
        grow segment into one pooled segment (O(grow) build work);
        ``seal_and_compact`` rebuilds everything (O(corpus))."""
        if self.compaction_mode == "incremental":
            return self.compact_incremental(key=key)
        return self.seal_and_compact(key=key)

    def delete(self, global_ids) -> int:
        """Tombstone docs by global id, wherever they live: sealed ids
        become (segment, local row) tombstones in the stacked alive mask,
        grow ids are mark-deleted in the grow segment. Both are
        shape-preserving — no executable is evicted. Returns the new
        snapshot version."""
        svc = self.service
        ids = np.atleast_1d(np.asarray(global_ids, np.int64))
        with svc._write_lock:
            snap = svc._snap
            pooled = isinstance(snap.index, SegmentPool)
            if pooled:
                grp, seg, loc = resolve_global_ids_pool(snap.index, ids)
                in_sealed = grp >= 0
            else:
                seg, loc = resolve_global_ids(snap.index, ids)
                in_sealed = seg >= 0
            grow, grow_gids = snap.grow, snap.grow_gids
            in_grow = np.zeros(ids.shape, bool)
            if grow is not None:
                gmap = np.asarray(grow_gids)
                in_grow = np.isin(ids, gmap) & ~in_sealed
                if in_grow.any():
                    # grow gids are allocated monotonically, so the map is
                    # sorted and searchsorted resolves local rows directly
                    # (row indices are identical in the raw and the padded
                    # view — padding only appends a dead tail)
                    rows = jnp.asarray(
                        np.searchsorted(gmap, ids[in_grow]), jnp.int32
                    )
                    grow = index_mark_deleted(grow, rows)
                    self._grow_raw = index_mark_deleted(self._grow_raw, rows)
            sealed = snap.index
            if in_sealed.any():
                if pooled:
                    sealed = mark_deleted_pool(
                        sealed, ids[in_sealed],
                        resolved=(grp[in_sealed], seg[in_sealed],
                                  loc[in_sealed]),
                    )
                else:
                    sealed = mark_deleted_segmented(
                        sealed, ids[in_sealed],
                        resolved=(seg[in_sealed], loc[in_sealed]),
                    )
            svc._publish(sealed, grow=grow, grow_gids=grow_gids)
            self.stats._deletes.inc()
            self.stats._deleted_docs.inc(int(in_sealed.sum()), target="sealed")
            self.stats._deleted_docs.inc(int(in_grow.sum()), target="grow")
            self.stats._deleted_docs.inc(
                int((~in_sealed & ~in_grow).sum()), target="unknown"
            )
            return svc._snap.version

    def seal_and_compact(self, *, key: Optional[jax.Array] = None) -> int:
        """Rebuild all surviving docs — sealed minus tombstones, plus live
        grow docs — into a fresh S-segment sealed index (S unchanged: the
        one-segment-per-device contract), remap the original global ids
        onto it, and publish atomically with the grow segment cleared.

        Physically drops every tombstoned id: this is the step that turns
        mark-deletion into reclaimed rows. Per-segment shapes change, so
        sealed executables recompile on the next read — the documented cost
        of compaction (DESIGN.md §6)."""
        svc = self.service
        with svc._write_lock:
            snap = svc._snap
            pooled = isinstance(snap.index, SegmentPool)
            if pooled:
                tombstoned = any(
                    bool(
                        (~np.asarray(g.index.alive)
                         & (np.asarray(g.global_ids) >= 0)).any()
                    )
                    for g in snap.index.groups
                )
                fragmented = snap.index.n_groups > 1
            else:
                tombstoned = bool(
                    (~np.asarray(snap.index.index.alive)
                     & (np.asarray(snap.index.global_ids) >= 0)).any()
                )
                fragmented = False
            if snap.grow is None and not tombstoned and not fragmented:
                return snap.version  # nothing growing, nothing to reclaim
            if pooled:
                sealed_corpus, sealed_gids, sealed_ents = alive_docs_pool(
                    snap.index
                )
            else:
                sealed_corpus, sealed_gids, sealed_ents = alive_docs(snap.index)
            parts_corpus, parts_gids = [sealed_corpus], [sealed_gids]
            parts_ents = [sealed_ents]
            ent_width = sealed_ents.shape[-1]
            if snap.grow is not None:
                live = np.flatnonzero(np.asarray(snap.grow.alive))
                if live.size:
                    parts_corpus.append(
                        jax.tree.map(
                            lambda a: jnp.asarray(np.asarray(a)[live]),
                            snap.grow.corpus,
                        )
                    )
                    parts_gids.append(np.asarray(snap.grow_gids)[live])
                    # grow entity rows, padded/clipped to the sealed width
                    # (a KG-less grow segment has width-1 all-PAD rows)
                    parts_ents.append(widen_entities(
                        np.asarray(snap.grow.doc_entities)[live], ent_width
                    ))
            corpus = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *parts_corpus
            )
            gids = np.concatenate(parts_gids)
            if key is None:
                key = jax.random.fold_in(jax.random.key(23), snap.version)
            kg_kwargs = {}
            if self._kg_triplets is not None:
                kg_kwargs = dict(
                    kg_triplets=self._kg_triplets,
                    doc_entities=np.concatenate(parts_ents, axis=0),
                    n_entities=self._n_entities,
                )
            if pooled:
                # a pool full-rebuild collapses every group into one fresh
                # stacked index spread over the mesh's segment devices —
                # total tombstone/fragmentation reclamation
                n_segments = (
                    mesh_segment_count(svc._mesh)
                    if svc._mesh is not None
                    else 1
                )
            else:
                n_segments = snap.index.n_segments
            new_seg = compact_segmented_index(
                corpus,
                gids,
                n_segments,
                self.build_cfg,
                mesh=svc._mesh,
                key=key,
                **kg_kwargs,
            )
            if self._corpus_dtype() == "int8":
                # builds are always fp32; sealed storage quantizes here
                new_seg = dataclasses.replace(
                    new_seg,
                    index=dataclasses.replace(
                        new_seg.index,
                        corpus=quantize_corpus(new_seg.index.corpus),
                    ),
                )
            if svc._mesh is not None:
                new_seg = place_segmented_index(new_seg, svc._mesh)
            published = self._as_pool(new_seg) if pooled else new_seg
            svc._publish(published, grow=None, grow_gids=None)
            self._grow_raw = None
            self.stats._compactions.inc(mode="full")
            version = svc._snap.version
        self._maybe_autocheckpoint()
        return version

    def compact_incremental(self, *, key: Optional[jax.Array] = None) -> int:
        """Seal the grow segment into ONE new pooled segment: rebuild only
        its live rows (O(grow segment) build work, asserted against the
        ``dispatch.build_rows`` counter by tests), carry their entity rows,
        drop its tombstones, and append to the pool — at pow2 capacity when
        ``RouterConfig.seal_pow2``, so segments land in reusable shape
        groups. Sealed segments are NEVER touched: their tombstones wait for
        ``merge_segments``/``seal_and_compact``, and every group the new
        segment does not join keeps its compiled executables byte-identical
        (verified by ``test_segment_pool.py``). Publishes atomically with
        the grow segment cleared; then runs the size-tier merge policy when
        ``auto_merge`` is on."""
        svc = self.service
        with svc._write_lock:
            snap = svc._snap
            if snap.grow is None:
                return snap.version
            pool = self._as_pool(snap.index)
            live = np.flatnonzero(np.asarray(snap.grow.alive))
            if live.size == 0:
                # every grow doc was tombstoned: dropping the grow segment
                # IS the compaction
                svc._publish(pool, grow=None, grow_gids=None)
                self._grow_raw = None
                self.stats._compactions.inc(mode="incremental")
                version = svc._snap.version
            else:
                grow_corpus = jax.tree.map(
                    lambda a: jnp.asarray(np.asarray(a)[live]), snap.grow.corpus
                )
                gids = np.asarray(snap.grow_gids)[live]
                ents = widen_entities(
                    np.asarray(snap.grow.doc_entities)[live],
                    self._entity_width(snap.index),
                )
                if key is None:
                    key = jax.random.fold_in(jax.random.key(29), snap.version)
                capacity = (
                    _next_pow2(int(live.size))
                    if self.config.seal_pow2
                    else int(live.size)
                )
                segment = build_pool_segment(
                    grow_corpus,
                    gids,
                    self.build_cfg,
                    capacity=capacity,
                    key=key,
                    corpus_dtype=self._corpus_dtype(),
                    **self._kg_kwargs(ents),
                )
                pool, _ = append_segment(pool, segment)
                pool = place_pool(pool, svc._mesh)
                svc._publish(pool, grow=None, grow_gids=None)
                self._grow_raw = None
                self.stats._compactions.inc(mode="incremental")
                version = svc._snap.version
        if self.config.auto_merge:
            if self.config.background_merge:
                self._notify_merge_worker()
            else:
                self.maybe_merge_segments()
                version = svc._snap.version
        self._maybe_autocheckpoint()
        return version

    def merge_segments(
        self,
        a: tuple[int, int],
        b: tuple[int, int],
        *,
        key: Optional[jax.Array] = None,
    ) -> int:
        """Coalesce two pooled segments — (group, segment-in-group) pairs —
        into one: gather their LIVE docs (tombstones are physically
        reclaimed here), rebuild one segment, remove the two old ones, and
        append the merged one. O(live docs of a + b); every group not
        holding a or b keeps its executables."""
        with self.service._write_lock:
            return self._merge_segments_locked(a, b, key=key)

    def _merge_segments_locked(
        self,
        a: tuple[int, int],
        b: tuple[int, int],
        *,
        key: Optional[jax.Array] = None,
    ) -> int:
        svc = self.service
        if a == b:
            raise ValueError("cannot merge a segment with itself")
        snap = svc._snap
        pool = self._as_pool(snap.index)
        for g, s in (a, b):
            if g >= pool.n_groups or s >= pool.groups[g].n_segments:
                raise ValueError(f"no pooled segment ({g}, {s})")
        ca, ga, ea = extract_segment_docs(pool, *a)
        cb, gb, eb = extract_segment_docs(pool, *b)
        width = max(ea.shape[-1], eb.shape[-1])
        corpus = jax.tree.map(
            lambda x, y: jnp.concatenate([x, y], axis=0), ca, cb
        )
        gids = np.concatenate([ga, gb])
        ents = np.concatenate(
            [widen_entities(ea, width), widen_entities(eb, width)], axis=0
        )
        pool = remove_segments(pool, [a, b])
        if corpus.n == 0:
            # both segments were fully tombstoned: removal is the merge
            if not pool.groups:
                return snap.version  # never publish an empty pool
        else:
            if key is None:
                key = jax.random.fold_in(jax.random.key(31), snap.version)
            capacity = (
                _next_pow2(int(corpus.n))
                if self.config.seal_pow2
                else int(corpus.n)
            )
            merged = build_pool_segment(
                corpus,
                gids,
                self.build_cfg,
                capacity=capacity,
                key=key,
                corpus_dtype=self._corpus_dtype(),
                **self._kg_kwargs(ents),
            )
            pool, _ = append_segment(pool, merged)
        pool = place_pool(pool, svc._mesh)
        svc._publish(pool, grow=snap.grow, grow_gids=snap.grow_gids)
        self.stats._merges.inc()
        return svc._snap.version

    def maybe_merge_segments(self, *, key: Optional[jax.Array] = None) -> int:
        """Enforce the size-tiered merge invariant: at most
        ``RouterConfig.tier_fanout`` segments per pow2-capacity tier. While
        a tier is over fanout, merge its two segments with the fewest live
        docs (LSM-style: merges migrate small segments up the tiers, so
        total merge work per doc is O(log corpus) over its lifetime).
        Each pick-and-merge runs atomically under the service write lock
        (a pick computed outside it could go stale against a concurrent
        compaction or merge). Returns the number of merges performed."""
        merges = 0
        while True:
            with self.service._write_lock:
                snap = self.service._snap
                if not isinstance(snap.index, SegmentPool):
                    return merges
                tiers: dict[int, list[tuple[int, int, int]]] = {}
                for g, s, cap, live in live_counts(snap.index):
                    tiers.setdefault(max(cap, 1).bit_length(), []).append(
                        (live, g, s)
                    )
                offending = [
                    members
                    for members in tiers.values()
                    if len(members) > self.config.tier_fanout
                ]
                if not offending:
                    return merges
                members = sorted(offending[0])
                a, b = members[0][1:], members[1][1:]
                v0 = snap.version
                self._merge_segments_locked(a, b, key=key)
                if self.service._snap.version == v0:
                    return merges  # merge declined (would empty the pool)
            merges += 1

    # -- background merge worker --------------------------------------------

    def _notify_merge_worker(self) -> None:
        """Wake (starting lazily if needed) the background merge worker.
        Called after each incremental compaction when ``background_merge``
        is on: the compaction returns as soon as the new segment publishes
        and the merge cascade runs off the caller's thread (each merge still
        takes the service write lock, so readers/writers stay correct)."""
        with self._merge_lock:
            if self._merge_thread is None or not self._merge_thread.is_alive():
                self._merge_stop.clear()
                self._merge_thread = threading.Thread(
                    target=self._merge_loop,
                    name="segment-router-merge",
                    daemon=True,
                )
                self._merge_thread.start()
            self._merge_wake.set()

    def _merge_loop(self) -> None:
        while True:
            self._merge_wake.wait()
            if self._merge_stop.is_set():
                return
            # order matters for wait_merges(): drop idle BEFORE consuming
            # the wake flag, so at every instant a pending merge shows as
            # either wake-set or idle-clear
            self._merge_idle.clear()
            self._merge_wake.clear()
            try:
                self.maybe_merge_segments()
            finally:
                self._merge_idle.set()

    def wait_merges(self, timeout_s: float = 120.0) -> None:
        """Block until the size-tier merge policy is quiescent: no pending
        wake-up and no merge cascade in flight. A no-op when nothing is
        pending; tests use it to make background merges deterministic."""
        deadline = time.monotonic() + timeout_s
        while self._merge_wake.is_set() or not self._merge_idle.is_set():
            if self._merge_stop.is_set():
                return
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"merge worker still busy after {timeout_s}s"
                )
            time.sleep(0.005)

    def stop_merge_worker(self, timeout_s: float = 60.0) -> None:
        """Clean-shutdown join of the merge worker (idempotent;
        ``HybridSearchService.stop_pump`` calls it). An in-flight policy run
        finishes — the stop flag is only checked between runs — so no merge
        is ever torn mid-publish."""
        with self._merge_lock:
            thread = self._merge_thread
            if thread is None:
                return
            self._merge_stop.set()
            self._merge_wake.set()
            thread.join(timeout=timeout_s)
            self._merge_thread = None
            self._merge_wake.clear()
            self._merge_stop.clear()

    # -- auto-checkpoint ----------------------------------------------------

    def _maybe_autocheckpoint(self) -> None:
        """Persist the sealed pool — paired with the fitted ingest pipeline
        when the router holds one — every ``autocheckpoint_every``
        compactions, so a crash loses at most the current grow segment plus
        one checkpoint window. Runs OUTSIDE the service write lock (the
        snapshot is immutable once published; serialization is disk I/O the
        write path must not wait on) and serializes concurrent writers on
        its own lock."""
        cfg = self.config
        if cfg.autocheckpoint_every <= 0 or cfg.autocheckpoint_dir is None:
            return
        with self._ckpt_lock:
            done = self.stats.compactions
            if done - self._last_ckpt_compactions < cfg.autocheckpoint_every:
                return
            pool = self.pool
            if pool is None:
                return
            # local import: checkpoint.index_io imports serving-adjacent
            # modules at load time; importing it lazily keeps the router
            # importable in minimal environments
            from repro.checkpoint.index_io import save_pool

            save_pool(cfg.autocheckpoint_dir, pool, ingest=self._ingest)
            self._last_ckpt_compactions = done
            self.stats._autocheckpoints.inc()
