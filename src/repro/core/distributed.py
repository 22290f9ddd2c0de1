"""Distributed retrieval: segment-sharded hybrid index over the production
mesh (paper §5.9 scaling story, Milvus/Starling-style data segments).

Sharding layout on a mesh with axes ("pod", "data", "model") — or any prefix:

  * the corpus is split into S = |pod|x|data| *segments*; every segment owns
    a full standalone hybrid index over its documents (graphs never cross
    segments, exactly like vector-DB data segments, so construction and
    updates stay embarrassingly parallel);
  * the "model" axis shards the *query batch* within each segment group —
    with 2x16 pods x 16-way model that is 512-way parallelism for a batched
    search;
  * each device runs the full beam search on its (segment, query-shard)
    block; results are merged with one all_gather over "model" (reassemble
    the batch) + one all_gather over ("pod", "data") (merge segment top-k) +
    a local top-k — the only collectives in the query path.

The per-device compute (gather + hybrid-distance kernel) is identical to the
single-device path, so the Pallas kernel is reused unchanged.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.build_pipeline import GraphArrays, _build_graph_program, build_index
from repro.core.fusion import (
    FusionSpec,
    PathStats,
    broadcast_spec,
    merge_rows_fused,
)
from repro.core.index import BuildConfig, HybridIndex
from repro.core.logical_edges import LogicalEdges, build_logical_edges
from repro.core.search import SearchParams, SearchResult, search_padded
from repro.core import usms
from repro.core.usms import PAD_IDX, FusedVectors, PathWeights
from repro.runtime import dispatch

SEGMENT_AXES = ("pod", "data")  # axes that shard segments (present subset used)
QUERY_AXIS = "model"  # axis that shards the query batch


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["index", "global_ids"],
    meta_fields=[],
)
@dataclasses.dataclass
class SegmentedIndex:
    """Per-segment hybrid indexes stacked on a leading segment axis.

    index: HybridIndex pytree whose leaves have shape (S, ...).
    global_ids: (S, n_seg) int32 mapping local row -> original doc id.
    """

    index: HybridIndex
    global_ids: jax.Array

    @property
    def n_segments(self) -> int:
        return self.global_ids.shape[0]


def segment_slices(n: int, n_segments: int) -> list[tuple[int, int]]:
    """Contiguous per-segment (lo, hi) slices; trailing segments may be
    EMPTY (lo == hi) when n < n_segments * ceil(n/n_segments) — e.g. after
    a compaction shrank the corpus below the segment layout."""
    per = -(-n // n_segments)  # ceil
    return [
        (min(s * per, n), min((s + 1) * per, n)) for s in range(n_segments)
    ]


def shard_corpus(
    corpus: FusedVectors, n_segments: int
) -> tuple[list[FusedVectors], np.ndarray]:
    """Split a corpus into equal segments (last one zero-padded).
    Returns per-segment corpora and the (S, n_seg) global id map."""
    n = corpus.n
    per = -(-n // n_segments)
    gids = np.full((n_segments, per), PAD_IDX, np.int32)
    parts = []
    for s, (lo, hi) in enumerate(segment_slices(n, n_segments)):
        gids[s, : hi - lo] = np.arange(lo, hi)
        part = corpus[slice(lo, hi)]
        pad = per - (hi - lo)
        if pad:
            part = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]
                ),
                part,
            )
        parts.append(part)
    return parts, gids


def build_segmented_index(
    corpus: FusedVectors,
    n_segments: int,
    cfg: BuildConfig = BuildConfig(),
    *,
    key: Optional[jax.Array] = None,
    kg_triplets: Optional[np.ndarray] = None,
    doc_entities: Optional[np.ndarray] = None,
    n_entities: int = 0,
) -> SegmentedIndex:
    """Build every segment's index independently (the distributed-construction
    model: on real hardware each host builds its own segments; here the loop
    is sequential but each build is the same jitted program)."""
    key = key if key is not None else jax.random.key(0)
    parts, gids = shard_corpus(corpus, n_segments)
    indexes = []
    for s, part in enumerate(parts):
        kg_kwargs = {}
        if kg_triplets is not None and doc_entities is not None:
            lo, hi = segment_slices(corpus.n, n_segments)[s]
            ents = np.full((part.n, doc_entities.shape[1]), PAD_IDX, np.int32)
            ents[: hi - lo] = doc_entities[lo:hi]
            kg_kwargs = dict(
                kg_triplets=kg_triplets, doc_entities=ents, n_entities=n_entities
            )
        idx = build_index(part, cfg, key=jax.random.fold_in(key, s), **kg_kwargs)
        # padded rows must never be returned
        valid = jnp.asarray(gids[s] >= 0)
        idx = dataclasses.replace(idx, alive=idx.alive & valid)
        indexes.append(idx)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *indexes)
    return SegmentedIndex(index=stacked, global_ids=jnp.asarray(gids))


def _present_axes(mesh: Mesh, axes: Sequence[str]) -> tuple[str, ...]:
    return tuple(a for a in axes if a in mesh.axis_names)


def mesh_segment_count(mesh: Mesh) -> int:
    """Number of devices on the segment axes. Sharded builds and searches
    require the stacked segment count S to be a MULTIPLE of this (each
    device owns S / mesh_segment_count segments — the segment-pool
    generalization of the old one-segment-per-device contract)."""
    seg_axes = _present_axes(mesh, SEGMENT_AXES)
    return int(np.prod([mesh.shape[a] for a in seg_axes])) if seg_axes else 1


# ---------------------------------------------------------------------------
# Global-id routing (serving-layer grow-segment scheme): deletion and
# compaction need to resolve original doc ids back to (segment, local row).
# ---------------------------------------------------------------------------


def resolve_global_ids(
    seg_index: SegmentedIndex, ids: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side routing: global doc id -> (segment, local row).

    Ids not present in ``global_ids`` (never indexed here, or compacted away)
    resolve to (-1, -1). Compaction leaves gaps in the id space, so the
    lookup is a searchsorted over the sorted valid ids, not an arange."""
    gids = np.asarray(seg_index.global_ids)
    per = gids.shape[1]
    flat = gids.reshape(-1)
    valid_pos = np.flatnonzero(flat >= 0)
    ids = np.atleast_1d(np.asarray(ids, np.int64))
    if valid_pos.size == 0:
        none = np.full(ids.shape, -1, np.int32)
        return none, none.copy()
    order = np.argsort(flat[valid_pos], kind="stable")
    sorted_g = flat[valid_pos][order]
    pos = valid_pos[order]
    j = np.clip(np.searchsorted(sorted_g, ids), 0, sorted_g.size - 1)
    found = (sorted_g[j] == ids) & (ids >= 0)
    p = np.where(found, pos[j], -1)
    seg = np.where(found, p // per, -1).astype(np.int32)
    loc = np.where(found, p % per, -1).astype(np.int32)
    return seg, loc


def mark_deleted_segmented(
    seg_index: SegmentedIndex,
    global_ids: np.ndarray,
    *,
    resolved: Optional[tuple[np.ndarray, np.ndarray]] = None,
) -> SegmentedIndex:
    """Tombstone docs by GLOBAL id: resolve to (segment, local row) and clear
    the per-segment alive mask. Shape-preserving, so cached search
    executables for this index keep serving. Unresolved ids are ignored.
    Pass ``resolved=(seg, loc)`` when the caller already routed the ids —
    skips a second full global_ids materialization + sort."""
    seg, loc = (
        resolved if resolved is not None
        else resolve_global_ids(seg_index, global_ids)
    )
    alive = seg_index.index.alive
    n_seg = alive.shape[0]
    seg_j = jnp.asarray(np.where(seg >= 0, seg, n_seg), jnp.int32)
    loc_j = jnp.asarray(np.where(loc >= 0, loc, 0), jnp.int32)
    alive = alive.at[seg_j, loc_j].set(False, mode="drop")
    return SegmentedIndex(
        index=dataclasses.replace(seg_index.index, alive=alive),
        global_ids=seg_index.global_ids,
    )


def alive_docs(
    seg_index: SegmentedIndex,
) -> tuple[FusedVectors, np.ndarray, np.ndarray]:
    """Gather the live (non-pad, non-tombstoned) docs of every segment on
    the host. Returns (corpus rows, their global ids, their doc-entity
    rows) — the compaction input. The entity rows are all-PAD width-1 for
    an index built without a knowledge graph. Quantized storage is
    dequantized here: every rebuild / merge input is fp32 (builds never see
    int8; re-quantization happens when the rebuilt segment seals)."""
    gids = np.asarray(seg_index.global_ids).reshape(-1)
    alive = np.asarray(seg_index.index.alive).reshape(-1)
    rows = np.flatnonzero((gids >= 0) & alive)
    corpus = jax.tree.map(
        lambda a: jnp.asarray(
            np.asarray(a).reshape((-1,) + a.shape[2:])[rows]
        ),
        seg_index.index.corpus,
    )
    if isinstance(corpus, usms.QuantizedFusedVectors):
        corpus = usms.dequantize_corpus(corpus)
    ents = np.asarray(seg_index.index.doc_entities)
    ents = ents.reshape((-1, ents.shape[-1]))[rows]
    return corpus, gids[rows].astype(np.int32), ents


def compact_segmented_index(
    corpus: FusedVectors,
    global_ids: np.ndarray,
    n_segments: int,
    cfg: BuildConfig = BuildConfig(),
    *,
    mesh: Optional[Mesh] = None,
    key: Optional[jax.Array] = None,
    kg_triplets: Optional[np.ndarray] = None,
    doc_entities: Optional[np.ndarray] = None,
    n_entities: int = 0,
) -> SegmentedIndex:
    """Rebuild a corpus of surviving docs into a fresh S-segment sealed
    index, PRESERVING the caller's global ids (positions change, identities
    don't — results keep referring to the original doc ids). Pass the
    knowledge graph (triplets + per-row doc entities) to rebuild the
    logical edges too — without it a KG-bearing index would lose its
    entity paths on compaction.

    Uses the parallel ``build_index_sharded`` when ``n_segments`` is a
    multiple of the mesh's segment-axis device count (the segment-pool
    placement contract), else the sequential ``build_segmented_index``."""
    global_ids = np.asarray(global_ids, np.int32)
    if corpus.n == 0:
        raise ValueError("cannot compact an empty corpus (all docs deleted)")
    if global_ids.shape[0] != corpus.n:
        raise ValueError("global_ids must map every corpus row")
    kg_kwargs = dict(
        kg_triplets=kg_triplets, doc_entities=doc_entities,
        n_entities=n_entities,
    )
    if mesh is not None and n_segments % mesh_segment_count(mesh) == 0:
        seg = build_index_sharded(
            corpus, n_segments, cfg, mesh=mesh, key=key, **kg_kwargs
        )
    else:
        seg = build_segmented_index(corpus, n_segments, cfg, key=key, **kg_kwargs)
    # the build assigned positional ids; remap to the surviving originals
    per = seg.global_ids.shape[1]
    new_g = np.full((n_segments, per), PAD_IDX, np.int32)
    for s, (lo, hi) in enumerate(segment_slices(corpus.n, n_segments)):
        new_g[s, : hi - lo] = global_ids[lo:hi]
    return SegmentedIndex(index=seg.index, global_ids=jnp.asarray(new_g))


def _segment_spec(mesh: Mesh) -> P:
    seg_axes = _present_axes(mesh, SEGMENT_AXES)
    return P(seg_axes if len(seg_axes) > 1 else (seg_axes[0] if seg_axes else None))


# ---------------------------------------------------------------------------
# Segment-parallel construction (paper §4.1 at scale): every device builds
# its segment's graph with the SAME device-resident program the single-device
# path uses (core/build_pipeline.py). Graphs never cross segments, so the
# build has zero collectives and scales linearly with devices.
# ---------------------------------------------------------------------------


_sharded_builder_cache: dict = {}


def make_sharded_graph_builder(mesh: Mesh, cfg: BuildConfig):
    """shard_map wrapper around the fused graph-build program.

    Returns fn(stacked_corpus, seg_key_data) -> GraphArrays with leaves
    (S, ...). Each device owns S / mesh_segment_count segments and streams
    its local block through ``lax.map`` (sequential per local segment, so
    the per-device memory high-water stays one build); keys travel as
    uint32 key data so they shard like ordinary arrays. Builders are cached
    on (mesh, cfg) so repeated sharded builds (periodic segment rebuilds)
    reuse the compiled program."""
    cache_key = (mesh, cfg)
    cached = _sharded_builder_cache.get(cache_key)
    if cached is not None:
        return cached
    spec = _segment_spec(mesh)

    def local_build(corpus_blk: FusedVectors, key_blk: jax.Array) -> GraphArrays:
        def one(args):
            corpus, key_data = args
            return _build_graph_program(
                corpus, jax.random.wrap_key_data(key_data), cfg
            )

        return jax.lax.map(one, (corpus_blk, key_blk))

    graph_specs = GraphArrays(
        knn_ids=spec,
        knn_scores=spec,
        semantic_edges=spec,
        keyword_edges=spec,
        entry_points=spec,
        self_ip=spec,
    )
    builder = jax.jit(
        jax.shard_map(
            local_build,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: spec, _queries_struct()), spec),
            out_specs=graph_specs,
            check_vma=False,
        )
    )
    _sharded_builder_cache[cache_key] = builder
    return builder


def build_index_sharded(
    corpus: FusedVectors,
    n_segments: int,
    cfg: BuildConfig = BuildConfig(),
    *,
    mesh: Mesh,
    key: Optional[jax.Array] = None,
    kg_triplets: Optional[np.ndarray] = None,
    doc_entities: Optional[np.ndarray] = None,
    n_entities: int = 0,
) -> SegmentedIndex:
    """Build every segment's graph IN PARALLEL across the mesh (one
    shard_map dispatch for all device-side stages), then assemble the
    SegmentedIndex on the host (logical edges are host-side numpy).

    Per-segment results match ``build_segmented_index`` (which runs the same
    program sequentially): segment s is built from ``fold_in(key, s)``."""
    key = key if key is not None else jax.random.key(0)
    n_mesh_segs = mesh_segment_count(mesh)
    if n_segments % n_mesh_segs != 0:
        raise ValueError(
            f"n_segments={n_segments} must be a multiple of the segment-axes "
            f"device count {n_mesh_segs} (each device builds "
            f"n_segments / {n_mesh_segs} segments)"
        )
    dispatch.build_rows_tick(corpus.n)
    parts, gids = shard_corpus(corpus, n_segments)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *parts)
    seg_keys = jnp.stack(
        [
            jax.random.key_data(jax.random.fold_in(key, s))
            for s in range(n_segments)
        ]
    )
    sharding = NamedSharding(mesh, _segment_spec(mesh))
    stacked = jax.tree.map(lambda a: jax.device_put(a, sharding), stacked)
    seg_keys = jax.device_put(seg_keys, sharding)

    builder = make_sharded_graph_builder(mesh, cfg)
    dispatch.tick()
    g = builder(stacked, seg_keys)  # GraphArrays, leaves (S, ...)

    # host-side assembly: logical edges + alive masks per segment
    per = gids.shape[1]
    slices = segment_slices(corpus.n, n_segments)
    logs = []
    for s in range(n_segments):
        if kg_triplets is not None and doc_entities is not None and n_entities > 0:
            lo, hi = slices[s]
            ents = np.full((per, doc_entities.shape[1]), PAD_IDX, np.int32)
            ents[: hi - lo] = doc_entities[lo:hi]
            logs.append(
                build_logical_edges(
                    kg_triplets,
                    ents,
                    n_entities,
                    l_cap=cfg.logical_cap,
                    m_cap=cfg.entity_doc_cap,
                )
            )
        else:
            logs.append(LogicalEdges.empty(per))
    stack_log = lambda get: jnp.stack([jnp.asarray(get(l)) for l in logs], axis=0)
    alive = jnp.asarray(gids >= 0)

    index = HybridIndex(
        corpus=stacked,
        semantic_edges=g.semantic_edges,
        keyword_edges=g.keyword_edges,
        logical_edges=stack_log(lambda l: l.edges),
        doc_entities=stack_log(lambda l: l.doc_entities),
        entity_to_docs=stack_log(lambda l: l.entity_to_docs),
        entity_adj=stack_log(lambda l: l.entity_adj),
        entry_points=g.entry_points,
        alive=alive,
        self_ip=g.self_ip,
    )
    return SegmentedIndex(index=index, global_ids=jnp.asarray(gids))


def _segment_to_global(
    idx: HybridIndex,
    gids: jax.Array,
    queries: FusedVectors,
    fusion: FusionSpec,
    keywords: jax.Array,
    entities: jax.Array,
    params: SearchParams,
):
    """One segment's search with local row ids mapped to GLOBAL doc ids
    (-inf scores on pad slots) — the unit every segment/pool merge
    composes. Per-path scores ride along so fusion-aware merges can
    recompute RRF ranks over the union (the merge contract, §11)."""
    res = search_padded(idx, queries, fusion, keywords, entities, params)
    g = jnp.where(
        res.ids >= 0, gids[jnp.clip(res.ids, 0, gids.shape[0] - 1)], PAD_IDX
    )
    scores = jnp.where(g >= 0, res.scores, -jnp.inf)
    ps = jnp.where((g >= 0)[:, :, None], res.path_scores, 0.0)
    return g, scores, ps, res.expanded


def _merge_rows_topk(g_all: jax.Array, s_all: jax.Array, k: int):
    """Per-row top-k over stacked (S, B, k) global-id results; returns
    (top scores, ids) with PAD ids on non-finite slots. Raw-score merge:
    correct for weighted/normalized fusion only — RRF results go through
    ``fusion.merge_rows_fused`` instead."""
    b = g_all.shape[1]
    g_flat = jnp.moveaxis(g_all, 0, 1).reshape(b, -1)
    s_flat = jnp.moveaxis(s_all, 0, 1).reshape(b, -1)
    top, pos = jax.lax.top_k(s_flat, k)
    ids = jnp.where(
        jnp.isfinite(top), jnp.take_along_axis(g_flat, pos, axis=-1), PAD_IDX
    )
    return top, ids


def make_distributed_search_padded(
    mesh: Mesh,
    params: SearchParams,
):
    """Build the jitted shard_map search for a given mesh, shape-stable form.

    Returns fn(seg_index, queries, fusion, keywords, entities) ->
    SearchResult with globally-merged ids. Fusion/keywords/entities travel
    as traced data per call (fusion leaves must be (B,)/(B, 3) arrays so
    they shard with the query batch), so one executable serves every path
    combination AND every fusion mode — this is the entry point the serving
    layer fronts sharded indexes with. Queries are sharded over the "model"
    axis (if present); the segmented index is sharded over ("pod", "data").
    S may be any MULTIPLE of the segment-axes device count: a device owning
    several segments searches them in one vmapped pass; all S segments'
    top-k then merge in ONE fusion-aware pass after the segment-axes gather
    (RRF rows re-rank over the union — merging local RRF scores by value
    across segments would be meaningless, §11).
    """
    seg_axes = _present_axes(mesh, SEGMENT_AXES)
    q_axes = _present_axes(mesh, (QUERY_AXIS,))
    seg_spec = _segment_spec(mesh)
    q_spec = P(q_axes[0]) if q_axes else P()
    NEG_FILL = jnp.float32(-1e30)

    def local_search(
        seg_index: SegmentedIndex,
        queries: FusedVectors,
        fusion: FusionSpec,
        keywords: jax.Array,
        entities: jax.Array,
    ):
        # shard_map gives each device a (segments_per_device, ...) block
        spd = seg_index.global_ids.shape[0]
        if spd == 1:
            g, scores, ps, exp = _segment_to_global(
                jax.tree.map(lambda a: a[0], seg_index.index),
                seg_index.global_ids[0],
                queries, fusion, keywords, entities, params,
            )
            g, scores, ps = g[None], scores[None], ps[None]
        else:
            # several same-device segments: one vmapped batched pass
            g, scores, ps, exp = jax.vmap(
                lambda idx, gids: _segment_to_global(
                    idx, gids, queries, fusion, keywords, entities, params
                )
            )(seg_index.index, seg_index.global_ids)  # (spd, B, k)
        expanded_local = exp.sum()

        # gather the OTHER devices' segment results FIRST, while rows are
        # still aligned with this device's local query shard (the fusion
        # spec rows are local), and fuse-merge all S segments in one pass
        if seg_axes:
            g = jax.lax.all_gather(g, seg_axes, axis=0, tiled=True)
            scores = jax.lax.all_gather(scores, seg_axes, axis=0, tiled=True)
            ps = jax.lax.all_gather(ps, seg_axes, axis=0, tiled=True)
        ids, top, ps_m = merge_rows_fused(g, scores, ps, fusion, params.k)

        # reassemble the query batch across the model axis
        if q_axes:
            ids = jax.lax.all_gather(ids, q_axes[0], axis=0, tiled=True)
            top = jax.lax.all_gather(top, q_axes[0], axis=0, tiled=True)
            ps_m = jax.lax.all_gather(ps_m, q_axes[0], axis=0, tiled=True)
        expanded = expanded_local
        all_axes = tuple(seg_axes) + tuple(q_axes)
        if all_axes:
            expanded = jax.lax.psum(expanded, all_axes)
        return (
            ids,
            jnp.where(jnp.isfinite(top), top, NEG_FILL),
            ps_m,
            expanded,
        )

    shard_fn = jax.shard_map(
        local_search,
        mesh=mesh,
        in_specs=(
            # a single prefix spec for the whole SegmentedIndex: every leaf
            # shards over the segment axes regardless of whether the corpus
            # subtree is FusedVectors or QuantizedFusedVectors (§13)
            seg_spec,
            jax.tree.map(lambda _: q_spec, _queries_struct()),
            jax.tree.map(lambda _: q_spec, _fusion_struct()),
            q_spec,
            q_spec,
        ),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )

    @jax.jit
    def run(
        seg_index: SegmentedIndex,
        queries: FusedVectors,
        fusion: Union[FusionSpec, PathWeights],
        keywords: jax.Array,
        entities: jax.Array,
    ) -> SearchResult:
        if isinstance(fusion, PathWeights):
            fusion = FusionSpec.from_weights(fusion)
        spec = broadcast_spec(fusion, queries.dense.shape[0])
        ids, scores, ps, expanded = shard_fn(
            seg_index, queries, spec, keywords, entities
        )
        return SearchResult(
            ids, scores, jnp.broadcast_to(expanded, (ids.shape[0],)), ps
        )

    return run


_local_group_search_cache: dict = {}


def make_local_group_search(params: SearchParams):
    """Single-host counterpart of ``make_distributed_search_padded``: search
    a stacked ``SegmentedIndex`` (a segment-pool group) with one vmapped
    ``search_padded`` pass over the leading segment axis and merge the
    per-segment top-k in global-id space — no mesh, no collectives. This is
    the executable the serving layer AOT-caches per pool shape-group when a
    group is not placed on (or not divisible over) the mesh's segment axes.
    Cached on ``params`` so every caller shares one jit cache."""
    cached = _local_group_search_cache.get(params)
    if cached is not None:
        return cached
    NEG_FILL = jnp.float32(-1e30)

    @jax.jit
    def run(
        seg_index: SegmentedIndex,
        queries: FusedVectors,
        fusion: Union[FusionSpec, PathWeights],
        keywords: jax.Array,
        entities: jax.Array,
    ) -> SearchResult:
        if isinstance(fusion, PathWeights):
            fusion = FusionSpec.from_weights(fusion)
        spec = broadcast_spec(fusion, queries.dense.shape[0])
        g_all, s_all, ps_all, exp = jax.vmap(
            lambda idx, gids: _segment_to_global(
                idx, gids, queries, spec, keywords, entities, params
            )
        )(seg_index.index, seg_index.global_ids)  # (S, B, k)
        ids, top, ps = merge_rows_fused(g_all, s_all, ps_all, spec, params.k)
        scores = jnp.where(jnp.isfinite(top), top, NEG_FILL)
        # whole-batch total broadcast per row — the same convention as the
        # sharded executable, so pool reads can sum the two coherently
        expanded = jnp.broadcast_to(exp.sum(), (ids.shape[0],))
        return SearchResult(ids, scores, expanded, ps)

    _local_group_search_cache[params] = run
    return run


def make_distributed_search(
    mesh: Mesh,
    fusion: Union[FusionSpec, PathWeights],
    params: SearchParams,
):
    """Fixed-fusion convenience wrapper over the shape-stable form (accepts
    a ``FusionSpec`` or bare ``PathWeights`` = weighted-sum).

    Returns fn(seg_index, queries) -> SearchResult with globally-merged ids.
    """
    run = make_distributed_search_padded(mesh, params)

    def fn(seg_index: SegmentedIndex, queries: FusedVectors) -> SearchResult:
        b = queries.dense.shape[0]
        pad = jnp.full((b, 1), PAD_IDX, jnp.int32)
        return run(seg_index, queries, fusion, pad, pad)

    return fn


def _index_struct():
    """A HybridIndex-shaped pytree of placeholders for building spec trees."""
    z = 0
    return HybridIndex(
        corpus=_queries_struct(),
        semantic_edges=z,
        keyword_edges=z,
        logical_edges=z,
        doc_entities=z,
        entity_to_docs=z,
        entity_adj=z,
        entry_points=z,
        alive=z,
        self_ip=z,
    )


def _queries_struct():
    from repro.core.usms import SparseVec

    z = 0
    return FusedVectors(dense=z, learned=SparseVec(z, z), lexical=SparseVec(z, z))


def _weights_struct():
    z = 0
    return PathWeights(dense=z, sparse=z, full=z, kg=z)


def _fusion_struct():
    """A FusionSpec-shaped pytree of placeholders (stats RESOLVED: the
    sharded entry point broadcasts specs before crossing into shard_map, so
    the in-spec tree always carries concrete stats leaves)."""
    z = 0
    return FusionSpec(
        mode=z,
        weights=_weights_struct(),
        rrf_k=z,
        stats=PathStats(minv=z, maxv=z, mean=z, std=z),
    )


def place_segmented_index(
    seg_index: SegmentedIndex, mesh: Mesh
) -> SegmentedIndex:
    """Device_put the segmented index with segments over ("pod", "data")."""
    sharding = NamedSharding(mesh, _segment_spec(mesh))
    return jax.tree.map(
        lambda a: jax.device_put(a, sharding) if hasattr(a, "shape") else a, seg_index
    )


# ---------------------------------------------------------------------------
# Distributed construction round (for the construction dry-run at scale):
# each segment runs one NN-Descent round locally under shard_map.
# ---------------------------------------------------------------------------


def make_distributed_descent_round(mesh: Mesh, cfg):
    """One lock-step NN-Descent round across all segments (shard_map). The
    graph of each segment is private, so no cross-device collectives appear in
    the construction path — the build scales linearly with devices."""
    from repro.core.knn_graph import _descent_round_chunk

    spec = _segment_spec(mesh)

    def local_round(corpus, nbr_ids, scores, rand_ids):
        corpus = jax.tree.map(lambda a: a[0], corpus)
        nbr_ids, scores, rand_ids = nbr_ids[0], scores[0], rand_ids[0]
        n = nbr_ids.shape[0]
        node_ids = jnp.arange(n, dtype=jnp.int32)
        ids, sc = _descent_round_chunk(
            corpus, nbr_ids, corpus, node_ids, nbr_ids, scores, rand_ids, cfg
        )
        return ids[None], sc[None]

    return jax.jit(
        jax.shard_map(
            local_round,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: spec, _queries_struct()),
                spec,
                spec,
                spec,
            ),
            out_specs=(spec, spec),
            check_vma=False,
        )
    )
