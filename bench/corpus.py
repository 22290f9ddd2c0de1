"""Seeded hybrid corpora made on the device in one jitted call.

The distribution is that of ``repro.data.corpus.make_corpus`` (topic
centres for the dense path, Zipf-weighted topic term pools that share a
global head of common terms for the learned-sparse and lexical paths, and
queries anchored on three relevant passages of one topic), vectorised so
that a 2**17-passage corpus takes seconds instead of a host loop's minute.
The random stream differs from ``make_corpus``'s; the shapes, pools, value
profile and query anchoring are the same. Knowledge-graph entities are not
generated: the benchmark's deployments run without logical edges.

Sampling without replacement under Zipf weights uses the Gumbel-top-k
trick, which draws the same distribution as successive weighted draws.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

PAD = -1


@dataclasses.dataclass(frozen=True)
class Widths:
    d_dense: int
    vocab_sparse: int
    vocab_lexical: int
    nnz_sparse: int
    nnz_lexical: int
    nnz_query_sparse: int
    nnz_query_lexical: int
    n_topics: int
    terms_per_topic: int
    keywords_per_topic: int
    dense_noise: float = 0.35
    relevant_per_query: int = 3

    @classmethod
    def from_config(cls, cfg: dict) -> "Widths":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in cfg.items() if k in names})


@dataclasses.dataclass
class Corpus:
    """Plain arrays: passages and queries as (dense, sparse idx/val, lexical
    idx/val), plus each query's keywords shared by all its relevant
    passages (smallest ids first, PAD-padded to 2)."""

    dense: jax.Array  # (N, Dd) f32
    s_idx: jax.Array  # (N, Ps) i32
    s_val: jax.Array  # (N, Ps) f32
    f_idx: jax.Array  # (N, Pf) i32
    f_val: jax.Array
    q_dense: jax.Array  # (Q, Dd)
    qs_idx: jax.Array  # (Q, Qs)
    qs_val: jax.Array
    qf_idx: jax.Array  # (Q, Qf)
    qf_val: jax.Array
    q_shared_kw: jax.Array  # (Q, 2)

    @property
    def n(self) -> int:
        return self.dense.shape[0]

    def rows(self, lo: int, hi: int) -> tuple:
        """Passages [lo, hi) as (dense, s_idx, s_val, f_idx, f_val)."""
        return tuple(
            a[lo:hi] for a in (self.dense, self.s_idx, self.s_val, self.f_idx, self.f_val)
        )

    def passages(self) -> tuple:
        return self.rows(0, self.n)

    def queries(self) -> tuple:
        return (self.q_dense, self.qs_idx, self.qs_val, self.qf_idx, self.qf_val)


jax.tree_util.register_dataclass(
    Corpus,
    data_fields=[f.name for f in dataclasses.fields(Corpus)],
    meta_fields=[],
)


def seed_key(seed: int) -> jax.Array:
    """A key that depends on every bit of a seed of up to 64 bits
    (``jax.random.key`` keeps only the low 32)."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def _zipf(n: int) -> jax.Array:
    z = 1.0 / jnp.arange(1, n + 1, dtype=jnp.float32)
    return z / z.sum()


def _topic_pools(key, n_topics: int, vocab: int, per_topic: int, n_common: int):
    """(T, n_common + per_topic) term ids: the global head, then rare terms
    of each topic drawn without replacement from the rest of the vocab."""
    keys = jax.random.split(key, n_topics)
    rare = jax.vmap(
        lambda k: jax.random.choice(k, vocab - n_common, (per_topic,), replace=False)
    )(keys)
    head = jnp.broadcast_to(jnp.arange(n_common), (n_topics, n_common))
    return jnp.concatenate([head, n_common + rare], axis=1).astype(jnp.int32)


def _sample_rows(key, pools, topics, nnz: int):
    """ELL rows: ``nnz`` distinct pool terms per row under Zipf weights, with
    values |N(1, 0.3)| / sqrt(1 + 50 p), sorted by value, largest first."""
    n = topics.shape[0]
    plen = pools.shape[1]
    p = _zipf(plen)
    kg, kv = jax.random.split(key)
    g = jax.random.gumbel(kg, (n, plen))
    _, pos = jax.lax.top_k(jnp.log(p)[None, :] + g, nnz)
    idx = pools[topics[:, None], pos]
    val = jnp.abs(1.0 + 0.3 * jax.random.normal(kv, (n, nnz))) / jnp.sqrt(1.0 + 50.0 * p[pos])
    order = jnp.argsort(-val, axis=1)
    return (
        jnp.take_along_axis(idx, order, axis=1),
        jnp.take_along_axis(val, order, axis=1).astype(jnp.float32),
    )


def _first_occurrence(ids):
    """Mask of the first occurrence of each non-PAD id in every row."""
    order = jnp.argsort(ids, axis=1)
    s = jnp.take_along_axis(ids, order, axis=1)
    first = jnp.concatenate(
        [jnp.ones(s.shape[:1] + (1,), bool), s[:, 1:] != s[:, :-1]], axis=1
    ) & (s >= 0)
    inv = jnp.argsort(order, axis=1)
    return jnp.take_along_axis(first, inv, axis=1)


def _query_terms(key, cand, nnz: int):
    """``nnz`` distinct ids drawn uniformly from each row's candidate ids
    (duplicates count once), values |N(1, 0.3)|, sorted by value."""
    kg, kv = jax.random.split(key)
    ok = _first_occurrence(cand)
    g = jnp.where(ok, jax.random.gumbel(kg, cand.shape), -jnp.inf)
    top, pos = jax.lax.top_k(g, nnz)
    idx = jnp.where(jnp.isfinite(top), jnp.take_along_axis(cand, pos, axis=1), PAD)
    val = jnp.where(idx >= 0, jnp.abs(1.0 + 0.3 * jax.random.normal(kv, idx.shape)), 0.0)
    order = jnp.argsort(-val, axis=1)
    return (
        jnp.take_along_axis(idx, order, axis=1).astype(jnp.int32),
        jnp.take_along_axis(val, order, axis=1).astype(jnp.float32),
    )


def _relevant_docs(key, doc_topics, q_topics, n_topics: int, r: int):
    """``r`` distinct passages of each query's topic, uniformly."""
    order = jnp.argsort(doc_topics)
    counts = jnp.bincount(doc_topics, length=n_topics)
    starts = jnp.cumsum(counts) - counts
    c = counts[q_topics]
    u = jax.random.uniform(key, (q_topics.shape[0], r))
    picks = []
    for j in range(r):  # j-th pick: uniform over the c - j offsets not taken
        off = jnp.floor(u[:, j] * jnp.maximum(c - j, 1)).astype(jnp.int32)
        for prev in _sorted_prev(picks):
            off = off + (off >= prev)
        picks.append(off)
    offs = jnp.stack(picks, axis=1)
    return order[starts[q_topics][:, None] + jnp.minimum(offs, c[:, None] - 1)]


def _sorted_prev(picks):
    """Offsets already taken, in increasing order, for the skip-over step."""
    if not picks:
        return []
    s = jnp.sort(jnp.stack(picks, axis=1), axis=1)
    return [s[:, i] for i in range(s.shape[1])]


@partial(jax.jit, static_argnames=("w", "n_docs", "n_queries"))
def generate(key, w: Widths, n_docs: int, n_queries: int) -> Corpus:
    ks = jax.random.split(key, 12)
    nt = w.n_topics
    centers = _unit(jax.random.normal(ks[0], (nt, w.d_dense)))
    n_common = max(w.terms_per_topic // 2, 8)
    n_common_kw = max(w.keywords_per_topic // 2, 4)
    term_pools = _topic_pools(ks[1], nt, w.vocab_sparse, w.terms_per_topic, n_common)
    kw_pools = _topic_pools(ks[2], nt, w.vocab_lexical, w.keywords_per_topic, n_common_kw)

    topics = jax.random.randint(ks[3], (n_docs,), 0, nt)
    dense = _unit(centers[topics] + w.dense_noise * jax.random.normal(ks[4], (n_docs, w.d_dense)))
    s_idx, s_val = _sample_rows(ks[5], term_pools, topics, w.nnz_sparse)
    f_idx, f_val = _sample_rows(ks[6], kw_pools, topics, w.nnz_lexical)

    qt = jax.random.randint(ks[7], (n_queries,), 0, nt)
    rel = _relevant_docs(ks[8], topics, qt, nt, w.relevant_per_query)  # (Q, R)
    q_dense = _unit(
        dense[rel].mean(axis=1)
        + 0.5 * w.dense_noise * jax.random.normal(ks[9], (n_queries, w.d_dense))
    )
    qs_idx, qs_val = _query_terms(ks[10], s_idx[rel].reshape(n_queries, -1), w.nnz_query_sparse)
    qf_idx, qf_val = _query_terms(ks[11], f_idx[rel].reshape(n_queries, -1), w.nnz_query_lexical)

    # keywords every relevant passage holds, smallest ids first
    kw0 = f_idx[rel[:, 0]]  # (Q, Pf)
    shared = jnp.ones(kw0.shape, bool)
    for j in range(1, w.relevant_per_query):
        other = f_idx[rel[:, j]]
        shared &= (kw0[:, :, None] == other[:, None, :]).any(-1)
    big = jnp.iinfo(jnp.int32).max
    kw_sorted = jnp.sort(jnp.where(shared, kw0, big), axis=1)[:, :2]
    q_shared_kw = jnp.where(kw_sorted == big, PAD, kw_sorted).astype(jnp.int32)

    return Corpus(
        dense=dense.astype(jnp.float32),
        s_idx=s_idx,
        s_val=s_val,
        f_idx=f_idx,
        f_val=f_val,
        q_dense=q_dense.astype(jnp.float32),
        qs_idx=qs_idx,
        qs_val=qs_val,
        qf_idx=qf_idx,
        qf_val=qf_val,
        q_shared_kw=q_shared_kw,
    )
