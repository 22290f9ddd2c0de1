"""The plain reference: exact hybrid scores under the service's rules.

Independent of the code under test (it imports nothing from ``repro``).
The semantics it implements:

* a passage's score on each path is an inner product with the query: the
  dense dot, and for the learned-sparse and lexical paths the sum of
  value products over the term ids the two rows share (PAD ids, -1, never
  match);
* a weighted-sum request scores ``sum_p w_p * s_p``; a z-score request
  scores ``sum_p w_p * (s_p - mean_p) / max(std_p, 1e-6)``, with the
  per-path mean and std of the L2 norms of the served passages' path rows;
* a request that carries required keywords may only return passages whose
  lexical row holds at least one of them.

Pair scores are computed on the host in float64. The exact top-k over the
whole corpus runs on the device in float32 at the highest matmul
precision, in blocks of queries and passages so that it fits beside
nothing else; ``compute_dtype=bfloat16`` gives the control, the same brute
force one precision lower.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

PAD = -1
STD_EPS = 1e-6
WEIGHTED_SUM, ZSCORE = "weighted_sum", "zscore"


# --------------------------------------------------------------------------
# host, float64: scores of given (query, passage) pairs
# --------------------------------------------------------------------------


def _sparse_pairs(qi, qv, pi, pv):
    """(M, Pq) query rows against (M, K, Pp) passage rows -> (M, K)."""
    match = (qi[:, None, :, None] == pi[:, :, None, :]) & (qi[:, None, :, None] >= 0)
    prod = qv[:, None, :, None] * pv[:, :, None, :]
    return np.where(match, prod, 0.0).sum(axis=(2, 3))


def pair_path_scores(queries, passages, qrow, ids, block: int = 512):
    """Raw per-path scores of passage ``ids[m, j]`` for query ``qrow[m]``:
    (M, K, 3) float64 [dense, learned, lexical]; PAD ids score 0. Also
    returns the Cauchy-Schwarz scale ``||q_p|| * ||d_p||`` of every pair,
    the size a rounding error of that dot is measured against."""
    qd, qsi, qsv, qfi, qfv = (np.asarray(a) for a in queries)
    pd, psi, psv, pfi, pfv = (np.asarray(a) for a in passages)
    qrow = np.asarray(qrow)
    ids = np.asarray(ids)
    m, k = ids.shape
    scores = np.zeros((m, k, 3))
    scale = np.zeros((m, k, 3))
    for lo in range(0, m, block):
        hi = min(lo + block, m)
        q = qrow[lo:hi]
        d = np.clip(ids[lo:hi], 0, pd.shape[0] - 1)
        live = ids[lo:hi] >= 0
        f64 = lambda a: a.astype(np.float64)
        s = np.stack(
            [
                np.einsum("md,mkd->mk", f64(qd[q]), f64(pd[d])),
                _sparse_pairs(qsi[q], f64(qsv[q]), psi[d], f64(psv[d])),
                _sparse_pairs(qfi[q], f64(qfv[q]), pfi[d], f64(pfv[d])),
            ],
            axis=-1,
        )
        qn = np.stack(
            [np.linalg.norm(f64(a[q]), axis=-1) for a in (qd, qsv, qfv)], axis=-1
        )
        dn = np.stack(
            [np.linalg.norm(f64(a[d]), axis=-1) for a in (pd, psv, pfv)], axis=-1
        )
        scores[lo:hi] = np.where(live[..., None], s, 0.0)
        scale[lo:hi] = np.where(live[..., None], qn[:, None, :] * dn, 0.0)
    return scores, scale


def path_stats(passages) -> dict:
    """Per-path mean/std/min/max of the passages' row L2 norms (float64)."""
    pd, _, psv, _, pfv = (np.asarray(a, np.float64) for a in passages)
    norms = [np.linalg.norm(a, axis=-1) for a in (pd, psv, pfv)]
    return {
        "mean": np.array([n.mean() for n in norms]),
        "std": np.array([n.std() for n in norms]),
        "min": np.array([n.min() for n in norms]),
        "max": np.array([n.max() for n in norms]),
    }


def fused_scores(path_scores, scale, mode: str, weights, stats):
    """Fused score of each pair and the size its error is measured against,
    for the modes whose score depends on the pair alone (weighted sum and
    z-score); None for rank fusion."""
    w = np.asarray(weights, np.float64)
    if mode == WEIGHTED_SUM:
        return (path_scores * w).sum(-1), (scale * np.abs(w)).sum(-1)
    if mode == ZSCORE:
        std = np.maximum(stats["std"], STD_EPS)
        z = (path_scores - stats["mean"]) / std
        return (z * w).sum(-1), ((scale + np.abs(stats["mean"])) / std * np.abs(w)).sum(-1)
    return None


def keyword_ok(passage_kw, ids, required) -> np.ndarray:
    """(M, K): passage ``ids[m, j]`` holds one of request m's required
    keywords (True where the request requires none, or the slot is PAD)."""
    pk = np.asarray(passage_kw)[np.clip(ids, 0, len(passage_kw) - 1)]  # (M, K, Pf)
    req = np.asarray(required)  # (M, R)
    has = (pk[:, :, :, None] == req[:, None, None, :]) & (req[:, None, None, :] >= 0)
    need = (req >= 0).any(axis=1)
    return has.any(axis=(2, 3)) | ~need[:, None] | (ids < 0)


# --------------------------------------------------------------------------
# device: exact top-k over the whole corpus
# --------------------------------------------------------------------------


def _scatter_rows(idx, val, vocab: int):
    """(B, P) sparse rows -> (vocab + 1, B) dense columns; PAD lands in the
    extra last row, which no passage id reads."""
    b = idx.shape[0]
    rows = jnp.where(idx >= 0, idx, vocab)
    out = jnp.zeros((vocab + 1, b), val.dtype)
    return out.at[rows, jnp.arange(b)[:, None]].add(val)


@partial(jax.jit, static_argnames=("k", "vocab_s", "vocab_f", "chunk", "compute_dtype"))
def _topk_block(
    q, w, kw_req, passages, *, k, vocab_s, vocab_f, chunk, compute_dtype
):
    qd, qsi, qsv, qfi, qfv = q
    pd, psi, psv, pfi, pfv = passages
    n = pd.shape[0]
    b = qd.shape[0]
    cd = compute_dtype
    hi = jax.lax.Precision.HIGHEST
    qs_cols = _scatter_rows(qsi, qsv.astype(cd), vocab_s)  # (Vs+1, B)
    qf_cols = _scatter_rows(qfi, qfv.astype(cd), vocab_f)
    kw_cols = _scatter_rows(kw_req, jnp.ones(kw_req.shape, jnp.int32), vocab_f) > 0
    need = (kw_req >= 0).any(axis=1)  # (B,)
    pad = (-n) % chunk
    grow = lambda a, fill: jnp.concatenate([a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)])
    blocks = jax.tree.map(
        lambda a, fill: grow(a, fill).reshape((-1, chunk) + a.shape[1:]),
        (pd, psi, psv, pfi, pfv, jnp.arange(n, dtype=jnp.int32)),
        (0, PAD, 0, PAD, 0, PAD),
    )

    def sparse(cols, idx, val):  # (C, P) passage rows -> (C, B)
        g = cols[jnp.where(idx >= 0, idx, cols.shape[0] - 1)]  # (C, P, B)
        return jnp.einsum("cpb,cp->cb", g, val.astype(cd), preferred_element_type=jnp.float32)

    def step(carry, blk):
        top_s, top_i = carry
        d, si, sv, fi, fv, ids = blk
        dense = jax.lax.dot_general(
            d.astype(cd), qd.astype(cd), (((1,), (1,)), ((), ())),
            precision=hi, preferred_element_type=jnp.float32,
        )  # (C, B)
        s = (
            w[None, :, 0] * dense
            + w[None, :, 1] * sparse(qs_cols, si, sv)
            + w[None, :, 2] * sparse(qf_cols, fi, fv)
        ).T  # (B, C)
        kw_hit = kw_cols[jnp.where(fi >= 0, fi, vocab_f)].any(axis=1).T  # (B, C)
        ok = (ids[None, :] >= 0) & (kw_hit | ~need[:, None])
        s = jnp.where(ok, s, -jnp.inf)
        all_s = jnp.concatenate([top_s, s], axis=1)
        all_i = jnp.concatenate([top_i, jnp.broadcast_to(ids, s.shape)], axis=1)
        ts, pos = jax.lax.top_k(all_s, k)
        return (ts, jnp.take_along_axis(all_i, pos, axis=1)), None

    init = (jnp.full((b, k), -jnp.inf, jnp.float32), jnp.full((b, k), PAD, jnp.int32))
    (ts, ti), _ = jax.lax.scan(step, init, blocks)
    return ts, jnp.where(jnp.isfinite(ts), ti, PAD)


def exact_topk(
    queries,
    weights,
    required_kw,
    passages,
    k: int,
    *,
    vocab_sparse: int,
    vocab_lexical: int,
    compute_dtype=jnp.float32,
    query_block: int = 128,
    chunk: int = 1024,
):
    """Exact weighted-sum top-k of each query over every passage, under the
    keyword rule: (scores, ids), each (Q, k), ids PAD where fewer than k
    passages qualify. ``weights`` is (Q, 3); ``required_kw`` (Q, R)."""
    weights = jnp.asarray(weights, jnp.float32)
    required_kw = jnp.asarray(required_kw, jnp.int32)
    q_all = tuple(jnp.asarray(a) for a in queries)
    nq = q_all[0].shape[0]
    chunk = min(chunk, passages[0].shape[0])
    out_s, out_i = [], []
    for lo in range(0, nq, query_block):
        hi = min(lo + query_block, nq)
        padq = query_block - (hi - lo)  # one compiled shape for every block
        take = lambda a: jnp.concatenate(
            [a[lo:hi], jnp.zeros((padq,) + a.shape[1:], a.dtype)]
        ) if padq else a[lo:hi]
        kw = required_kw[lo:hi]
        if padq:
            kw = jnp.concatenate([kw, jnp.full((padq, kw.shape[1]), PAD, kw.dtype)])
        s, i = _topk_block(
            tuple(take(a) for a in q_all), take(weights), kw, passages,
            k=k, vocab_s=vocab_sparse, vocab_f=vocab_lexical, chunk=chunk,
            compute_dtype=compute_dtype,
        )
        out_s.append(np.asarray(s)[: hi - lo])
        out_i.append(np.asarray(i)[: hi - lo])
    return np.concatenate(out_s), np.concatenate(out_i)
