"""The benchmark's plain reference agrees with the program's jnp oracle
(``ops.pairwise_scores_chunked``) under the service's keyword rule, on a
small CPU corpus, for every fusion mode of the benchmark's mix."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from bench import corpus as corpus_mod
from bench import reference

from repro.core.usms import FusedVectors, PathWeights, SparseVec, weighted_query
from repro.kernels import ops

ROOT = Path(__file__).resolve().parents[2]
MIX = json.loads((ROOT / "bench" / "traffic" / "open-steady.json").read_text())["fusion_mix"]
W = corpus_mod.Widths(
    d_dense=24, vocab_sparse=512, vocab_lexical=256, nnz_sparse=12, nnz_lexical=8,
    nnz_query_sparse=6, nnz_query_lexical=4, n_topics=8, terms_per_topic=24,
    keywords_per_topic=16,
)
N, Q, K = 300, 24, 10


@pytest.fixture(scope="module")
def data():
    c = corpus_mod.generate(corpus_mod.seed_key(2**33 + 7), W, N, Q)
    # every other query requires its relevant passages' shared keywords
    kw = np.where((np.arange(Q) % 2 == 0)[:, None], np.asarray(c.q_shared_kw), -1)
    return c, kw


def _fv(a):
    d, si, sv, fi, fv = a
    return FusedVectors(d, SparseVec(si, sv), SparseVec(fi, fv))


def _oracle_paths(c):
    """(Q, N, 3) per-path scores from the program's oracle: one pass per
    path with the other paths' query weights zeroed."""
    q = _fv(c.queries())
    out = []
    for w in ((1.0, 0.0, 0.0), (0.0, 1.0, 0.0), (0.0, 0.0, 1.0)):
        qw = weighted_query(q, PathWeights.make(*w))
        out.append(np.asarray(ops.pairwise_scores_chunked(qw, _fv(c.passages()), chunk=128)))
    return np.stack(out, axis=-1)


def _keyword_mask(c, kw):
    pk = np.asarray(c.f_idx)
    has = (pk[None, :, :, None] == kw[:, None, None, :]) & (kw[:, None, None, :] >= 0)
    need = (kw >= 0).any(axis=1)
    return has.any(axis=(2, 3)) | ~need[:, None]


def test_generator_shapes_and_keyword_anchoring(data):
    c, _ = data
    assert c.dense.shape == (N, W.d_dense) and c.s_idx.shape == (N, W.nnz_sparse)
    assert c.qs_idx.shape == (Q, W.nnz_query_sparse) and c.qf_idx.shape == (Q, W.nnz_query_lexical)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(c.dense), axis=1), 1.0, rtol=1e-5)
    s = np.asarray(c.s_idx)
    assert all(len(set(r)) == len(r) for r in s), "ELL ids repeat within a row"
    # a shared keyword is held by at least three passages (the relevant ones)
    kw = np.asarray(c.q_shared_kw)[:, 0]
    held = [(np.asarray(c.f_idx) == k).any(axis=1).sum() for k in kw if k >= 0]
    assert held and min(held) >= 3
    again = corpus_mod.generate(corpus_mod.seed_key(2**33 + 7), W, N, Q)
    np.testing.assert_array_equal(np.asarray(again.s_idx), s)


def test_pair_path_scores_equal_the_oracle(data):
    c, _ = data
    oracle = _oracle_paths(c)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, N, size=(Q, K))
    ids[0, -2:] = -1
    got, scale = reference.pair_path_scores(c.queries(), c.passages(), np.arange(Q), ids)
    want = np.take_along_axis(oracle, np.clip(ids, 0, N - 1)[:, :, None], axis=1)
    want = np.where(ids[:, :, None] >= 0, want, 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert (np.abs(got) <= scale * (1 + 1e-9) + 1e-12).all()  # Cauchy-Schwarz


@pytest.mark.parametrize("fusion", MIX, ids=[f["name"] for f in MIX])
def test_reference_agrees_with_the_oracle_under_the_keyword_rule(data, fusion):
    c, kw = data
    oracle = _oracle_paths(c)
    ok = _keyword_mask(c, kw)
    w = np.asarray(fusion["weights"], np.float64)
    stats = reference.path_stats(c.passages())
    if fusion["mode"] == "rrf":
        # rank fusion ranks each path's scores over the candidate pool: the
        # reference holds the per-path scores it ranks by
        ids = np.argsort(-np.where(ok, oracle[:, :, 0], -np.inf), axis=1)[:, :K]
        got, _ = reference.pair_path_scores(c.queries(), c.passages(), np.arange(Q), ids)
        np.testing.assert_allclose(
            got, np.take_along_axis(oracle, ids[:, :, None], axis=1), rtol=1e-5, atol=1e-5
        )
        return
    if fusion["mode"] == "zscore":
        # z-score fusion orders passages as a weighted sum with w / std
        eff = w / np.maximum(stats["std"], reference.STD_EPS)
        fused = ((oracle - stats["mean"]) / np.maximum(stats["std"], 1e-6) * w).sum(-1)
    else:
        eff = w
        fused = (oracle * w).sum(-1)
    fused = np.where(ok, fused, -np.inf)
    want = np.argsort(-fused, axis=1, kind="stable")[:, :K]
    _, got = reference.exact_topk(
        c.queries(), np.broadcast_to(eff, (Q, 3)), kw, c.passages(), K,
        vocab_sparse=W.vocab_sparse, vocab_lexical=W.vocab_lexical, query_block=16, chunk=64,
    )
    for i in range(Q):
        # equal as sets up to a tie at the 10th score
        assert set(got[i].tolist()) == set(want[i].tolist()) or np.isclose(
            np.sort(fused[i])[-K], np.sort(fused[i])[-K - 1]
        )
        assert ok[i, got[i][got[i] >= 0]].all()
    ref_fused, _ = reference.fused_scores(
        *reference.pair_path_scores(c.queries(), c.passages(), np.arange(Q), want),
        fusion["mode"], w, stats,
    )
    np.testing.assert_allclose(
        ref_fused, np.take_along_axis(fused, want, axis=1), rtol=1e-5, atol=1e-5
    )


def test_the_control_reads_wider_than_float32(data):
    c, kw = data
    w = np.ones((Q, 3))
    s32, ids = reference.exact_topk(
        c.queries(), w, kw, c.passages(), K, vocab_sparse=W.vocab_sparse,
        vocab_lexical=W.vocab_lexical, query_block=16, chunk=64,
    )
    s16, ids16 = reference.exact_topk(
        c.queries(), w, kw, c.passages(), K, vocab_sparse=W.vocab_sparse,
        vocab_lexical=W.vocab_lexical, query_block=16, chunk=64, compute_dtype=jnp.bfloat16,
    )

    def gap(s, i):
        path, scale = reference.pair_path_scores(c.queries(), c.passages(), np.arange(Q), i)
        f64, size = reference.fused_scores(path, scale, "weighted_sum", w[:, None, :], None)
        return np.max(np.abs(s - f64) / size)

    assert gap(s32, ids) < 1e-5
    assert gap(s16, ids16) > 30 * gap(s32, ids)
