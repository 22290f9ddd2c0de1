"""Fused distance+top-k selection vs the lax.top_k oracle (DESIGN.md §10).

The fused kernel keeps the per-row running top-k in VMEM across candidate
tiles, so the (B, C) score matrix never reaches HBM. These tests pin the
contract: equal to score-then-``lax.top_k`` up to float summation order
(positions exactly, except across float-ulp ties), with PAD candidates,
k > live-candidate counts, non-multiple-of-C_TILE candidate counts, and the
pre-selection bias all covered. Kernel runs use interpret mode (CPU CI).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

try:  # property tests need hypothesis (a [test] extra); the rest run without
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import jax
import jax.numpy as jnp

from repro.core.search import SearchParams, resolve_params
from repro.core.usms import PAD_IDX, quantize_corpus
from repro.kernels import ops, ref
from repro.kernels.fused_topk import K_LANE, NEG, k_pad
from tests.helpers import random_fused


def _case(seed, b, c, *, dd=16, ps=4, pf=2, pad_frac=0.0, with_bias=False):
    rng = np.random.default_rng(seed)
    q = random_fused(rng, (b,), d_dense=dd, ps=ps, pf=pf, vs=97, vf=31)
    cands = random_fused(rng, (b, c), d_dense=dd, ps=ps, pf=pf, vs=97, vf=31)
    cid = rng.integers(0, 10_000, size=(b, c)).astype(np.int32)
    cid[rng.random((b, c)) < pad_frac] = PAD_IDX
    bias = (
        jnp.asarray(rng.normal(size=(b, c)).astype(np.float32))
        if with_bias
        else None
    )
    return q, cands, jnp.asarray(cid), bias


def assert_topk_match(got, want):
    """Scores up to float summation order; positions exact except across
    float-ulp ties (both orders are then valid lax.top_k tie-breaks)."""
    gs, gi = np.asarray(got[0]), np.asarray(got[1])
    ws, wi = np.asarray(want[0]), np.asarray(want[1])
    np.testing.assert_allclose(gs, ws, rtol=1e-5, atol=1e-5)
    flip = gi != wi
    assert np.all(np.abs(gs - ws)[flip] <= 1e-4), (
        f"positions diverged beyond tie tolerance:\n{gi}\nvs\n{wi}"
    )


def test_k_pad_rule():
    assert k_pad(1) == K_LANE
    assert k_pad(K_LANE) == K_LANE
    assert k_pad(K_LANE + 1) == 2 * K_LANE
    with pytest.raises(ValueError):
        k_pad(0)


@pytest.mark.parametrize("c_tile", [8, 32])
@pytest.mark.parametrize(
    "b,c,k,pad_frac,with_bias",
    [
        (2, 40, 10, 0.0, False),
        (3, 33, 5, 0.3, True),  # C not a multiple of the tile
        (1, 7, 7, 0.5, False),
        (2, 130, 32, 0.1, True),
    ],
)
def test_kernel_matches_oracle(b, c, k, pad_frac, with_bias, c_tile):
    q, cands, cid, bias = _case(
        hash((b, c, k, c_tile)) % 2**31, b, c,
        pad_frac=pad_frac, with_bias=with_bias,
    )
    got = ops.fused_topk(
        q, cands, cid, k, bias=bias, c_tile=c_tile,
        use_kernel=True, interpret=True,
    )
    want = ref.fused_topk_ref(q, cands, cid, bias, k)
    assert_topk_match(got, want)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("op", ["hybrid_scores", "fused_topk"])
def test_kernel_matches_oracle_with_wider_candidates(op, quantized):
    """Queries with fewer nnz slots than the candidates (the serving shape:
    32/16-nnz queries against 128/64-nnz passages): every candidate slot
    must reach the kernel, not just the first query-width ones."""
    rng = np.random.default_rng(17)
    q = random_fused(rng, (3,), d_dense=16, ps=3, pf=2, vs=13, vf=7)
    cands = random_fused(rng, (3, 40), d_dense=16, ps=9, pf=5, vs=13, vf=7)
    if quantized:
        cands = quantize_corpus(cands)
    if op == "hybrid_scores":
        got = ops.hybrid_scores(q, cands, c_tile=8, use_kernel=True, interpret=True)
        want = ops.hybrid_scores(q, cands, use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    else:
        cid = jnp.arange(120, dtype=jnp.int32).reshape(3, 40)
        got = ops.fused_topk(q, cands, cid, 8, c_tile=8, use_kernel=True, interpret=True)
        want = ops.fused_topk(q, cands, cid, 8, use_kernel=False)
        assert_topk_match(got, want)


def test_oracle_matches_raw_lax_topk():
    """ref.fused_topk_ref really is score-then-lax.top_k on masked scores."""
    q, cands, cid, bias = _case(11, 2, 20, pad_frac=0.2, with_bias=True)
    scores = ref.hybrid_scores_ref(q, cands) + bias
    scores = jnp.where(cid >= 0, scores, NEG)
    top, pos = jax.lax.top_k(scores, 6)
    ws, wi = ref.fused_topk_ref(q, cands, cid, bias, 6)
    np.testing.assert_array_equal(np.asarray(ws), np.asarray(top))
    np.testing.assert_array_equal(np.asarray(wi), np.asarray(pos))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_all_pad_candidates(use_kernel):
    """A round whose candidate tile is entirely PAD: every slot invalid."""
    q, cands, cid, _ = _case(3, 2, 16)
    cid = jnp.full_like(cid, PAD_IDX)
    s, p = ops.fused_topk(
        q, cands, cid, 4, c_tile=8,
        use_kernel=use_kernel, interpret=use_kernel,
    )
    assert np.all(np.asarray(s) == NEG)
    assert np.all(np.asarray(p) == PAD_IDX)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_k_exceeds_live_candidates(use_kernel):
    """k > live candidates: the tail holds (NEG, PAD_IDX) sentinels."""
    q, cands, cid, _ = _case(5, 2, 6)
    cid = cid.at[:, 3:].set(PAD_IDX)  # 3 live candidates per row
    k = 9
    s, p = ops.fused_topk(
        q, cands, cid, k, c_tile=8,
        use_kernel=use_kernel, interpret=use_kernel,
    )
    assert s.shape == (2, k) and p.shape == (2, k)
    assert np.all(np.asarray(s)[:, 3:] == NEG)
    assert np.all(np.asarray(p)[:, 3:] == PAD_IDX)
    assert np.all(np.asarray(p)[:, :3] >= 0)
    want = ref.fused_topk_ref(q, cands, cid, None, k)
    assert_topk_match((s, p), want)


def test_explicit_pad_candidates_are_inert():
    """Appending PAD candidates (the wrapper's non-multiple-of-C_TILE ELL
    padding: idx==PAD_IDX, val==0, cid==PAD_IDX) never changes the result."""
    q, cands, cid, bias = _case(7, 2, 10, with_bias=True)
    base = ops.fused_topk(
        q, cands, cid, 4, bias=bias, c_tile=8, use_kernel=True, interpret=True
    )
    grow = 6  # 10 -> 16, a full extra tile of explicit padding
    padded_cands = jax.tree.map(
        lambda a: jnp.pad(
            a,
            [(0, 0), (0, grow)] + [(0, 0)] * (a.ndim - 2),
            constant_values=PAD_IDX if a.dtype == jnp.int32 else 0,
        ),
        cands,
    )
    padded = ops.fused_topk(
        q,
        padded_cands,
        jnp.pad(cid, ((0, 0), (0, grow)), constant_values=PAD_IDX),
        4,
        bias=jnp.pad(bias, ((0, 0), (0, grow))),
        c_tile=8,
        use_kernel=True,
        interpret=True,
    )
    assert_topk_match(padded, base)


def test_bias_shifts_selection():
    """A huge bias on one candidate forces it to rank first; zero bias is a
    no-op vs the unbiased call."""
    q, cands, cid, _ = _case(9, 2, 12)
    bias = jnp.zeros((2, 12), jnp.float32).at[:, 5].set(1e6)
    s, p = ops.fused_topk(
        q, cands, cid, 3, bias=bias, c_tile=8, use_kernel=True, interpret=True
    )
    assert np.all(np.asarray(p)[:, 0] == 5)
    no_bias = ops.fused_topk(
        q, cands, cid, 3, c_tile=8, use_kernel=True, interpret=True
    )
    zero_bias = ops.fused_topk(
        q, cands, cid, 3, bias=jnp.zeros((2, 12), jnp.float32),
        c_tile=8, use_kernel=True, interpret=True,
    )
    assert_topk_match(zero_bias, no_bias)


def test_take_topk_roundtrip():
    """Positions resolve back to candidate ids/metadata; PAD -> fill."""
    q, cands, cid, _ = _case(13, 2, 20, pad_frac=0.6)
    s, p = ops.fused_topk(q, cands, cid, 8, c_tile=8, use_kernel=False)
    got_ids = np.asarray(ops.take_topk_ids(cid, p))
    pos = np.asarray(p)
    cid_np = np.asarray(cid)
    for b in range(2):
        for j in range(8):
            want = PAD_IDX if pos[b, j] < 0 else cid_np[b, pos[b, j]]
            assert got_ids[b, j] == want
    meta = jnp.arange(40, dtype=jnp.float32).reshape(2, 20)
    got_meta = np.asarray(ops.take_topk(meta, p, -7.0))
    assert np.all(got_meta[pos < 0] == -7.0)


# ---------------------------------------------------------------------------
# Property tests (hypothesis)
# ---------------------------------------------------------------------------


if HAVE_HYPOTHESIS:

    @st.composite
    def topk_case(draw):
        b = draw(st.integers(1, 3))
        c = draw(st.integers(1, 40))
        k = draw(st.integers(1, 12))
        pad_frac = draw(st.sampled_from([0.0, 0.25, 0.9, 1.0]))
        with_bias = draw(st.booleans())
        seed = draw(st.integers(0, 2**20))
        return _case(seed, b, c, pad_frac=pad_frac, with_bias=with_bias) + (k,)

    @settings(max_examples=25, deadline=None)
    @given(topk_case())
    def test_property_kernel_equals_lax_topk(case):
        """Fused kernel == score-then-lax.top_k up to tie order, across PAD
        density, k vs live-count, and non-multiple-of-C_TILE counts."""
        q, cands, cid, bias, k = case
        got = ops.fused_topk(
            q, cands, cid, k, bias=bias, c_tile=8,
            use_kernel=True, interpret=True,
        )
        want = ref.fused_topk_ref(q, cands, cid, bias, k)
        assert_topk_match(got, want)

    @settings(max_examples=15, deadline=None)
    @given(topk_case())
    def test_property_invalid_slots_are_sentinels(case):
        """Every returned slot is either a live candidate (pos valid, score
        finite) or the (NEG, PAD_IDX) sentinel — never a PAD candidate."""
        q, cands, cid, bias, k = case
        s, p = ops.fused_topk(
            q, cands, cid, k, bias=bias, c_tile=8,
            use_kernel=True, interpret=True,
        )
        s, p = np.asarray(s), np.asarray(p)
        cid_np = np.asarray(cid)
        live = p >= 0
        assert np.all(s[~live] == NEG)
        n_live = (cid_np >= 0).sum(axis=1)
        for b in range(p.shape[0]):
            assert live[b].sum() == min(k, n_live[b])
            assert np.all(cid_np[b, p[b, live[b]]] >= 0)


# ---------------------------------------------------------------------------
# Serving cache key (satellite: kernel mode must be a cache-key component)
# ---------------------------------------------------------------------------


def test_resolved_params_distinguish_kernel_mode():
    """HybridSearchService keys its AOT executable cache on
    (index key, bucket, params) — see hybrid_service._compile_cached callers.
    resolve_params must pin use_kernel to a concrete bool so kernel and
    oracle executables can never collide under one key."""
    auto = SearchParams(k=4, use_kernel=None)
    resolved = resolve_params(auto)
    assert resolved.use_kernel in (True, False)
    assert resolved.use_kernel == ops.resolve_use_kernel(None)
    on = dataclasses.replace(resolved, use_kernel=True)
    off = dataclasses.replace(resolved, use_kernel=False)
    assert on != off
    assert hash(("idx", 8, on)) != hash(("idx", 8, off))
    # resolving is idempotent and a no-op on already-concrete params
    assert resolve_params(resolved) == resolved
    assert resolve_params(on).use_kernel is True
    assert resolve_params(off).use_kernel is False
