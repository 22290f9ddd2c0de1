"""The main-path kernels compile for a TPU v5e at MS MARCO-passage widths.

Nothing runs: the TPU compiler, which is installed here, compiles for a
chip that is described and not attached, and refuses what the chip's
compiler would refuse (block layouts not aligned to the tiling, vector
types Mosaic cannot load, programs that overflow the chip's memory). Each
test asserts that the compiled program holds the Pallas kernel
(``tpu_custom_call``), not the jnp oracle.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.fusion import FusionSpec, stack_specs
from repro.core.index import HybridIndex
from repro.core.search import SearchParams, scan_padded, search_padded
from repro.core.usms import FusedVectors, QuantizedFusedVectors, SparseVec
from repro.kernels import ops

B = 32  # queries (or nodes) per kernel call
C = 256  # candidates per query
DD, PS, PF = 768, 128, 64  # passage widths
QPS, QPF = 32, 16  # query nnz


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off for these tests
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernel wrappers choose interpret mode from the backend, which is
    the CPU here; compile the real kernel instead."""
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)


def _fused(sharding, lead, dd, ps, pf, *, quantized=False):
    s = lambda shape, dt: jax.ShapeDtypeStruct(lead + shape, dt, sharding=sharding)
    if quantized:
        return QuantizedFusedVectors(
            s((dd,), jnp.int8),
            s((), jnp.float32),
            SparseVec(s((ps,), jnp.int32), s((ps,), jnp.float16)),
            SparseVec(s((pf,), jnp.int32), s((pf,), jnp.float16)),
        )
    return FusedVectors(
        s((dd,), jnp.float32),
        SparseVec(s((ps,), jnp.int32), s((ps,), jnp.float32)),
        SparseVec(s((pf,), jnp.int32), s((pf,), jnp.float32)),
    )


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("quantized", [False, True], ids=["fp32", "int8"])
def test_hybrid_distance_compiles(one_chip, on_tpu, quantized):
    q = _fused(one_chip, (B,), DD, QPS, QPF)
    cands = _fused(one_chip, (B, C), DD, PS, PF, quantized=quantized)
    hlo = _hlo(lambda q, c: ops.hybrid_scores(q, c, use_kernel=True), q, cands)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("quantized", [False, True], ids=["fp32", "int8"])
def test_fused_topk_compiles(one_chip, on_tpu, quantized):
    q = _fused(one_chip, (B,), DD, QPS, QPF)
    cands = _fused(one_chip, (B, C), DD, PS, PF, quantized=quantized)
    cid = jax.ShapeDtypeStruct((B, C), jnp.int32, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((B, C), jnp.float32, sharding=one_chip)
    hlo = _hlo(
        lambda q, c, i, b: ops.fused_topk(q, c, i, 10, bias=b, use_kernel=True),
        q, cands, cid, bias,
    )
    assert "tpu_custom_call" in hlo


def test_pairwise_tile_compiles(one_chip, on_tpu):
    tile = _fused(one_chip, (B, 32), DD, PS, PF)  # K = 32 candidates per node
    hlo = _hlo(lambda t: ops.pairwise_tile_scores(t, use_kernel=True), tile)
    assert "tpu_custom_call" in hlo


def test_search_padded_compiles(one_chip, on_tpu):
    n, n_entities, batch = 1 << 17, 256, 64
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    index = HybridIndex(
        corpus=_fused(one_chip, (n,), DD, PS, PF),
        semantic_edges=s((n, 16), jnp.int32),
        keyword_edges=s((n, 8), jnp.int32),
        logical_edges=s((n, 16, 4), jnp.int32),
        doc_entities=s((n, 4), jnp.int32),
        entity_to_docs=s((n_entities, 8), jnp.int32),
        entity_adj=s((n_entities, n_entities), jnp.bool_),
        entry_points=s((16,), jnp.int32),
        alive=s((n,), jnp.bool_),
        self_ip=s((n,), jnp.float32),
    )
    spec = jax.eval_shape(
        lambda: stack_specs([FusionSpec.three_path(), FusionSpec.rrf()] * (batch // 2))
    )
    spec = jax.tree.map(lambda a: s(a.shape, a.dtype), spec)
    params = SearchParams(k=10, use_keywords=True, use_kernel=True)
    lowered = search_padded.lower(
        index,
        _fused(one_chip, (batch,), DD, QPS, QPF),
        spec,
        s((batch, 4), jnp.int32),
        s((batch, 1), jnp.int32),
        params,
    )
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_scan_padded_compiles(one_chip, on_tpu):
    """The grow segment's exact read: every row scored through the kernel."""
    n, batch = 1024, 64
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    index = HybridIndex(
        corpus=_fused(one_chip, (n,), DD, PS, PF),
        semantic_edges=s((n, 16), jnp.int32),
        keyword_edges=s((n, 8), jnp.int32),
        logical_edges=s((n, 1, 4), jnp.int32),
        doc_entities=s((n, 1), jnp.int32),
        entity_to_docs=s((1, 1), jnp.int32),
        entity_adj=s((1, 1), jnp.bool_),
        entry_points=s((16,), jnp.int32),
        alive=s((n,), jnp.bool_),
        self_ip=s((n,), jnp.float32),
    )
    spec = jax.eval_shape(
        lambda: stack_specs([FusionSpec.three_path(), FusionSpec.rrf()] * (batch // 2))
    )
    spec = jax.tree.map(lambda a: s(a.shape, a.dtype), spec)
    params = SearchParams(k=10, use_keywords=True, use_kernel=True)
    lowered = scan_padded.lower(
        index,
        _fused(one_chip, (batch,), DD, QPS, QPF),
        spec,
        s((batch, 4), jnp.int32),
        s((batch, 1), jnp.int32),
        params,
    )
    assert "tpu_custom_call" in lowered.compile().as_text()
