"""Faults and the control, planted in the timed path, for the readings that
set the limits of ``correct`` (``bench/control.py --fault``) and for the
tests that see a run with the timed path broken come out not correct. The
benchmark's own runs never plant one.

* ``int8_corpus``: the control, the program's own lower-precision path
  switched on: the sealed corpus stored as the program quantizes it (dense
  rows per-row symmetric int8, sparse values float16), searched with
  ``corpus_dtype="int8"``, where the configuration states float32;
* ``shuffled_neighbours``: every node's semantic and keyword neighbour
  lists swapped for another node's, a broken neighbour list; the traversal
  then walks a random graph from the right entry points;
* ``half_rounds``: the traversal cut short, to half the configured rounds.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

FAULTS = ("int8_corpus", "shuffled_neighbours", "half_rounds")


def shuffled_neighbours(pool, seed: int = 0):
    """``pool`` with the neighbour lists of each segment permuted over its
    nodes."""
    groups = []
    for g in pool.groups:
        idx = g.index
        perm = np.random.default_rng(seed).permutation(idx.semantic_edges.shape[1])
        idx = dataclasses.replace(
            idx,
            semantic_edges=jnp.asarray(idx.semantic_edges)[:, perm],
            keyword_edges=jnp.asarray(idx.keyword_edges)[:, perm],
        )
        groups.append(dataclasses.replace(g, index=idx))
    return dataclasses.replace(pool, groups=groups)


def int8_corpus(pool):
    """``pool`` with each group's corpus quantized as the program seals an
    int8 segment."""
    from repro.core.usms import quantize_corpus

    groups = []
    for g in pool.groups:
        corpus = jax.tree.map(jnp.asarray, g.index.corpus)
        idx = dataclasses.replace(g.index, corpus=quantize_corpus(corpus))
        groups.append(dataclasses.replace(g, index=idx))
    return dataclasses.replace(pool, groups=groups)


def half_rounds(cfg: dict) -> dict:
    return {**cfg, "search": {**cfg["search"], "iters": cfg["search"]["iters"] // 2}}


def plant(name: str, harness) -> None:
    """Plant fault ``name`` under ``harness.make_service``, which every run
    serves its index through."""
    real = harness.make_service
    if name == "int8_corpus":
        int8 = lambda cfg: {**cfg, "search": {**cfg["search"], "corpus_dtype": "int8"}}
        harness.make_service = lambda pool, cfg, mix: real(int8_corpus(pool), int8(cfg), mix)
    elif name == "shuffled_neighbours":
        harness.make_service = lambda pool, cfg, mix: real(shuffled_neighbours(pool), cfg, mix)
    elif name == "half_rounds":
        harness.make_service = lambda pool, cfg, mix: real(pool, half_rounds(cfg), mix)
    else:
        raise ValueError(f"unknown fault {name!r}; faults: {FAULTS}")
