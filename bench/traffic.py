"""The one generator every traffic mix goes through.

A mix is a JSON file of parameters under ``bench/traffic/``. Its ``kind``
says how requests are offered; the one kind there is, ``open``, is an open
loop: Poisson arrivals at ``rate_qps`` for the whole window, sent by
``clients`` client threads, so that a request falls due whatever the
service is doing with earlier ones. Every seed gets the same arrival gaps
(drawn once from a fixed stream) in its own order, so the count and
spacing of the work is the same from seed to seed and only the order and
the data change.

Search requests are drawn from a pool of ``query_pool`` entries: entry i
is query row i of the corpus with one fusion mode of ``fusion_mix`` (each
mode on an equal share of the entries) and, on a ``keyword_share`` of the
entries, the first one or two of the keywords its relevant passages share
(``keywords`` cycles through the counts). Request r uses entry
``order[r % query_pool]``, ``order`` a permutation drawn from the seed.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

ARRIVAL_STREAM = 0x5EED  # fixed: every seed sees the same set of gaps
PAD = -1


@dataclasses.dataclass(frozen=True)
class Entry:
    qrow: int
    fusion: str  # name in the mix
    mode: str
    weights: tuple  # (dense, learned, lexical)
    keywords: tuple  # required keyword ids, possibly empty


def load(path: Path) -> dict:
    with open(path) as f:
        traffic = json.load(f)
    if traffic.get("kind") != "open":
        raise ValueError(f"{path}: unknown traffic kind {traffic.get('kind')!r}")
    return traffic


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, stream])


def pool_entries(mix: dict, shared_kw: np.ndarray, seed: int) -> list:
    """The request pool of a search mix over the corpus's queries.
    ``shared_kw`` is (Q, 2): keywords each query's relevant passages share,
    smallest first, PAD-padded."""
    fusions = mix["fusion_mix"]
    n = int(mix["query_pool"])
    if shared_kw.shape[0] < n:
        raise ValueError(f"the corpus has {shared_kw.shape[0]} queries, the pool needs {n}")
    rng = _rng(seed, 1)
    which = rng.permutation(np.arange(n) % len(fusions))
    n_kw = int(round(float(mix.get("keyword_share", 0.0)) * n))
    counts = list(mix.get("keywords", [1]))
    kw_rows = rng.permutation(n)[:n_kw]
    want = np.zeros(n, np.int64)
    want[kw_rows] = [counts[i % len(counts)] for i in range(n_kw)]
    out = []
    for i in range(n):
        f = fusions[which[i]]
        kws = tuple(int(x) for x in shared_kw[i, : want[i]] if x >= 0)
        out.append(Entry(i, f["name"], f["mode"], tuple(float(w) for w in f["weights"]), kws))
    return out


def request_order(n_pool: int, n_requests: int, seed: int) -> np.ndarray:
    """Pool entry of each request: a seeded permutation, cycled."""
    order = _rng(seed, 2).permutation(n_pool)
    return order[np.arange(n_requests) % n_pool]


def arrivals(rate_qps: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds) of ``round(rate * seconds)`` Poisson
    arrivals: a fixed set of gaps, in an order drawn from the seed."""
    n = max(int(round(rate_qps * seconds)), 1)
    base = np.sort(np.random.default_rng(ARRIVAL_STREAM).uniform(0.0, seconds, n))
    gaps = np.diff(np.concatenate([[0.0], base]))
    return np.cumsum(_rng(seed, 3).permutation(gaps))


def bucket_widths(entries) -> list:
    """Keyword widths of the shape buckets a pool's requests can fall in
    (the batcher pads a batch's keyword list to a power of two, at least 1)."""
    most = max((len(e.keywords) for e in entries), default=0)
    widths, w = [1], 1
    while w < most:
        w *= 2
        widths.append(w)
    return widths
