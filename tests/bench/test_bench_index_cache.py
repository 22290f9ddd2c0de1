"""The benchmark's index cache cannot go stale, and a cached index serves
what the fresh build serves."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import jax

from bench import corpus as corpus_mod
from bench import harness, index_cache
from bench import traffic as traffic_mod

from repro.core.segment_pool import SegmentPool, build_pool_segment

ROOT = Path(__file__).resolve().parents[2]


def _tree(root: Path) -> Path:
    (root / "src" / "repro").mkdir(parents=True)
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "src" / "repro" / "kernel.py").write_text("X = 1\n")
    (root / "bench" / "gen.py").write_text("Y = 2\n")
    cfg = root / "bench" / "configs" / "c.json"
    cfg.write_text('{"n_docs": 8}')
    return cfg


def test_one_changed_byte_of_a_hashed_file_changes_the_key(tmp_path):
    cfg = _tree(tmp_path)
    key = index_cache.cache_key(tmp_path, cfg, 7, "TPU v5 lite")
    assert key == index_cache.cache_key(tmp_path, cfg, 7, "TPU v5 lite")
    # what the cache holds, and byte-code, are not sources
    (tmp_path / "bench" / ".cache").mkdir()
    (tmp_path / "bench" / ".cache" / "x").write_text("anything")
    (tmp_path / "src" / "repro" / "__pycache__").mkdir()
    (tmp_path / "src" / "repro" / "__pycache__" / "kernel.pyc").write_bytes(b"\0")
    assert key == index_cache.cache_key(tmp_path, cfg, 7, "TPU v5 lite")

    src = tmp_path / "src" / "repro" / "kernel.py"
    src.write_text("X = 2\n")
    changed_src = index_cache.cache_key(tmp_path, cfg, 7, "TPU v5 lite")
    src.write_text("X = 1\n")
    (tmp_path / "bench" / "gen.py").write_text("Y = 3\n")
    changed_bench = index_cache.cache_key(tmp_path, cfg, 7, "TPU v5 lite")
    (tmp_path / "bench" / "gen.py").write_text("Y = 2\n")
    cfg.write_text('{"n_docs": 9}')
    changed_cfg = index_cache.cache_key(tmp_path, cfg, 7, "TPU v5 lite")
    cfg.write_text('{"n_docs": 8}')
    keys = {
        key,
        changed_src,
        changed_bench,
        changed_cfg,
        index_cache.cache_key(tmp_path, cfg, 8, "TPU v5 lite"),
        index_cache.cache_key(tmp_path, cfg, 7, "cpu"),
    }
    assert len(keys) == 6
    assert key == index_cache.cache_key(tmp_path, cfg, 7, "TPU v5 lite")


def test_a_cached_index_serves_the_fresh_build_top10(tmp_path):
    cfg = json.loads((ROOT / "bench" / "configs" / "msmarco-splade.json").read_text())
    cfg = {**cfg, **cfg["rehearse"], "n_docs": 384}
    mix = json.loads((ROOT / "bench" / "traffic" / "open-steady.json").read_text())
    mix["query_pool"] = 48
    c = corpus_mod.generate(
        corpus_mod.seed_key(99), corpus_mod.Widths.from_config(cfg), cfg["n_docs"], 48
    )
    seg = build_pool_segment(
        harness.fused(c.passages()), np.arange(c.n), harness.build_config(cfg),
        key=jax.random.key(3),
    )
    fresh = SegmentPool.from_segmented(seg)
    index_cache.save(tmp_path, "m", "k1", fresh)
    loaded = index_cache.load(tmp_path, "m", "k1")
    assert loaded is not None and index_cache.load(tmp_path, "m", "k2") is None
    for a, b in zip(jax.tree.leaves(fresh), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    entries = traffic_mod.pool_entries(mix, np.asarray(c.q_shared_kw), 99)
    reqs = harness.make_requests(entries, tuple(np.asarray(a) for a in c.queries()))

    def serve(pool):
        service = harness.make_service(pool, cfg, mix)
        pend = [service.submit(r) for r in reqs]
        service.flush()
        out = [p.result() for p in pend]
        service.stop_pump()
        return np.stack([o[0] for o in out]), np.stack([o[1] for o in out])

    ids_f, sc_f = serve(fresh)
    ids_l, sc_l = serve(loaded)
    np.testing.assert_array_equal(ids_f, ids_l)
    np.testing.assert_array_equal(sc_f, sc_l)
    assert (ids_f[:, 0] >= 0).all()


def test_eviction_keeps_each_configurations_newest_entries(tmp_path, monkeypatch):
    import os

    from repro.checkpoint import index_io

    monkeypatch.setattr(index_io, "save_pool", lambda d, pool: Path(d).mkdir(parents=True))
    monkeypatch.setattr(index_cache, "KEEP", 2)
    t = 1_000_000
    for name, key in (("a", "1"), ("b", "1"), ("a", "2"), ("a", "3"), ("b", "2"), ("b", "3")):
        index_cache.save(tmp_path, name, key, None)
        os.utime(index_cache.entry_dir(tmp_path, name, key), (t, t))
        t += 10
    left = sorted(p.name for p in tmp_path.iterdir())
    # another configuration's entries never push one out
    assert left == ["a-2", "a-3", "b-2", "b-3"]
