"""Built indexes kept inside the checkout, keyed so that none is ever stale.

A sealed pool is saved with ``checkpoint.index_io.save_pool`` and read back
with ``load_pool``, the way a search node comes back up. The key hashes the
configuration file, the seed, the device kind, and the bytes of every file
under ``src/`` and ``bench/`` (the cache itself and byte-code aside), so a
change to the build, the data generator or the harness builds afresh.
Only the newest ``KEEP`` entries of each configuration are kept: a check
runs a cell in two sets on the same seeds, six or so to a set, so the second
set finds every index the first one built, whatever other configurations
cache in between. An entry at 2**16 MS MARCO passages takes about 0.3 GB.
"""

from __future__ import annotations

import hashlib
import shutil
from pathlib import Path

KEEP = 12
HASHED_DIRS = ("src", "bench")
_SKIP_PARTS = {".cache", "__pycache__"}


def source_digest(root: Path) -> str:
    """sha256 over the relative path and bytes of every hashed file."""
    h = hashlib.sha256()
    for top in HASHED_DIRS:
        base = root / top
        if not base.is_dir():
            continue
        for path in sorted(base.rglob("*")):
            rel = path.relative_to(root)
            if not path.is_file() or _SKIP_PARTS & set(rel.parts) or path.suffix == ".pyc":
                continue
            h.update(str(rel).encode() + b"\0")
            h.update(hashlib.sha256(path.read_bytes()).digest())
    return h.hexdigest()


def cache_key(root: Path, config_file: Path, seed: int, device_kind: str) -> str:
    h = hashlib.sha256()
    h.update(config_file.read_bytes())
    h.update(f"\0{int(seed)}\0{device_kind}\0".encode())
    h.update(source_digest(root).encode())
    return h.hexdigest()[:32]


def entry_dir(cache_root: Path, config_name: str, key: str) -> Path:
    return cache_root / f"{config_name}-{key}"


def load(cache_root: Path, config_name: str, key: str):
    """The cached pool (host arrays), or None."""
    from repro.checkpoint.index_io import load_pool

    d = entry_dir(cache_root, config_name, key)
    if not d.is_dir():
        return None
    try:
        return load_pool(d)
    except (FileNotFoundError, ValueError):
        return None


def save(cache_root: Path, config_name: str, key: str, pool) -> None:
    from repro.checkpoint.index_io import save_pool

    cache_root.mkdir(parents=True, exist_ok=True)
    save_pool(entry_dir(cache_root, config_name, key), pool)
    entries = sorted(
        (p for p in cache_root.glob(f"{config_name}-*") if p.is_dir()),
        key=lambda p: p.stat().st_mtime,
    )
    for old in entries[:-KEEP]:
        shutil.rmtree(old, ignore_errors=True)
