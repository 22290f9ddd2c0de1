"""Process set-up shared by the benchmark's command-line entry points."""

from __future__ import annotations

import os
from pathlib import Path


def prepare(root: Path) -> None:
    """Keep JAX's persistent compilation cache, every program in it, and the
    TPU runtime's logs inside the checkout (``bench/.cache``). Call before
    anything imports JAX."""
    cache = root / "bench" / ".cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache / "jax")
    os.environ.setdefault("TPU_LOG_DIR", str(cache / "tpu_logs"))
    Path(os.environ["TPU_LOG_DIR"]).mkdir(parents=True, exist_ok=True)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
