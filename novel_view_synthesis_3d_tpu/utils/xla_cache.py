"""Persistent XLA compilation-cache wiring, shared by every entry point.

This module is the ONLY writer of `jax_compilation_cache_dir`: cli,
bench.py, tools/, serve/replica_main and tests/conftest.py all call
`setup_compilation_cache`, so the cache sits in one place that is
decided from outside the program:

  - `JAX_COMPILATION_CACHE_DIR` (env) wins when set, and no code sets
    another directory;
  - otherwise `<checkout>/.jax_cache` (git-ignored). The directory is
    part of the cache key, so it is a fixed path inside the checkout —
    never a home directory, a temp directory or a generated name, which
    a machine that is thrown away after each run would never hit;
  - `NVS3D_NO_COMPILE_CACHE=1` disables entirely (debugging cold
    compiles, read-only checkouts).

Knobs (env-overridable because the right floor differs between a laptop
CPU run and a pod): `NVS3D_CACHE_MIN_COMPILE_S` — only compilations at
least this long are persisted (default 1.0 s);
`NVS3D_CACHE_MIN_ENTRY_BYTES` — minimum executable size persisted
(default -1 = everything).
"""

from __future__ import annotations

import os
import sys
from typing import Optional

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compilation_cache(
        default_dir: Optional[str] = None,
        min_compile_secs: float = 1.0,
        min_entry_bytes: int = -1) -> Optional[str]:
    """Enable the persistent compilation cache; returns the active dir.

    Call before the first jitted dispatch (jax.config updates are
    effective any time before a program is compiled). `default_dir`
    (a fleet spec's shared directory) replaces `<checkout>/.jax_cache`,
    and only when `JAX_COMPILATION_CACHE_DIR` is unset. Returns None —
    and leaves jax untouched — when caching is disabled or the cache
    directory cannot be created (a broken cache dir must never kill a
    run that would merely compile slower without it).
    """
    if os.environ.get("NVS3D_NO_COMPILE_CACHE") == "1":
        return None
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR") or default_dir
                 or DEFAULT_CACHE_DIR)
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as e:
        print(f"warning: compilation cache dir {cache_dir!r} unavailable "
              f"({e}); continuing without persistent cache", file=sys.stderr)
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ.get("NVS3D_CACHE_MIN_COMPILE_S", min_compile_secs)))
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes",
        int(os.environ.get("NVS3D_CACHE_MIN_ENTRY_BYTES", min_entry_bytes)))
    return cache_dir
