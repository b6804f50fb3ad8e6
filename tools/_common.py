"""Shared start-up for the tools/ entry points.

One place for what every standalone script needs before it touches JAX:
the repo on `sys.path` and the persistent compilation cache, placed by
the one helper every entry point uses (utils/xla_cache.py:
JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache).
"""

from __future__ import annotations

import os
import sys


def init_jax_env() -> None:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from novel_view_synthesis_3d_tpu.utils.xla_cache import (
        setup_compilation_cache)

    setup_compilation_cache(min_entry_bytes=0)
