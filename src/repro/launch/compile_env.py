"""Process-level JAX compilation settings for the entry points.

``enable_compile_cache`` places JAX's persistent compilation cache. Entry
points call it from their ``main``; nothing calls it at import time, and tests
never call it, because a compile for a described (not attached) TPU writes
entries that cannot be read back without the chip.

``CompileCounter`` counts the XLA compilations a block of code triggers, so a
run can check that its steady state compiles nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax import monitoring

# <checkout>/src/repro/launch/compile_env.py -> <checkout>
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]

# recorded once per executable JAX builds, whether compiled or read back from
# the persistent cache
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory. Otherwise the
    cache lives in ``<checkout>/.jax_cache``: a fixed path, because the path
    is part of what a later run must find again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_ROOT / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Context manager counting backend compilations and their seconds."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self) -> "CompileCounter":
        monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        monitoring.unregister_event_duration_listener(self._listen)
