"""Which device JAX runs on, and the one persistent compile cache.

Every jit path of the repo (``evo/``, ``sim/``, ``kernels/``) calls
:func:`ensure_compile_cache` before its first compile, so all of them
share one on-disk cache:

* ``JAX_COMPILATION_CACHE_DIR``, when it is set (JAX reads it itself, and
  no other directory is configured here);
* otherwise ``<checkout>/.jax_cache`` — a fixed path, because the path is
  part of the cache key and a directory that moves never hits.

Platform queries raise when JAX cannot initialize: a broken accelerator
must surface, never read as "not a TPU".
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

__all__ = [
    "DEFAULT_CACHE_DIR",
    "ensure_compile_cache",
    "host_only_process",
    "on_tpu",
    "platform",
]

DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")

_CACHE_READY = False


def platform() -> str:
    """JAX's default backend (``"cpu"``, ``"tpu"``, ...)."""
    import jax

    return jax.default_backend()


def on_tpu() -> bool:
    return platform() == "tpu"


def host_only_process() -> None:
    """Pin this process to JAX's CPU backend.  Called first thing in every
    spawned pool worker: a chip belongs to one process, and that is the
    parent."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_platforms", "cpu")


def ensure_compile_cache() -> None:
    """Point JAX's persistent compilation cache at the repo's one cache
    directory (idempotent; see the module docstring)."""
    global _CACHE_READY
    if _CACHE_READY:
        return
    _CACHE_READY = True
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # A compile that ran before this call fixed the cache as unused for the
    # process; start it over so the directory takes effect.
    compilation_cache.reset_cache()
