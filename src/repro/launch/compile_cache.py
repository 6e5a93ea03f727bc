"""Persistent XLA compilation cache for the repo's entry points.

``use_compile_cache(root)`` is called at start-up by ``chip_smoke.py``,
``benchmarks/run.py`` and the scripts under ``scripts/``, each passing the
checkout it runs from — never when the library is imported.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache goes to the fixed path
``<root>/.jax_cache`` (gitignored): the directory is part of what a later
run must find again, so it is never derived from a temp dir, pid or time.
"""
from __future__ import annotations

import os

import jax


def use_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``root`` is the checkout the entry point runs from; it must hold
    ``src/repro``, so an installed copy of the package never places a
    cache outside a checkout."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise ValueError(f"{root!r} is not a checkout of this repo "
                         "(no src/repro below it)")
    cache_dir = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
