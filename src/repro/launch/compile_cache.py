"""JAX's persistent compilation cache for the entry points.

A cold run on the chip spends minutes compiling; the cache lets the next
process skip that. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
itself and this leaves it alone; otherwise the cache goes to a fixed path
in the repository (git-ignored) — the path is part of the cache key, so it
must not move between runs. The CPU backend (tests, interpret-mode runs)
keeps no persistent cache: its entries are tied to the host's CPU features.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    if jax.default_backend() == "cpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
