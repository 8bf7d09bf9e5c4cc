"""Process-wide JAX settings that entry points apply before their first
compile. Nothing here runs at `import repro`: tests compile for a described
TPU, and cache entries written that way cannot be read back."""
from __future__ import annotations

import os
from pathlib import Path

import jax


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is read by JAX itself and wins;
    otherwise the cache lives at the fixed `<checkout>/.jax_cache`, so a
    later run of the same checkout finds it again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pin_cpu() -> None:
    """Run this process's JAX on the host CPU. A chip belongs to one process
    at a time; host-side work (aggregation, fleet workers) must never take it
    from the trainer. Call before anything starts a JAX backend."""
    jax.config.update("jax_platforms", "cpu")
