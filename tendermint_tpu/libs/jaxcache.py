"""Where JAX's persistent compilation cache lives — one rule for every
entry point (`cmd start`, bench.py, chip_smoke.py, __graft_entry__.py,
tools/, tests/conftest.py).

`JAX_COMPILATION_CACHE_DIR` set: JAX reads it by itself and nothing
here sets another. Unset: one fixed path inside the checkout
(`<repo>/.jax_cache`, git-ignored) — never a temporary name, a pid or
the time, because the directory is part of the cache key and one that
moves never hits. The verify kernels are very large programs (tens of
seconds of XLA compile per shape), so a cold process is mostly
compiling and a second one should not be."""

from __future__ import annotations

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure() -> str:
    """Place the cache; returns its directory. Call before the first
    compile; works before or after `import jax`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO, ".jax_cache")
        # children (e2e node subprocesses) share the parent's cache
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read its environment at import; tell the live config too
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))
    return path
