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

import functools
import os
import sys
import threading

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


def one_program(factory):
    """`functools.cache` for a function that builds a jitted program,
    safe for threads. `functools.cache` alone runs the function again
    for every thread that asks before the first call has returned, and
    each gets a jit object of its own: launch sites warmed side by
    side then compile (or load) their shapes on objects nobody meets
    again, and the object that stayed in the cache traces, lowers and
    loads those shapes anew at their next launch, in series (~22 s a
    shape in a fast-sync warm replay: PERF.md §6, PR 36). Keeps
    `cache_clear` and `__wrapped__`."""
    cached = functools.cache(factory)
    lock = threading.Lock()

    @functools.wraps(factory)
    def build(*args):
        with lock:
            return cached(*args)

    build.cache_clear = cached.cache_clear
    return build
