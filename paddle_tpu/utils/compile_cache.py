"""Where the persistent XLA compilation cache lives (docs/perf.md).

One resolver for every caller (Executor, tools/serve_bench.py,
chip_smoke.py): the directory is `JAX_COMPILATION_CACHE_DIR` when the
environment sets it, else `<checkout>/.jax_cache`. Never a temporary
name, a pid or a timestamp — a second process can only hit what the
first one wrote if both resolve the same path, and the machine that runs
the program decides where that is by setting the JAX variable.
"""
import os

import jax

ENV = 'JAX_COMPILATION_CACHE_DIR'

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def resolve():
    """The cache directory for this process (not created, not wired)."""
    return os.environ.get(ENV) or os.path.join(_CHECKOUT, '.jax_cache')


def enable():
    """Point jax's persistent cache at resolve() and return the directory.

    The min-compile-time / min-entry-size floors are zeroed so EVERY
    executable persists (the Executor's hit/miss probe relies on a miss
    always writing an entry), and jax's path-embedding XLA-autotune-cache
    option is disabled: by default the cache dir's absolute path lands
    inside the hashed compile options, so an AOT blob exported on one
    machine would never hit on another (a GPU-only feature; TPU/CPU lose
    nothing). The directory only ever goes unset -> resolve() within a
    process, so jax's lazily built cache object needs no reset."""
    d = resolve()
    os.makedirs(d, exist_ok=True)
    jax.config.update('jax_compilation_cache_dir', d)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    jax.config.update('jax_persistent_cache_enable_xla_caches', '')
    return d


def wired():
    """The directory this process's compiles persist to, or None.

    The environment naming a directory turns the cache on for every
    Executor (a restarted Trainer, a serving replica); without it the
    cache is on only once an entry point called enable(). A directory
    somebody else put into jax's config is not ours to probe."""
    if os.environ.get(ENV):
        return enable()
    d = jax.config.jax_compilation_cache_dir
    return d if d == resolve() else None
