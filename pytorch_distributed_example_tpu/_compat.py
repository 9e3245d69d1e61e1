"""Process-level JAX set-up shared across the package: the one
`shard_map` spelling, the N-device CPU mesh, and the compile cache."""

from __future__ import annotations

import os
from typing import Optional

# The one place a compile-cache directory is chosen in code (see
# `enable_compile_cache`): a fixed, git-ignored directory inside the
# checkout. The directory is part of the cache key, so it is derived
# from the package's own path and nothing that moves between runs.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache",
)


def shard_map_fn(f, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off (the package's
    collective bodies return per-rank values the checker cannot type)."""
    import jax

    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def force_cpu_devices(n: int) -> None:
    """Pin the process to an ``n``-device virtual CPU mesh (the examples'
    `--cpu` path). Must run before the first backend touch."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(n))


def enable_compile_cache(
    cache_dir: Optional[str] = None, min_compile_secs: float = 0.2
) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX has already read it and
    no directory is set in code — the machine's choice wins over
    ``cache_dir``. Otherwise the cache lives at ``cache_dir`` if the
    caller names one (a gang-shared pre-warm directory), else at
    `COMPILE_CACHE_DIR`. Every entry point (examples, `chip_smoke.py`,
    the test harness, serve pre-warm) comes through here, so
    a machine that pins the variable gets one cache for all of them.
    `min_compile_secs` keeps trivial programs off the disk (JAX has no
    eviction). Scope paths and source locations are part of the cache key
    (see below), so an edit that moves traced lines compiles again.
    """
    import jax

    pinned = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if pinned:
        cache_dir = pinned
    else:
        cache_dir = cache_dir or COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_secs)
    )
    # The key keeps each operation's scope path and source location. JAX's
    # default strips them, so a program that differs from a cached one only
    # in its `jax.named_scope`s loads the OLD executable with the OLD names,
    # and a profiler trace then reports scopes the source no longer has (or
    # lacks the ones it gained): seen on the chip, where two checkouts
    # shared the machine's cache directory. Per-layer metrics are read from
    # those names (bench_matrix/reduce/scopes.py).
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return cache_dir
