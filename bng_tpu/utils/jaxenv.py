"""JAX backend set-up shared by the entry points and the tests.

- ``force_cpu(n_devices)`` pins this process to the CPU backend with an
  ``n_devices``-device virtual mesh: the test suite (tests/conftest.py),
  ``bng chaos``, ``__graft_entry__.dryrun_multichip`` and ``bng run
  --shards N`` under ``JAX_PLATFORMS=cpu``. Nothing else in the repo
  chooses the CPU: an entry point that finds no chip fails.

- ``enable_compilation_cache()`` turns on JAX's persistent compile cache
  at a place the caller does not choose: ``JAX_COMPILATION_CACHE_DIR``
  when the environment sets it, ``<checkout>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import os

# the path is part of the cache key's neighbourhood: a directory that
# moves (a temporary name, a pid, the time) never hits
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _ensure_host_device_count(n_devices: int) -> None:
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    opt = "--xla_force_host_platform_device_count"
    m = re.search(rf"{opt}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = f"{flags} {opt}={n_devices}".strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(m.group(0), f"{opt}={n_devices}")


def force_cpu(n_devices: int = 8) -> None:
    """Pin this process to the CPU backend with a virtual mesh.

    Safe to call multiple times. Must run before the first backend
    initialization (the device count is an XLA flag read at start-up).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    _ensure_host_device_count(n_devices)

    import jax

    jax.config.update("jax_platforms", "cpu")


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; call before the first
    compile. Returns the directory in force, or None where the cache
    stays off.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set in code. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``. Both thresholds drop to zero so the many
    small per-geometry programs are cached, not just the big ones.

    CPU GUARD (PERF_NOTES §4): executables DESERIALIZED from the cache
    by XLA:CPU computed wrong results for the donated fused-pipeline
    programs (cold-write runs pass, warm-read runs fail NAT/fast-lane
    e2e and abort the sharded step). So the cache stays off on the CPU
    backend unless ``BNG_JAX_CACHE_CPU=1`` opts in.
    """
    import jax

    if (jax.default_backend() == "cpu"
            and os.environ.get("BNG_JAX_CACHE_CPU") != "1"):
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
