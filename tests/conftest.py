"""Test environment: hermetic CPU JAX with an 8-device virtual mesh.

Tests must be hermetic (no dependence on a chip), and the multi-chip
sharding paths (bng_tpu.parallel) need >1 device. Mirrors the
reference's strategy of running everything against stub platform backends
(SURVEY.md §4.6: _linux.go/_stub.go pairs, nil-safe loader).

The pin itself (JAX_PLATFORMS=cpu, virtual device count) lives in
bng_tpu.utils.jaxenv.force_cpu; this file just invokes it before any
backend initialization.
"""

from bng_tpu.utils.jaxenv import enable_compilation_cache, force_cpu

force_cpu(8)
# The helper self-guards: XLA:CPU executables DESERIALIZED from the
# persistent cache computed wrong results for the donated pipeline
# programs (PERF_NOTES §4), so CPU runs stay uncached unless
# BNG_JAX_CACHE_CPU=1. The CPU time win comes from the
# @pytest.mark.slow tier instead.
enable_compilation_cache()

# ---------------------------------------------------------------------------
# BNG_SANITIZE=1 — runtime sanitizer around hot-path tests
# ---------------------------------------------------------------------------
# The dynamic cross-check of bngcheck's static transfer lint
# (bng_tpu/analysis): tests marked `hotpath` run under
# jax.transfer_guard_device_to_host("disallow") + jax.debug_nans, so an
# implicit device->host transfer the lint missed fails the test instead
# of silently blocking the dispatch path. Best-effort on XLA:CPU — the
# d2h guard is inert there (measured, see analysis/sanitize.py); the
# debug_nans half and the planted h2d tests keep teeth everywhere.
# BNG_SANITIZE=strict additionally disallows implicit host->device
# transfers (only hotpath tests whose inputs are explicitly staged
# survive that).

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _bng_sanitize(request):
    from bng_tpu.analysis import sanitize

    if (not sanitize.enabled()
            or request.node.get_closest_marker("hotpath") is None):
        yield
        return
    with sanitize.sanitized(
            h2d="disallow" if sanitize.strict() else "allow"):
        yield

