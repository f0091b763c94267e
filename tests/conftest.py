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


# ---------------------------------------------------------------------------
# tests/benchmark: the stand-in of a cell added since its literals were written
# ---------------------------------------------------------------------------
# `tiny_dir` (tests/benchmark/test_benchmark.py) maps every layer file's
# cells through the literals TINY_CELLS / TINY_ARGV / BASE_OF, and a cell
# they lack stops every rehearsal with a KeyError. Files under
# tests/benchmark/ may be added and not edited, and its conftest.py exists
# (PR 32's stand-in), so the stand-in of `dualstack-cgnat-1M-wire.flood-64B`
# is registered from here, before the fixture reads the literals: whether
# the whole directory runs or test_benchmark.py alone. The next `benchmark`
# issue moves the entries into the literals (PERF.md section 7 row 1 xv).

@pytest.fixture(scope="module", autouse=True)
def _dualstack_cell_has_a_stand_in():
    import sys

    tb = sys.modules.get("test_benchmark")
    if tb is None:  # a module that does not rehearse through tiny_dir
        return
    # 4,096 dual-stack subscribers, 128 of them behind NAT
    tb.TINY_ARGV.setdefault("tiny-dualstack",
                            tb.TINY_ARGV["tiny-wire"] + ["--ipv6-fastpath"])
    tb.BASE_OF.setdefault("tiny-dualstack", "dualstack-cgnat-1M-wire")
    tb.TINY_CELLS.setdefault(
        "tiny-dualstack.flood",
        ("dualstack-cgnat-1M-wire.flood-64B", "tiny-dualstack", "tiny-flood"))
