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
# the stand-in of a benchmark cell added since tests/benchmark was written
# ---------------------------------------------------------------------------
# `tiny_dir` (tests/benchmark/test_benchmark.py) gives every layer file's
# cells a tiny stand-in through three literals, and a layer file that names
# a cell they lack stops every rehearsal with a KeyError. PR 42 adds
# `cgnat-sharded4-1M.flood-64B` and may edit no file under tests/benchmark,
# so its stand-in `tiny4-nat.flood` (a `4` in the name: `tiny_dir` gives
# such a cell four chips) is added to them from here, before the fixture
# reads them, as PR 34 did; the next `benchmark` issue moves the entries
# into the literals (PERF.md section 7 row 1 xvii).

@pytest.fixture(scope="module", autouse=True)
def _shardnat_cell_has_a_stand_in():
    import sys

    tb = sys.modules.get("test_benchmark")
    if tb is None:  # not a module that rehearses through tiny_dir
        return
    # tiny-sharded's four shards with the two capacities that size a
    # shard's NAT tables; tiny_dir gives it 4 public addresses, one a shard
    tb.TINY_ARGV.setdefault(
        "tiny4-nat", tb.TINY_ARGV["tiny-sharded"]
        + ["--max-nat-sessions", "512", "--max-nat-subscribers", "128"])
    tb.BASE_OF.setdefault("tiny4-nat", "ipoe-cgnat-sharded4-1M")
    tb.TINY_CELLS.setdefault(
        "tiny4-nat.flood",
        ("cgnat-sharded4-1M.flood-64B", "tiny4-nat", "tiny-flood-32"))
