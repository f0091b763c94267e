"""bng_tpu.utils.jaxenv: where the compile cache goes, and that slow-path
worker children can never reach the accelerator."""

import os
import subprocess
import sys

import pytest

from bng_tpu.utils import jaxenv

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls without applying them: the suite
    must not really turn the cache on (see the CPU guard)."""
    import jax

    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda key, val: seen.__setitem__(key, val))
    return seen


def test_cache_dir_from_environment_is_not_set_in_code(monkeypatch,
                                                       config_updates):
    monkeypatch.setenv("BNG_JAX_CACHE_CPU", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert jaxenv.enable_compilation_cache() == "/some/dir"
    assert "jax_compilation_cache_dir" not in config_updates
    # only the two thresholds are lowered
    assert set(config_updates) == {
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes"}


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                        config_updates):
    monkeypatch.setenv("BNG_JAX_CACHE_CPU", "1")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_ROOT, ".jax_cache")
    assert jaxenv.enable_compilation_cache() == want
    assert jaxenv.enable_compilation_cache() == want  # same on every call
    assert config_updates["jax_compilation_cache_dir"] == want
    # and in another process
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", BNG_JAX_CACHE_CPU="1")
    out = subprocess.run(
        [sys.executable, "-c",
         "from bng_tpu.utils.jaxenv import enable_compilation_cache as e;"
         "print(e())"],
        env=env, cwd=_ROOT, capture_output=True, text=True, timeout=120)
    assert out.stdout.strip().splitlines()[-1] == want, out.stderr


def test_cpu_guard_keeps_the_cache_off(monkeypatch, config_updates):
    monkeypatch.delenv("BNG_JAX_CACHE_CPU", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert jaxenv.enable_compilation_cache() is None
    assert config_updates == {}


def test_fleet_worker_children_start_on_the_cpu_backend(monkeypatch):
    """A child inherits its environment at start(): inside the spawn
    window it names the CPU backend, whatever the parent runs on, and
    the parent's own value is back afterwards."""
    from bng_tpu.control.fleet import FleetSpec, SlowPathFleet
    from bng_tpu.control.pool import Pool, PoolManager
    from bng_tpu.utils.net import ip_to_u32

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    at_start = []
    real_spawn = SlowPathFleet._spawn_one

    def spy(self, i):
        at_start.append(os.environ.get("JAX_PLATFORMS"))
        return real_spawn(self, i)

    monkeypatch.setattr(SlowPathFleet, "_spawn_one", spy)
    sip = ip_to_u32("10.9.0.1")
    pools = PoolManager(None)
    pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.9.0.0"),
                        prefix_len=24, gateway=sip, lease_time=3600))
    fleet = SlowPathFleet(FleetSpec.from_pool_manager(b"\x02" * 6, sip, pools),
                          n_workers=2, pools=pools, mode="process")
    try:
        assert at_start == ["cpu", "cpu"]
        assert os.environ["JAX_PLATFORMS"] == "tpu"
        assert fleet.stats_snapshot()["worker_failures"] == 0
    finally:
        fleet.close()
