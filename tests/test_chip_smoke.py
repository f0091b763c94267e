"""CPU rehearsal of chip_smoke.py's phases at a tiny size, plus the
capacity wiring the smoke stands on. The program itself has no CPU mode:
these tests call its phase functions."""

import subprocess
import sys

import pytest

import chip_smoke
from bng_tpu.cli import BNGApp, BNGConfig
from bng_tpu.ops.table import nbuckets_for

TINY = chip_smoke.Sizes(subscribers=4096, nat_subscribers=128,
                        flows_per_nat_subscriber=2, batch=64, discovers=24,
                        requests=8, new_macs=3, nat_probes=16)


def test_one_chip_phases_rehearse_on_cpu(capsys):
    chip_smoke.run_one_chip(TINY, platform="cpu")
    out = capsys.readouterr().out
    assert "fact: express fallbacks: none" in out
    assert "fact: ring: PyRing" in out


@pytest.mark.sharded
def test_sharded_phase_rehearses_on_four_cpu_devices(capsys):
    chip_smoke.run_sharded(TINY._replace(batch=256), shards=4, platform="cpu")
    out = capsys.readouterr().out
    assert "fact: missteers: 0" in out
    assert "fact: per_shard_frames" in out


def _bucket_counts(app) -> dict:
    c = app.components
    eng = c["engine"]
    return {
        "subscriber": c["fastpath"].sub.nbuckets,
        "vlan": c["fastpath"].vlan.nbuckets,
        "circuit_id": c["fastpath"].cid.nbuckets,
        "qos_up": c["qos"].up.nbuckets, "qos_down": c["qos"].down.nbuckets,
        "antispoof": c["antispoof"].bindings.nbuckets,
        "garden": eng.garden.subscribers.nbuckets,
        "nat_sessions": c["nat"].sessions.nbuckets,
        "nat_reverse": c["nat"].reverse.nbuckets,
        "subscriber_nat": c["nat"].sub_nat.nbuckets,
    }


def _quiet(**kw) -> BNGConfig:
    return BNGConfig(dhcpv6_enabled=False, slaac_enabled=False,
                     metrics_enabled=False, **kw)


def test_config_capacities_reach_every_device_table():
    app = BNGApp(_quiet(max_subscribers=3000, max_nat_sessions=20000,
                        max_nat_subscribers=5000))
    try:
        got = _bucket_counts(app)
        # and the engine's compiled geometry is the same objects' geometry
        assert app.components["engine"].geom.dhcp.sub.nbuckets == 2048
    finally:
        app.close()
    subs, flows, nat_subs = (nbuckets_for(3000), nbuckets_for(20000),
                             nbuckets_for(5000))
    assert (subs, flows, nat_subs) == (2048, 16384, 4096)
    assert got == {
        "subscriber": subs, "vlan": subs, "circuit_id": subs,
        "qos_up": subs, "qos_down": subs, "antispoof": subs, "garden": subs,
        "nat_sessions": flows, "nat_reverse": flows,
        "subscriber_nat": nat_subs}


def test_default_capacities_are_unchanged():
    app = BNGApp(_quiet())
    try:
        assert _bucket_counts(app) == {
            "subscriber": 1 << 15, "vlan": 1 << 12, "circuit_id": 1 << 12,
            "qos_up": 1 << 12, "qos_down": 1 << 12, "antispoof": 1 << 12,
            "garden": 1 << 12, "nat_sessions": 1 << 14,
            "nat_reverse": 1 << 14, "subscriber_nat": 1 << 10}
    finally:
        app.close()


def test_one_million_subscribers_ask_for_reference_geometry():
    """What `chip_smoke.py` asks of `bng run` is the geometry
    tests/test_tpu_lowering.py compiles for the described chip."""
    from bng_tpu.runtime.verify import REAL_1M

    s = chip_smoke.Sizes()
    assert (s.subscribers, s.nat_flows, s.nat_subscribers) == (
        1_000_000, 1_000_000, 250_000)
    assert nbuckets_for(s.subscribers) == REAL_1M.sub_nbuckets == 1 << 19
    assert nbuckets_for(s.nat_flows) == REAL_1M.nat_sessions_nbuckets
    assert nbuckets_for(s.nat_subscribers) == REAL_1M.sub_nat_nbuckets
    assert s.batch == REAL_1M.batch


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """Chip or fail: on this CPU-only machine the program exits non-zero
    before building anything and prints no result line."""
    out = subprocess.run([sys.executable, chip_smoke.__file__],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "build:" not in out.stdout
