"""Storm-suite tests (bng_tpu/chaos/storms.py + the substrate it rides).

Fast deterministic variants of the five storms (same code, reduced
`scale`), the generator's byte-identity proof, the new invariant checks
(v6 lease-vs-pool, NAT block accounting, QoS mirror) with planted
violations, the expiry-batching/jitter engine changes, and the
exhaustion-hygiene counters. `make verify-storm` runs the `storm`
marker; the full-scale storms run under `bng chaos run` (verify-chaos
bit-determinism gate).
"""

from __future__ import annotations

import json

import pytest

from bng_tpu.chaos.invariants import audit_invariants
from bng_tpu.chaos.scenarios import SERVER_IP, SERVER_MAC, _mac, _reply
from bng_tpu.chaos.storms import STORMS
from bng_tpu.control import dhcp_codec, packets
from bng_tpu.loadtest.harness import (BenchmarkConfig, BenchmarkResult,
                                      StormFrameFactory)
from bng_tpu.utils.net import ip_to_u32, mac_to_u64

pytestmark = pytest.mark.storm

SEED = 123


# ---------------------------------------------------------------------------
# generator: template patch-in must be byte-identical to the codec
# ---------------------------------------------------------------------------

class TestStormFrameFactory:
    MAC = bytes.fromhex("02c500001a2b")
    IP = ip_to_u32("10.0.7.9")

    def test_discover_byte_identical(self):
        fac = StormFrameFactory(SERVER_IP)
        p = dhcp_codec.build_request(self.MAC, dhcp_codec.DISCOVER,
                                     xid=0x1234)
        ref = packets.udp_packet(self.MAC, b"\xff" * 6, 0, 0xFFFFFFFF,
                                 68, 67, p.encode().ljust(300, b"\x00"))
        assert fac.discover(self.MAC, 0x1234) == ref

    def test_request_byte_identical(self):
        fac = StormFrameFactory(SERVER_IP)
        p = dhcp_codec.build_request(self.MAC, dhcp_codec.REQUEST, xid=7,
                                     requested_ip=self.IP,
                                     server_id=SERVER_IP)
        ref = packets.udp_packet(self.MAC, b"\xff" * 6, 0, 0xFFFFFFFF,
                                 68, 67, p.encode().ljust(300, b"\x00"))
        assert fac.request(self.MAC, self.IP, 7) == ref

    def test_renew_byte_identical_incl_checksum(self):
        fac = StormFrameFactory(SERVER_IP)
        p = dhcp_codec.build_request(self.MAC, dhcp_codec.REQUEST, xid=9,
                                     ciaddr=self.IP)
        ref = packets.udp_packet(self.MAC, b"\xff" * 6, self.IP, SERVER_IP,
                                 68, 67, p.encode().ljust(300, b"\x00"))
        got = fac.renew(self.MAC, self.IP, 9)
        assert got == ref
        assert packets.decode(got).ip_checksum_ok

    def test_rendered_frames_decode_through_the_server_path(self):
        fac = StormFrameFactory(SERVER_IP)
        dec = packets.decode(fac.discover(self.MAC, 5))
        req = dhcp_codec.decode(dec.payload)
        assert req.msg_type == dhcp_codec.DISCOVER
        assert req.chaddr[:6] == self.MAC and req.xid == 5


# ---------------------------------------------------------------------------
# the five storms, reduced scale (same code as `bng chaos run`)
# ---------------------------------------------------------------------------

class TestStormsFast:
    def test_flash_crowd(self):
        r = STORMS["flash_crowd_reconnect"](SEED, scale=0.01)
        assert r["ok"], json.dumps(r, indent=1)
        assert r["req_after_offer_shed"] == 0
        assert r["unique_ips"] == r["leased"]
        assert sum(r["shed"].values()) > 0  # the storm actually shed
        assert r["workers_final"] > 4  # autoscaler grew under load
        assert r["calm_shed"] == 0  # admission recovered

    def test_lease_expiry_avalanche(self):
        r = STORMS["lease_expiry_avalanche"](SEED, scale=0.02)
        assert r["ok"], json.dumps(r, indent=1)
        assert r["cliff_expiries"] == 1
        assert all(s <= r["reap_budget"] for s in r["sweeps"])
        assert len(r["sweeps"]) >= 2  # the cliff took several ticks
        assert r["mid_cliff_doras"] == len(r["sweeps"])
        assert r["jitter_expiries"] >= r["jitter_buckets_min"]

    def test_cgnat_port_exhaustion(self):
        r = STORMS["cgnat_port_exhaustion"](SEED, scale=0.05)
        assert r["ok"], json.dumps(r, indent=1)
        # every refusal is a counted degraded verdict
        assert r["counted_block"] == r["blocks_refused"] > 0
        assert r["counted_port"] == r["flows_refused"] > 0
        assert r["reused_after_release"] > 0

    def test_coa_policy_flap(self):
        r = STORMS["coa_policy_flap"](SEED, scale=0.05)
        assert r["ok"], json.dumps(r, indent=1)
        assert r["renew_ok"] == r["renew_total"]
        assert r["coa_nak"] == r["flap_rounds"]
        assert r["bad_auth"] == r["flap_rounds"]

    def test_dual_stack_bringup_books_agree_with_bitmaps(self):
        """The satellite: after the storm, the v4 AND v6 lease books
        agree with their pool bitmaps for the same MAC set."""
        r = STORMS["dual_stack_bringup"](SEED, scale=0.1)
        assert r["ok"], json.dumps(r, indent=1)
        n = r["subscribers"]
        assert r["dual_stacked"] == n
        # v4: every lease is fleet-owned in the parent bitmap
        assert r["v4_pool_fleet_owned"] >= r["leased_v4"] == n
        # v6: bindings == allocations, both IA_NA and IA_PD
        assert r["v6_allocated_na"] == r["leased_v6_na"] == n
        assert r["v6_allocated_pd"] == r["leased_v6_pd"] == n
        assert r["ra_seen"] == r["rs_answered"] == n
        assert r["audit_ok"] and not r["violations"]

    def test_storms_deterministic(self):
        from bng_tpu.chaos import runner

        names = ["flash_crowd_reconnect", "lease_expiry_avalanche",
                 "cgnat_port_exhaustion", "dual_stack_bringup"]
        a = runner.canonical_json(runner.run_scenarios(
            seed=9, names=names, storm_scale=0.01))
        b = runner.canonical_json(runner.run_scenarios(
            seed=9, names=names, storm_scale=0.01))
        assert a == b
        assert json.loads(a)["ok"] is True


# ---------------------------------------------------------------------------
# new invariant checks: planted violations must be detected
# ---------------------------------------------------------------------------

class TestV6Audit:
    def _server(self):
        from bng_tpu.control.dhcpv6.server import (AddressPool6,
                                                   DHCPv6Server,
                                                   DHCPv6ServerConfig,
                                                   PrefixPool6)

        return DHCPv6Server(
            DHCPv6ServerConfig(server_mac=SERVER_MAC, rapid_commit=True),
            address_pool=AddressPool6("2001:db8:100::/64"),
            prefix_pool=PrefixPool6("2001:db8:f000::/40",
                                    delegated_len=56),
            clock=lambda: 1000.0)

    def _bind_one(self, srv):
        from bng_tpu.control.dhcpv6 import protocol as p6
        from bng_tpu.control.dhcpv6.protocol import (DHCPv6Message, IANA,
                                                     IAPD,
                                                     generate_duid_ll)

        m = DHCPv6Message(p6.SOLICIT, 1)
        m.add(p6.OPT_CLIENTID, generate_duid_ll(_mac(1)).encode())
        m.add_ia_na(IANA(1))
        m.add_ia_pd(IAPD(1))
        m.add(p6.OPT_RAPID_COMMIT, b"")
        assert srv.handle_message(m.encode()) is not None

    def test_clean_book_audits_clean(self):
        srv = self._server()
        self._bind_one(srv)
        report = audit_invariants(dhcpv6=srv, check_roundtrip=False)
        assert report.ok, report.to_dict()
        assert report.checks["v6_leases_na"] == 1
        assert report.checks["v6_leases_pd"] == 1

    def test_planted_unallocated_binding_detected(self):
        srv = self._server()
        self._bind_one(srv)
        lease = next(l for (d, i, pd), l in srv.leases.items() if not pd)
        srv.addr_pool._allocated.pop(lease.address)  # plant the leak
        report = audit_invariants(dhcpv6=srv, check_roundtrip=False)
        assert not report.ok
        assert "v6-lease-not-allocated" in report.violations_by_kind()

    def test_planted_orphan_allocation_detected(self):
        srv = self._server()
        self._bind_one(srv)
        srv.addr_pool.allocate()  # allocated, never bound
        report = audit_invariants(dhcpv6=srv, check_roundtrip=False)
        assert not report.ok
        assert "v6-alloc-orphan" in report.violations_by_kind()


class TestNATBlockAccounting:
    def _nat(self):
        from bng_tpu.control.nat import NATManager

        return NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                          ports_per_subscriber=64,
                          port_range=(1024, 1024 + 64 * 4 - 1),
                          sessions_nbuckets=256, sub_nat_nbuckets=64)

    def test_exhausted_allocator_audits_clean_and_counts(self):
        nat = self._nat()
        subs = [ip_to_u32("10.9.0.1") + i for i in range(6)]
        granted = [s for s in subs if nat.allocate_nat(s, 0)]
        assert len(granted) == 4
        assert nat.exhausted["block"] == 2
        report = audit_invariants(nat=nat, check_roundtrip=False)
        assert report.ok, report.to_dict()
        assert report.checks["nat_exhausted_block"] == 2

    def test_planted_block_leak_detected(self):
        nat = self._nat()
        subs = [ip_to_u32("10.9.0.1") + i for i in range(3)]
        for s in subs:
            nat.allocate_nat(s, 0)
        # plant the leak: drop a block without returning it to the free
        # list (carved != allocated + free)
        leaked = nat.blocks.pop(subs[0])
        nat.sub_nat.delete([subs[0]])
        report = audit_invariants(nat=nat, check_roundtrip=False)
        assert not report.ok
        assert "nat-block-accounting" in report.violations_by_kind()
        assert leaked["port_start"] >= 1024


# ---------------------------------------------------------------------------
# expiry batching + lease jitter (the engine half of the avalanche)
# ---------------------------------------------------------------------------

class TestExpiryBatching:
    def _server(self, n=40, jitter=0.0):
        from bng_tpu.control.dhcp_server import DHCPServer
        from bng_tpu.control.pool import Pool, PoolManager

        pools = PoolManager()
        pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.0.0.0"),
                            prefix_len=20, gateway=SERVER_IP,
                            lease_time=600))
        server = DHCPServer(SERVER_MAC, SERVER_IP, pools,
                            clock=lambda: 1000.0,
                            lease_jitter_frac=jitter)
        fac = StormFrameFactory(SERVER_IP)
        for i in range(n):
            m = _mac(9000 + i)
            off = server.handle_frame(fac.discover(m, i))
            server.handle_frame(fac.request(m, _reply(off).yiaddr, n + i))
        return server

    def test_max_reaps_bounds_each_sweep(self):
        server = self._server(n=40)
        assert len({l.expiry for l in server.leases.values()}) == 1
        sweeps = []
        while server.leases:
            sweeps.append(server.cleanup_expired(10_000, max_reaps=16))
        assert sweeps == [16, 16, 8]
        # the partially-reaped intermediate states stayed consistent
        # (proved against the pools the sweep releases into)
        assert sum(sweeps) == 40

    def test_unbounded_default_reaps_everything(self):
        server = self._server(n=10)
        assert server.cleanup_expired(10_000) == 10

    def test_partial_reap_state_is_audit_clean(self):
        server = self._server(n=30)
        server.cleanup_expired(10_000, max_reaps=7)
        report = audit_invariants(pools=server.pools, dhcp=server,
                                  check_roundtrip=False)
        assert report.ok, report.to_dict()

    def test_jitter_spreads_the_cliff_and_only_extends(self):
        server = self._server(n=64, jitter=0.5)
        exps = sorted({l.expiry for l in server.leases.values()})
        assert len(exps) >= server.LEASE_JITTER_BUCKETS // 2
        assert exps[0] >= 1000 + 600  # never shortened
        assert exps[-1] <= 1000 + 600 * 2  # bounded by lt*(1+frac)
        # quantized: at most BUCKETS distinct values (template cache
        # stays bounded)
        assert len(exps) <= server.LEASE_JITTER_BUCKETS

    def test_jitter_is_deterministic_per_mac(self):
        a = self._server(n=16, jitter=0.5)
        b = self._server(n=16, jitter=0.5)
        ea = {mk: l.expiry for mk, l in a.leases.items()}
        eb = {mk: l.expiry for mk, l in b.leases.items()}
        assert ea == eb

    def test_client_is_told_the_jittered_lease_time(self):
        from bng_tpu.control.dhcp_server import DHCPServer
        from bng_tpu.control.pool import Pool, PoolManager

        pools = PoolManager()
        pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.0.0.0"),
                            prefix_len=24, gateway=SERVER_IP,
                            lease_time=600))
        server = DHCPServer(SERVER_MAC, SERVER_IP, pools,
                            clock=lambda: 1000.0, lease_jitter_frac=0.5)
        fac = StormFrameFactory(SERVER_IP)
        m = _mac(4242)
        off = server.handle_frame(fac.discover(m, 1))
        ack = _reply(server.handle_frame(
            fac.request(m, _reply(off).yiaddr, 2)))
        opt = dict(ack.options)[dhcp_codec.OPT_LEASE_TIME]
        told = int.from_bytes(opt, "big")
        lease = server.leases[mac_to_u64(m)]
        # server expiry and the client's advertised lease time agree —
        # jitter must never strand a renewal
        assert lease.expiry == 1000 + told

    def test_dhcpv6_bounded_cleanup(self):
        from bng_tpu.control.dhcpv6.server import (AddressPool6,
                                                   DHCPv6Server,
                                                   DHCPv6ServerConfig,
                                                   Lease6)

        srv = DHCPv6Server(DHCPv6ServerConfig(server_mac=SERVER_MAC),
                           address_pool=AddressPool6("2001:db8:100::/64"),
                           clock=lambda: 1000.0)
        for i in range(9):
            addr = srv.addr_pool.allocate()
            srv.leases[(b"d%d" % i, 1, False)] = Lease6(
                b"d%d" % i, 1, addr, 128, expiry=500.0)
        assert srv.cleanup_expired(1000.0, max_reaps=4) == 4
        assert srv.cleanup_expired(1000.0, max_reaps=4) == 4
        assert srv.cleanup_expired(1000.0) == 1
        assert not srv.leases and not srv.addr_pool._allocated


# ---------------------------------------------------------------------------
# exhaustion hygiene: counted + exposed, never silent
# ---------------------------------------------------------------------------

class TestExhaustionHygiene:
    def test_dhcp_pool_exhaustion_counted(self):
        from bng_tpu.control.dhcp_server import DHCPServer
        from bng_tpu.control.pool import Pool, PoolManager

        pools = PoolManager()
        pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.0.0.0"),
                            prefix_len=30, gateway=ip_to_u32("10.0.0.1"),
                            lease_time=600))  # 1 usable address
        server = DHCPServer(SERVER_MAC, ip_to_u32("10.0.0.1"), pools,
                            clock=lambda: 1000.0)
        fac = StormFrameFactory(ip_to_u32("10.0.0.1"))
        assert server.handle_frame(fac.discover(_mac(1), 1)) is not None
        # second client: pool dry -> silent per protocol, COUNTED here
        assert server.handle_frame(fac.discover(_mac(2), 2)) is None
        assert server.stats.pool_exhausted == 1

    def test_dhcpv6_exhaustion_counted(self):
        from bng_tpu.control.dhcpv6 import protocol as p6
        from bng_tpu.control.dhcpv6.protocol import (DHCPv6Message, IANA,
                                                     generate_duid_ll)
        from bng_tpu.control.dhcpv6.server import (AddressPool6,
                                                   DHCPv6Server,
                                                   DHCPv6ServerConfig)

        srv = DHCPv6Server(
            DHCPv6ServerConfig(server_mac=SERVER_MAC, rapid_commit=True),
            address_pool=AddressPool6("2001:db8:100::/126"),  # 2 usable
            clock=lambda: 1000.0)
        for i in range(5):
            m = DHCPv6Message(p6.SOLICIT, i + 1)
            m.add(p6.OPT_CLIENTID, generate_duid_ll(_mac(i)).encode())
            m.add_ia_na(IANA(1))
            m.add(p6.OPT_RAPID_COMMIT, b"")
            srv.handle_message(m.encode())
        assert srv.stats.addr_exhausted == 3
        assert srv.stats.no_addrs == 3

    def test_metrics_family_exposed(self):
        from bng_tpu.control.metrics import BNGMetrics
        from bng_tpu.control.nat import NATManager

        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         ports_per_subscriber=64,
                         port_range=(1024, 1024 + 63),
                         sessions_nbuckets=256, sub_nat_nbuckets=64)
        assert nat.allocate_nat(ip_to_u32("10.1.0.1"), 0) is not None
        assert nat.allocate_nat(ip_to_u32("10.1.0.2"), 0) is None
        m = BNGMetrics()
        m.collect_exhaustion(nat=nat)
        text = m.expose()
        assert 'bng_pool_exhausted_total{resource="nat_block"} 1' in text

    def test_fleet_slice_exhaustion_monotonic_across_resize(self):
        """bng_pool_exhausted_total{resource=fleet_slice} is a COUNTER:
        a resize restarts per-worker ServerStats at 0, so the exposed
        total must come from the fleet's monotonic fold, never move
        backward, and keep counting in the new worker generation."""
        from bng_tpu.chaos.storms import _build_storm_fleet

        fleet, pools, fastpath = _build_storm_fleet(
            2, lambda: 1000.0, prefix_len=29,  # 6 usable addrs total
            sub_nbuckets=256, slice_size=2, inbox=64)
        fac = StormFrameFactory(SERVER_IP)
        # drive DISCOVERs until the slices + parent pool run dry
        out = fleet.handle_batch(
            [(i, fac.discover(_mac(7000 + i), i + 1)) for i in range(24)],
            now=1000.0)
        exhausted = fleet.pool_exhausted_total()
        assert exhausted > 0
        assert sum(1 for _l, r in out if r is None) == exhausted
        fleet.resize(3)  # per-worker stats restart at 0
        assert fleet.pool_exhausted_total() >= exhausted  # never backward
        out2 = fleet.handle_batch(
            [(i, fac.discover(_mac(7100 + i), 100 + i)) for i in range(8)],
            now=1001.0)
        assert any(r is None for _l, r in out2)
        assert fleet.pool_exhausted_total() > exhausted  # still counting
        assert (fleet.stats_snapshot()["pool_exhausted_total"]
                == fleet.pool_exhausted_total())

    def test_benchmark_result_carries_scenario_shed_degraded(self):
        res = BenchmarkResult(scenario="flash_crowd",
                              shed={"inbox_full": 3},
                              degraded={"dhcp_pool": 2})
        d = res.to_dict()
        assert d["scenario"] == "flash_crowd"
        assert d["shed"] == {"inbox_full": 3}
        assert d["degraded"] == {"dhcp_pool": 2}
        assert "Shed:" in res.summary()
        assert BenchmarkConfig(scenario="x").scenario == "x"


# ---------------------------------------------------------------------------
# QoS host/device mirror audit (the CoA-flap checker) — planted divergence
# ---------------------------------------------------------------------------

class TestQosMirrorAudit:
    def _engine_with_qos(self):
        from bng_tpu.chaos.scenarios import _build_server_stack
        from bng_tpu.runtime.engine import Engine, QoSTables

        server, pools, fastpath, nat = _build_server_stack(
            lambda: 1000.0)
        qos = QoSTables()
        eng = Engine(fastpath, nat, qos=qos, batch_size=32,
                     slow_path=server.handle_frame)
        qos.set_subscriber(ip_to_u32("10.0.1.5"), 100_000_000, 20_000_000)
        eng.process([])  # drain the row to the device
        return eng, qos, server, pools, nat

    def test_clean_mirror_audits_clean(self):
        eng, qos, server, pools, nat = self._engine_with_qos()
        report = audit_invariants(engine=eng, pools=pools, dhcp=server,
                                  nat=nat, check_roundtrip=False)
        assert report.ok, report.to_dict()
        assert "mirror_slots.qos.up" in report.checks

    def test_planted_config_divergence_detected(self):
        from bng_tpu.ops.qtable import QW_BURST

        eng, qos, server, pools, nat = self._engine_with_qos()
        slot = qos.up._find(ip_to_u32("10.0.1.5"))
        # corrupt a host CONFIG word without marking the slot dirty —
        # the drain will never ship it, so host and device now disagree
        qos.up.rows[slot][QW_BURST] += 1
        report = audit_invariants(engine=eng, pools=pools, dhcp=server,
                                  nat=nat, check_roundtrip=False)
        assert not report.ok
        assert "qos-mirror-mismatch" in report.violations_by_kind()

    def test_device_token_words_are_exempt(self):
        from bng_tpu.ops.qtable import QW_TOKENS

        eng, qos, server, pools, nat = self._engine_with_qos()
        slot = qos.up._find(ip_to_u32("10.0.1.5"))
        # token words are device-authoritative — host drift there is
        # EXPECTED (fold_device_authoritative owns it), never a finding
        qos.up.rows[slot][QW_TOKENS] += 7
        report = audit_invariants(engine=eng, pools=pools, dhcp=server,
                                  nat=nat, check_roundtrip=False)
        assert report.ok, report.to_dict()


# ---------------------------------------------------------------------------
# runner + CLI integration
# ---------------------------------------------------------------------------

class TestRunnerAndCLI:
    def test_catalog_covers_every_scenario(self):
        from bng_tpu.chaos.runner import ALL_SCENARIOS, scenario_catalog

        cat = dict(scenario_catalog())
        assert set(cat) == set(ALL_SCENARIOS)
        assert all(desc for desc in cat.values())
        for storm in STORMS:
            assert storm in cat

    def test_unknown_scenario_raises_with_names(self):
        from bng_tpu.chaos import runner

        with pytest.raises(ValueError, match="flash_crowd_reconnect"):
            runner.run_scenarios(seed=1, names=["nope"])

    def test_cli_list_prints_catalog(self, capsys):
        from bng_tpu.cli import main

        assert main(["chaos", "run", "--list"]) == 0
        out = capsys.readouterr().out
        for storm in STORMS:
            assert storm in out

    def test_cli_unknown_scenario_rc2_with_catalog(self, capsys):
        from bng_tpu.cli import main

        assert main(["chaos", "run", "--scenario", "nope"]) == 2
        err = capsys.readouterr().err
        assert "scenario catalog" in err
        assert "flash_crowd_reconnect" in err

    def test_cli_storm_scale(self, capsys):
        from bng_tpu.cli import main

        rc = main(["chaos", "run", "--seed", "5",
                   "--scenario", "cgnat_port_exhaustion",
                   "--storm-scale", "0.05"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0 and out["ok"]
        assert out["storm_scale"] == 0.05
