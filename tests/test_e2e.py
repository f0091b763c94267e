"""End-to-end slice: engine + fused pipeline + slow-path control plane.

The SURVEY.md §7 milestone: one DORA cycle where DISCOVER #1 misses to the
slow path and DISCOVER #2 is answered on-device, plus NAT conntrack-hybrid
(first packet punts, second fast-paths), QoS shaping and antispoof drops —
all through the public Engine surface.
"""

import numpy as np
import pytest

from bng_tpu.control import dhcp_codec, packets
from bng_tpu.control.dhcp_server import DHCPServer
from bng_tpu.control.nat import NATManager
from bng_tpu.control.pool import Pool, PoolManager
from bng_tpu.ops.antispoof import MODE_STRICT
from bng_tpu.runtime.engine import AntispoofTables, Engine, QoSTables
from bng_tpu.runtime.tables import FastPathTables
from bng_tpu.utils.net import ip_to_u32, u32_to_ip

SERVER_MAC = bytes.fromhex("02aabbccdd01")
SERVER_IP = ip_to_u32("10.0.0.1")
T0 = 1_753_000_000


class FakeClock:
    def __init__(self, t=T0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture
def stack():
    clock = FakeClock()
    fastpath = FastPathTables(sub_nbuckets=512, vlan_nbuckets=64, cid_nbuckets=64, max_pools=16)
    fastpath.set_server_config(SERVER_MAC, SERVER_IP)
    pools = PoolManager(fastpath)
    pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.0.0.0"), prefix_len=24,
                        gateway=SERVER_IP, dns_primary=ip_to_u32("1.1.1.1"),
                        lease_time=3600))
    nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                     sessions_nbuckets=256, sub_nat_nbuckets=64)
    qos = QoSTables(nbuckets=256)
    spoof = AntispoofTables(nbuckets=256)
    server = DHCPServer(SERVER_MAC, SERVER_IP, pools, fastpath_tables=fastpath,
                        nat_hook=lambda ip, now: nat.allocate_nat(ip, now), clock=clock)
    engine = Engine(fastpath, nat, qos, spoof, batch_size=8,
                    slow_path=server.handle_frame, clock=clock)
    return engine, server, nat, qos, spoof, clock


def client_frame(mac, msg_type, **kw):
    src_ip = kw.pop("src_ip", 0)
    pkt = dhcp_codec.build_request(mac, msg_type, **kw)
    pkt.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
    return packets.udp_packet(mac, b"\xff" * 6, src_ip, 0xFFFFFFFF, 68, 67,
                              pkt.encode().ljust(320, b"\x00"))


def data_frame(src_mac, src_ip, dst_ip, sport, dport, payload=b"data", proto="udp"):
    if proto == "udp":
        return packets.udp_packet(src_mac, SERVER_MAC, src_ip, dst_ip, sport, dport, payload)
    return packets.tcp_packet(src_mac, SERVER_MAC, src_ip, dst_ip, sport, dport, payload)


class TestDORA:
    def test_full_dora_then_fastpath(self, stack):
        engine, server, nat, qos, spoof, clock = stack
        mac = bytes.fromhex("02c0ffee0001")

        # DISCOVER #1 -> slow path -> OFFER from server
        r1 = engine.process([client_frame(mac, dhcp_codec.DISCOVER)])
        assert r1["tx"] == [] and len(r1["slow"]) == 1
        lane, offer_frame = r1["slow"][0]
        assert offer_frame is not None
        offer = dhcp_codec.decode(packets.decode(offer_frame).payload)
        assert offer.msg_type == dhcp_codec.OFFER
        ip = offer.yiaddr
        assert u32_to_ip(ip).startswith("10.0.0.")

        # REQUEST -> slow path -> ACK + fast-path cache populated
        r2 = engine.process([client_frame(mac, dhcp_codec.REQUEST, requested_ip=ip,
                                          server_id=SERVER_IP)])
        _, ack_frame = r2["slow"][0]
        ack = dhcp_codec.decode(packets.decode(ack_frame).payload)
        assert ack.msg_type == dhcp_codec.ACK
        assert ack.yiaddr == ip
        assert server.stats.ack == 1

        # DISCOVER #2 -> answered ON DEVICE (the fast-path milestone)
        r3 = engine.process([client_frame(mac, dhcp_codec.DISCOVER)])
        assert len(r3["tx"]) == 1
        _, dev_frame = r3["tx"][0]
        dev_offer = dhcp_codec.decode(packets.decode(dev_frame).payload)
        assert dev_offer.msg_type == dhcp_codec.OFFER
        assert dev_offer.yiaddr == ip

        # renewal REQUEST also on device
        r4 = engine.process([client_frame(mac, dhcp_codec.REQUEST, requested_ip=ip,
                                          server_id=SERVER_IP)])
        assert len(r4["tx"]) == 1

    def test_release_invalidates_fastpath(self, stack):
        engine, server, nat, qos, spoof, clock = stack
        mac = bytes.fromhex("02c0ffee0002")
        engine.process([client_frame(mac, dhcp_codec.DISCOVER)])
        r = engine.process([client_frame(mac, dhcp_codec.REQUEST,
                                         requested_ip=0, server_id=SERVER_IP)])
        ack = dhcp_codec.decode(packets.decode(r["slow"][0][1]).payload)
        ip = ack.yiaddr
        # fast path now answers
        r = engine.process([client_frame(mac, dhcp_codec.DISCOVER)])
        assert len(r["tx"]) == 1
        # RELEASE tears down lease + cache
        engine.process([client_frame(mac, dhcp_codec.RELEASE, ciaddr=ip)])
        r = engine.process([client_frame(mac, dhcp_codec.DISCOVER)])
        assert r["tx"] == []  # back to slow path
        assert server.stats.release == 1

    def test_lease_expiry_goes_slow_path(self, stack):
        engine, server, nat, qos, spoof, clock = stack
        mac = bytes.fromhex("02c0ffee0003")
        engine.process([client_frame(mac, dhcp_codec.DISCOVER)])
        engine.process([client_frame(mac, dhcp_codec.REQUEST, server_id=SERVER_IP)])
        r = engine.process([client_frame(mac, dhcp_codec.DISCOVER)])
        assert len(r["tx"]) == 1
        clock.advance(4000)  # beyond 3600s lease
        r = engine.process([client_frame(mac, dhcp_codec.DISCOVER)])
        assert r["tx"] == []  # expired -> slow path (renews)


class TestNATFlow:
    def test_conntrack_hybrid(self, stack):
        engine, server, nat, qos, spoof, clock = stack
        sub_mac = bytes.fromhex("02c0ffee0010")
        sub_ip = ip_to_u32("10.0.0.55")
        remote = ip_to_u32("93.184.216.34")
        nat.allocate_nat(sub_ip, T0)

        f = data_frame(sub_mac, sub_ip, remote, 40000, 443)
        # packet 1: new flow -> punt, host creates session
        r1 = engine.process([f])
        assert r1["fwd"] == [] and len(r1["slow"]) == 1
        assert nat.sessions.count == 1

        # packet 2: device SNAT
        r2 = engine.process([f])
        assert len(r2["fwd"]) == 1
        _, out = r2["fwd"][0]
        d = packets.decode(out)
        assert d.src_ip == ip_to_u32("203.0.113.1")
        assert 1024 <= d.src_port <= 65535
        assert d.dst_ip == remote
        nat_port = d.src_port

        # reply from the internet: device DNAT back to subscriber
        reply = packets.udp_packet(SERVER_MAC, sub_mac, remote,
                                   ip_to_u32("203.0.113.1"), 443, nat_port, b"resp")
        r3 = engine.process([reply], from_access=False)
        assert len(r3["fwd"]) == 1
        _, back = r3["fwd"][0]
        db = packets.decode(back)
        assert db.dst_ip == sub_ip
        assert db.dst_port == 40000
        assert db.src_ip == remote

    def test_no_allocation_passes_unnatted(self, stack):
        engine, server, nat, qos, spoof, clock = stack
        f = data_frame(b"\x02" * 6, ip_to_u32("10.0.0.99"), ip_to_u32("8.8.8.8"), 1234, 53)
        r = engine.process([f])
        assert r["fwd"] == [] and len(r["slow"]) == 1
        assert nat.sessions.count == 0  # no port block -> no session

    def test_eim_stable_mapping(self, stack):
        """RFC 4787: same internal ip:port -> same external mapping."""
        engine, server, nat, qos, spoof, clock = stack
        sub_ip = ip_to_u32("10.0.0.56")
        nat.allocate_nat(sub_ip, T0)
        mac = bytes.fromhex("02c0ffee0011")
        ports = set()
        for dst in ("1.1.1.1", "2.2.2.2", "3.3.3.3"):
            f = data_frame(mac, sub_ip, ip_to_u32(dst), 50000, 443)
            engine.process([f])  # punt -> create
            r = engine.process([f])  # fast path
            d = packets.decode(r["fwd"][0][1])
            ports.add((d.src_ip, d.src_port))
        assert len(ports) == 1  # endpoint-independent


class TestQoS:
    def test_rate_limit_drops(self, stack):
        engine, server, nat, qos, spoof, clock = stack
        sub_ip = ip_to_u32("10.0.0.60")
        # 8 kbps => 1000 bytes/s; burst 1500
        qos.set_subscriber(sub_ip, down_bps=8000, up_bps=8000, up_burst=1500, down_burst=1500)
        mac = bytes.fromhex("02c0ffee0020")
        big = data_frame(mac, sub_ip, ip_to_u32("8.8.8.8"), 1111, 9999, b"x" * 400)
        frames = [big] * 8
        r = engine.process(frames)
        # 1500-byte bucket / ~442-byte frames -> 3 pass, rest dropped
        assert len(r["dropped"]) >= 4
        assert engine.stats.qos[1] >= 4  # QST_PKTS_DROPPED

    def test_download_direction_rate_limit(self, stack):
        """qos_egress parity (qos_ratelimit.c:126-172): DOWNLOAD shaping
        keys on the post-DNAT destination — network-side lanes must hit
        the qos_down table, not ride for free."""
        engine, server, nat, qos, spoof, clock = stack
        sub_ip = ip_to_u32("10.0.0.61")
        nat.allocate_nat(sub_ip, T0)
        nat_ip, nat_port = nat.handle_new_flow(
            sub_ip, ip_to_u32("1.2.3.4"), 40000, 443, 17, 600, T0)[:2]
        qos.set_subscriber(sub_ip, down_bps=8000, up_bps=8000,
                           up_burst=1000, down_burst=1000)
        # inbound: internet -> subscriber's public mapping (DNAT resolves)
        down = packets.udp_packet(b"\x04" * 6, SERVER_MAC,
                                  ip_to_u32("1.2.3.4"), nat_ip, 443, nat_port,
                                  b"d" * 458)
        r = engine.process([down] * 3, from_access=False)
        # 2x500B fit the 1000B bucket; the 3rd must drop
        assert len(r["fwd"]) == 2 and len(r["dropped"]) == 1, r

    def test_refill_after_time(self, stack):
        engine, server, nat, qos, spoof, clock = stack
        sub_ip = ip_to_u32("10.0.0.61")
        qos.set_subscriber(sub_ip, down_bps=80000, up_bps=80000, up_burst=1000, down_burst=1000)
        mac = bytes.fromhex("02c0ffee0021")
        f = data_frame(mac, sub_ip, ip_to_u32("8.8.8.8"), 1111, 9999, b"x" * 800)
        r = engine.process([f])
        assert r["dropped"] == []
        r = engine.process([f])  # bucket nearly empty
        assert len(r["dropped"]) == 1
        clock.advance(1.0)  # 10kB/s refill
        r = engine.process([f])
        assert r["dropped"] == []

    def test_unlimited_rate_passes(self, stack):
        engine, server, nat, qos, spoof, clock = stack
        sub_ip = ip_to_u32("10.0.0.62")
        qos.set_subscriber(sub_ip, down_bps=0, up_bps=0)
        mac = bytes.fromhex("02c0ffee0022")
        f = data_frame(mac, sub_ip, ip_to_u32("8.8.8.8"), 1111, 9999, b"x" * 1000)
        for _ in range(3):
            r = engine.process([f])
            assert r["dropped"] == []


class TestAntispoof:
    def test_strict_mode_drops_spoofed(self, stack):
        engine, server, nat, qos, spoof, clock = stack
        mac = bytes.fromhex("02c0ffee0030")
        good_ip = ip_to_u32("10.0.0.70")
        spoof.add_binding(mac, good_ip, MODE_STRICT)
        violations = []
        engine.violation_sink = lambda lane, frame: violations.append(lane)

        ok = data_frame(mac, good_ip, ip_to_u32("8.8.8.8"), 1000, 53)
        bad = data_frame(mac, ip_to_u32("10.0.0.71"), ip_to_u32("8.8.8.8"), 1000, 53)
        engine.antispoof.set_config(0, log_violations=True)
        r = engine.process([ok, bad])
        assert r["dropped"] == [1]
        assert violations == [1]

    def test_dhcp_exempt_from_antispoof(self, stack):
        """DISCOVER src 0.0.0.0 must reach the slow path despite strict mode."""
        engine, server, nat, qos, spoof, clock = stack
        mac = bytes.fromhex("02c0ffee0031")
        spoof.add_binding(mac, ip_to_u32("10.0.0.72"), MODE_STRICT)
        r = engine.process([client_frame(mac, dhcp_codec.DISCOVER)])
        assert r["dropped"] == []
        assert r["slow"][0][1] is not None  # got an OFFER


class TestStatsAndExpiry:
    def test_session_counters_and_expiry(self, stack):
        engine, server, nat, qos, spoof, clock = stack
        sub_ip = ip_to_u32("10.0.0.80")
        nat.allocate_nat(sub_ip, T0)
        mac = bytes.fromhex("02c0ffee0040")
        f = data_frame(mac, sub_ip, ip_to_u32("9.9.9.9"), 1234, 443)
        engine.process([f])  # create
        for _ in range(3):
            engine.process([f])  # 3 fast-path packets
        vals = engine.fetch_session_vals()
        from bng_tpu.ops.nat44 import SV_PKTS_OUT

        slots = np.nonzero(np.asarray(nat.sessions.used))[0]
        assert len(slots) == 1
        # 1 seeded by the host on create (nat44.c:722 parity) + 3 on device
        assert vals[slots[0], SV_PKTS_OUT] == 4

        # idle expiry (UDP timeout 120s)
        clock.advance(200)
        n = engine.expire()
        assert n == 1
        assert nat.sessions.count == 0 and nat.reverse.count == 0


def test_nat_release_purges_sessions_before_block_reuse():
    """Recycled port blocks must not resurrect the old subscriber's
    reverse-table rows (cross-subscriber traffic leakage)."""
    from bng_tpu.control.nat import NATManager

    nat = NATManager(public_ips=[0xCB007101], ports_per_subscriber=64,
                     sessions_nbuckets=256, sub_nat_nbuckets=64)
    a, b = 0x0A000005, 0x0A000006
    nat.allocate_nat(a, now=100)
    got = nat.handle_new_flow(a, 0x5DB8D822, 40000, 443, 17, 100, now=100)
    assert got is not None
    nat_ip, nat_port = got
    # A's reverse row exists
    rkey = [0x5DB8D822, nat_ip, 443, nat_port, 17]
    key = [rkey[0], rkey[1], ((rkey[2] & 0xFFFF) << 16) | (rkey[3] & 0xFFFF), rkey[4]]
    assert nat.reverse.lookup(key) is not None
    nat.release_nat(a, now=200)
    # stale rows are gone
    assert nat.reverse.lookup(key) is None
    assert nat.sessions.used.sum() == 0
    # B gets the recycled block
    blk = nat.allocate_nat(b, now=300)
    assert blk["port_start"] == 1024  # reused A's block


class TestDHCPFastLane:
    """process_dhcp: the DHCP-only device program (latency fast lane).

    Reference hook-order parity: bpf/dhcp_fastpath.c is its own XDP
    program — XDP_TX replies never traverse the TC chain — so a control
    batch runs a several-fold smaller program than the fused step."""

    def test_parity_with_fused_step(self, stack):
        engine, server, *_ , clock = stack
        mac = bytes.fromhex("02deadbe0001")
        disc = client_frame(mac, dhcp_codec.DISCOVER, xid=0x41)
        # DORA through the slow path installs the subscriber
        out = engine.process_dhcp([disc])
        assert len(out["slow"]) == 1 and out["slow"][0][1] is not None
        offered = dhcp_codec.decode(packets.decode(out["slow"][0][1]).payload)
        req = client_frame(mac, dhcp_codec.REQUEST, xid=0x42,
                           requested_ip=offered.yiaddr)
        out = engine.process_dhcp([req])
        assert len(out["slow"]) == 1  # REQUEST completes via slow path too

        # now cached: the SAME DISCOVER must be answered on-device by BOTH
        # programs, byte-for-byte
        fast = engine.process_dhcp([disc])
        assert len(fast["tx"]) == 1, fast
        fused = engine.process([disc])
        assert len(fused["tx"]) == 1, fused
        assert fast["tx"][0][1] == fused["tx"][0][1]

    def test_shared_table_state_both_directions(self, stack):
        engine, server, *_ , clock = stack
        mac = bytes.fromhex("02deadbe0002")
        ip = ip_to_u32("10.0.0.77")
        # install via the host mirror; drain through the DHCP-ONLY step
        engine.fastpath.add_subscriber(mac, pool_id=1, ip=ip,
                                       lease_expiry=T0 + 900)
        disc = client_frame(mac, dhcp_codec.DISCOVER, xid=0x43)
        assert len(engine.process_dhcp([disc])["tx"]) == 1
        # the fused step sees the same (threaded) tables — no re-drain
        assert len(engine.process([disc])["tx"]) == 1

        # and deletion drained through the FUSED step hides it from the
        # dhcp-only program too
        engine.fastpath.remove_subscriber(mac)
        assert len(engine.process([disc])["slow"]) == 1
        assert len(engine.process_dhcp([disc])["tx"]) == 0

    def test_non_dhcp_frames_fall_out_as_slow(self, stack):
        engine, *_ = stack
        junk = data_frame(b"\x02" * 6, ip_to_u32("10.0.0.9"),
                          ip_to_u32("8.8.8.8"), 1234, 80)
        out = engine.process_dhcp([junk])
        assert out["tx"] == [] and len(out["slow"]) == 1


class TestCoADeviceIntegration:
    """RADIUS CoA -> device QoS enforcement, end to end (the reference's
    EBPFQoSUpdaterFunc flow, coa_handler.go:175-460: a policy change must
    reach the packet path with no session restart)."""

    def test_coa_policy_change_enforced_on_next_step(self, stack):
        from bng_tpu.control.radius import packet as rp
        from bng_tpu.control.radius.coa import CoAProcessor, CoAServer
        from bng_tpu.control.radius.policy import PolicyManager, QoSPolicy

        engine, server, nat, qos, spoof, clock = stack
        sub_ip = ip_to_u32("10.0.0.66")
        mac = bytes.fromhex("02c0ffee0066")
        # generous initial policy: everything passes
        qos.set_subscriber(sub_ip, down_bps=1_000_000_000, up_bps=1_000_000_000)
        frames = [data_frame(mac, sub_ip, ip_to_u32("8.8.8.8"), 1111, 9999,
                             b"x" * 400)] * 6
        r = engine.process(frames)
        assert len(r["dropped"]) == 0

        # CoA: throttle to a policy whose burst admits ~2 of these frames
        pm = PolicyManager()
        pm.add(QoSPolicy("throttled", download_bps=8_000, upload_bps=8_000))
        session = type("S", (), {"ip": sub_ip, "mac": mac})()

        def qos_update(ip, policy_name):
            p = pm.get(policy_name)
            # burst pinned to 1000B so the admitted-frame count below is
            # deterministic regardless of the policy's burst_factor
            qos.set_subscriber(ip, down_bps=p.download_bps, up_bps=p.upload_bps,
                               down_burst=1000, up_burst=1000,
                               priority=p.priority)
            return True

        proc = CoAProcessor(find_by_ip=lambda ip: session,
                            qos_update=qos_update, policy_manager=pm)
        srv = CoAServer(b"secret", proc)
        req = rp.RadiusPacket(rp.COA_REQUEST, 9)
        req.add(rp.FRAMED_IP_ADDRESS, sub_ip)
        req.add(rp.FILTER_ID, "throttled")
        resp = rp.RadiusPacket.decode(srv.handle_raw(req.encode(b"secret")))
        assert resp.code == rp.COA_ACK

        # the policy change rides the bounded update drain into the very
        # next device step: 1000B bucket / ~442B frames -> ~2 pass, rest drop
        clock.advance(0.001)
        r2 = engine.process(frames)
        assert len(r2["dropped"]) >= 3, r2


class TestDeviceWalledGarden:
    """Device-side walled-garden gate (beyond the reference, whose garden
    maps reach no bpf program — walledgarden/manager.go:172-178): a
    pre-auth subscriber's packet to an arbitrary IP DROPs on device;
    portal/DNS destinations pass; post-auth everything passes. Membership
    changes flow through the bounded update drain like every table."""

    PORTAL = ip_to_u32("10.255.255.1")
    DNS = ip_to_u32("8.8.8.8")

    def _stack_with_garden(self):
        from bng_tpu.runtime.engine import GardenTables

        clock = FakeClock()
        fastpath = FastPathTables(sub_nbuckets=512, vlan_nbuckets=64,
                                  cid_nbuckets=64, max_pools=16)
        fastpath.set_server_config(SERVER_MAC, SERVER_IP)
        pools = PoolManager(fastpath)
        pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.0.0.0"),
                            prefix_len=24, gateway=SERVER_IP, lease_time=3600))
        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         sessions_nbuckets=256, sub_nat_nbuckets=64)
        garden = GardenTables(nbuckets=256)
        garden.allow_destination(self.PORTAL, 8080, 6)   # portal TCP
        garden.allow_destination(self.DNS, 53, 0)        # DNS any proto
        server = DHCPServer(SERVER_MAC, SERVER_IP, pools,
                            fastpath_tables=fastpath,
                            nat_hook=lambda ip, now: nat.allocate_nat(ip, now),
                            clock=clock)
        engine = Engine(fastpath, nat, garden=garden, batch_size=8,
                        slow_path=server.handle_frame, clock=clock)
        return engine, server, nat, garden, clock

    def test_pre_auth_drops_on_device_post_auth_passes(self):
        engine, server, nat, garden, clock = self._stack_with_garden()
        mac = bytes.fromhex("02aabb000077")
        sub_ip = ip_to_u32("10.0.0.77")
        nat.allocate_nat(sub_ip, T0)
        nat.handle_new_flow(sub_ip, ip_to_u32("93.184.216.34"), 40000, 443,
                            17, 600, T0)
        garden.set_gardened(sub_ip, True)  # pre-auth

        arbitrary = data_frame(mac, sub_ip, ip_to_u32("93.184.216.34"),
                               40000, 443)
        dns = data_frame(mac, sub_ip, self.DNS, 40000, 53)
        portal = data_frame(mac, sub_ip, self.PORTAL, 40000, 8080,
                            proto="tcp")
        discover = client_frame(mac, dhcp_codec.DISCOVER)
        out = engine.process([arbitrary, dns, portal, discover],
                             from_access=True)
        # arbitrary dest: DROPPED ON DEVICE despite live NAT state
        assert out["dropped"] == [0], out
        # portal + DNS reach the slow path (allowed destinations)
        slow_lanes = [i for i, _ in out["slow"]]
        assert 1 in slow_lanes and 2 in slow_lanes
        # DHCP must still flow (DORA happens while gardened)
        assert 3 in slow_lanes or any(i == 3 for i, _ in out["tx"])

        # post-auth: release via the update drain — next batch forwards
        garden.set_gardened(sub_ip, False)
        out2 = engine.process([arbitrary, dns, portal], from_access=True)
        assert out2["dropped"] == []
        assert 0 in [i for i, _ in out2["fwd"]]  # NAT'd on device again

    def test_gate_never_touches_other_subscribers(self):
        engine, server, nat, garden, clock = self._stack_with_garden()
        gardened_ip = ip_to_u32("10.0.0.88")
        free_ip = ip_to_u32("10.0.0.89")
        garden.set_gardened(gardened_ip, True)
        nat.allocate_nat(free_ip, T0)
        nat.handle_new_flow(free_ip, ip_to_u32("1.2.3.4"), 41000, 443,
                            17, 600, T0)
        blocked = data_frame(bytes.fromhex("02aabb000088"), gardened_ip,
                             ip_to_u32("1.2.3.4"), 41000, 443)
        ok = data_frame(bytes.fromhex("02aabb000089"), free_ip,
                        ip_to_u32("1.2.3.4"), 41000, 443)
        out = engine.process([blocked, ok], from_access=True)
        assert out["dropped"] == [0]
        assert 1 in [i for i, _ in out["fwd"]]

    def test_cli_garden_transitions_drive_device_gate(self):
        """BNGApp: a garden transition + live lease lands in the engine's
        device gate through the composition-root sync."""
        import types

        from bng_tpu.cli import BNGApp, BNGConfig
        from bng_tpu.utils.net import mac_to_u64

        app = BNGApp(BNGConfig())
        try:
            dhcp = app.components["dhcp"]
            garden_mgr = app.components["walledgarden"]
            gt = app.components["engine"].garden
            mac = "02:00:00:00:00:61"
            ip = ip_to_u32("10.0.0.61")
            dhcp.leases[mac_to_u64(mac)] = types.SimpleNamespace(
                ip=ip, mac=mac, session_id="s1")
            garden_mgr.add_to_walled_garden(mac)
            assert gt.subscribers.lookup([ip]) is not None
            garden_mgr.release_from_walled_garden(mac)
            assert gt.subscribers.lookup([ip]) is None
            # portal/DNS allowed destinations were seeded from config
            assert (gt.allowed[:, 0] != 0).sum() >= 3
        finally:
            app.close()
