"""The device qinq stage (ops/qinq.py, `bng run --qinq-enabled`) against the
plain reference, through the engine's ring loop, and its control plane.

(a) `Engine.process_ring_pipelined` over seeded random layouts and frames
agrees with `benchmark/kits/qinq.py Plain` on the framing of every forwarded
frame, byte for byte: every combination of {IPoE, PPPoE} x {up, down} x
{UDP, TCP}, DHCP DISCOVER and REQUEST over tags, a subscriber without a
pair, a frame the host gets (byte for byte as it came), a lane at the
slot's edge. The reference is `struct` and plain Python over three dicts;
the frame inside is held as the default kit holds it (mapping, payload,
both checksums), a DHCP reply byte for byte against a host-only DHCPServer.

(b) the control plane through `bng run`'s app: a DORA and a PADI..IPCP over
tags publish the pair in the step that publishes the lease's and the
session's other rows, data flows both ways, release and session down take
the pair out; checkpoint and restore; the blockers; the counters.

Seeded random tables and frames, tiny sizes, CPU.
"""

import os
import re
import struct
import sys
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.kits.ipoe import l4_checksum_ok  # noqa: E402
from benchmark.kits.qinq import Plain  # noqa: E402
from bng_tpu.control import dhcp_codec, packets  # noqa: E402
from bng_tpu.control.dhcp_server import DHCPServer  # noqa: E402
from bng_tpu.control.nat import NATManager  # noqa: E402
from bng_tpu.control.pool import Pool, PoolManager  # noqa: E402
from bng_tpu.control.pppoe import codec  # noqa: E402
from bng_tpu.ops import antispoof as A  # noqa: E402
from bng_tpu.ops.qinq import QQ_MISS, QQ_OVERSIZE, QQ_POP, QQ_PUSH  # noqa: E402
from bng_tpu.runtime import hostpath  # noqa: E402
from bng_tpu.runtime.engine import AntispoofTables, Engine, QoSTables  # noqa: E402
from bng_tpu.runtime.ring import PyRing  # noqa: E402
from bng_tpu.runtime.tables import (FastPathTables, PPPoEFastPathTables,  # noqa: E402
                                    QinQFastPathTables, V6FastPathTables)
from bng_tpu.telemetry import spans as tele  # noqa: E402
from bng_tpu.utils.net import ip_to_u32, mac_to_u64  # noqa: E402

SERVER_MAC = bytes.fromhex("02aabbccdd01")
ROUTER_MAC = bytes.fromhex("02ee00000001")
SERVER_IP = ip_to_u32("10.0.0.1")
T0 = 1_753_000_000
SUBS = 24
BATCH = 64


def _pool() -> Pool:
    return Pool(pool_id=1, network=ip_to_u32("10.0.0.0"), prefix_len=24,
                gateway=SERVER_IP, dns_primary=ip_to_u32("1.1.1.1"),
                lease_time=3600)


class Stack:
    """One engine over seeded random tables. Subscriber i has QoS rows, a
    strict binding, a NAT block and a UDP and a TCP flow; every third one
    is PPPoE (an open session), the others IPoE (a DHCP row by MAC and, with
    a pair, one by pair); one in eight has no pair. The reference's dicts
    are filled beside the tables, by nothing the tables compute."""

    def __init__(self, seed, stage=True, slot=1536, vlan_ip_shift=0,
                 v6=False):
        rng = np.random.default_rng(seed)
        fastpath = self.fastpath = FastPathTables(
            sub_nbuckets=256, vlan_nbuckets=64, cid_nbuckets=64, max_pools=16)
        fastpath.set_server_config(SERVER_MAC, SERVER_IP)
        PoolManager(fastpath).add_pool(_pool())
        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         sessions_nbuckets=256, sub_nat_nbuckets=64)
        qos = QoSTables(nbuckets=256)
        spoof = AntispoofTables(nbuckets=256)
        spoof.set_config(A.MODE_STRICT, log_violations=True)
        self.pppoe = PPPoEFastPathTables(nbuckets=64, server_mac=SERVER_MAC)
        self.qinq = QinQFastPathTables(nbuckets=64) if stage else None
        self.v6 = V6FastPathTables(spoof, nbuckets=64) if v6 else None
        self.macs = [bytes([0x02, *rng.integers(0, 256, 5).tolist()])
                     for _ in range(SUBS)]
        self.ips = [int(x) for x in
                    ip_to_u32("10.0.0.10") + rng.permutation(100)[:SUBS]]
        lines = rng.permutation(4094 * 4094)[:SUBS]
        self.pairs, self.by_ip, self.by_sid, self.flows = {}, {}, {}, []
        self.is_pppoe = [i % 3 == 0 for i in range(SUBS)]
        for i, (mac, ip) in enumerate(zip(self.macs, self.ips)):
            pair = None if i % 8 == 7 else (int(1 + lines[i] // 4094),
                                            int(1 + lines[i] % 4094))
            qos.set_subscriber(ip, down_bps=80_000, up_bps=80_000,
                               down_burst=1 << 20, up_burst=1 << 20)
            spoof.add_binding(mac, ip, A.MODE_STRICT)
            if pair is not None:
                self.pairs[ip] = pair
                if stage:
                    assert self.qinq.bind(ip, *pair)
            if self.is_pppoe[i]:
                sid = i + 1
                self.pppoe.session_up(SimpleNamespace(
                    session_id=sid, client_mac=mac, assigned_ip=ip))
                self.by_ip[ip], self.by_sid[sid] = (sid, mac), (mac, ip)
            else:
                fastpath.add_subscriber(mac, pool_id=1, ip=ip,
                                        lease_expiry=T0 + 86400)
                if pair is not None:
                    fastpath.add_vlan_subscriber(
                        *pair, pool_id=1, ip=ip + vlan_ip_shift,
                        lease_expiry=T0 + 86400)
            assert nat.allocate_nat(ip, T0) is not None
            for proto in (17, 6):
                dst = int(ip_to_u32("93.184.0.0") + rng.integers(1, 60000))
                sport = int(rng.integers(20000, 60000))
                nat_ip, nat_port = nat.handle_new_flow(ip, dst, sport, 443,
                                                       proto, 64, T0)
                self.flows.append((i, dst, sport, proto, nat_ip, nat_port))
        self.engine = Engine(fastpath, nat, qos, spoof, batch_size=BATCH,
                             pkt_slot=slot, clock=lambda: float(T0),
                             pppoe=self.pppoe, qinq=self.qinq, v6=self.v6)
        self.plain = Plain(self.pairs, self.by_ip, self.by_sid, SERVER_MAC)
        self.host = DHCPServer(SERVER_MAC, SERVER_IP, PoolManager(),
                               clock=lambda: float(T0))
        self.host.pools.add_pool(_pool())

    # -- frames, each with its id in the payload's last four bytes ----------
    def up(self, flow, fid: int, pad: int = 10) -> bytes:
        i, dst, sport, proto, _nat_ip, _nat_port = flow
        mac, ip = self.macs[i], self.ips[i]
        vlans = list(self.pairs[ip]) if ip in self.pairs else None
        make = packets.udp_packet if proto == 17 else packets.tcp_packet
        payload = bytes(pad) + fid.to_bytes(4, "big")
        plain = make(mac, SERVER_MAC, ip, dst, sport, 443, payload)
        if not self.is_pppoe[i]:
            return plain[:12] + packets.eth_header(b"", b"", 0x0800,
                                                   vlans) + plain[14:]
        session = codec.PPPoEPacket(
            code=codec.CODE_SESSION, session_id=self.by_ip[ip][0],
            payload=codec.ppp_frame(0x0021, plain[14:])).encode()
        return codec.eth_frame(SERVER_MAC, mac, 0x8864, session, vlans=vlans)

    def down(self, flow, fid: int, pad: int = 10) -> bytes:
        _i, dst, _sport, proto, nat_ip, nat_port = flow
        make = packets.udp_packet if proto == 17 else packets.tcp_packet
        return make(ROUTER_MAC, SERVER_MAC, dst, nat_ip, 443, nat_port,
                    bytes(pad) + fid.to_bytes(4, "big"))

    def dhcp(self, i: int, msg, xid: int) -> bytes:
        mac, ip = self.macs[i], self.ips[i]
        p = dhcp_codec.build_request(
            mac, msg, xid=xid,
            requested_ip=ip if msg == dhcp_codec.REQUEST else 0,
            server_id=SERVER_IP if msg == dhcp_codec.REQUEST else 0)
        p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST,
                          bytes([1, 3, 6, 51, 54])))
        vlans = list(self.pairs[ip]) if ip in self.pairs else None
        return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                  p.encode().ljust(320, b"\x00"), vlans=vlans)

    def host_reply(self, frame: bytes, i: int, ip: int | None = None) -> bytes:
        """What a host-only DHCPServer answers the request with."""
        self.host._offers[mac_to_u64(self.macs[i])] = (
            self.ips[i] if ip is None else ip, 1)
        return self.host.handle_frame(frame)

    # -- the loop ------------------------------------------------------------
    def serve(self, frames):
        """`frames`: (bytes, from_access) in windows of at most BATCH through
        `process_ring_pipelined`. Returns (tx, fwd, slow) as lists of bytes:
        replies, forwarded frames, and what the host's slow path was handed."""
        ring = PyRing(nframes=256, frame_size=2048, depth=128)
        tx, fwd, slow = [], [], []
        self.engine.slow_path = lambda frame: slow.append(frame)

        def pop():
            for out, one in ((tx, ring.tx_pop), (fwd, ring.fwd_pop)):
                while (got := one()) is not None:
                    out.append(got[0])

        try:
            for at in range(0, len(frames), BATCH):
                for raw, from_access in frames[at:at + BATCH]:
                    assert ring.rx_push(raw, from_access=from_access)
                self.engine.process_ring_pipelined(ring, now=T0 + 0.01 * at)
                pop()
            self.engine.flush_pipeline()
            pop()
        finally:
            ring.close()
        return tx, fwd, slow

    def holds_inside(self, raw: bytes, want: tuple) -> bool:
        """The default kit's comparison of an untagged IPv4 frame."""
        d = packets.decode(raw)
        return ((d.src_ip, d.src_port, d.dst_ip, d.dst_port, d.proto,
                 d.payload) == want
                and d.ip_checksum_ok and l4_checksum_ok(raw))


def _xid(reply: bytes) -> int:
    at = 46 + (8 if reply[12:14] == b"\x88\xa8" else 0)
    return int.from_bytes(reply[at:at + 4], "big")


def _by_id(frames) -> dict:
    return {int.from_bytes(f[-4:], "big"): f for f in frames}


@pytest.mark.parametrize("seed", [40001, 40002, 40003])
def test_every_combination_agrees_with_the_plain_reference(seed):
    st = Stack(seed)
    sent, seen = {}, set()
    fid = 100
    for flow in st.flows:
        for direction in ("up", "down"):
            fid += 1
            raw = (st.up if direction == "up" else st.down)(flow, fid)
            sent[fid] = (raw, direction, flow)
    # a frame the host gets: tagged ARP from a subscriber behind a pair
    arp_from = next(i for i in range(SUBS) if st.ips[i] in st.pairs)
    arp = (b"\xff" * 6 + st.macs[arp_from]
           + packets.eth_header(b"", b"", 0x0806,
                                list(st.pairs[st.ips[arp_from]]))
           + bytes(28))
    dhcp = {}
    for i in range(SUBS):
        if not st.is_pppoe[i]:
            for msg in (dhcp_codec.DISCOVER, dhcp_codec.REQUEST):
                xid = 0x5000 + 2 * i + (msg == dhcp_codec.REQUEST)
                dhcp[xid] = (st.dhcp(i, msg, xid), i)
    frames = ([(raw, d == "up") for raw, d, _f in sent.values()]
              + [(arp, True)] + [(raw, True) for raw, _i in dhcp.values()])
    order = np.random.default_rng(seed).permutation(len(frames))
    tx, fwd, slow = st.serve([frames[k] for k in order])

    assert slow == [arp]  # byte for byte as it came, tags and all
    got = _by_id(fwd)
    assert set(got) == set(sent)
    pushed = popped = missed = 0
    for fid, (raw, direction, flow) in sent.items():
        i, dst, sport, proto, nat_ip, nat_port = flow
        ip, out = st.ips[i], got[fid]
        payload = raw[-14:]
        seen.add(("pppoe" if st.is_pppoe[i] else "ipoe", direction, proto))
        if direction == "up":
            inner = st.plain.up(raw)
            assert inner is not None and len(out) == len(inner)
            assert out[:14] == inner[:14] and out[12:14] == b"\x08\x00"
            assert st.holds_inside(out, (nat_ip, nat_port, dst, 443, proto,
                                         payload))
            popped += ip in st.pairs
        else:
            packet = st.plain.packet_of(out)
            assert packet is not None
            assert out == st.plain.down(raw, packet, ip)
            assert st.holds_inside(raw[:12] + b"\x08\x00" + packet,
                                   (dst, 443, ip, sport, proto, payload))
            # the framing the rules give: tags in front of the session header
            want_len = (len(raw) + (8 if ip in st.pairs else 0)
                        + (8 if st.is_pppoe[i] else 0))
            assert len(out) == want_len
            pushed += ip in st.pairs
            missed += ip not in st.pairs
    assert seen == {(k, d, p) for k in ("ipoe", "pppoe")
                    for d in ("up", "down") for p in (17, 6)}
    assert missed > 0  # a subscriber without a pair is served untagged

    # DHCP: answered from the device, byte for byte the host-only server's
    # reply to the request as it came, the tags back on it
    replies = {_xid(r): r for r in tx}
    assert set(replies) == set(dhcp)
    for xid, (raw, i) in dhcp.items():
        assert replies[xid] == st.host_reply(raw, i)
        tagged = st.ips[i] in st.pairs
        assert (replies[xid][12:14] == b"\x88\xa8") == tagged
        if tagged:
            assert replies[xid][12:20] == raw[12:20]
    assert st.engine.stats.passed == 1 and st.engine.stats.dropped == 0

    stats = st.engine.stats.qinq
    assert (int(stats[QQ_PUSH]), int(stats[QQ_POP]), int(stats[QQ_MISS]),
            int(stats[QQ_OVERSIZE])) == (pushed, popped, missed, 0)


def test_a_lane_at_the_slots_edge_is_not_pushed_and_is_counted():
    """A downstream frame the pair would push past the slot leaves
    forwarded as it is, and is counted; one byte group shorter it is
    pushed. (The PPPoE encap has the same rule for its own eight bytes.)"""
    slot = 512
    st = Stack(40100, slot=slot)
    flow = next(f for f in st.flows if not st.is_pppoe[f[0]]
                and st.ips[f[0]] in st.pairs and f[3] == 17)
    ip = st.ips[flow[0]]
    fits = st.down(flow, 1, pad=slot - 8 - 42 - 4)  # 504 bytes, 512 pushed
    edge = st.down(flow, 2, pad=slot - 4 - 42 - 4)  # 508 bytes: no room
    assert (len(fits), len(edge)) == (slot - 8, slot - 4)
    _tx, fwd, _slow = st.serve([(fits, False), (edge, False)])
    got = _by_id(fwd)
    assert len(got[1]) == slot and got[1] == st.plain.down(
        fits, st.plain.packet_of(got[1]), ip)
    assert len(got[2]) == slot - 4 and got[2][12:14] == b"\x08\x00"
    assert st.plain.packet_of(got[2]) == st.plain.packet_of(
        st.plain.down(edge, got[2][14:], ip))
    stats = st.engine.stats.qinq
    assert (int(stats[QQ_PUSH]), int(stats[QQ_OVERSIZE]),
            int(stats[QQ_MISS])) == (1, 1, 0)


def test_the_first_tier_is_the_one_that_answers():
    """`vlan_subscriber_pools` is the lookup's first tier: a tagged request
    is answered from the pair's row. The pair's row holds another address
    than the MAC's here, so the reply says which tier answered. With the
    MAC row removed the reply is unchanged; with the pair's row removed it
    is the MAC tier's."""
    shift = 100

    def reply(st, i):
        raw = st.dhcp(i, dhcp_codec.DISCOVER, 0x77)
        tx, _fwd, slow = st.serve([(raw, True)])
        assert len(tx) == 1 and not slow
        return raw, tx[0]

    st = Stack(40200, vlan_ip_shift=shift)
    i = next(k for k in range(SUBS)
             if not st.is_pppoe[k] and st.ips[k] in st.pairs)
    raw, both = reply(st, i)
    assert both == st.host_reply(raw, i, st.ips[i] + shift)
    assert st.fastpath.remove_subscriber(st.macs[i])
    assert reply(st, i)[1] == both

    st = Stack(40200, vlan_ip_shift=shift)
    assert st.fastpath.remove_vlan_subscriber(*st.pairs[st.ips[i]])
    raw, mac_tier = reply(st, i)
    assert mac_tier == st.host_reply(raw, i, st.ips[i]) != both


def test_beside_the_v6_stage_an_ipv6_lane_is_popped_and_pushed_like_a_v4_one():
    """`--qinq-enabled --ipv6-fastpath`: a bound subscriber's IPv6 frames
    lose the tags upstream and get the pair downstream, keyed by the IPv4
    address stage `v6` resolves from the /128; bytes otherwise untouched."""
    import ipaddress

    st = Stack(40500, v6=True)
    remote = ipaddress.IPv6Address("2001:db8:ffff::9").packed

    def frame6(src_mac, dst_mac, src, dst, fid):
        payload = struct.pack("!HHHH", 40000, 443, 8 + 8, 0) + bytes(4) \
            + fid.to_bytes(4, "big")
        return (dst_mac + src_mac + b"\x86\xdd" + bytes([0x60, 0, 0, 0])
                + len(payload).to_bytes(2, "big") + bytes([17, 64])
                + src + dst + payload)

    sent = []
    for i in range(SUBS):
        if st.is_pppoe[i]:
            continue
        addr = ipaddress.IPv6Address(f"2001:db8:1::{i + 1:x}").packed
        st.v6.bind(st.macs[i], addr)
        pair = st.pairs.get(st.ips[i])
        up = Plain.tag(frame6(st.macs[i], SERVER_MAC, addr, remote, 2 * i),
                       pair)
        down = frame6(ROUTER_MAC, SERVER_MAC, remote, addr, 2 * i + 1)
        sent.append((up, down, pair))
    _tx, fwd, slow = st.serve([(up, True) for up, _d, _p in sent]
                              + [(down, False) for _u, down, _p in sent])
    got = _by_id(fwd)
    assert not slow and len(got) == 2 * len(sent)
    for i, (up, down, pair) in enumerate(sent):
        assert got[int.from_bytes(up[-4:], "big")] == Plain.untag(up)[1]
        assert got[int.from_bytes(down[-4:], "big")] == Plain.tag(down, pair)
    tagged = sum(p is not None for _u, _d, p in sent)
    assert 0 < tagged < len(sent)
    assert [int(x) for x in st.engine.stats.qinq] == [
        tagged, tagged, len(sent) - tagged, 0]
    assert int(st.engine.stats.v6[:2].sum()) == 2 * len(sent)


def _step_hlo(st) -> str:
    eng = st.engine
    return str(eng._step.lower(
        eng.tables,
        jnp.zeros((hostpath.window_rows(BATCH, eng.L), eng.L), jnp.uint8),
        np.uint32(1), np.uint32(1)
    ).compiler_ir(dialect="stablehlo"))


# (index operand's shape, result's shape, result's type) of every gather; an
# index of shape `1` is a static column pick (`x[:, 3]`), no per-lane gather
GATHER = re.compile(r'"stablehlo\.gather"[^\n]*tensor<([0-9x]+)xi32>\) '
                    r'-> tensor<([0-9x]+)x(\w+)>')


def test_without_the_flag_no_qinq_op_is_compiled_and_with_it_no_byte_gather():
    """Flag off: no pair table in the pytree, no geometry, and the step's
    StableHLO does not depend on the stage's code (the same text from two
    builds; parent against change is PERF.md's sha256). Flag on: one table
    probe (two bucket rows, one value row) and byte moves that are selects
    among static shifts: no per-lane gather over the slot, no loop."""
    off = Stack(40300, stage=False)
    assert off.engine.tables.qinq_by_ip is None and off.engine.geom.qinq is None
    hlo_off = _step_hlo(off)
    assert hlo_off == _step_hlo(Stack(40300, stage=False))
    hlo_on = _step_hlo(Stack(40300))
    assert "stablehlo.while" not in hlo_on and "dynamic_slice" not in hlo_on
    on, without = GATHER.findall(hlo_on), GATHER.findall(hlo_off)
    assert without, "the pattern no longer finds the step's gathers"
    u8 = lambda found: sorted(d for _i, d, ty in found if ty == "ui8")  # noqa: E731
    assert u8(on) == u8(without)
    rows = lambda found: sorted(d for i, d, ty in found  # noqa: E731
                                if ty == "ui32" and i != "1")
    added = rows(on)
    for d in rows(without):
        added.remove(d)
    assert added == [f"{BATCH}x32", f"{BATCH}x32", f"{BATCH}x8"]
    # and a tagged frame keeps its tag where the stage is off
    flow = next(f for f in off.flows if not off.is_pppoe[f[0]]
                and off.ips[f[0]] in off.pairs)
    _tx, fwd, _slow = off.serve([(off.up(flow, 9), True),
                                 (off.down(flow, 10), False)])
    got = _by_id(fwd)
    assert got[9][12:14] == b"\x88\xa8" and got[10][12:14] == b"\x08\x00"
    assert off.engine.stats.qinq.sum() == 0


@pytest.mark.parametrize("armed", [True, False])
def test_the_counters_reach_the_tracer_armed_and_cost_nothing_disarmed(armed):
    st = Stack(40400)
    flows = [f for f in st.flows if st.ips[f[0]] in st.pairs][:6]
    bare = next(f for f in st.flows if st.ips[f[0]] not in st.pairs)
    frames = ([(st.up(f, 10 + k), True) for k, f in enumerate(flows)]
              + [(st.down(f, 30 + k), False) for k, f in enumerate(flows)]
              + [(st.down(bare, 50), False)])
    tracer = tele.Tracer()
    if armed:
        tele.arm(tracer)
    try:
        st.serve(frames)
    finally:
        if armed:
            tele.disarm()
    assert [int(x) for x in st.engine.stats.qinq] == [6, 6, 1, 0]
    # disarmed, and in a program without the stage, the sums stay 0
    sums = tracer.sums()
    assert (sums["qinq_push"], sums["qinq_pop"], sums["qinq_miss"]) == (
        (6, 6, 1) if armed else (0, 0, 0))


def test_the_registry_is_what_every_pair_is_written_through():
    """`QinQMapper` (control/qinq.py) holds which subscriber has which
    pair: a pair another subscriber holds is refused and nothing is
    written, a subscriber that comes up on another line moves, a single
    tag registers nowhere, and the bulk writer is all or nothing."""
    from bng_tpu.control.qinq import VLANPair

    t = QinQFastPathTables(nbuckets=64)
    assert t.bind(10, 100, 200) and t.pair_of(10) == (100, 200)
    assert not t.bind(11, 100, 200) and t.pair_of(11) is None
    assert t.refused == 1
    assert t.bind(10, 100, 201) and t.pair_of(10) == (100, 201)
    assert t.registry.get_subscriber(VLANPair(100, 200)) is None
    assert t.bind(11, 100, 200)  # the line 10 left is free again
    assert not t.bind(12, 0, 7) and not t.bind(12, 7, 0)  # one tag: none
    assert t.unbind(10) and not t.unbind(10)
    assert t.registry.get_vlan(10) is None and t.by_ip.count == 1
    with pytest.raises(ValueError):
        t.bulk_bind([20, 21], [5, 100], [6, 200])  # 100.200 is 11's
    assert t.pair_of(20) is None and t.registry.get_vlan(20) is None
    t.bulk_bind([20, 21], [5, 5], [6, 7])
    assert t.pair_of(21) == (5, 7)
    assert t.registry.get_subscriber(VLANPair(5, 6)) == 20
    assert t.registry.stats() == {"total_mappings": 3, "double_tagged": 3,
                                  "single_tagged": 0}


def test_the_bulk_writer_of_the_vlan_tier_writes_add_vlan_subscribers_rows():
    one, bulk = (FastPathTables(sub_nbuckets=64, vlan_nbuckets=64,
                                cid_nbuckets=64, max_pools=4)
                 for _ in range(2))
    s, c = np.arange(1, 41), np.arange(101, 141)
    ips = np.arange(40) + ip_to_u32("10.0.0.10")
    for k in range(40):
        one.add_vlan_subscriber(int(s[k]), int(c[k]), pool_id=1,
                                ip=int(ips[k]), lease_expiry=T0)
    bulk.add_vlan_subscribers_bulk(s, c, 1, ips, np.uint32(T0))
    keys = ((s.astype(np.uint32) << 16) | c.astype(np.uint32))[:, None]
    assert (one.vlan.lookup_batch_host(keys)
            == bulk.vlan.lookup_batch_host(keys)).all()
    assert bulk.vlan.count == 40


# --------------------------------------------------------------------------
# (b) the control plane, through `bng run`'s app and the engine's ring loop
# --------------------------------------------------------------------------

from test_pppoe import SimClient  # noqa: E402

from bng_tpu.cli import BNGApp, BNGConfig  # noqa: E402
from bng_tpu.runtime import checkpoint as ck  # noqa: E402

LINE_A, LINE_B = (300, 41), (300, 42)
IPOE_MAC = bytes.fromhex("02cc00000051")
PPPOE_MAC = bytes.fromhex("02cc00000052")
REMOTE = ip_to_u32("93.184.216.34")


class TaggedClient(SimClient):
    """test_pppoe.py's client behind a pair of tags, talking to the app
    through the ring: what it sends goes out tagged, what comes back is
    untagged before it reacts."""

    def __init__(self, app, mac, line):
        super().__init__(app.c["pppoe"], mac=mac)
        self.app, self.line = app, line

    def _pump(self, frames, now):
        pending = list(frames)
        while pending:
            tx, _fwd = self.app.offer(Plain.tag(pending.pop(0), self.line))
            for out in tx:
                tags, bare = Plain.untag(out)
                assert tags == self.line  # the reply is on the client's line
                pending.extend(self._react(bare, now))


class App:
    """`bng run --pppoe-enabled --qinq-enabled` at a tiny size; frames in
    and out by the ring, a clock the test moves."""

    def __init__(self, **flags):
        self.now = float(T0)
        cfg = BNGConfig(qinq_enabled=True, pppoe_enabled=True,
                        pppoe_auth="none", slaac_enabled=False,
                        dhcpv6_enabled=False, walled_garden_enabled=False,
                        metrics_enabled=False, batch_size=8, lease_time=600,
                        **flags)
        self.app = BNGApp(cfg, clock=lambda: self.now)
        self.ring = self.app.components["ring"] = PyRing(
            nframes=128, frame_size=2048, depth=32)
        self.c = self.app.components
        self.xid = 0x200

    def offer(self, frame, from_access=True):
        """One frame through the loop: (replies on TX, frames forwarded)."""
        assert self.ring.rx_push(frame, from_access=from_access)
        # the pipelined loop retires a beat later; the first frame of a NAT
        # flow goes through the chip twice (dispatch, retire and hold,
        # dispatch behind the apply, retire: PR 53)
        for _ in range(4):
            self.app.drive_once()
        tx, fwd = [], []
        while (got := self.ring.tx_pop()) is not None:
            tx.append(got[0])
        while (got := self.ring.fwd_pop()) is not None:
            fwd.append(got[0])
        return tx, fwd

    def dhcp(self, msg, line, requested=0, host=False):
        """One client message over `line`; the decoded reply. `host`: handed
        to the host's server, as a request the device has no live row for."""
        self.xid += 1
        p = dhcp_codec.build_request(
            IPOE_MAC, msg, xid=self.xid, requested_ip=requested,
            server_id=ip_to_u32(self.app.config.server_ip) if requested else 0)
        p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST,
                          bytes([1, 3, 6, 51, 54])))
        frame = packets.udp_packet(IPOE_MAC, b"\xff" * 6, 0, 0xFFFFFFFF, 68,
                                   67, p.encode().ljust(320, b"\x00"),
                                   vlans=list(line))
        if host:
            tx, fwd = [self.c["dhcp"].handle_frame(frame)], []
        else:
            tx, fwd = self.offer(frame)
        assert len(tx) == 1 and not fwd
        tags, bare = Plain.untag(tx[0])
        assert tags == line
        return dhcp_codec.decode(packets.decode(bare).payload)

    def dora(self, line) -> int:
        offer = self.dhcp(dhcp_codec.DISCOVER, line)
        ack = self.dhcp(dhcp_codec.REQUEST, line, requested=offer.yiaddr)
        assert ack.msg_type == dhcp_codec.ACK
        return ack.yiaddr

    def data(self, mac, ip, line, session_id=0):
        """Both directions of one new flow: the upstream frame (tagged, in
        the session framing where there is one) and, by the mapping it
        left with, the downstream one. Returns what was forwarded."""
        server_mac = bytes.fromhex(self.app.config.server_mac.replace(":", ""))
        plain = packets.udp_packet(mac, server_mac, ip, REMOTE, 40000, 443,
                                   b"up-" + bytes(8))
        up = plain
        if session_id:
            up = codec.eth_frame(server_mac, mac, 0x8864, codec.PPPoEPacket(
                code=codec.CODE_SESSION, session_id=session_id,
                payload=codec.ppp_frame(0x0021, plain[14:])).encode())
        # the first frame of a flow makes its session and leaves translated
        # itself (PR 53), as the second does: the same bytes
        first = self.offer(Plain.tag(up, line))[1]
        fwd_up = self.offer(Plain.tag(up, line))[1]
        assert len(fwd_up) == 1 and first == fwd_up
        d = packets.decode(fwd_up[0])
        down = packets.udp_packet(ROUTER_MAC, server_mac, REMOTE, d.src_ip,
                                  443, d.src_port, b"down-" + bytes(8))
        return fwd_up[0], self.offer(down, from_access=False)[1]


@pytest.fixture(scope="module")
def app():
    a = App()
    yield a
    a.app.close()


def test_a_lease_over_tags_publishes_its_pair_with_its_other_rows(app):
    q = app.c["qinq_tables"]
    assert app.c["engine"].tables.qinq_by_ip is not None
    assert app.c["dhcp"].qinq is q
    ip = app.dora(LINE_A)
    lease = app.c["dhcp"].leases[mac_to_u64(IPOE_MAC)]
    assert (lease.s_tag, lease.c_tag) == LINE_A and q.pair_of(ip) == LINE_A
    assert app.c["fastpath"].vlan.lookup([(LINE_A[0] << 16) | LINE_A[1]]) is not None
    # the rows of the lease and the pair went up in one drain: the next
    # frames are served from the chip, tagged downstream and not upstream
    hits = int(app.c["engine"].stats.dhcp[0])
    up, down = app.data(IPOE_MAC, ip, LINE_A)
    assert up[12:14] == b"\x08\x00" and Plain.untag(up)[0] == ()
    assert len(down) == 1 and Plain.untag(down[0])[0] == LINE_A
    assert packets.decode(Plain.untag(down[0])[1]).dst_ip == ip
    # a renewal from another line that reaches the host moves the pair and
    # the VLAN-tier row (one the device answers from the MAC tier does not
    # reach it: ROADMAP M1, the line is not checked on the chip)
    assert app.dhcp(dhcp_codec.REQUEST, LINE_B, requested=ip).yiaddr == ip
    assert q.pair_of(ip) == LINE_A
    ack = app.dhcp(dhcp_codec.REQUEST, LINE_B, requested=ip, host=True)
    assert ack.msg_type == dhcp_codec.ACK and q.pair_of(ip) == LINE_B
    assert app.c["fastpath"].vlan.lookup([(LINE_A[0] << 16) | LINE_A[1]]) is None
    _up, down = app.data(IPOE_MAC, ip, LINE_B)
    assert Plain.untag(down[0])[0] == LINE_B
    assert int(app.c["engine"].stats.dhcp[0]) >= hits
    # release: the pair goes with the lease, downstream is a miss, untagged
    rel = dhcp_codec.build_request(IPOE_MAC, dhcp_codec.RELEASE, xid=9)
    rel.ciaddr = ip
    app.offer(packets.udp_packet(IPOE_MAC, b"\xff" * 6, ip, 0xFFFFFFFF, 68,
                                 67, rel.encode().ljust(320, b"\x00"),
                                 vlans=list(LINE_B)))
    assert q.pair_of(ip) is None and q.registry.get_vlan(ip) is None
    assert not app.c["dhcp"].leases
    stats = app.app.stats()["qinq"]
    assert stats["pairs"] == 0 and stats["device"]["push"] >= 2


def test_a_session_over_tags_publishes_its_pair_and_loses_it_on_close(app):
    q = app.c["qinq_tables"]
    cli = TaggedClient(app, PPPOE_MAC, LINE_A)
    cli.connect(now=app.now)
    assert cli.ipcp_done and cli.session_id and cli.ip
    sess = app.c["pppoe"].sessions.get(cli.session_id)
    assert sess.vlans == list(LINE_A) and q.pair_of(cli.ip) == LINE_A
    up, down = app.data(PPPOE_MAC, cli.ip, LINE_A, session_id=cli.session_id)
    assert up[12:14] == b"\x08\x00" and len(down) == 1
    tags, bare = Plain.untag(down[0])
    assert tags == LINE_A and bare[12:14] == b"\x88\x64"  # tags, then PPPoE
    assert bare[:6] == PPPOE_MAC
    assert struct.unpack_from("!H", bare, 16)[0] == cli.session_id
    # another subscriber cannot take the line while the session holds it
    assert not q.bind(ip_to_u32("10.0.3.3"), *LINE_A)
    from bng_tpu.control.pppoe.session import TerminateCause

    app.c["pppoe"].terminate(cli.session_id, TerminateCause.ADMIN_RESET,
                             app.now)
    assert q.pair_of(cli.ip) is None
    assert q.bind(ip_to_u32("10.0.3.3"), *LINE_A)
    assert q.unbind(ip_to_u32("10.0.3.3"))


def test_a_checkpoint_written_before_restores_after(app):
    q = app.c["qinq_tables"]
    ip = app.dora(LINE_A)
    snap = ck.roundtrip_checkpoint(ck.build_checkpoint(
        1, app.now, engine=app.c["engine"]))
    assert "qinq" in snap.meta["components"]
    after = App()
    try:
        assert after.c["qinq_tables"].pair_of(ip) is None
        rows = ck.restore_checkpoint(snap, engine=after.c["engine"])
        assert rows["qinq.by_ip"] == q.by_ip.count == 1
        assert after.c["qinq_tables"].pair_of(ip) == LINE_A
        # the registry is rebuilt from the table
        assert not after.c["qinq_tables"].bind(ip + 1, *LINE_A)
        # a program without the stage refuses the component, whole
        bare = BNGApp(BNGConfig(slaac_enabled=False, metrics_enabled=False,
                                dhcpv6_enabled=False,
                                walled_garden_enabled=False, batch_size=8))
        try:
            with pytest.raises(ck.CheckpointError, match="qinq"):
                ck.restore_checkpoint(snap, engine=bare.components["engine"])
        finally:
            bare.close()
    finally:
        after.app.close()


@pytest.mark.parametrize("flags,where", [
    ({"shards": 2}, "sharded_blockers"),
    ({"slowpath_workers": 2, "slowpath_worker_mode": "inline"},
     "fleet_blockers")])
def test_the_flag_is_a_named_blocker_where_the_stage_is_not_wired(flags, where):
    cfg = BNGConfig(qinq_enabled=True, slaac_enabled=False,
                    dhcpv6_enabled=False, walled_garden_enabled=False,
                    metrics_enabled=False, batch_size=8, shard_nbuckets=64,
                    **flags)
    a = BNGApp(cfg)
    try:
        assert "qinq" in getattr(a, where)
        if where == "fleet_blockers":
            # collapsed, and said so: the in-process server commits every
            # lease, so a lease's pair has to reach the table through it
            assert "fleet" not in a.components
            assert a.components["dhcp"].qinq is a.components["qinq_tables"]
        else:
            assert a.components["dhcp"].qinq is None
    finally:
        a.close()


def test_the_flag_is_off_by_default_and_sizes_its_table_like_the_others():
    from bng_tpu.ops.table import nbuckets_for

    off = BNGApp(BNGConfig(slaac_enabled=False, metrics_enabled=False,
                           dhcpv6_enabled=False, walled_garden_enabled=False,
                           batch_size=8))
    try:
        assert BNGConfig().qinq_enabled is False
        assert "qinq_tables" not in off.components
        assert off.components["engine"].tables.qinq_by_ip is None
        assert off.components["dhcp"].qinq is None
        assert "qinq" not in off.stats()
    finally:
        off.close()
    t = QinQFastPathTables(nbuckets=nbuckets_for(1_000_000))
    assert t.by_ip.nbuckets == 524_288 and t.by_ip.KW == 8


def test_without_the_flag_a_tagged_request_names_no_subscriber():
    """The 1:1 model is the flag's: without it a request's tags are a
    shared service VLAN's, and a lease writes no VLAN-tier row from them
    (that row would answer every client behind the VLAN)."""
    host = DHCPServer(SERVER_MAC, SERVER_IP, PoolManager(),
                      fastpath_tables=FastPathTables(
                          sub_nbuckets=64, vlan_nbuckets=64, cid_nbuckets=64,
                          max_pools=4), clock=lambda: float(T0))
    host.pools.add_pool(_pool())
    p = dhcp_codec.build_request(IPOE_MAC, dhcp_codec.REQUEST, xid=5,
                                 requested_ip=ip_to_u32("10.0.0.50"),
                                 server_id=SERVER_IP)
    frame = packets.udp_packet(IPOE_MAC, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                               p.encode().ljust(320, b"\x00"),
                               vlans=list(LINE_A))
    assert host.handle_frame(frame) is not None
    lease = host.leases[mac_to_u64(IPOE_MAC)]
    assert (lease.s_tag, lease.c_tag) == (0, 0) and host.tables.vlan.count == 0
