"""DHCP fast-path kernel golden tests.

Packets are built with the host codec (bng_tpu.control), run through the
device kernel, and the reply bytes are decoded back with the independent
host parser — asserting the same externally-visible behavior as
dhcp_fastpath_prog (bpf/dhcp_fastpath.c:619-813).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from bng_tpu.control import dhcp_codec, packets
from bng_tpu.ops.dhcp import (
    NSTATS, ST_TOTAL, ST_HIT, ST_MISS, ST_ERROR, ST_EXPIRED,
    ST_OPT82_PRESENT, ST_BCAST, ST_UCAST, ST_VLAN,
    dhcp_fastpath,
)
from bng_tpu.ops.parse import parse_batch
from bng_tpu.runtime.tables import FastPathTables
from bng_tpu.utils.net import ip_to_u32, mac_to_u64

L = 512
B = 8

SERVER_MAC = bytes.fromhex("02aabbccdd01")
SERVER_IP = ip_to_u32("10.0.0.1")
BCAST_MAC = b"\xff" * 6
NOW = 1_700_000_000


def make_tables(**kw):
    t = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64, cid_nbuckets=64, max_pools=16, **kw)
    t.set_server_config(SERVER_MAC, SERVER_IP)
    t.add_pool(1, network=ip_to_u32("10.0.0.0"), prefix_len=24, gateway=ip_to_u32("10.0.0.1"),
               dns_primary=ip_to_u32("8.8.8.8"), dns_secondary=ip_to_u32("8.8.4.4"), lease_time=3600)
    return t


def dhcp_frame(mac, msg_type, vlans=None, giaddr=0, ciaddr=0, broadcast=False,
               circuit_id=b"", pad_before_53=0, src_ip=0):
    """Build a realistic client frame.

    Real clients pad the BOOTP payload (min 300 bytes; relayed packets are
    larger still) — the fast path, like the reference, requires 12 bytes of
    options for the msg-type scan (c:221) and a 64-byte window for the
    option-82 scan (c:276), so minimal unpadded packets go slow-path.
    """
    pkt = dhcp_codec.build_request(mac, msg_type, giaddr=giaddr, ciaddr=ciaddr,
                                   broadcast=broadcast, circuit_id=circuit_id)
    if not circuit_id:
        # typical client option-55 parameter request list (keeps option 82,
        # when present, directly after option 53 — the reference's position A)
        pkt.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 15, 51, 54])))
    if pad_before_53:
        pkt.options = [(dhcp_codec.OPT_PAD, b"")] * pad_before_53 + pkt.options
    payload = pkt.encode().ljust(320, b"\x00")
    return packets.udp_packet(
        src_mac=mac, dst_mac=BCAST_MAC, src_ip=src_ip, dst_ip=0xFFFFFFFF,
        src_port=68, dst_port=67, payload=payload, vlans=vlans,
    )


import functools
import jax


@functools.lru_cache(maxsize=4)
def _jitted(geom):
    @jax.jit
    def step(pkt, length, dev_tables, now):
        parsed = parse_batch(pkt, length)
        return dhcp_fastpath(pkt, length, parsed, dev_tables, geom, now)

    return step


def run_kernel(frames, tables):
    pkt = np.zeros((B, L), dtype=np.uint8)
    length = np.zeros((B,), dtype=np.uint32)
    for i, f in enumerate(frames):
        pkt[i, : len(f)] = np.frombuffer(f, dtype=np.uint8)
        length[i] = len(f)
    step = _jitted(tables.geom)
    return step(jnp.asarray(pkt), jnp.asarray(length), tables.device_tables(), jnp.uint32(NOW))


def reply_bytes(res, i):
    n = int(res.out_len[i])
    return bytes(np.asarray(res.out_pkt[i, :n], dtype=np.uint8))


class TestDiscoverOffer:
    def test_known_mac_gets_offer(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef01")
        t.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.50"), lease_expiry=NOW + 600)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.DISCOVER)], t)
        assert bool(res.is_reply[0])
        raw = reply_bytes(res, 0)
        dec = packets.decode(raw)
        assert dec.dst_mac == BCAST_MAC  # DISCOVER w/o ciaddr -> broadcast (c:443-461)
        assert dec.src_mac == SERVER_MAC
        assert dec.src_ip == SERVER_IP and dec.dst_ip == 0xFFFFFFFF
        assert dec.ttl == 64 and dec.proto == 17
        assert dec.ip_checksum_ok, "IP header checksum must be valid"
        assert dec.src_port == 67 and dec.dst_port == 68
        assert dec.ip_total_len == len(raw) - 14
        d = dhcp_codec.decode(dec.payload)
        assert d.op == 2
        assert d.msg_type == dhcp_codec.OFFER
        assert d.yiaddr == ip_to_u32("10.0.0.50")
        assert d.siaddr == SERVER_IP
        assert d.chaddr[:6] == mac
        assert d.server_id == SERVER_IP
        assert d.opt(dhcp_codec.OPT_LEASE_TIME) == (3600).to_bytes(4, "big")
        assert d.opt(dhcp_codec.OPT_SUBNET_MASK) == bytes([255, 255, 255, 0])
        assert d.opt(dhcp_codec.OPT_ROUTER) == SERVER_IP.to_bytes(4, "big")
        assert d.opt(dhcp_codec.OPT_DNS) == ip_to_u32("8.8.8.8").to_bytes(4, "big") + ip_to_u32("8.8.4.4").to_bytes(4, "big")
        assert d.opt(dhcp_codec.OPT_RENEWAL_TIME) == (1800).to_bytes(4, "big")
        assert d.opt(dhcp_codec.OPT_REBIND_TIME) == (3150).to_bytes(4, "big")
        assert d.sname == b"" and d.file == b""
        st = np.asarray(res.stats)
        assert st[ST_TOTAL] == 1 and st[ST_HIT] == 1 and st[ST_MISS] == 0
        assert st[ST_BCAST] == 1 and st[ST_UCAST] == 0

    def test_request_gets_ack(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef02")
        t.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.51"), lease_expiry=NOW + 600)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.REQUEST)], t)
        assert bool(res.is_reply[0])
        d = dhcp_codec.decode(packets.decode(reply_bytes(res, 0)).payload)
        assert d.msg_type == dhcp_codec.ACK
        assert d.yiaddr == ip_to_u32("10.0.0.51")

    def test_unknown_mac_passes(self):
        t = make_tables()
        res = run_kernel([dhcp_frame(bytes.fromhex("02000000aa01"), dhcp_codec.DISCOVER)], t)
        assert not bool(res.is_reply[0])
        assert bool(res.is_dhcp[0])
        st = np.asarray(res.stats)
        assert st[ST_MISS] == 1 and st[ST_HIT] == 0

    def test_expired_lease_passes(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef03")
        t.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.52"), lease_expiry=NOW - 1)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.DISCOVER)], t)
        assert not bool(res.is_reply[0])
        assert np.asarray(res.stats)[ST_EXPIRED] == 1

    def test_bad_pool_is_error(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef04")
        t.add_subscriber(mac, pool_id=9, ip=ip_to_u32("10.0.0.53"), lease_expiry=NOW + 600)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.DISCOVER)], t)
        assert not bool(res.is_reply[0])
        assert np.asarray(res.stats)[ST_ERROR] == 1

    def test_non_dhcp_ignored(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef05")
        tcp = packets.tcp_packet(mac, SERVER_MAC, ip_to_u32("10.0.0.5"), ip_to_u32("1.1.1.1"), 1234, 80)
        udp = packets.udp_packet(mac, SERVER_MAC, ip_to_u32("10.0.0.5"), ip_to_u32("1.1.1.1"), 53, 53, b"x")
        res = run_kernel([tcp, udp], t)
        assert not bool(res.is_dhcp[0]) and not bool(res.is_dhcp[1])
        assert np.asarray(res.stats)[ST_TOTAL] == 0

    def test_release_passes_to_slow_path(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef06")
        t.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.54"), lease_expiry=NOW + 600)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.RELEASE)], t)
        assert not bool(res.is_reply[0])
        assert np.asarray(res.stats)[ST_MISS] == 1  # wrong-type counted as miss (:643)


class TestMsgTypeOffsets:
    def test_pad_shifted_option53(self):
        """Option 53 after 1 pad byte is found (offset 1 checked, c:229)."""
        t = make_tables()
        mac = bytes.fromhex("02deadbeef07")
        t.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.55"), lease_expiry=NOW + 600)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.DISCOVER, pad_before_53=1)], t)
        assert bool(res.is_reply[0])

    def test_offset2_not_checked_passes(self):
        """Offset 2 is deliberately NOT in the reference's scan (c:224-246)."""
        t = make_tables()
        mac = bytes.fromhex("02deadbeef08")
        t.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.56"), lease_expiry=NOW + 600)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.DISCOVER, pad_before_53=2)], t)
        assert not bool(res.is_reply[0])  # slow path, like the reference


class TestVLAN:
    def test_single_tag_vlan_lookup_and_reply(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef09")
        t.add_vlan_subscriber(s_tag=100, c_tag=0, pool_id=1,
                              ip=ip_to_u32("10.0.0.60"), lease_expiry=NOW + 600)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.DISCOVER, vlans=[100])], t)
        assert bool(res.is_reply[0])
        dec = packets.decode(reply_bytes(res, 0))
        assert dec.vlans == [100], "VLAN tag must be preserved in reply"
        d = dhcp_codec.decode(dec.payload)
        assert d.yiaddr == ip_to_u32("10.0.0.60")
        assert np.asarray(res.stats)[ST_VLAN] == 1

    def test_qinq_lookup_and_reply(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef0a")
        t.add_vlan_subscriber(s_tag=200, c_tag=31, pool_id=1,
                              ip=ip_to_u32("10.0.0.61"), lease_expiry=NOW + 600)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.DISCOVER, vlans=[200, 31])], t)
        assert bool(res.is_reply[0])
        dec = packets.decode(reply_bytes(res, 0))
        assert dec.vlans == [200, 31]
        assert dhcp_codec.decode(dec.payload).yiaddr == ip_to_u32("10.0.0.61")

    def test_vlan_miss_falls_back_to_mac(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef0b")
        t.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.62"), lease_expiry=NOW + 600)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.DISCOVER, vlans=[999])], t)
        assert bool(res.is_reply[0])
        assert dhcp_codec.decode(packets.decode(reply_bytes(res, 0)).payload).yiaddr == ip_to_u32("10.0.0.62")


class TestOption82:
    def test_circuit_id_lookup(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef0c")
        t.add_circuit_id_subscriber(b"olt1/slot2/port3", pool_id=1,
                                    ip=ip_to_u32("10.0.0.70"), lease_expiry=NOW + 600)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.DISCOVER, circuit_id=b"olt1/slot2/port3")], t)
        assert bool(res.is_reply[0])
        d = dhcp_codec.decode(packets.decode(reply_bytes(res, 0)).payload)
        assert d.yiaddr == ip_to_u32("10.0.0.70")
        assert np.asarray(res.stats)[ST_OPT82_PRESENT] == 1


class TestRelayAndUnicast:
    def test_relayed_reply_unicast_to_giaddr(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef0d")
        t.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.80"), lease_expiry=NOW + 600)
        relay_ip = ip_to_u32("10.9.9.9")
        frame = dhcp_frame(mac, dhcp_codec.DISCOVER, giaddr=relay_ip)
        res = run_kernel([frame], t)
        assert bool(res.is_reply[0])
        dec = packets.decode(reply_bytes(res, 0))
        assert dec.dst_mac == mac  # relay's MAC = requester frame's src MAC (:729)
        assert dec.dst_ip == relay_ip
        assert dec.src_port == 67 and dec.dst_port == 67  # :739-740
        assert dec.ip_checksum_ok
        d = dhcp_codec.decode(dec.payload)
        assert d.giaddr == relay_ip  # giaddr preserved

    def test_renewing_client_gets_l2_unicast(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef0e")
        t.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.81"), lease_expiry=NOW + 600)
        frame = dhcp_frame(mac, dhcp_codec.REQUEST, ciaddr=ip_to_u32("10.0.0.81"),
                           src_ip=ip_to_u32("10.0.0.81"))
        res = run_kernel([frame], t)
        assert bool(res.is_reply[0])
        dec = packets.decode(reply_bytes(res, 0))
        assert dec.dst_mac == mac  # ciaddr set + no bcast flag -> unicast (:462)
        assert np.asarray(res.stats)[ST_UCAST] == 1

    def test_broadcast_flag_forces_broadcast(self):
        t = make_tables()
        mac = bytes.fromhex("02deadbeef0f")
        t.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.82"), lease_expiry=NOW + 600)
        frame = dhcp_frame(mac, dhcp_codec.REQUEST, ciaddr=ip_to_u32("10.0.0.82"),
                           broadcast=True, src_ip=ip_to_u32("10.0.0.82"))
        res = run_kernel([frame], t)
        dec = packets.decode(reply_bytes(res, 0))
        assert dec.dst_mac == BCAST_MAC


class TestDNSVariants:
    @pytest.mark.parametrize("dns1,dns2,expect", [
        (0, 0, None),
        (ip_to_u32("9.9.9.9"), 0, ip_to_u32("9.9.9.9").to_bytes(4, "big")),
        (ip_to_u32("9.9.9.9"), ip_to_u32("1.1.1.1"),
         ip_to_u32("9.9.9.9").to_bytes(4, "big") + ip_to_u32("1.1.1.1").to_bytes(4, "big")),
    ])
    def test_dns_layout(self, dns1, dns2, expect):
        t = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64, cid_nbuckets=64, max_pools=16)
        t.set_server_config(SERVER_MAC, SERVER_IP)
        t.add_pool(1, network=ip_to_u32("10.0.0.0"), prefix_len=24,
                   gateway=ip_to_u32("10.0.0.1"), dns_primary=dns1, dns_secondary=dns2,
                   lease_time=7200)
        mac = bytes.fromhex("02deadbe1f01")
        t.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.90"), lease_expiry=NOW + 600)
        res = run_kernel([dhcp_frame(mac, dhcp_codec.DISCOVER)], t)
        assert bool(res.is_reply[0])
        d = dhcp_codec.decode(packets.decode(reply_bytes(res, 0)).payload)
        assert d.opt(dhcp_codec.OPT_DNS) == expect
        # options after the DNS shift must still be intact
        assert d.opt(dhcp_codec.OPT_RENEWAL_TIME) == (3600).to_bytes(4, "big")
        assert d.opt(dhcp_codec.OPT_REBIND_TIME) == (6300).to_bytes(4, "big")


@pytest.mark.hotpath
class TestBatch:
    def test_mixed_batch(self):
        t = make_tables()
        known = bytes.fromhex("02deadbe2f01")
        t.add_subscriber(known, pool_id=1, ip=ip_to_u32("10.0.0.100"), lease_expiry=NOW + 600)
        frames = [
            dhcp_frame(known, dhcp_codec.DISCOVER),
            dhcp_frame(bytes.fromhex("020000000001"), dhcp_codec.DISCOVER),  # miss
            packets.tcp_packet(known, SERVER_MAC, ip_to_u32("10.0.0.5"), ip_to_u32("1.1.1.1"), 1, 2),
            dhcp_frame(known, dhcp_codec.REQUEST),
        ]
        res = run_kernel(frames, t)
        assert np.asarray(res.is_reply)[:4].tolist() == [True, False, False, True]
        st = np.asarray(res.stats)
        assert st[ST_TOTAL] == 3 and st[ST_HIT] == 2 and st[ST_MISS] == 1


# --- reply bytes against a plain numpy reference -------------------------
# The reference below shares nothing with ops/dhcp.py: it builds the
# canonical (untagged) reply with struct, then reinserts the request's
# VLAN tags the way the kernel always has, one byte index a column:
# np.take_along_axis by clip(j - vlan_offset, 0, L-1). The kernel's
# compose (selects over statically shifted copies) must give the same
# bytes, and zeros from out_len to the slot's end.

import struct

_DNS_POOLS = {  # pool_id -> (dns1, dns2)
    0: (0, 0),
    1: (ip_to_u32("9.9.9.9"), 0),
    2: (ip_to_u32("9.9.9.9"), ip_to_u32("1.1.1.1")),
}
_TAGS = {"untagged": None, "dot1q": [100], "qinq": [200, 31]}
_MODES = ("broadcast", "relayed", "ciaddr_unicast")
_RELAY_IP = ip_to_u32("10.9.9.9")


def _ip_csum(hdr: bytes) -> int:
    s = sum(struct.unpack("!10H", hdr))
    while s >> 16:
        s = (s & 0xFFFF) + (s >> 16)
    return ~s & 0xFFFF


def _ref_canonical(frame: bytes, yiaddr: int, pool: dict) -> tuple[bytes, int]:
    """(canonical untagged reply, vlan_offset) for one request frame."""
    vo = 0
    if frame[12:14] in (b"\x81\x00", b"\x88\xa8"):
        vo = 8 if frame[16:18] == b"\x81\x00" else 4
    d = 14 + vo + 20 + 8  # BOOTP start (IHL is 5 in every test frame)
    xid, secs, flags = frame[d + 4:d + 8], frame[d + 8:d + 10], frame[d + 10:d + 12]
    ciaddr, giaddr, chaddr = frame[d + 12:d + 16], frame[d + 24:d + 28], frame[d + 28:d + 44]
    mtype = frame[d + 240 + 2]  # option 53 leads in every test frame
    relayed = giaddr != b"\0\0\0\0"
    bcast = not relayed and (flags[0] & 0x80 or ciaddr == b"\0\0\0\0")
    dst_mac = frame[6:12] if relayed else BCAST_MAC if bcast else chaddr[:6]
    server = struct.pack("!I", SERVER_IP)
    lease = pool["lease"]
    opts = bytes([53, 1, dhcp_codec.OFFER if mtype == dhcp_codec.DISCOVER else dhcp_codec.ACK])
    opts += bytes([54, 4]) + server + bytes([51, 4]) + struct.pack("!I", lease)
    opts += bytes([1, 4]) + struct.pack("!I", (0xFFFFFFFF << (32 - pool["prefix"])) & 0xFFFFFFFF)
    opts += bytes([3, 4]) + struct.pack("!I", pool["gateway"])
    servers = [a for a in pool["dns"] if a]
    if servers:
        opts += bytes([6, 4 * len(servers)]) + b"".join(struct.pack("!I", a) for a in servers)
    opts += bytes([58, 4]) + struct.pack("!I", lease // 2)
    opts += bytes([59, 4]) + struct.pack("!I", lease * 7 // 8) + b"\xff"
    bootp = (bytes([2, 1, 6, 0]) + xid + secs + flags + ciaddr + struct.pack("!I", yiaddr)
             + server + giaddr + chaddr + bytes(192) + struct.pack("!I", 0x63825363) + opts)
    udp = struct.pack("!HHHH", 67, 67 if relayed else 68, 8 + len(bootp), 0)
    ip = struct.pack("!BBHHHBBH", 0x45, 0, 20 + len(udp) + len(bootp), 0, 0, 64, 17, 0)
    ip += server + (giaddr if relayed else b"\xff" * 4)
    ip = ip[:10] + struct.pack("!H", _ip_csum(ip)) + ip[12:]
    return dst_mac + SERVER_MAC + b"\x08\x00" + ip + udp + bootp, vo


def _ref_out(frames, canon, vo, slot):
    """Today's VLAN reinsertion over a [n, slot] batch, in numpy."""
    n = len(frames)
    pkt = np.zeros((n, slot), np.uint8)
    canon_l = np.zeros((n, slot), np.uint8)
    for i, (f, c) in enumerate(zip(frames, canon)):
        pkt[i, :len(f)] = np.frombuffer(f, np.uint8)
        canon_l[i, :len(c)] = np.frombuffer(c, np.uint8)
    j = np.arange(slot)[None, :]
    v = np.asarray(vo)[:, None]
    shifted = np.take_along_axis(canon_l, np.clip(j - v, 0, slot - 1), axis=1)
    out = np.where(j < 12, canon_l, np.where(j < 14 + v, pkt, shifted))
    out_len = np.array([len(c) for c in canon])[:, None] + v
    return np.where(j < out_len, out, 0).astype(np.uint8), out_len[:, 0]


@functools.lru_cache(maxsize=1)
def _parity_tables():
    t = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64, cid_nbuckets=64, max_pools=16)
    t.set_server_config(SERVER_MAC, SERVER_IP)
    pools = {}
    for pid, (d1, d2) in _DNS_POOLS.items():
        pools[pid] = dict(prefix=20 + pid, gateway=ip_to_u32(f"10.{pid}.0.1"),
                          dns=(d1, d2), lease=3600 * (pid + 1))
        t.add_pool(pid, network=ip_to_u32(f"10.{pid}.0.0"), prefix_len=20 + pid,
                   gateway=pools[pid]["gateway"], dns_primary=d1, dns_secondary=d2,
                   lease_time=pools[pid]["lease"])
    return t, pools


def _junk_frames():
    rng = np.random.default_rng(26)
    junk = []
    for i in range(3):
        b = bytearray(rng.integers(0, 256, 90 + 130 * i, dtype=np.uint8).tobytes())
        if i == 1:
            b[12:14] = b"\x81\x00"
        if i == 2:
            b[12:14], b[16:18] = b"\x88\xa8", b"\x81\x00"
        junk.append(bytes(b))
    junk.append(packets.tcp_packet(bytes.fromhex("020000000001"), SERVER_MAC,
                                   ip_to_u32("10.0.0.5"), ip_to_u32("1.1.1.1"), 1, 2))
    return junk


@pytest.mark.parametrize("slot", [512, 1536])
@pytest.mark.parametrize("mode", _MODES)
@pytest.mark.parametrize("n_dns", [0, 1, 2])
@pytest.mark.parametrize("tags", list(_TAGS))
def test_reply_bytes_match_numpy_reference(tags, n_dns, mode, slot):
    t, pools = _parity_tables()
    pool = pools[n_dns]
    tag_no = list(_TAGS).index(tags)
    mode_no = _MODES.index(mode)
    frames, yiaddrs = [], []
    for k, mtype in enumerate((dhcp_codec.DISCOVER, dhcp_codec.REQUEST)):
        mac = bytes([2, 0x26, tag_no, n_dns, mode_no, k])
        ip = ip_to_u32(f"10.{n_dns}.{1 + tag_no}.{10 + 2 * mode_no + k}")
        t.add_subscriber(mac, pool_id=n_dns, ip=ip, lease_expiry=NOW + 600)
        kw = {}
        if mode == "relayed":
            kw = dict(giaddr=_RELAY_IP)
        elif mode == "ciaddr_unicast":
            kw = dict(ciaddr=ip, src_ip=ip)
        elif k:  # broadcast: a DISCOVER with no ciaddr, a REQUEST with the flag
            kw = dict(ciaddr=ip, src_ip=ip, broadcast=True)
        frames.append(dhcp_frame(mac, mtype, vlans=_TAGS[tags], **kw))
        yiaddrs.append(ip)
    junk = _junk_frames()

    pkt = np.zeros((B, slot), dtype=np.uint8)
    length = np.zeros((B,), dtype=np.uint32)
    for i, f in enumerate(frames + junk):
        pkt[i, :len(f)] = np.frombuffer(f, dtype=np.uint8)
        length[i] = len(f)
    res = _jitted(t.geom)(jnp.asarray(pkt), jnp.asarray(length), t.device_tables(),
                          jnp.uint32(NOW))

    canon, vo = zip(*(_ref_canonical(f, ip, pool) for f, ip in zip(frames, yiaddrs)))
    assert vo == ({"untagged": 0, "dot1q": 4, "qinq": 8}[tags],) * 2
    want, want_len = _ref_out(frames, canon, vo, slot)
    got, got_len = np.asarray(res.out_pkt), np.asarray(res.out_len)
    n = len(frames)
    assert np.asarray(res.is_reply)[:n].all()
    assert got_len[:n].tolist() == want_len.tolist()
    assert got.shape == (B, slot)
    for i in range(n):
        assert bytes(got[i]) == bytes(want[i]), f"lane {i} differs from the reference"
        assert not got[i, got_len[i]:].any(), "bytes beyond out_len must be zero"
        # the host parser reads what the reference built
        dec = packets.decode(bytes(got[i, :got_len[i]]))
        assert dec.vlans == (_TAGS[tags] or []) and dec.ip_checksum_ok
        assert dhcp_codec.decode(dec.payload).yiaddr == yiaddrs[i]
    # junk and data lanes in the same batch (and the empty lanes) answer nothing
    assert not np.asarray(res.is_reply)[n:].any()
    assert not got_len[n:].any()
