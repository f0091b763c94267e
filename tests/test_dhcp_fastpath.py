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


# ---------------------------------------------------------------------------
# The request read through one statically aligned window (ISSUE 31): the
# parent addressed every request byte with a per-lane gather at
# `dhcp_off + n`. Its reads are kept here, verbatim, as the plain
# reference: `_parent_extract_msg_type`, `_parent_extract_circuit_id`
# and `_parent_header_reads`. The kernel must give the same (found, cid),
# echo the header fields the parent read, and, against itself run over the
# parent's addressing (`window_at` swapped for the per-lane gather, which reads
# `pkt[clip(dhcp_off + j)]` on EVERY lane, whatever its base), the same
# five outputs; `pipeline_step` the same packet, length and verdict on
# every lane.

from bng_tpu.ops import bytes as B_
from bng_tpu.ops import dhcp as dhcp_mod
from bng_tpu.ops.dhcp import CID_KEY_LEN, REQ_BASES, REQ_WIN


def _parent_extract_msg_type(pkt, opts_off, opts_in_bounds):
    found = jnp.zeros_like(opts_in_bounds)
    mtype = jnp.zeros(pkt.shape[0], dtype=jnp.uint32)
    for o in (0, 1, 3, 4, 5, 6):
        ok = (B_.u8_at(pkt, opts_off + o) == 53) & (B_.u8_at(pkt, opts_off + o + 1) == 1)
        take = ok & ~found & opts_in_bounds
        mtype = jnp.where(take, B_.u8_at(pkt, opts_off + o + 2), mtype)
        found = found | take
    return jnp.where(opts_in_bounds, mtype, 0)


def _parent_extract_circuit_id(pkt, opts_off, length):
    Bsz = pkt.shape[0]
    scan_ok = (opts_off.astype(jnp.uint32) + 64) <= length

    found = jnp.zeros((Bsz,), dtype=bool)
    cid = jnp.zeros((Bsz, CID_KEY_LEN), dtype=jnp.uint8)

    def try_pos(found, cid, tag_off, len_off, sub_off, cidlen_off, cid_off, extra_ok):
        tag = B_.u8_at(pkt, opts_off + tag_off)
        o82len = B_.u8_at(pkt, opts_off + len_off)
        sub1 = B_.u8_at(pkt, opts_off + sub_off)
        cl = B_.u8_at(pkt, opts_off + cidlen_off)
        in_b = (opts_off.astype(jnp.uint32) + cid_off + cl) <= length
        ok = (
            scan_ok & extra_ok & (tag == 82) & (o82len >= 4) & (sub1 == 1)
            & (cl > 0) & (cl <= CID_KEY_LEN) & in_b & ~found
        )
        raw = B_.bytes_at(pkt, opts_off + cid_off, CID_KEY_LEN)  # [B, 32]
        mask = jnp.arange(CID_KEY_LEN)[None, :] < cl[:, None]
        cand = jnp.where(mask, raw, 0)
        cid = jnp.where(ok[:, None], cand, cid)
        return found | ok, cid

    o82len_a = B_.u8_at(pkt, opts_off + 4)
    a_extra = (opts_off.astype(jnp.uint32) + 5 + o82len_a) <= length
    found, cid = try_pos(found, cid, 3, 4, 5, 6, 7, a_extra)
    for p in range(12, 20):
        p_extra = (opts_off.astype(jnp.uint32) + p + 8) <= length
        found, cid = try_pos(found, cid, p, p + 1, p + 2, p + 3, p + 4, p_extra)
    return found, cid


def _parent_header_reads(pkt, dhcp_off):
    return dict(
        op=B_.u8_at(pkt, dhcp_off),
        magic=B_.be32_at(pkt, dhcp_off + 236),
        mac_hi=B_.be16_at(pkt, dhcp_off + 28),
        mac_lo=B_.be32_at(pkt, dhcp_off + 30),
        xid_b=B_.bytes_at(pkt, dhcp_off + 4, 4),
        secs_b=B_.bytes_at(pkt, dhcp_off + 8, 2),
        flags=B_.be16_at(pkt, dhcp_off + 10),
        ciaddr=B_.be32_at(pkt, dhcp_off + 12),
        giaddr=B_.be32_at(pkt, dhcp_off + 24),
        chaddr_b=B_.bytes_at(pkt, dhcp_off + 28, 16),
        giaddr_b=B_.bytes_at(pkt, dhcp_off + 24, 4),
        req_src=B_.bytes_at(pkt, jnp.zeros_like(dhcp_off) + 6, 6),
    )


def _parent_addressing(fn):
    """`fn` traced with `window_at`'s contract met the parent's way: one
    byte an index (the kernel reads it through the module, at trace time)."""
    def traced(*args):
        real = B_.window_at
        B_.window_at = lambda pkt, offs, bases, n: B_.bytes_at(pkt, offs, n)
        try:
            return fn(*args)
        finally:
            B_.window_at = real
    return jax.jit(traced)


@functools.lru_cache(maxsize=4)
def _old_and_new(geom):
    """One program holding both sides: the reads, and the kernel over the
    window and over the parent's addressing."""

    def reads(pkt, length):
        parsed = parse_batch(pkt, length)
        dhcp_off = parsed.l4_off + 8
        opts_off = dhcp_off + 240
        in_b = (opts_off.astype(jnp.uint32) + 12) <= length
        opts = B_.window_at(pkt, dhcp_off, REQ_BASES, REQ_WIN)[:, 240:]
        new = dict(mtype=dhcp_mod._extract_msg_type(opts, in_b),
                   cid=dhcp_mod._extract_circuit_id(opts, opts_off, length.astype(jnp.uint32)))
        old = dict(_parent_header_reads(pkt, dhcp_off),
                   mtype=_parent_extract_msg_type(pkt, opts_off, in_b),
                   cid=_parent_extract_circuit_id(pkt, opts_off, length.astype(jnp.uint32)))
        return dhcp_off, new, old

    def kernel(pkt, length, dev_tables, now):
        return dhcp_fastpath(pkt, length, parse_batch(pkt, length), dev_tables, geom, now)

    return jax.jit(reads), jax.jit(kernel), _parent_addressing(kernel)


_M_TAGS = {0: None, 4: [100], 8: [200, 31]}
_M_BASES = {}  # base -> (tag bytes, ihl): every way the matrix reaches it
for _vo in (0, 4, 8):
    for _ihl in (20, 24, 40, 60):
        _M_BASES.setdefault(14 + _vo + _ihl + 8, (_vo, _ihl))
for _ihl in range(20, 64, 4):  # the bases IHL 5, 6, 10, 15 leave out
    for _vo in (0, 4, 8):
        _M_BASES.setdefault(14 + _vo + _ihl + 8, (_vo, _ihl))
assert sorted(_M_BASES) == list(REQ_BASES)
_M_POSITIONS = (3,) + tuple(range(12, 20))  # tag at opts+3 (position A), 12..19
_M_CID_LENS = (1, 16, 32, 33, 0)  # the last two are refused
_M_CUTS = ("whole", "o82len_lies", "scan_exact", "scan_ok", "extra", "in_b",
           "opts_in_bounds", "hdr_in_bounds")
_M_SLOTS = (512, 1536, 384)  # 384 < 90 + 304: the window runs past the slot
_M_LANES = 64


def _ip_options(frame: bytes, vo: int, ihl: int) -> bytes:
    """Grow the frame's IPv4 header to `ihl` bytes with NOP options (the
    header checksum goes stale: neither the kernel nor the reference's
    parser verifies it)."""
    l3, extra = 14 + vo, ihl - 20
    b = bytearray(frame)
    b[l3] = 0x40 | (ihl // 4)
    b[l3 + 2:l3 + 4] = (int.from_bytes(b[l3 + 2:l3 + 4], "big") + extra).to_bytes(2, "big")
    b[l3 + 20:l3 + 20] = b"\x01" * extra
    return bytes(b)


def _matrix_frame(mac, mtype, vo, ihl, pos, cl, cid, o82len=None):
    """A relayed-looking request with Option 82's tag at opts+`pos`."""
    hdr = dhcp_codec.build_request(mac, mtype).encode()[:240]
    opts = bytes([53, 1, mtype])
    if pos != 3:  # a client-id fills up to the tag: [61][n][...]
        n = pos - 5
        opts += bytes([61, n]) + bytes(range(1, n + 1))
    rid = bytes([2, 4]) + b"RMID"  # a remote-id follows, as relays send it
    opts += bytes([82, 2 + len(cid) + len(rid) if o82len is None else o82len, 1, cl]) + cid + rid
    payload = (hdr + opts + b"\xff").ljust(320, b"\x00")
    f = packets.udp_packet(src_mac=mac, dst_mac=BCAST_MAC, src_ip=0, dst_ip=0xFFFFFFFF,
                           src_port=68, dst_port=67, payload=payload, vlans=_M_TAGS[vo])
    return _ip_options(f, vo, ihl)


def _matrix_cut(cut, whole, opts_off, pos, cl, o82len):
    cid_off = pos + 4
    return {
        "whole": whole, "o82len_lies": whole,
        "scan_exact": opts_off + 64,  # the shortest frame both scans accept
        "scan_ok": opts_off + 63,
        # a_extra (position A) / p_extra, each one byte short
        "extra": opts_off + (5 + o82len if pos == 3 else pos + 8) - 1,
        "in_b": opts_off + cid_off + cl - 1,
        "opts_in_bounds": opts_off + 11,
        "hdr_in_bounds": opts_off - 1,
    }[cut]


def _matrix_junk(slot):
    """Lanes that are no DHCP request: on these `base` must mask whatever
    the window holds (their `dhcp_off` may be none of REQ_BASES)."""
    mac = bytes.fromhex("02310000ee01")
    good = _matrix_frame(mac, dhcp_codec.DISCOVER, 4, 24, 3, 8, b"junklane")
    short_ihl = bytearray(good)
    short_ihl[14 + 4] = 0x43  # ihl 12 < 20: not IPv4 to parse_batch
    v6 = bytearray(good)
    v6[12 + 4:14 + 4] = b"\x86\xdd"
    arp = bytearray(good)
    arp[12 + 4:14 + 4] = b"\x08\x06"
    rng = np.random.default_rng(31)
    rnd = [bytes(rng.integers(0, 256, n, dtype=np.uint8)) for n in (60, 200, 380)]
    tcp = packets.tcp_packet(mac, SERVER_MAC, ip_to_u32("10.0.0.5"), ip_to_u32("1.1.1.1"), 1, 2)
    lanes = [(bytes(short_ihl), None), (bytes(v6), None), (bytes(arp), None), (tcp, None)]
    lanes += [(r, None) for r in rnd]
    lanes.append((good, 0))  # an inert lane: bytes of a request, length 0
    lanes.append((good, slot + 200))  # a length that lies past the slot
    return lanes


def _matrix_lanes(base, pos):
    """(lane, cut, cl, mac, cid) of one base and one Option 82 position."""
    for lane, (cl, cut) in enumerate((cl, cut) for cl in _M_CID_LENS for cut in _M_CUTS):
        yield (lane, cut, cl, bytes([2, 0x31, base, pos, cl, _M_CUTS.index(cut)]),
               bytes([0x40 + (base + pos + j) % 64 for j in range(cl)]))


def _matrix_batch(slot, base, picks):
    """(pkt, length, expect) at one base: the picked (pos, matrix lane)
    pairs, then the junk lanes."""
    vo, ihl = _M_BASES[base]
    opts_off = base + 240
    pkt = np.zeros((_M_LANES, slot), dtype=np.uint8)
    length = np.zeros((_M_LANES,), dtype=np.uint32)
    expect = []  # (lane, cut, cl, cid, the address its MAC holds)
    for lane, (pos, (mlane, cut, cl, mac, cid)) in enumerate(picks):
        mtype = dhcp_codec.REQUEST if lane % 2 else dhcp_codec.DISCOVER
        lies = 120 if cut == "o82len_lies" else None
        f = _matrix_frame(mac, mtype, vo, ihl, pos, cl, cid, o82len=lies)
        n = min(_matrix_cut(cut, len(f), opts_off, pos, cl, 2 + cl + 6), slot)
        pkt[lane, :min(len(f), slot)] = np.frombuffer(f[:slot], dtype=np.uint8)
        length[lane] = n
        expect.append((lane, cut, cl, cid, ip_to_u32(f"10.0.0.{10 + mlane}")))
    lane = len(expect)
    for f, n in _matrix_junk(slot):
        pkt[lane, :min(len(f), slot)] = np.frombuffer(f[:slot], dtype=np.uint8)
        length[lane] = min(len(f), slot) if n is None else n
        lane += 1
    assert lane <= _M_LANES
    return pkt, length, expect


def _matrix_provision(t):
    """Every matrix lane's MAC with an address of its own, and every
    circuit-ID the kernel may accept with another."""
    t.add_pool(1, network=ip_to_u32("10.0.0.0"), prefix_len=24, gateway=ip_to_u32("10.0.0.1"),
               dns_primary=ip_to_u32("8.8.8.8"), lease_time=3600)
    cids = set()
    for base in REQ_BASES:
        for pos in _M_POSITIONS:
            for lane, _, cl, mac, cid in _matrix_lanes(base, pos):
                t.add_subscriber(mac, pool_id=1, ip=ip_to_u32(f"10.0.0.{10 + lane}"),
                                 lease_expiry=NOW + 600)
                if cl in (1, 16, 32):
                    cids.add(cid)
    for cid in sorted(cids):
        t.add_circuit_id_subscriber(cid, pool_id=1, ip=ip_to_u32("10.0.0.200"),
                                    lease_expiry=NOW + 600)
    return t


@functools.lru_cache(maxsize=1)
def _matrix_tables():
    t = FastPathTables(sub_nbuckets=4096, vlan_nbuckets=64, cid_nbuckets=256, max_pools=16)
    t.set_server_config(SERVER_MAC, SERVER_IP)
    return _matrix_provision(t)


# where the canonical reply echoes a request field (ops/dhcp.py's compose)
_ECHOED = dict(xid_b=46, secs_b=50, flags=52, ciaddr=54, giaddr_b=66, chaddr_b=70)
_ECHO_WORDS = dict(flags=2, ciaddr=4)


def _same(a, b, what, lanes=slice(None)):
    a, b = np.asarray(a)[lanes], np.asarray(b)[lanes]
    assert a.shape == b.shape and a.dtype == b.dtype, what
    bad = np.argwhere(a != b)
    assert not len(bad), f"{what}: old and new differ at {bad[:4].tolist()}"


@pytest.mark.parametrize("pos", _M_POSITIONS)
@pytest.mark.parametrize("base", REQ_BASES)
@pytest.mark.parametrize("slot", _M_SLOTS)
def test_request_window_matches_parent_reads(slot, base, pos):
    t = _matrix_tables()
    pkt, length, expect = _matrix_batch(slot, base, [(pos, m) for m in _matrix_lanes(base, pos)])
    reads, kernel, kernel_parent = _old_and_new(t.geom)
    args = (jnp.asarray(pkt), jnp.asarray(length))
    dhcp_off, new, old = reads(*args)

    # the matrix lanes sit at this base; the junk lanes where they fall
    dhcp_off = np.asarray(dhcp_off)
    n = len(expect)
    assert (dhcp_off[:n] == base).all()
    at_base = np.isin(dhcp_off, REQ_BASES)
    assert not at_base.all(), "no junk lane falls off the static bases"
    _same(new["mtype"], old["mtype"], "mtype", at_base)
    _same(new["cid"][0], old["cid"][0], "cid found", at_base)
    _same(new["cid"][1], old["cid"][1], "cid bytes", at_base)

    # the matrix is not vacuous: what the reference accepts is accepted
    found, cid = (np.asarray(x) for x in new["cid"])
    opts_off, vo = base + 240, _M_BASES[base][0]
    for lane, cut, cl, want, _ in expect:
        fits = opts_off + 64 <= length[lane]
        ok = fits and cl in (1, 16, 32) and cut not in ("extra", "in_b") and not (
            cut == "o82len_lies" and pos == 3)
        assert bool(found[lane]) == ok, (lane, cut, cl)
        if ok:
            assert bytes(cid[lane]) == want.ljust(32, b"\0")

    dev = t.device_tables()
    res = kernel(*args, dev, jnp.uint32(NOW))
    ref = kernel_parent(*args, dev, jnp.uint32(NOW))
    for name in ("is_reply", "is_dhcp", "out_len", "stats"):
        _same(getattr(res, name), getattr(ref, name), name)
    replied = np.asarray(ref.is_reply)
    _same(res.out_pkt, ref.out_pkt, "out_pkt", replied)
    assert not replied[n:].any(), "a junk lane answered"
    # a found circuit-ID answers from the circuit-ID table, the rest by MAC
    got_len = np.asarray(res.out_len)
    for lane, cut, cl, _, mac_ip in expect:
        assert replied[lane] == (opts_off + 12 <= length[lane]), (lane, cut, cl)
        if not replied[lane]:
            continue
        raw = bytes(np.asarray(res.out_pkt[lane, :got_len[lane]]))
        d = dhcp_codec.decode(packets.decode(raw).payload)
        assert d.yiaddr == (ip_to_u32("10.0.0.200") if found[lane] else mac_ip)
        # the reply echoes the header fields as the parent read them
        for k, at in _ECHOED.items():
            want = np.asarray(old[k][lane])
            want = bytes(want) if want.ndim else int(want).to_bytes(_ECHO_WORDS[k], "big")
            assert raw[at + vo:at + vo + len(want)] == want, (lane, k)


@functools.lru_cache(maxsize=3)
def _matrix_pipeline(slot):
    """The fused step's stages over an engine that holds the matrix's
    subscribers: (tables, step, the step over the parent's addressing)."""
    from bng_tpu.ops.pipeline import pipeline_step
    from bng_tpu.runtime import verify

    eng = verify._engine(verify.Geometry(batch=_M_LANES, pkt_slot=slot, sub_nbuckets=4096))
    _matrix_provision(eng.fastpath)

    def step(tables, pkt, length):
        r = pipeline_step(tables, pkt, length, jnp.ones(pkt.shape[:1], dtype=bool), eng.geom,
                          jnp.uint32(NOW), jnp.uint32(1))
        return r.verdict, r.out_pkt, r.out_len, r.dhcp_stats

    return eng._device_tables(), jax.jit(step), _parent_addressing(step)


@pytest.mark.parametrize("base", REQ_BASES)
@pytest.mark.parametrize("slot", _M_SLOTS)
def test_pipeline_step_matches_parent_addressing(slot, base):
    """Every lane of the fused step: packet, length and verdict."""
    from bng_tpu.ops.pipeline import VERDICT_TX

    picks = [(pos, m) for pos in _M_POSITIONS for m in _matrix_lanes(base, pos)
             if (m[2] in (1, 32, 33) and m[1] == "whole") or (m[2] == 16 and m[1] == "scan_ok")]
    pkt, length, expect = _matrix_batch(slot, base, picks)
    tables, step, step_parent = _matrix_pipeline(slot)
    got = step(tables, jnp.asarray(pkt), jnp.asarray(length))
    want = step_parent(tables, jnp.asarray(pkt), jnp.asarray(length))
    for name, a, b in zip(("verdict", "out_pkt", "out_len", "dhcp_stats"), got, want):
        _same(a, b, name)
    verdict = np.asarray(got[0])
    n = len(expect)
    assert (verdict[:n] == VERDICT_TX).all() and not (verdict[n:] == VERDICT_TX).any()


# ---- the lowered programs hold no request-byte gather (ISSUE 31) ----------

import re

_GATHER = r'"stablehlo\.gather"[^\n]*-> tensor<([0-9x]+)x(\w+)>'


def _byte_gathers(hlo):
    """Result shapes of the `ui8` gathers in a lowered program's text."""
    gathers = re.findall(_GATHER, hlo)
    assert gathers, "the pattern no longer finds the gathers"
    return gathers, sorted(dims for dims, ty in gathers if ty == "ui8")


def _lowered(program):
    from bng_tpu.runtime import verify

    if program == "sharded4_step":
        from jax.sharding import Mesh
        from bng_tpu.parallel.sharded import AXIS
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices (conftest gives the CPU backend 8)")
        fn, args = verify.build_sharded(Mesh(np.array(jax.devices()[:4]), (AXIS,)))
    else:
        fn, args = {"fused_step": verify.build_pipeline,
                    "dhcp_only_step": verify.build_dhcp_express}[program]()
    return fn.lower(*args).as_text()


@pytest.mark.parametrize("program", ["fused_step", "dhcp_only_step", "sharded4_step"])
def test_no_program_gathers_request_bytes(program):
    """The nine `[B, 32]` circuit-ID gathers were 24.9 ms of a 58.4 ms fused
    step on a v5e (PERF.md section 6, PR 31). What byte gathers stay are
    parse_batch's single bytes and antispoof's 16-byte IPv6 source, whose
    bases are free."""
    _, byte_dims = _byte_gathers(_lowered(program))
    cols = [int(d.split("x")[-1]) if "x" in d else 1 for d in byte_dims]
    assert CID_KEY_LEN not in cols and max(cols) <= 16, byte_dims


def test_dhcp_fastpath_gathers_only_tables():
    """`dhcp_fastpath` alone, `Parsed` handed in: 16 gathers, every one a
    `ui32` table read (the three cuckoo lookups with their stashes, the
    pool row, the server words). The parent lowered 22, six of them `ui8`
    reads of the request."""
    from bng_tpu.runtime import verify

    fp = verify._fastpath(verify.TOY)
    pkt = jnp.zeros((256, L), dtype=jnp.uint8)
    length = jnp.full((256,), 300, dtype=jnp.uint32)
    parsed = jax.eval_shape(parse_batch, pkt, length)
    hlo = jax.jit(
        lambda t, pkt, length, par: dhcp_fastpath(pkt, length, par, t, fp.geom, jnp.uint32(1))
    ).lower(fp.device_tables(), pkt, length, parsed).as_text()
    gathers, byte_dims = _byte_gathers(hlo)
    assert not byte_dims, f"request bytes gathered again: {byte_dims}"
    assert len(gathers) == 16, f"{len(gathers)} gathers in dhcp_fastpath (16 since PR 31)"
