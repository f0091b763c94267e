"""The dual-stack kit: IPoE subscribers with IPv4 behind CGNAT and IPv6
native, each holding one IA_NA /128.

The default kit's layout, and for every subscriber an IPv6 address
(`2001:db8:1::` + index + 1, inside the program's `AddressPool6`): in its
antispoof binding row beside the v4 address (strict, both families), in the
device's by-address table, and as a committed lease in the host
`DHCPv6Server`, so that the once-a-minute expiry sweep walks what a
deployment's would. Leases are published by provisioning, as the default
kit publishes DHCP bindings without a DORA.

Traffic is the default kit's mix with the kit's framing: `v6_data_share_pct`
of the data frames are IPv6, the rest the default kit's IPv4 (SNAT up, DNAT
down). Each family is half upstream, half the matching downstream. The v6
frames are the smallest that hold the headers and the harness's 4-byte
frame id: 66 bytes UDP and 78 bytes TCP, alternating by subscriber, port
443, no extension header, untagged; upstream from the subscriber's MAC and
bound address to a peer in 2001:db8:ffff::/48, downstream the reverse from
the core side; subscribers drawn uniformly over all of them. No DHCPv6, RS,
NS or other control frame is offered.

The plain reference for IPv6 is `Plain.verdict_of`, a per-frame function
written from the deployment's four rules with `struct` and `ipaddress`
over the kit's own mappings (MAC -> (v4, v6), v6 -> v4): nothing of
`bng_tpu/ops`, no table, no jax. A forwarded v6 frame has to be the frame
that was sent, byte for byte, and the reference's verdict for it has to be
forward. IPv4 data and DHCP are held as the default kit holds them.

`stale-binding` here: one subscriber in eight was renumbered to a fresh
IA_NA. The traffic, the reference and the host's lease book hold the new
address; the device's binding rows and by-address table are the ones from
before, so that subscriber's upstream v6 is a strict violation (dropped)
and its downstream v6 a miss (passed to the host, which has no answer: the
frame is lost). The warm-up's v6 frames are drawn from the other seven in
eight: `run.py` gives up on a warm-up that loses a frame, and the window,
not the warm-up, is what the control is there to fail.
"""

from __future__ import annotations

import ipaddress
import struct
import time

import jax
import numpy as np

from benchmark.kits import ipoe
from benchmark.lib.app import BenchError, shape
from benchmark.lib.gen import (_be16, _be32, _csum, _words, mac_cols,
                               row_bytes)

ETH_P_IPV6 = 0x86DD
V6_PREFIX = "2001:db8:1::/64"  # `bng run --dhcpv6-prefix`, its default
SUB_HI = (0x20010DB8, 0x00010000, 0)  # 2001:db8:1::/96 + (index + 1)
PEER_HI = (0x20010DB8, 0xFFFF0000, 0)  # peers, in 2001:db8:ffff::/48
REMOTE_PORT, LOCAL_PORT = 443, 40000
UP6, DOWN6 = 4, 5  # frame kinds beside lib/gen.py's DISCOVER .. DOWN
FORWARD, DROP, PASS = "forward", "drop", "pass"


def stage_bytes(batch: int, slot: int) -> int:
    """Bytes the IPv6 stage must read and write in one step, from shapes:
    the destination window (three static 16-byte slices of each lane read,
    one selected and written) and the by-address probe (two bucket rows of
    four ways x 8 words and one 8-word value row a lane). `slot` is not in
    it: the stage never moves a packet."""
    del slot
    window = batch * (3 * 16 + 16)
    probe = batch * (2 * 4 * 8 * 4 + 8 * 4)
    return window + probe


# --------------------------------------------------------------------------
# the plain reference for an IPv6 frame
# --------------------------------------------------------------------------

class Plain:
    """What the deployment does with one IPv6 frame, from its rules:

    1. upstream control (source in fe80::/10 or ::, or destination in
       ff00::/8 or fe80::/10) is never a violation: it passes to the host;
    2. upstream data from a MAC whose binding holds a /128 equal to the
       source is forwarded, policed on the binding's v4 address; anything
       else is judged by the antispoof mode (bpf/antispoof.c:256-288):
       disabled allows, a bound MAC with another source violates, an
       unbound MAC is allowed under loose alone; a violation is dropped
       unless the mode is log-only; what is allowed and not forwarded
       passes to the host;
    3. downstream to a bound /128 is forwarded, policed on that
       subscriber's v4 address; to anything else it passes to the host;
    4. a frame that is not IPv6 with its 40-byte header whole is none of
       this stage's (None).

    `bindings`: MAC (6 bytes) -> (v4, v6 as 16 bytes or None[, mode]);
    `by_addr`: v6 (16 bytes) -> v4; anything with `.get`."""

    def __init__(self, bindings, by_addr, default_mode: str = "strict"):
        self.bindings, self.by_addr = bindings, by_addr
        self.default_mode = default_mode

    @staticmethod
    def l3_offset(frame: bytes) -> tuple[int, int]:
        """(offset of the L3 header, inner ethertype) behind 0, 1 or 2 tags."""
        (et,) = struct.unpack_from("!H", frame, 12)
        if et not in (0x8100, 0x88A8) or len(frame) < 18:
            return 14, et
        (et1,) = struct.unpack_from("!H", frame, 16)
        if et1 != 0x8100 or len(frame) < 22:
            return 18, et1
        return 22, struct.unpack_from("!H", frame, 20)[0]

    def verdict_of(self, frame: bytes, from_access: bool):
        """(FORWARD | DROP | PASS, the v4 address whose buckets a forwarded
        frame draws on) or None where the frame is not IPv6."""
        if len(frame) < 14:
            return None
        l3, ethertype = self.l3_offset(frame)
        if ethertype != ETH_P_IPV6 or len(frame) < l3 + 40:
            return None
        src = ipaddress.IPv6Address(frame[l3 + 8:l3 + 24])
        dst = ipaddress.IPv6Address(frame[l3 + 24:l3 + 40])
        if not from_access:
            v4 = self.by_addr.get(dst.packed)
            return (PASS, None) if v4 is None else (FORWARD, v4)
        if (src.is_link_local or src.is_unspecified or dst.is_multicast
                or dst.is_link_local):
            return PASS, None
        v4 = v6 = None
        mode = self.default_mode
        bound = self.bindings.get(bytes(frame[6:12]))
        if bound:
            v4, v6, *own = bound
            mode = own[0] if own else mode
        if v6 is not None and src.packed == v6:
            return FORWARD, v4
        if mode == "disabled":
            return PASS, None
        allowed = False if v6 is not None else mode == "loose"
        if allowed or mode == "log-only":
            return PASS, None
        return DROP, None


# --------------------------------------------------------------------------
# layout and provisioning
# --------------------------------------------------------------------------

class Layout(ipoe.Layout):
    """The default layout, and each subscriber's IA_NA address."""

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        s = config["sizes"]
        self.v6_bindings = int(s.get("v6_bindings", self.subscribers))
        if self.v6_bindings != self.subscribers:
            raise BenchError(f"v6_bindings {self.v6_bindings}: every one of "
                             f"the {self.subscribers} subscribers is dual "
                             f"stack in this deployment")
        self.v6_share = int(s.get("v6_data_share_pct", 40)) / 100.0

    def renumbered(self, idx):
        """The stale-binding control's one subscriber in eight."""
        return np.asarray(idx) % 8 == 0

    def sub_v6(self, idx, fresh: bool = False) -> np.ndarray:
        """[N, 4] uint32 words of each subscriber's IA_NA. `fresh`: the
        renumbered ones hold an address beyond everybody's first one."""
        idx = np.asarray(idx, np.int64)
        low = idx + 1
        if fresh:
            low = np.where(self.renumbered(idx),
                           self.subscribers + idx // 8 + 1, low)
        out = np.empty((len(idx), 4), np.uint32)
        out[:, :3] = SUB_HI
        out[:, 3] = low
        return out

    @staticmethod
    def peer_v6(idx) -> np.ndarray:
        out = np.empty((len(idx), 4), np.uint32)
        out[:, :3] = PEER_HI
        out[:, 3] = (np.asarray(idx, np.int64) & 0xFFFF) + 1
        return out


def words_bytes(words: np.ndarray) -> list[bytes]:
    """[N, 4] uint32 -> N 16-byte addresses."""
    return row_bytes(np.ascontiguousarray(words, np.uint32).astype(">u4")
                     .view(np.uint8).reshape(-1, 16))


def provision(app, lay: Layout, stale: bool = False) -> dict:
    """The default kit's tables through the same bulk writers, with the
    dual-stack bindings in place of the v4-only ones, and the leases."""
    from bng_tpu.ops.antispoof import MODE_STRICT

    if shape(app) == "cluster":
        raise BenchError("the IPv6 stage is not wired under --shards "
                         "(ROADMAP M5)")
    c = app.components
    if "v6_tables" not in c:
        raise BenchError("the app has no IPv6 stage: the configuration's "
                         "argv lacks --ipv6-fastpath")
    if app.config.dhcpv6_prefix != V6_PREFIX:
        raise BenchError(f"the kit's addresses are in {V6_PREFIX}, the "
                         f"app's pool in {app.config.dhcpv6_prefix}")
    now = int(app.clock())
    took = {}
    idx = np.arange(lay.subscribers)
    macs, ips = lay.sub_macs(idx), lay.sub_ips(idx)
    t0 = time.time()
    c["fastpath"].add_subscribers_bulk(macs, pool_ids=1, ips=ips,
                                       lease_expiries=np.uint32(now + 86400))
    took["subscribers"] = time.time() - t0

    t0 = time.time()
    policy = c["policies"].get(app.config.default_policy)
    c["qos"].bulk_set_subscribers(ips, policy.download_bps, policy.upload_bps)
    # the stale-binding control: the device's rows are the ones from before
    c["v6_tables"].bulk_bind(macs, ips, lay.sub_v6(idx), MODE_STRICT)
    c["antispoof"].set_config(MODE_STRICT, log_violations=True)
    took["qos+bindings"] = time.time() - t0

    t0 = time.time()
    j = np.arange(lay.nat_subscribers)
    made = c["nat"].bulk_allocate_nat(lay.sub_ips(lay.nat_sub_index(j)), now)
    if made != lay.nat_subscribers:
        raise BenchError(f"NAT blocks: {made} of {lay.nat_subscribers}")
    src, dst, sport, dport, proto = lay.flows(np.arange(lay.nat_flows))
    nat_ip, nat_port, ok = c["nat"].bulk_flows(src, dst, sport, dport, proto,
                                               pkt_len=64, now=now)
    if not bool(ok.all()):
        raise BenchError(f"NAT flows: {int(ok.sum())} of {len(ok)}")
    took["nat"] = time.time() - t0

    t0 = time.time()
    # a committed lease a subscriber in the host server, DUID-LL of its MAC
    duids = [b"\x00\x03\x00\x01" + m for m in row_bytes(mac_cols(macs))]
    held = c["dhcpv6"].adopt_na_leases(
        duids, words_bytes(lay.sub_v6(idx, fresh=stale)),
        expiry=float(now + 86400))
    if held != lay.subscribers and not stale:
        raise BenchError(f"DHCPv6 leases: {held} of {lay.subscribers}")
    took["leases"] = time.time() - t0

    t0 = time.time()
    c["engine"].resync_tables()
    jax.block_until_ready(jax.tree_util.tree_leaves(c["engine"].tables))
    took["upload"] = time.time() - t0
    return {"took": took, "nat_ip": np.asarray(nat_ip, np.uint32),
            "nat_port": np.asarray(nat_port, np.uint32), "stale": stale}


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

def v6_frames(src_mac, dst_mac, src_words, dst_words, sport: int, dport: int,
              udp, ids) -> list[bytes]:
    """Ethernet + IPv6 + UDP (66 bytes) or TCP (PSH|ACK, 78 bytes), no
    extension header, the 4-byte frame id as payload, the L4 checksum
    valid over the IPv6 pseudo-header."""
    udp = np.asarray(udp, bool)
    out: list = [None] * len(ids)
    for is_udp, l4 in ((True, 8), (False, 20)):
        rows = np.nonzero(udp == is_udp)[0]
        n = len(rows)
        if not n:
            continue
        seg, nh = l4 + 4, 17 if is_udp else 6
        buf = np.zeros((n, 54 + seg), np.uint8)
        buf[:, 0:6] = dst_mac if np.ndim(dst_mac) == 1 else dst_mac[rows]
        buf[:, 6:12] = src_mac if np.ndim(src_mac) == 1 else src_mac[rows]
        buf[:, 12:14] = (0x86, 0xDD)
        buf[:, 14] = 0x60
        buf[:, 18:20] = _be16(np.full(n, seg))
        buf[:, 20] = nh
        buf[:, 21] = 64
        buf[:, 22:38] = _be32(src_words[rows]).reshape(n, 16)
        buf[:, 38:54] = _be32(dst_words[rows]).reshape(n, 16)
        buf[:, 54:56] = _be16(np.full(n, sport))
        buf[:, 56:58] = _be16(np.full(n, dport))
        if is_udp:
            buf[:, 58:60] = _be16(np.full(n, seg))
            at = 60
        else:
            buf[:, 66] = 5 << 4
            buf[:, 67] = 0x18
            buf[:, 68:70] = (0xFF, 0xFF)
            at = 70
        buf[:, -4:] = _be32(np.asarray(ids)[rows])
        c = _csum(_words(buf[:, 22:54]) + np.uint64(seg + nh)
                  + _words(buf[:, 54:]))
        if is_udp:
            c = np.where(c == 0, 0xFFFF, c).astype(np.uint16)
        buf[:, at:at + 2] = _be16(c)
        for r, raw in zip(rows, row_bytes(buf)):
            out[r] = raw
    return out


class Traffic(ipoe.Traffic):
    def __init__(self, mix: dict, lay: Layout, prov: dict, app, seed: int,
                 seconds: float, stream: int = 0):
        self.rng6 = np.random.default_rng([int(seed), 0x6D6, stream])
        # the stale-binding control's warm-up (stream 1) keeps off the
        # renumbered subscribers: see the module's docstring
        self.spare_renumbered = bool(prov.get("stale")) and stream == 1
        self.fresh = bool(prov.get("stale"))
        super().__init__(mix, lay, prov, app, seed, seconds, stream)

    def build_frames(self, ids, n_dhcp, flow_up, flow_down, prov, app):
        from bng_tpu.utils.net import parse_mac

        lay = self.lay
        frames = super().build_frames(ids, n_dhcp, flow_up, flow_down, prov,
                                      app)
        n_up, n_down = len(flow_up), len(flow_down)
        # the first share of each direction's data frames is IPv6, each
        # downstream one the reverse of the upstream one at its place
        n6_down = int(round(lay.v6_share * n_down))
        n6_up = max(int(round(lay.v6_share * n_up)), n6_down)
        subs = self.rng6.integers(0, lay.subscribers, n6_up)
        if self.spare_renumbered:
            subs = np.where(lay.renumbered(subs), subs + 1, subs) % lay.subscribers
            subs = np.where(lay.renumbered(subs), 1, subs)
        up_at = n_dhcp + np.arange(n6_up)
        down_at = n_dhcp + n_up + np.arange(n6_down)
        self.kind[up_at], self.key[up_at] = UP6, subs
        self.kind[down_at], self.key[down_at] = DOWN6, subs[:n6_down]
        server_mac = np.frombuffer(parse_mac(app.config.server_mac), np.uint8)
        router_mac = np.frombuffer(ipoe.ROUTER_MAC, np.uint8)
        mine, peer = lay.sub_v6(subs, self.fresh), lay.peer_v6(subs)
        udp = subs % 2 == 0
        up = v6_frames(mac_cols(lay.sub_macs(subs)), server_mac, mine, peer,
                       LOCAL_PORT, REMOTE_PORT, udp, ids[up_at])
        down = v6_frames(router_mac, server_mac, peer[:n6_down],
                         mine[:n6_down], REMOTE_PORT, LOCAL_PORT,
                         udp[:n6_down], ids[down_at])
        for at, raw in zip((*up_at, *down_at), (*up, *down)):
            frames[at] = raw
        return frames


# --------------------------------------------------------------------------
# the reference a run is held to
# --------------------------------------------------------------------------

class _Bindings:
    """MAC -> (v4, v6) of the layout, as the reference holds them (under
    the stale-binding control: after the renumbering), by arithmetic."""

    def __init__(self, lay: Layout, fresh: bool):
        self.lay, self.fresh = lay, fresh

    def get(self, mac: bytes):
        idx = int.from_bytes(mac, "big") - self.lay.mac_base
        if not 0 <= idx < self.lay.subscribers:
            return None
        return (int(self.lay.sub_ips([idx])[0]),
                words_bytes(self.lay.sub_v6([idx], self.fresh))[0])


class _ByAddr:
    """v6 -> v4 of the layout, the inverse of `_Bindings`."""

    def __init__(self, lay: Layout, fresh: bool):
        self.lay, self.fresh = lay, fresh
        self.base = int.from_bytes(words_bytes(np.array([(*SUB_HI, 0)]))[0],
                                   "big")

    def get(self, addr: bytes):
        low, lay = int.from_bytes(addr, "big") - self.base - 1, self.lay
        if low >= lay.subscribers and self.fresh:
            low = (low - lay.subscribers) * 8  # a renumbered one's new address
        elif self.fresh and bool(lay.renumbered(low)):
            return None  # the address it gave up
        if not 0 <= low < lay.subscribers:
            return None
        return int(lay.sub_ips([low])[0])


class Reference(ipoe.Reference):
    """DHCP and IPv4 data as the default kit. A forwarded IPv6 frame is
    the frame that was sent, byte for byte, and `Plain`'s verdict for that
    frame is forward."""

    FAMILIES = {4: "translated IPv4 frames", 6: "forwarded IPv6 frames"}

    def __init__(self, app, traffic: Traffic):
        super().__init__(app, traffic)
        lay, fresh = traffic.lay, traffic.fresh
        self.plain = Plain(_Bindings(lay, fresh), _ByAddr(lay, fresh))
        self.seen = dict.fromkeys(self.FAMILIES, 0)

    @property
    def kinds(self) -> dict:
        """Three kinds of reply: DHCP, translated v4, forwarded v6. The
        harness counts a kind without a sample as missing, so each family
        of data frames the sample has not held yet is a kind of its own."""
        out = {True: "DHCP replies byte-for-byte",
               False: f"data frames ({self.seen[4]} IPv4 by mapping, payload "
                      f"and both checksums; {self.seen[6]} IPv6 byte-for-byte "
                      f"with the reference's verdict forward)"}
        out.update({f"none-v{k}": "of the " + what
                    for k, what in self.FAMILIES.items() if not self.seen[k]})
        return out

    def holds(self, fid: int, raw: bytes) -> bool:
        tr = self.tr
        kind = int(tr.kind[fid])
        if kind not in (UP6, DOWN6):
            if not tr.is_dhcp[fid]:
                self.seen[4] += 1
            return super().holds(fid, raw)
        self.seen[6] += 1
        sent = tr.frames[fid]
        verdict = self.plain.verdict_of(sent, kind == UP6)
        return raw == sent and verdict is not None and verdict[0] == FORWARD
