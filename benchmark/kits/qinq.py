"""The QinQ kit: a 1:1-VLAN access network, PPPoE and IPoE subscribers side
by side behind CGNAT, every one of them behind an S- and a C-tag.

The PPPoE kit's layout (NAT subscribers 0..S-1 hold an OPEN PPPoE session,
the others are IPoE with DHCP bindings; QoS rows, strict antispoof
bindings, NAT blocks and flows for everybody) and, for every subscriber, a
pair of tags: subscriber i behind S-tag 1 + i // 4094 and C-tag
1 + i % 4094 (an S-tag an access node, a C-tag a line; outer TPID 0x88A8,
inner 0x8100, priority bits 0). The pair is resident in the device's
by-address table for all of them, written through the program's registry
(`QinQMapper`); for an IPoE subscriber also in `vlan_subscriber_pools`
beside the MAC row and on a host lease; for a PPPoE subscriber on the host
session (`vlans`).

Traffic is the default kit's mix in the kit's framing. Data is drawn over
the flows of ALL NAT subscribers. Upstream IPoE: the mix's 60-byte frame
with the two tags behind the MAC addresses (68 in; SNAT, pop: 60 out).
Upstream PPPoE: tags, then the session framing (76 in; decap behind two
tags, SNAT, pop: 60 out). Downstream: the matching plain IPv4 frame from the
core (60 in; DNAT, push: 68 out for IPoE; DNAT, encap, push: 76 out for
PPPoE). DHCP from the IPoE MACs, double tagged (370 bytes), answered from
the VLAN tier, the reply tagged. No PPPoE control frame is offered.

The plain reference is `Plain`: per frame, `struct` and plain Python over
the kit's own mappings (address -> pair, address -> session, session id ->
client): strip or build the tags and the PPPoE framing from the framing
rules. Nothing of `bng_tpu` is in it. The frame inside is then held as the
default kit holds it (mapping, payload, both checksums), a DHCP reply byte
for byte against the host-only `DHCPServer` given the request's tags.

`stale-binding` here: one subscriber in eight was moved to another line (a
pair beyond everybody's first one). The clients, the host's registry,
leases and sessions and `vlan_subscriber_pools` hold the new pair; the
by-address table that set-up uploads is the one from before, so the device
really tags that subscriber's downstream frames for the old line. No frame
of a moved subscriber is lost (a pushed frame is forwarded, a popped one
too, DHCP hits the VLAN tier under the new pair), so the warm-up stream
draws from everybody.
"""

from __future__ import annotations

import struct
import time

import numpy as np

from benchmark.kits import ipoe, pppoe
from benchmark.lib.app import BenchError, shape
from benchmark.lib.gen import UP

TPID_S, TPID_C = 0x88A8, 0x8100
ETH_P_IP, ETH_PPPOE_SESSION, PPP_IPV4 = 0x0800, 0x8864, 0x0021
TAGS = 8  # two tags of four bytes
LINES = 4094  # C-tags an S-tag holds: 1..4094


def stage_bytes(batch: int, slot: int) -> int:
    """Bytes the pop and the push must move in one step, from shapes: each
    reads the [batch, slot] packet array once and writes it once."""
    return 2 * 2 * batch * slot


# --------------------------------------------------------------------------
# the plain reference for the framing
# --------------------------------------------------------------------------

class Plain:
    """What the deployment does to the framing of one forwarded frame:

    1. an upstream frame leaves toward the core without its access tags,
       and without its PPPoE header where it had one: MAC addresses,
       0x0800, the IPv4 packet the PPPoE length (or the frame) holds. A
       session frame is one whose session id the concentrator holds for
       that source MAC, version/type 0x11, code 0, PPP protocol 0x0021;
    2. a downstream frame leaves toward the access with its subscriber's
       S- and C-tag behind the MAC addresses (0x88A8, 0x8100, priority 0);
       where the subscriber has a session, the tags stand in front of the
       PPPoE header (0x11, code 0, the session's id, length of PPP protocol
       and packet; PPP 0x0021), the destination MAC is the client's and
       the source the concentrator's; a subscriber without a pair gets no
       tags.

    `pairs`: address -> (s_tag, c_tag); `by_ip`: address -> (session id,
    client MAC); `by_sid`: session id -> (client MAC, address); anything
    with `.get`. `ac_mac`: the concentrator's."""

    def __init__(self, pairs, by_ip, by_sid, ac_mac: bytes):
        self.pairs, self.by_ip, self.by_sid = pairs, by_ip, by_sid
        self.ac_mac = ac_mac

    @staticmethod
    def untag(frame: bytes) -> tuple[tuple[int, ...], bytes]:
        """(the VIDs in front, the frame without them): 0, 1 or 2 tags, an
        inner one 802.1Q only."""
        (et,) = struct.unpack_from("!H", frame, 12)
        if et not in (TPID_C, TPID_S) or len(frame) < 18:
            return (), frame
        outer, et1 = struct.unpack_from("!HH", frame, 14)
        if et1 != TPID_C or len(frame) < 22:
            return (outer & 0xFFF,), frame[:12] + frame[16:]
        (inner,) = struct.unpack_from("!H", frame, 18)
        return (outer & 0xFFF, inner & 0xFFF), frame[:12] + frame[20:]

    @staticmethod
    def tag(frame: bytes, pair) -> bytes:
        if pair is None:
            return frame
        return (frame[:12] + struct.pack("!HHHH", TPID_S, pair[0] & 0xFFF,
                                         TPID_C, pair[1] & 0xFFF)
                + frame[12:])

    def up(self, frame: bytes) -> bytes | None:
        """The frame a forwarded upstream `frame` is toward the core,
        before translation; None: a session frame that is not data of a
        session the concentrator holds for that MAC."""
        _tags, body = self.untag(frame)
        (et,) = struct.unpack_from("!H", body, 12)
        if et != ETH_PPPOE_SESSION:
            return body
        if len(body) < 22:
            return None
        ver_type, code, sid, plen, proto = struct.unpack_from("!BBHHH", body, 14)
        held = self.by_sid.get(sid)
        if (ver_type != 0x11 or code != 0 or proto != PPP_IPV4 or plen < 2
                or 20 + plen > len(body) or held is None
                or held[0] != body[6:12]):
            return None
        return body[:12] + struct.pack("!H", ETH_P_IP) + body[22:20 + plen]

    def down(self, sent: bytes, packet: bytes, sub_ip: int) -> bytes:
        """The frame the access side gets for the IPv4 `packet` (as
        translated) that came from the core in `sent`, for the subscriber
        at `sub_ip`."""
        session = self.by_ip.get(sub_ip)
        if session is None:
            framed = sent[:12] + struct.pack("!H", ETH_P_IP) + packet
        else:
            sid, client = session
            framed = (client + self.ac_mac
                      + struct.pack("!HBBHHH", ETH_PPPOE_SESSION, 0x11, 0,
                                    sid, len(packet) + 2, PPP_IPV4) + packet)
        return self.tag(framed, self.pairs.get(sub_ip))

    def packet_of(self, frame: bytes) -> bytes | None:
        """The IPv4 packet inside a downstream frame's framing (tags, then
        PPPoE or not); None where there is none."""
        _tags, body = self.untag(frame)
        (et,) = struct.unpack_from("!H", body, 12)
        if et == ETH_P_IP:
            return body[14:]
        if et != ETH_PPPOE_SESSION or len(body) < 22:
            return None
        plen, proto = struct.unpack_from("!HH", body, 18)
        if proto != PPP_IPV4 or plen < 2 or 20 + plen > len(body):
            return None
        return body[22:20 + plen]


# --------------------------------------------------------------------------
# layout and provisioning
# --------------------------------------------------------------------------

class Layout(pppoe.Layout):
    """The PPPoE kit's layout, and each subscriber's pair."""

    def __init__(self, config: dict, seed: int):
        s = config["sizes"]
        if "pppoe_sessions" not in s:
            # the deployment's mix at any size: a quarter of the NAT
            # subscribers (65,535 of 250,000 is 26.2%), IPoE the rest
            s = dict(s, pppoe_sessions=min(max(1, int(s["nat_subscribers"]) // 4),
                                           0xFFFF))
        super().__init__(dict(config, sizes=s), seed)
        self.qinq_pairs = int(s.get("qinq_pairs", self.subscribers))
        if self.qinq_pairs != self.subscribers:
            raise BenchError(f"qinq_pairs {self.qinq_pairs}: every one of the "
                             f"{self.subscribers} subscribers is behind a "
                             f"pair in this deployment")
        if self.subscribers + self.subscribers // 8 + 1 > LINES * LINES:
            raise BenchError(f"{self.subscribers} subscribers: more than "
                             f"{LINES} x {LINES} lines hold")

    @staticmethod
    def moved(idx):
        """The stale-binding control's one subscriber in eight."""
        return np.asarray(idx) % 8 == 0

    def pairs(self, idx, moved: bool = False):
        """(s_tags, c_tags) of each subscriber. `moved`: the one in eight
        is on a line beyond everybody's first one."""
        idx = np.asarray(idx, np.int64)
        line = idx
        if moved:
            line = np.where(self.moved(idx), self.subscribers + idx // 8, idx)
        return ((1 + line // LINES).astype(np.uint32),
                (1 + line % LINES).astype(np.uint32))


def provision(app, lay: Layout, stale: bool = False) -> dict:
    """The PPPoE kit's tables and sessions through the same bulk writers,
    and every subscriber's pair: in the by-address table, in
    `vlan_subscriber_pools` and on a host lease for the IPoE subscribers,
    on the host session for the PPPoE ones. Returns the PPPoE kit's dict
    and `stale`."""
    from bng_tpu.control.dhcp_server import Lease
    from bng_tpu.ops.qinq import QV_S_TAG

    if shape(app) == "cluster":
        raise BenchError("the qinq stage is not wired under --shards "
                         "(ROADMAP M1)")
    c = app.components
    if "qinq_tables" not in c:
        raise BenchError("the app has no qinq stage: the configuration's "
                         "argv lacks --qinq-enabled")
    now = int(app.clock())
    idx = np.arange(lay.subscribers)
    macs, ips = lay.sub_macs(idx), lay.sub_ips(idx)
    s_new, c_new = lay.pairs(idx, moved=stale)
    t0 = time.time()
    # what the host holds: the pair each subscriber is behind today
    q = c["qinq_tables"]
    q.bulk_bind(ips, s_new, c_new)
    if stale:
        # the stale-binding control: the table that is uploaded still
        # holds the line each moved subscriber was on before
        s_old, c_old = lay.pairs(idx)
        for i in np.nonzero(lay.moved(idx))[0]:
            q.by_ip.update_val_words([ips[i]], QV_S_TAG, [s_old[i], c_old[i]])
    ipoe_idx = lay.ipoe_subs()
    expiry = now + 86400
    c["fastpath"].add_vlan_subscribers_bulk(
        s_new[ipoe_idx], c_new[ipoe_idx], pool_ids=1, ips=ips[ipoe_idx],
        lease_expiries=np.uint32(expiry))
    leases = c["dhcp"].leases
    for i, mac, ip, s, t in zip(ipoe_idx.tolist(), macs[ipoe_idx].tolist(),
                                ips[ipoe_idx].tolist(),
                                s_new[ipoe_idx].tolist(),
                                c_new[ipoe_idx].tolist()):
        leases[mac] = Lease(mac=mac.to_bytes(6, "big"), ip=ip, pool_id=1,
                            expiry=expiry, s_tag=s, c_tag=t,
                            session_id=f"bench-{i:x}")
    pairs_took = time.time() - t0

    # the rest as the PPPoE kit (its one upload is the whole one)
    prov = pppoe.provision(app, lay, stale=False)
    p_idx = lay.pppoe_subs()
    sessions = c["pppoe"].sessions
    for mac, s, t in zip(macs[p_idx].tolist(), s_new[p_idx].tolist(),
                         c_new[p_idx].tolist()):
        sessions.by_mac(mac.to_bytes(6, "big")).vlans = [s, t]
    prov["took"] = {"pairs": pairs_took, **prov["took"]}
    prov["stale"] = stale
    return prov


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

class _Drawn:
    """What the default kit's Traffic draws its keys from: DHCP from the
    IPoE subscribers, data from every NAT subscriber's flows."""

    def __init__(self, lay: Layout):
        self.subscribers = lay.subscribers - lay.pppoe_sessions
        self.nat_flows = lay.nat_flows
        self.xid_base = lay.xid_base


class Traffic(ipoe.Traffic):
    def __init__(self, mix: dict, lay: Layout, prov: dict, app, seed: int,
                 seconds: float, stream: int = 0):
        self.whole = lay
        self.stale = bool(prov.get("stale"))
        super().__init__(mix, _Drawn(lay), prov, app, seed, seconds, stream)

    def sub_of(self, i: int) -> int:
        """The subscriber index frame id i belongs to."""
        lay = self.lay
        if self.is_dhcp[i]:
            return int(self.key[i])
        return int(lay.nat_sub_index(self.key[i] // lay.flows_per))

    def build_frames(self, ids, n_dhcp, flow_up, flow_down, prov, app):
        lay = self.lay = self.whole
        # the DHCP keys were drawn as ranks among the IPoE subscribers
        self.key[:n_dhcp] = lay.ipoe_subs()[self.key[:n_dhcp]]
        frames = super().build_frames(ids, n_dhcp, flow_up, flow_down, prov,
                                      app)
        nat_sub = np.asarray(flow_up) // lay.flows_per
        subs = np.concatenate([self.key[:n_dhcp], lay.nat_sub_index(nat_sub)])
        s_tags, c_tags = lay.pairs(subs, moved=self.stale)
        session_id = self.session_id = prov["session_id"]
        for at, (s, c) in enumerate(zip(s_tags.tolist(), c_tags.tolist())):
            f = frames[at]
            j = int(nat_sub[at - n_dhcp]) if at >= n_dhcp else lay.pppoe_sessions
            inner = f[12:]
            if j < lay.pppoe_sessions:
                # upstream PPPoE: the session framing behind the tags
                inner = struct.pack("!HBBHHH", ETH_PPPOE_SESSION, 0x11, 0,
                                    int(session_id[j]), len(f) - 14 + 2,
                                    PPP_IPV4) + f[14:]
            frames[at] = (f[:12] + struct.pack("!HHHH", TPID_S, s, TPID_C, c)
                          + inner)
        return frames

    def reply_id(self, raw: bytes) -> tuple[bool, int]:
        """(is a DHCP reply, frame id): a DHCP reply carries its request's
        two tags, so its headers sit eight bytes further in."""
        if (len(raw) >= 240 + TAGS and raw[12:14] == b"\x88\xa8"
                and raw[23 + TAGS] == 17
                and raw[34 + TAGS:36 + TAGS] == b"\x00\x43"):
            return True, (int.from_bytes(raw[46 + TAGS:50 + TAGS], "big")
                          - self.xid_base)
        return False, int.from_bytes(raw[-4:], "big")

    def expected_data(self, i: int, app):
        want = super().expected_data(i, app)
        if want is not None and self.kind[i] == UP:
            # the payload sits behind the tags (and the session framing)
            lay = self.lay
            framing = TAGS + (pppoe.FRAMING if self.key[i] // lay.flows_per
                              < lay.pppoe_sessions else 0)
            at = framing + (42 if want[4] == 17 else 54)
            want = want[:5] + (self.frames[i][at:],)
        return want


# --------------------------------------------------------------------------
# the reference a run is held to
# --------------------------------------------------------------------------

class _Pairs:
    """address -> (s_tag, c_tag) of the layout, as the host holds them
    (under the stale-binding control: after the move), by arithmetic."""

    def __init__(self, lay: Layout, moved: bool):
        self.lay, self.moved = lay, moved

    def get(self, ip: int):
        idx = int(ip) - ipoe.SUB_IP_BASE
        if not 0 <= idx < self.lay.subscribers:
            return None
        s, c = self.lay.pairs([idx], self.moved)
        return int(s[0]), int(c[0])


class Reference(ipoe.Reference):
    """DHCP as the default kit, the request's tags given to the host-only
    server. A data frame's framing is `Plain`'s, byte for byte; the frame
    inside is held as the default kit holds it."""

    FAMILIES = {"ipoe": "IPoE data frames", "pppoe": "PPPoE data frames"}

    def __init__(self, app, traffic: Traffic):
        super().__init__(app, traffic)
        lay = traffic.lay
        p = np.arange(lay.pppoe_sessions)
        p_idx = lay.nat_sub_index(p)
        macs = [int(m).to_bytes(6, "big") for m in lay.sub_macs(p_idx)]
        ips = [int(x) for x in lay.sub_ips(p_idx)]
        sids = [int(x) for x in traffic.session_id]
        self.plain = Plain(
            _Pairs(lay, traffic.stale),
            by_ip=dict(zip(ips, zip(sids, macs))),
            by_sid=dict(zip(sids, zip(macs, ips))),
            ac_mac=bytes(app.components["pppoe"].config.server_mac))
        self.seen = dict.fromkeys(self.FAMILIES, 0)

    @property
    def kinds(self) -> dict:
        """Three kinds of reply: DHCP, IPoE data, PPPoE data. The harness
        counts a kind without a sample as missing, so each family of data
        frames the sample has not held yet is a kind of its own."""
        out = {True: "DHCP replies byte-for-byte, tagged as the request",
               False: f"data frames, the framing byte-for-byte and the frame "
                      f"inside by mapping, payload and both checksums "
                      f"({self.seen['ipoe']} IPoE, {self.seen['pppoe']} PPPoE)"}
        out.update({f"none-{k}": "of the " + what
                    for k, what in self.FAMILIES.items() if not self.seen[k]})
        return out

    def holds(self, fid: int, raw: bytes) -> bool:
        tr, plain = self.tr, self.plain
        if tr.is_dhcp[fid]:
            return super().holds(fid, raw)
        lay = tr.lay
        j = int(tr.key[fid]) // lay.flows_per
        self.seen["pppoe" if j < lay.pppoe_sessions else "ipoe"] += 1
        sent = tr.frames[fid]
        if int(tr.kind[fid]) == UP:
            inner = plain.up(sent)
            return (inner is not None and len(raw) == len(inner)
                    and raw[:14] == inner[:14] and super().holds(fid, raw))
        packet = plain.packet_of(raw)
        if packet is None:
            return False
        sub_ip = int(lay.sub_ips([lay.nat_sub_index(j)])[0])
        return (raw == plain.down(sent, packet, sub_ip)
                and super().holds(fid, sent[:12] + b"\x08\x00" + packet))
