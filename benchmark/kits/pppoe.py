"""The PPPoE kit: one access concentrator's sessions beside IPoE subscribers,
all behind CGNAT.

The default kit's layout with one difference: NAT subscribers 0..S-1
(`sizes.pppoe_sessions`, at most the 65,535 ids a 16-bit session id gives an
access concentrator) reach the BNG over PPPoE. Each holds an OPEN session in
the host `PPPoEServer.sessions` and in both device tables; the others are IPoE
and hold the DHCP bindings. QoS rows, strict antispoof bindings, NAT blocks
and flows are the default kit's, for every subscriber.

Traffic is the default kit's mix with the kit's framing: data frames are drawn
over the PPPoE subscribers' flows, upstream as PPPoE session frames (the mix's
60-byte frame with the 8 bytes of PPPoE / PPP behind the Ethernet header: 68
in, 60 out, SNAT), downstream as the matching plain IPv4 frames from the core
(DNAT, then encap: 60 in, 68 out); DHCP comes from the IPoE MACs, untagged. No
PPPoE control frame is offered.

The reference is the host codec (`bng_tpu/control/pppoe/codec.py`) in front of
and behind the default kit's: an upstream reply is held against the inner
frame the codec strips out of the request, a downstream reply against the
framing the codec builds from the host `SessionManager`'s session, each then
by the default kit's comparison (mapping, payload, both checksums).

`stale-binding` here: one session in eight was re-established under a new
session id. The host's sessions, the clients and the decap table hold the new
id; the encap table that set-up uploads is the one from before, so the device
really frames that subscriber's downstream traffic with the old id. (With the
decap table stale too every upstream frame of such a session punts and never
comes back: the closed loop fills with them and the run fails by
`lost_frames` before the sample says anything.)
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmark.kits import ipoe
from benchmark.lib.app import BenchError, shape
from benchmark.lib.gen import DOWN, UP

ETH_PPPOE_SESSION = 0x8864
PPP_IPV4 = 0x0021
FRAMING = 8  # PPPoE header (6) + PPP protocol (2)
ECHO_HELD_S = 3600.0  # no LCP echo falls due inside a run (configuration: off)


def stage_bytes(batch: int, slot: int) -> int:
    """Bytes the decap and the encap must move in one step, from shapes: each
    reads the [batch, slot] packet array once and writes it once."""
    return 2 * 2 * batch * slot


class Layout(ipoe.Layout):
    """The default layout, and which of its subscribers are PPPoE."""

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        s = config["sizes"]
        self.pppoe_sessions = int(s.get("pppoe_sessions",
                                        min(self.nat_subscribers, 0xFFFF)))
        if not 0 < self.pppoe_sessions <= min(self.nat_subscribers, 0xFFFF):
            raise BenchError(f"pppoe_sessions {self.pppoe_sessions}: not "
                             f"within 1..min(nat_subscribers, 65535)")
        self.pppoe_flows = self.pppoe_sessions * self.flows_per

    def pppoe_subs(self):
        """Subscriber index of each PPPoE subscriber, by NAT subscriber."""
        return self.nat_sub_index(np.arange(self.pppoe_sessions))

    def ipoe_subs(self):
        is_pppoe = np.zeros(self.subscribers, bool)
        is_pppoe[self.pppoe_subs()] = True
        return np.nonzero(~is_pppoe)[0]


# --------------------------------------------------------------------------
# provisioning
# --------------------------------------------------------------------------

def open_sessions(app, macs: list[bytes], ips, now: float) -> None:
    """One OPEN session a MAC in the host PPPoEServer, as a negotiation
    without authentication leaves it: LCP, IPCP and IPV6CP opened, the
    address assigned, and the next LCP echo held beyond the run."""
    from bng_tpu.control.pppoe.fsm import OPENED
    from bng_tpu.control.pppoe.ipcp import IPCP
    from bng_tpu.control.pppoe.ipv6cp import IPV6CP
    from bng_tpu.control.pppoe.lcp import LCP
    from bng_tpu.control.pppoe.session import Phase

    srv = app.components["pppoe"]
    cfg = srv.config
    ac = cfg.server_mac
    for mac, ip in zip(macs, ips):
        sess = srv.sessions.allocate(mac, now)
        if sess is None:
            raise BenchError("the host SessionManager is full")
        sess.lcp = LCP(magic=sess.session_id, auth_proto=0)
        sess.ipcp = IPCP(our_ip=cfg.our_ip, client_ip=int(ip))
        sess.ipv6cp = IPV6CP(our_iid=ac[:3] + b"\xff\xfe" + ac[3:],
                             client_iid=mac[:3] + b"\xff\xfe" + mac[3:])
        for fsm in (sess.lcp, sess.ipcp, sess.ipv6cp):
            fsm.state = OPENED
        sess.assigned_ip = int(ip)
        sess.phase = Phase.OPEN
        sess.last_echo_tx = now + ECHO_HELD_S
        srv.stats.sessions_opened += 1


def provision(app, lay: Layout, stale: bool = False) -> dict:
    """The default kit's tables through the same bulk writers, the DHCP
    bindings for the IPoE subscribers only, and the sessions. Returns the
    default kit's dict and `session_id`, each PPPoE subscriber's id as its
    client knows it."""
    from bng_tpu.ops.antispoof import MODE_STRICT
    from bng_tpu.ops.pppoe import PS_SESSION_ID
    from bng_tpu.ops.table import WAYS

    if shape(app) == "cluster":
        raise BenchError("PPPoE is not wired under --shards (ROADMAP M1)")
    c = app.components
    slots = c["pppoe_tables"].by_sid.nbuckets * WAYS
    if lay.pppoe_sessions > slots // 2:
        # the program's sizing rule is half load (ops/table.py nbuckets_for)
        raise BenchError(f"the program's session tables have {slots} slots: "
                         f"{lay.pppoe_sessions} sessions do not fit them")
    now = int(app.clock())
    took = {}
    idx = np.arange(lay.subscribers)
    macs, ips = lay.sub_macs(idx), lay.sub_ips(idx)
    ipoe_idx = lay.ipoe_subs()
    t0 = time.time()
    c["fastpath"].add_subscribers_bulk(macs[ipoe_idx], pool_ids=1,
                                       ips=ips[ipoe_idx],
                                       lease_expiries=np.uint32(now + 86400))
    took["subscribers"] = time.time() - t0

    t0 = time.time()
    policy = c["policies"].get(app.config.default_policy)
    c["qos"].bulk_set_subscribers(ips, policy.download_bps, policy.upload_bps)
    c["antispoof"].bulk_add_bindings(macs, ips, MODE_STRICT)
    c["antispoof"].set_config(MODE_STRICT, log_violations=True)
    took["qos+antispoof"] = time.time() - t0

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
    p_idx = lay.pppoe_subs()
    p_macs, p_ips = macs[p_idx], ips[p_idx]
    mac_bytes = [int(m).to_bytes(6, "big") for m in p_macs]
    srv = c["pppoe"]
    open_sessions(app, mac_bytes, p_ips, float(now))
    before = np.array([srv.sessions.by_mac(m).session_id for m in mac_bytes],
                      np.uint32)
    if stale:
        # one session in eight goes down and comes up again, each under the
        # id the one after it gave back
        again = [k for k in range(lay.pppoe_sessions) if k % 8 == 0]
        for k in again:
            srv.sessions.remove(int(before[k]))
        again = again[1:] + again[:1]
        open_sessions(app, [mac_bytes[k] for k in again], p_ips[again],
                      float(now))
    session_id = np.array([srv.sessions.by_mac(m).session_id
                           for m in mac_bytes], np.uint32)
    c["pppoe_tables"].sessions_up_bulk(session_id, p_macs, p_ips)
    for k in np.nonzero(session_id != before)[0]:
        # the stale-binding control: the encap table from before
        c["pppoe_tables"].by_ip.update_val_words([p_ips[k]], PS_SESSION_ID,
                                                 before[k])
    if len(srv.sessions) != lay.pppoe_sessions:
        raise BenchError(f"sessions: {len(srv.sessions)} of "
                         f"{lay.pppoe_sessions}")
    took["sessions"] = time.time() - t0

    t0 = time.time()
    c["engine"].resync_tables()
    jax.block_until_ready(jax.tree_util.tree_leaves(c["engine"].tables))
    took["upload"] = time.time() - t0
    return {"took": took, "nat_ip": np.asarray(nat_ip, np.uint32),
            "nat_port": np.asarray(nat_port, np.uint32),
            "session_id": session_id}


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

class _Drawn:
    """What the default kit's Traffic draws its keys from, narrowed: DHCP
    from the IPoE subscribers, data from the PPPoE subscribers' flows."""

    def __init__(self, lay: Layout):
        self.subscribers = lay.subscribers - lay.pppoe_sessions
        self.nat_flows = lay.pppoe_flows
        self.xid_base = lay.xid_base


class Traffic(ipoe.Traffic):
    def __init__(self, mix: dict, lay: Layout, prov: dict, app, seed: int,
                 seconds: float, stream: int = 0):
        self.whole = lay
        super().__init__(mix, _Drawn(lay), prov, app, seed, seconds, stream)

    def build_frames(self, ids, n_dhcp, flow_up, flow_down, prov, app):
        lay = self.lay = self.whole
        # the DHCP keys were drawn as ranks among the IPoE subscribers
        self.key[:n_dhcp] = lay.ipoe_subs()[self.key[:n_dhcp]]
        frames = super().build_frames(ids, n_dhcp, flow_up, flow_down, prov,
                                      app)
        # upstream: the session framing goes in behind the addresses
        sid = prov["session_id"][np.asarray(flow_up) // lay.flows_per]
        for at, s in zip(range(n_dhcp, n_dhcp + len(flow_up)), sid):
            f = frames[at]
            frames[at] = (f[:12] + ETH_PPPOE_SESSION.to_bytes(2, "big")
                          + b"\x11\x00" + int(s).to_bytes(2, "big")
                          + (len(f) - 14 + 2).to_bytes(2, "big")
                          + PPP_IPV4.to_bytes(2, "big") + f[14:])
        return frames

    def expected_data(self, i: int, app):
        want = super().expected_data(i, app)
        if want is not None and self.kind[i] == UP:
            # the payload sits behind the framing in the request
            at = FRAMING + (42 if want[4] == 17 else 54)
            want = want[:5] + (self.frames[i][at:],)
        return want


# --------------------------------------------------------------------------
# the plain reference: the host codec around the default kit's
# --------------------------------------------------------------------------

class Reference(ipoe.Reference):
    """DHCP as the default kit. An upstream reply is the inner frame the
    codec strips out of the request, translated; a downstream reply is the
    translated frame inside the framing the codec builds for the session
    the host SessionManager holds for that client."""

    DIRECTIONS = {UP: "upstream frames out of the session framing",
                  DOWN: "downstream frames in the session's framing, "
                        "byte-for-byte"}

    def __init__(self, app, traffic: Traffic):
        super().__init__(app, traffic)
        self.sessions = app.components["pppoe"].sessions
        self.ac_mac = app.components["pppoe"].config.server_mac
        self.seen = dict.fromkeys(self.DIRECTIONS, 0)

    @property
    def kinds(self) -> dict:
        """The default kit's two kinds, and each direction of data frames
        the sample has not held yet: the harness counts a kind without a
        sample as missing, so a sample that lacks a direction is not
        correct."""
        up, down = self.seen[UP], self.seen[DOWN]
        out = {True: "DHCP replies byte-for-byte",
               False: "data frames by mapping, payload and both checksums "
                      f"({up} upstream, decapsulated; {down} downstream, "
                      f"framing byte-for-byte)"}
        out.update({f"none-{k}": "of the " + what
                    for k, what in self.DIRECTIONS.items() if not self.seen[k]})
        return out

    def holds(self, fid: int, raw: bytes) -> bool:
        from bng_tpu.control.pppoe import codec

        tr = self.tr
        if tr.is_dhcp[fid]:
            return super().holds(fid, raw)
        kind = int(tr.kind[fid])
        self.seen[kind] += 1
        try:
            if kind == UP:
                # what the codec strips out of the request, as a frame
                dst, src, _et, payload = codec.parse_eth(tr.frames[fid])
                proto, ip = codec.parse_ppp(
                    codec.PPPoEPacket.decode(payload).payload)
                inner = codec.eth_frame(dst, src, 0x0800, ip)
                return (proto == PPP_IPV4 and len(raw) == len(inner)
                        and raw[:14] == inner[:14] and super().holds(fid, raw))
            dst, _src, _et, payload = codec.parse_eth(raw)
            _proto, ip = codec.parse_ppp(
                codec.PPPoEPacket.decode(payload).payload)
        except ValueError:
            return False
        sess = self.sessions.by_mac(dst)
        if sess is None:
            return False
        framed = codec.eth_frame(
            sess.client_mac, self.ac_mac, ETH_PPPOE_SESSION,
            codec.PPPoEPacket(code=codec.CODE_SESSION,
                              session_id=sess.session_id,
                              payload=codec.ppp_frame(PPP_IPV4, ip)).encode())
        # the client is this flow's subscriber: its MAC and its address
        sub = int(tr.lay.nat_sub_index(tr.key[fid] // tr.lay.flows_per))
        return (raw == framed
                and sess.client_mac == int(tr.lay.sub_macs([sub])[0]).to_bytes(6, "big")
                and sess.assigned_ip == int(tr.lay.sub_ips([sub])[0])
                and super().holds(fid, codec.eth_frame(dst, self.ac_mac,
                                                       0x0800, ip)))
