"""The default kit: untagged IPoE subscribers behind CGNAT.

A kit is what belongs to one deployment and not to the harness: the
addresses the tables are filled with (`Layout`), the provisioning through
the program's bulk writers (`provision`, with the `stale-binding` control
planted in it), the traffic object for a mix (`Traffic`: two streams of
frames, `n`, `flood`, `due`, which ids the device's DHCP responder answers
(`is_dhcp`), and `reply_id`, which says of a frame the ring gave back what
it answers), and the plain reference (`Reference`: whether a popped frame
is the reply frame id i has to get, by nothing the device computed, and
the kinds of reply a sample must hold). `run.py` keeps the loop, the
window, the drain, the count checks, the sampling and the result line, and
reaches the rest through these five names. A configuration names its kit
with a `kit` key; without one it gets this file.

This is the code `lib/app.py` and `lib/gen.py` held until PR 27, moved:
for a fixed seed it builds the same bytes (tests/benchmark pins them). A
later deployment adds `kits/<name>.py` beside it, imports what it shares
from here, and edits nothing.

Every frame carries a 32-bit id that comes back with its reply: a DHCP
frame in its xid (offset 46), a data frame in the last four bytes of its
payload (NAT rewrites headers only). Two kinds of mix:

- ``flood``: a pool of frames, cycled. Before each beat the loop tops the
  RX ring up (see `Loop` in run.py for the bound on frames outstanding).
- ``fixed_rate``: open loop. A fixed number of arrivals, drawn uniformly
  over the window from the seed (a Poisson process given its count, so
  every seed offers the same number of frames), each timed from when it
  was due.

A mix's frames split into two streams by the side they enter on: the
access side (DHCP and upstream data) and the network side (downstream).
"""

from __future__ import annotations

import ipaddress
import struct
import time

import jax
import numpy as np

from benchmark.lib.app import BenchError, shape
from benchmark.lib.gen import (DISCOVER, DOWN, REQUEST, UP, Stream,
                               data_frames, dhcp_frames, mac_cols, row_bytes)

SUB_IP_BASE = (10 << 24) | (16 << 16)  # 10.16.0.0 + i: the pool's top half
ROUTER_MAC = bytes.fromhex("02ee00000001")  # network-side next hop
REMOTE_PORT = 443
FLOW_PORT_BASE = 40000


class Layout:
    """Sizes from the configuration file, addresses from the seed."""

    def __init__(self, config: dict, seed: int):
        s = config["sizes"]
        self.subscribers = int(s["subscribers"])
        self.nat_subscribers = int(s["nat_subscribers"])
        self.flows_per = int(s["flows_per_nat_subscriber"])
        self.nat_flows = self.nat_subscribers * self.flows_per
        rng = np.random.default_rng([int(seed), 0xB46])
        # subscriber i has MAC mac_base + i: an 11-bit salt above the
        # 20 bits of i moves every key to other buckets
        self.mac_base = 0x02AA00000000 + (int(rng.integers(0, 1 << 11)) << 20)
        self.remote_base = (93 << 24) | (int(rng.integers(0, 256)) << 16)
        self.xid_base = int(rng.integers(1, 1 << 7)) << 24

    def sub_macs(self, idx):
        return np.asarray(idx, dtype=np.uint64) + np.uint64(self.mac_base)

    @staticmethod
    def sub_ips(idx):
        return (np.asarray(idx, dtype=np.int64) + SUB_IP_BASE).astype(np.uint32)

    def nat_sub_index(self, j):
        """NAT subscriber j -> its subscriber index (spread over the range)."""
        return np.asarray(j) * (self.subscribers // self.nat_subscribers)

    def flows(self, k):
        """Columns (src_ip, dst_ip, src_port, dst_port, proto) of flow ids
        k = j * flows_per + f; UDP and TCP alternate by f."""
        k = np.asarray(k, dtype=np.int64)
        j, f = k // self.flows_per, k % self.flows_per
        src = self.sub_ips(self.nat_sub_index(j))
        dst = (self.remote_base + (j & 0xFFFF)).astype(np.uint32)
        return (src, dst, (FLOW_PORT_BASE + f).astype(np.uint32),
                np.full(len(k), REMOTE_PORT, np.uint32),
                np.where(f % 2 == 0, 17, 6).astype(np.uint32))


# --------------------------------------------------------------------------
# provisioning: the tables a run serves from
# --------------------------------------------------------------------------

def dhcp_table_ips(lay: Layout, idx, stale: bool):
    """The addresses the DHCP table is filled with. `stale` is the
    stale-binding control: one subscriber in eight was renumbered (to the
    address the layout, the reference and every other table hold) and the
    DHCP table that is uploaded still has the address from before."""
    ips = lay.sub_ips(idx)
    if stale:
        ips[np.asarray(idx) % 8 == 0] -= np.uint32(1 << 20)
    return ips


def provision(app, lay: Layout, stale: bool = False) -> dict:
    """Fill the tables the app serves from, then one full upload. Returns
    seconds per step and the NAT mapping of every flow id."""
    one = provision_sharded if shape(app) == "cluster" else provision_one_chip
    return one(app, lay, stale)


def provision_one_chip(app, lay: Layout, stale: bool = False) -> dict:
    """Through the bulk writers of the one engine's host tables."""
    from bng_tpu.ops.antispoof import MODE_STRICT

    c = app.components
    now = int(app.clock())
    took = {}
    idx = np.arange(lay.subscribers)
    macs, ips = lay.sub_macs(idx), lay.sub_ips(idx)
    t0 = time.time()
    c["fastpath"].add_subscribers_bulk(macs, pool_ids=1,
                                       ips=dhcp_table_ips(lay, idx, stale),
                                       lease_expiries=np.uint32(now + 86400))
    took["subscribers"] = time.time() - t0

    t0 = time.time()
    policy = c["policies"].get(app.config.default_policy)
    c["qos"].bulk_set_subscribers(ips, policy.download_bps, policy.upload_bps)
    c["antispoof"].bulk_add_bindings(macs, ips, MODE_STRICT)
    # strict for unbound MACs too (enforced on the access side only)
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
    c["engine"].resync_tables()
    jax.block_until_ready(jax.tree_util.tree_leaves(c["engine"].tables))
    took["upload"] = time.time() - t0
    return {"took": took, "nat_ip": np.asarray(nat_ip, np.uint32),
            "nat_port": np.asarray(nat_port, np.uint32)}


def provision_sharded(app, lay: Layout, stale: bool = False) -> dict:
    """The sharded twin: subscribers hash-sharded by MAC; QoS rows,
    antispoof bindings and NAT state on each subscriber's affinity shard."""
    from bng_tpu.ops.antispoof import MODE_STRICT
    from bng_tpu.runtime.hostpath import fnv1a32_cols

    cl = app.components["cluster"]
    now = int(app.clock())
    took = {}
    idx = np.arange(lay.subscribers)
    macs, ips = lay.sub_macs(idx), lay.sub_ips(idx)
    t0 = time.time()
    cl.add_subscribers_bulk(macs, pool_ids=1,
                            ips=dhcp_table_ips(lay, idx, stale),
                            lease_expiries=np.uint32(now + 86400))
    took["subscribers"] = time.time() - t0

    t0 = time.time()
    # ShardedCluster.affinity_shard_ip, vectorized: FNV-1a32 over the
    # four wire-order address bytes
    owner = fnv1a32_cols(ips.astype(">u4").view(np.uint8).reshape(-1, 4)) % cl.n
    policy = app.components["policies"].get(app.config.default_policy)
    for sh in range(cl.n):
        m = owner == sh
        cl.qos[sh].bulk_set_subscribers(ips[m], policy.download_bps,
                                        policy.upload_bps)
        cl.spoof[sh].bulk_add_bindings(macs[m], ips[m], MODE_STRICT)
        cl.spoof[sh].set_config(MODE_STRICT, log_violations=True)
    took["qos+antispoof"] = time.time() - t0

    t0 = time.time()
    cols = lay.flows(np.arange(lay.nat_flows))
    nat_ip = np.zeros(lay.nat_flows, np.uint32)
    nat_port = np.zeros(lay.nat_flows, np.uint32)
    for k in range(lay.nat_flows):
        src, dst, sport, dport, proto = (int(col[k]) for col in cols)
        if cl.affinity_shard_ip(src) != int(owner[src - SUB_IP_BASE]):
            raise BenchError("vectorized affinity differs from the cluster's")
        if k % lay.flows_per == 0 and cl.allocate_nat(src, now)[1] is None:
            raise BenchError("a shard has no NAT block left")
        got = cl.handle_new_flow(src, dst, sport, dport, proto, 64, now)[1]
        if got is None:
            raise BenchError("NAT flow refused")
        nat_ip[k], nat_port[k] = got
    took["nat"] = time.time() - t0

    t0 = time.time()
    cl.sync_tables()
    jax.block_until_ready(jax.tree_util.tree_leaves(cl.tables))
    took["upload"] = time.time() - t0
    return {"took": took, "nat_ip": nat_ip, "nat_port": nat_port}


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

class Traffic:
    """One mix, built for one layout and seed. `kind[i]`, `key[i]` (a
    subscriber index or a flow id) and `due[i]` describe frame id i."""

    def __init__(self, mix: dict, lay: Layout, prov: dict, app, seed: int,
                 seconds: float, stream: int = 0):
        self.mix, self.lay = mix, lay
        self.flood = mix["kind"] == "flood"
        if not self.flood and mix["kind"] != "fixed_rate":
            raise ValueError(f"unknown traffic kind {mix['kind']!r}")
        rng = np.random.default_rng([int(seed), 0x7AF, stream])
        if self.flood:
            pool = int(mix["pool_frames"])
            n_dhcp = int(round(pool * mix["dhcp_share"]))
            n_data = pool - n_dhcp
        else:
            n_dhcp = int(round(mix["dhcp_rate"] * seconds))
            n_data = int(round(mix["data_rate"] * seconds))
        # half the data frames enter from the network side, each the
        # downstream twin of an upstream frame's flow
        n_down = n_data // 2
        n_up = n_data - n_down
        n = n_dhcp + n_data
        self.n = n
        ids = np.arange(n)
        self.kind = np.empty(n, np.int8)
        self.key = np.empty(n, np.int64)
        renew = rng.random(n_dhcp) < mix["renewal_ratio"]
        self.kind[:n_dhcp] = np.where(renew, REQUEST, DISCOVER)
        # a client renews once in a window: no MAC twice while they last
        self.key[:n_dhcp] = (rng.choice(lay.subscribers, n_dhcp, replace=False)
                             if n_dhcp <= lay.subscribers
                             else rng.integers(0, lay.subscribers, n_dhcp))
        flow_up = rng.integers(0, lay.nat_flows, n_up)
        flow_down = flow_up[:n_down]
        self.kind[n_dhcp:n_dhcp + n_up] = UP
        self.kind[n_dhcp + n_up:] = DOWN
        self.key[n_dhcp:n_dhcp + n_up] = flow_up
        self.key[n_dhcp + n_up:] = flow_down
        self.xid_base = lay.xid_base

        frames = self.build_frames(ids, n_dhcp, flow_up, flow_down, prov, app)

        acc_ids, net_ids = ids[:n_dhcp + n_up], ids[n_dhcp + n_up:]
        if self.flood:
            self.due = None
            acc_ids, net_ids = rng.permutation(acc_ids), rng.permutation(net_ids)
            due_acc = due_net = None
        else:
            self.due = np.empty(n, np.float64)
            self.due[:] = rng.random(n) * seconds
            acc_ids = acc_ids[np.argsort(self.due[acc_ids], kind="stable")]
            net_ids = net_ids[np.argsort(self.due[net_ids], kind="stable")]
            due_acc, due_net = self.due[acc_ids], self.due[net_ids]
        self.streams = [
            Stream(True, acc_ids, [frames[i] for i in acc_ids], due_acc),
            Stream(False, net_ids, [frames[i] for i in net_ids], due_net)]
        self.frames = frames
        self.is_dhcp = self.kind <= REQUEST

    def build_frames(self, ids, n_dhcp: int, flow_up, flow_down, prov: dict,
                     app) -> list[bytes]:
        """The bytes of frame ids 0..n: DHCP, then upstream, then downstream
        data. What a kit for other framing overrides."""
        from bng_tpu.utils.net import ip_to_u32, parse_mac

        lay, n_up = self.lay, len(flow_up)
        server_mac = np.frombuffer(parse_mac(app.config.server_mac), np.uint8)
        server_ip = ip_to_u32(app.config.server_ip)
        d = slice(0, n_dhcp)
        dh = dhcp_frames(lay.sub_macs(self.key[d]), self.kind[d],
                         (ids[d] + self.xid_base).astype(np.uint32),
                         lay.sub_ips(self.key[d]), server_ip)
        src, dst, sport, dport, proto = lay.flows(flow_up)
        sub = src.astype(np.int64) - SUB_IP_BASE
        up = data_frames(mac_cols(lay.sub_macs(sub)), server_mac, src, dst,
                         sport, dport, proto, ids[n_dhcp:n_dhcp + n_up])
        _src, dst, _sport, dport, proto = lay.flows(flow_down)
        down = data_frames(np.frombuffer(ROUTER_MAC, np.uint8), server_mac,
                           dst, prov["nat_ip"][flow_down], dport,
                           prov["nat_port"][flow_down], proto,
                           ids[n_dhcp + n_up:])
        return row_bytes(dh) + row_bytes(up) + row_bytes(down)

    # -- what a frame the ring gave back answers ----------------------------

    def reply_id(self, raw: bytes) -> tuple[bool, int]:
        """(is a DHCP reply, frame id) of one frame the ring gave back."""
        if len(raw) >= 240 and raw[23] == 17 and raw[34:36] == b"\x00\x43":
            return True, int.from_bytes(raw[46:50], "big") - self.xid_base
        return False, int.from_bytes(raw[-4:], "big")

    def expected_data(self, i: int, app) -> tuple | None:
        """(src_ip, src_port, dst_ip, dst_port, proto, payload) that data
        frame i leaves with, by the host NATManager's session mirror."""
        cols = self.lay.flows([self.key[i]])
        src, dst, sport, dport, proto = (int(c[0]) for c in cols)
        got = nat_mapping(nat_of(app, src), (src, dst, sport, dport, proto))
        if got is None:
            return None
        payload = self.frames[i][42 if proto == 17 else 54:]
        if self.kind[i] == UP:
            return (got[0], got[1], dst, dport, proto, payload)
        return (dst, dport, src, sport, proto, payload)


# --------------------------------------------------------------------------
# the plain reference: the host control plane, which the device never runs
# --------------------------------------------------------------------------

class ReferenceDHCP:
    """The slow path's codec-built reply for a subscriber whose binding is
    known: a host-only DHCPServer over the same pool settings."""

    def __init__(self, app):
        from bng_tpu.control.dhcp_server import DHCPServer
        from bng_tpu.control.pool import Pool, PoolManager
        from bng_tpu.utils.net import ip_to_u32, parse_mac

        cfg = app.config
        net = ipaddress.ip_network(cfg.pool_cidr)
        pools = PoolManager()
        pools.add_pool(Pool(
            pool_id=1, network=int(net.network_address),
            prefix_len=net.prefixlen, gateway=int(net.network_address) + 1,
            dns_primary=ip_to_u32(cfg.dns_primary),
            dns_secondary=ip_to_u32(cfg.dns_secondary),
            lease_time=cfg.lease_time))
        self.server = DHCPServer(parse_mac(cfg.server_mac),
                                 ip_to_u32(cfg.server_ip), pools,
                                 clock=app.clock)

    def reply(self, frame: bytes, mac_u64: int, ip: int) -> bytes | None:
        self.server._offers[mac_u64] = (ip, 1)
        return self.server.handle_frame(frame)


def l4_checksum_ok(raw: bytes) -> bool:
    from bng_tpu.control import packets

    d = packets.decode(raw)
    if d.proto == 17 and d.l4_checksum == 0:
        return True  # UDP over IPv4: checksum not used
    seg = raw[34:14 + d.ip_total_len]
    pseudo = struct.pack("!IIBBH", d.src_ip, d.dst_ip, 0, d.proto, len(seg))
    return packets.checksum16(pseudo + seg) == 0


def nat_mapping(nat, flow) -> tuple[int, int] | None:
    """The mapping the host NATManager's session mirror holds for a flow."""
    from bng_tpu.ops.nat44 import SV_NAT_IP, SV_NAT_PORT

    src, dst, sport, dport, proto = flow
    row = nat.sessions.lookup([src, dst, (sport << 16) | dport, proto])
    if row is None:
        return None
    return int(row[SV_NAT_IP]), int(row[SV_NAT_PORT])


def nat_of(app, src_ip: int):
    c = app.components
    if shape(app) == "cluster":
        return c["cluster"].nat[c["cluster"].affinity_shard_ip(src_ip)]
    return c["nat"]


class Reference:
    """Whether a frame the ring gave back is the reply frame id i has to
    get: a DHCP reply byte-for-byte `ReferenceDHCP`'s, a data frame with the
    host NATManager's mapping, its payload and both checksums valid."""

    # is a DHCP reply -> what the check says of that kind; a sample that
    # lacks one of these kinds proves nothing and is not correct
    kinds = {True: "DHCP replies byte-for-byte",
             False: "data frames by mapping, payload and both checksums"}

    def __init__(self, app, traffic: Traffic):
        self.app, self.tr = app, traffic
        self.dhcp = ReferenceDHCP(app)

    def holds(self, fid: int, raw: bytes) -> bool:
        from bng_tpu.control import packets

        tr, lay = self.tr, self.tr.lay
        if tr.is_dhcp[fid]:
            sub = int(tr.key[fid])
            want = self.dhcp.reply(tr.frames[fid], lay.mac_base + sub,
                                   int(lay.sub_ips([sub])[0]))
            return want is not None and raw == want
        want = tr.expected_data(fid, self.app)
        d = packets.decode(raw)
        return (want is not None
                and (d.src_ip, d.src_port, d.dst_ip, d.dst_port, d.proto,
                     d.payload) == want
                and d.ip_checksum_ok and l4_checksum_ok(raw))
