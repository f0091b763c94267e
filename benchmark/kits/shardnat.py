"""The sharded-NAT kit: every subscriber of a four-chip BNG behind CGNAT.

The default kit's deployment (untagged IPoE subscribers, DHCP bindings,
QoS rows, strict antispoof bindings, a port block and flows for each NAT
subscriber) over `bng run --shards N`, at the size at which NAT no longer
fits a cache: every subscriber is a NAT subscriber, the public pool is
thousands of addresses, and each shard owns its own run of them. State is
partitioned by subscriber affinity (FNV-1a32 of the private address): a
subscriber's block and sessions live on one shard, and the ring steers its
upstream frames there by source and its downstream frames by the ownership
of the destination address.

`Layout` and `Traffic` are the default kit's (`kits/ipoe.py`, imported, not
edited): the same addresses, flows and frames for a seed.

`provision` is `provision_sharded`'s steps with the NAT step in bulk: one
`bulk_allocate_nat` and one `bulk_flows` on the cluster, which splits them
by affinity and hands each shard's share to its own `NATManager`. Before
any insert it checks that each shard's pool can hold its subscribers, so a
program that gives a shard one address (the parent of PR 42) fails at once
with the shard, its addresses and its subscribers named.

The plain reference is `Plain`: `struct`, plain Python and numpy over two
un-sharded mappings built from what provisioning returned (flow -> external
endpoint; external endpoint -> internal endpoint). It knows nothing of
shards and holds nothing of `bng_tpu`. A frame the ring gave back is held
to the frame that was sent, byte for byte outside the rewritten endpoint
and the two checksum fields, and both checksums are verified by a plain
one's-complement sum (verified, not compared with a recomputed value: an
incremental update may write the other zero). At provisioning it checks
the `sharding` guarantee over all flows: the mapping back is injective, and
each public address appears under one owner's flows alone.

`stale-binding` is the default kit's: one subscriber in eight was
renumbered and the DHCP table that is uploaded is the one from before.
"""

from __future__ import annotations

import struct
import time

import jax
import numpy as np

from benchmark.kits import ipoe
from benchmark.kits.ipoe import Layout, Traffic as _Traffic, dhcp_table_ips
from benchmark.lib.app import BenchError, shape
from benchmark.lib.gen import UP

UDP, TCP = 17, 6
IP_AT, L4_AT = 14, 34  # untagged, no IP options
IP_CSUM = slice(24, 26)
L4_CSUM = {UDP: slice(40, 42), TCP: slice(50, 52)}


def ones_sum(data: bytes) -> int:
    """The folded one's-complement sum of `data`'s 16-bit big-endian words
    (an odd last byte padded with zero): 0xFFFF over a header or segment
    that holds its own valid checksum."""
    if len(data) % 2:
        data += b"\x00"
    s = sum(struct.unpack(f"!{len(data) // 2}H", data))
    while s >> 16:
        s = (s & 0xFFFF) + (s >> 16)
    return s


def _endpoint(ip, port, proto) -> np.ndarray:
    """(address, port, protocol) as one sortable uint64."""
    return ((np.asarray(ip, np.uint64) << np.uint64(24))
            | (np.asarray(port, np.uint64) << np.uint64(8))
            | np.asarray(proto, np.uint64))


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

class Plain:
    """What the deployment does to one forwarded IPv4 frame:

    1. an upstream frame leaves toward the core with its source address
       and port rewritten to the external endpoint its flow was given;
    2. a downstream frame leaves toward the access with its destination
       address and port rewritten to the internal endpoint that the
       (external address, external port, protocol) it was sent to maps
       back to;
    3. every other byte of the frame, payload included, is as it was sent,
       and the IPv4 header checksum and the UDP / TCP checksum are valid.

    Built from per-flow columns: the flows' 5-tuples (`src`, `dst`,
    `sport`, `dport`, `proto`) and the external endpoint each was given
    (`nat_ip`, `nat_port`). `owner[k]` is an opaque label of whoever holds
    flow k's state; `check_partition` is all it is used for."""

    def __init__(self, src, dst, sport, dport, proto, nat_ip, nat_port,
                 owner=None):
        cols = [np.asarray(c, np.uint32) for c in
                (src, dst, sport, dport, proto, nat_ip, nat_port)]
        self.src, self.dst, self.sport, self.dport, self.proto = cols[:5]
        self.nat_ip, self.nat_port = cols[5:]
        self.owner = None if owner is None else np.asarray(owner)
        # flow -> external endpoint: the flows in the order of their
        # internal endpoint, so a 5-tuple is found among the few flows
        # that share its endpoint
        self.internal = _endpoint(self.src, self.sport, self.proto)
        self.by_internal = np.argsort(self.internal, kind="stable")
        self.internal_sorted = self.internal[self.by_internal]
        # external endpoint -> internal endpoint
        self.external = _endpoint(self.nat_ip, self.nat_port, self.proto)
        self.by_external = np.argsort(self.external, kind="stable")
        self.external_sorted = self.external[self.by_external]

    # -- the guarantees, over every flow ------------------------------------

    def check_partition(self) -> dict:
        """The configuration's partition guarantee as far as the mappings
        show it; raises ValueError naming the first offender. Returns what
        was counted."""
        ext, order = self.external_sorted, self.by_external
        same_ext = ext[1:] == ext[:-1]
        inner = self.internal[order]
        clash = same_ext & (inner[1:] != inner[:-1])
        if clash.any():
            a, b = order[np.nonzero(clash)[0][0]:][:2]
            raise ValueError(
                f"flows {int(a)} and {int(b)} share the external endpoint "
                f"{int(self.nat_ip[a]):#x}:{int(self.nat_port[a])} "
                f"(protocol {int(self.proto[a])}) from different internal "
                f"endpoints: the mapping back is not injective")
        out = {"flows": len(ext),
               "external_endpoints": int(len(ext) - same_ext.sum()),
               "public_addresses": len(np.unique(self.nat_ip))}
        if self.owner is not None:
            # each address under one owner, each internal address too
            for what, key in (("public address", self.nat_ip),
                              ("subscriber", self.src)):
                by = np.lexsort((self.owner, key))
                k, o = key[by], self.owner[by]
                split = (k[1:] == k[:-1]) & (o[1:] != o[:-1])
                if split.any():
                    at = np.nonzero(split)[0][0]
                    raise ValueError(
                        f"{what} {int(k[at]):#x} appears under owners "
                        f"{int(o[at])} and {int(o[at + 1])}")
            out["owners"] = len(np.unique(self.owner))
        return out

    # -- one frame ------------------------------------------------------------

    def external_of(self, src: int, dst: int, sport: int, dport: int,
                    proto: int):
        """The external endpoint of a flow's 5-tuple, or None."""
        key = _endpoint(src, sport, proto)
        lo = int(np.searchsorted(self.internal_sorted, key, side="left"))
        hi = int(np.searchsorted(self.internal_sorted, key, side="right"))
        for k in self.by_internal[lo:hi]:
            if int(self.dst[k]) == dst and int(self.dport[k]) == dport:
                return int(self.nat_ip[k]), int(self.nat_port[k])
        return None

    def internal_of(self, ip: int, port: int, proto: int):
        """The internal endpoint an external one maps back to, or None."""
        key = _endpoint(ip, port, proto)
        at = int(np.searchsorted(self.external_sorted, key, side="left"))
        if at == len(self.external_sorted) or self.external_sorted[at] != key:
            return None
        k = self.by_external[at]
        return int(self.src[k]), int(self.sport[k])

    def expect(self, sent: bytes, up: bool) -> bytes | None:
        """The frame `sent` has to leave as, its checksum fields left as
        sent (they are verified, not compared); None where the mappings
        hold nothing for it or it is not an untagged IPv4 UDP / TCP frame
        without options."""
        if (len(sent) < L4_AT + 8 or sent[12:14] != b"\x08\x00"
                or sent[IP_AT] != 0x45 or sent[23] not in L4_CSUM):
            return None
        proto = sent[23]
        src, dst = struct.unpack_from("!II", sent, 26)
        sport, dport = struct.unpack_from("!HH", sent, L4_AT)
        if up:
            got = self.external_of(src, dst, sport, dport, proto)
            if got is None:
                return None
            return (sent[:26] + struct.pack("!I", got[0]) + sent[30:L4_AT]
                    + struct.pack("!H", got[1]) + sent[L4_AT + 2:])
        got = self.internal_of(dst, dport, proto)
        if got is None:
            return None
        return (sent[:30] + struct.pack("!I", got[0]) + sent[L4_AT:L4_AT + 2]
                + struct.pack("!H", got[1]) + sent[L4_AT + 4:])

    @staticmethod
    def checksums_ok(raw: bytes) -> bool:
        """Both checksums of an untagged IPv4 UDP / TCP frame verify."""
        proto = raw[23]
        total = struct.unpack_from("!H", raw, 16)[0]
        seg = raw[L4_AT:IP_AT + total]
        if ones_sum(raw[IP_AT:L4_AT]) != 0xFFFF or len(seg) < 8:
            return False
        if proto == UDP and raw[L4_CSUM[UDP]] == b"\x00\x00":
            return False  # "no checksum": every frame sent carries one
        pseudo = raw[26:34] + struct.pack("!BBH", 0, proto, len(seg))
        return ones_sum(pseudo + seg) == 0xFFFF

    def holds(self, sent: bytes, raw: bytes, up: bool) -> bool:
        """Whether `raw` is the one frame `sent` has to leave as."""
        want = self.expect(sent, up)
        if want is None or len(raw) != len(want):
            return False
        fields = (IP_CSUM, L4_CSUM[sent[23]])
        blank = bytearray(raw), bytearray(want)
        for b in blank:
            for f in fields:
                b[f] = b"\x00\x00"
        return blank[0] == blank[1] and self.checksums_ok(raw)


# --------------------------------------------------------------------------
# provisioning
# --------------------------------------------------------------------------

def pool_room(cl, owner_of_nat_subs: np.ndarray) -> list[dict]:
    """Each shard's pool against the NAT subscribers it owns: addresses,
    the port blocks they hold, the subscribers. Raises BenchError at the
    first shard whose pool is too small, before anything is inserted."""
    rooms = []
    for s in range(cl.n):
        nat = cl.nat[s]
        lo, hi = nat.port_range
        blocks = len(nat.public_ips) * ((hi - lo + 1) // nat.ports_per_subscriber)
        subs = int((owner_of_nat_subs == s).sum())
        rooms.append({"shard": s, "addresses": len(nat.public_ips),
                      "blocks": blocks, "subscribers": subs})
        if subs > blocks:
            raise BenchError(
                f"shard {s} owns {len(nat.public_ips)} public address(es), "
                f"{blocks} port blocks of {nat.ports_per_subscriber}, and "
                f"{subs} NAT subscribers hash to it: its pool cannot hold "
                f"them (a program whose shards take one address each of "
                f"--nat-public-ips cannot run this configuration)")
    return rooms


def provision(app, lay: Layout, stale: bool = False) -> dict:
    """`kits/ipoe.py provision_sharded`'s steps, the NAT step in bulk a
    shard. Returns seconds per step, the NAT mapping of every flow id and
    the plain reference built from it (`plain`)."""
    from bng_tpu.ops.antispoof import MODE_STRICT
    from bng_tpu.runtime.hostpath import fnv1a32_cols

    if shape(app) != "cluster":
        raise BenchError("the shardnat kit provisions a cluster: the "
                         "configuration's argv lacks --shards")
    cl = app.components["cluster"]
    now = int(app.clock())
    took = {}
    idx = np.arange(lay.subscribers)
    macs, ips = lay.sub_macs(idx), lay.sub_ips(idx)
    # ShardedCluster.affinity_shard_ip, vectorized: FNV-1a32 over the four
    # wire-order address bytes
    owner = (fnv1a32_cols(ips.astype(">u4").view(np.uint8).reshape(-1, 4))
             % cl.n).astype(np.int64)
    nat_idx = lay.nat_sub_index(np.arange(lay.nat_subscribers))
    for probe in (0, lay.subscribers // 2, lay.subscribers - 1):
        if cl.affinity_shard_ip(int(ips[probe])) != int(owner[probe]):
            raise BenchError("vectorized affinity differs from the cluster's")
    rooms = pool_room(cl, owner[nat_idx])

    t0 = time.time()
    cl.add_subscribers_bulk(macs, pool_ids=1,
                            ips=dhcp_table_ips(lay, idx, stale),
                            lease_expiries=np.uint32(now + 86400))
    took["subscribers"] = time.time() - t0

    t0 = time.time()
    policy = app.components["policies"].get(app.config.default_policy)
    for sh in range(cl.n):
        m = owner == sh
        cl.qos[sh].bulk_set_subscribers(ips[m], policy.download_bps,
                                        policy.upload_bps)
        cl.spoof[sh].bulk_add_bindings(macs[m], ips[m], MODE_STRICT)
        cl.spoof[sh].set_config(MODE_STRICT, log_violations=True)
    took["qos+antispoof"] = time.time() - t0

    t0 = time.time()
    made = cl.bulk_allocate_nat(ips[nat_idx], now)
    if int(made.sum()) != lay.nat_subscribers:
        raise BenchError(f"NAT blocks: {made.tolist()} a shard of "
                         f"{lay.nat_subscribers}")
    took["nat_blocks"] = time.time() - t0
    t0 = time.time()
    src, dst, sport, dport, proto = lay.flows(np.arange(lay.nat_flows))
    nat_ip, nat_port, ok = cl.bulk_flows(src, dst, sport, dport, proto,
                                         pkt_len=64, now=now)
    if not bool(ok.all()):
        raise BenchError(f"NAT flows: {int(ok.sum())} of {len(ok)}")
    took["nat_flows"] = time.time() - t0

    t0 = time.time()
    plain = Plain(src, dst, sport, dport, proto, nat_ip, nat_port,
                  owner=owner[src.astype(np.int64) - ipoe.SUB_IP_BASE])
    try:
        counted = plain.check_partition()
    except ValueError as e:
        raise BenchError(f"the sharding guarantee does not hold: {e}") from e
    took["guarantee"] = time.time() - t0
    print("shards: " + "; ".join(
        f"{r['shard']}: {r['addresses']} addresses, {r['subscribers']} NAT "
        f"subscribers of {r['blocks']} blocks, {cl.nat[r['shard']].sessions.count} "
        f"sessions" for r in rooms) + f"; checked {counted}", flush=True)

    t0 = time.time()
    cl.sync_tables()
    jax.block_until_ready(jax.tree_util.tree_leaves(cl.tables))
    took["upload"] = time.time() - t0
    return {"took": took, "nat_ip": nat_ip, "nat_port": nat_port,
            "plain": plain}


# --------------------------------------------------------------------------
# traffic and the reference a run is held to
# --------------------------------------------------------------------------

class Traffic(_Traffic):
    """The default kit's mix, frame for frame; it keeps the plain
    reference that provisioning built for the check."""

    def __init__(self, mix: dict, lay: Layout, prov: dict, app, seed: int,
                 seconds: float, stream: int = 0):
        self.plain = prov["plain"]
        super().__init__(mix, lay, prov, app, seed, seconds, stream)


class Reference(ipoe.Reference):
    """DHCP as the default kit (byte for byte a host-only `DHCPServer`'s);
    a data frame is `Plain`'s, and nothing the cluster holds is asked."""

    kinds = {True: "DHCP replies byte-for-byte",
             False: "data frames byte-for-byte outside the rewritten "
                    "endpoint, both checksums verified"}

    def holds(self, fid: int, raw: bytes) -> bool:
        tr = self.tr
        if tr.is_dhcp[fid]:
            return super().holds(fid, raw)
        return tr.plain.holds(tr.frames[fid], raw, int(tr.kind[fid]) == UP)
