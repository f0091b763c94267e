#!/usr/bin/env python3
"""chip_smoke.py — `bng run`'s own loop on the chip, at reference capacity.

Builds the BNGApp that `bng run --scheduler-enabled` builds (same flags,
same `_config_from_args`), loads a 1,000,000-subscriber table set through
the bulk writers, and beats `app.drive_once()` over frames pushed on the
app's ring. Every reply is checked against an expectation no device code
computed: a host-only reference DHCP server (the codec), and the host
mirrors of the NAT, QoS and antispoof tables.

One process, one chip, chip-or-fail: no TPU, no result. It measures
nothing — it is the quickest proof that the system still starts and
answers correctly on the device.

    python chip_smoke.py              # one chip, the served path
    python chip_smoke.py --chips 4    # only the sharded path, four chips

The phases are plain functions over a `Sizes`; tests/test_chip_smoke.py
rehearses them on the CPU at a tiny size. This program has no CPU mode.
"""

from __future__ import annotations

import argparse
import ipaddress
import json
import struct
import sys
import time
from typing import NamedTuple

import jax
import numpy as np

import bng_tpu.ops.qos as qos_mod
from bng_tpu import cli
from bng_tpu.control import dhcp_codec, packets
from bng_tpu.control.dhcp_server import DHCPServer
from bng_tpu.control.pool import Pool, PoolManager
from bng_tpu.ops.antispoof import AST_DROPPED, AST_V4_VIOL, MODE_STRICT
from bng_tpu.ops.dhcp import ST_HIT
from bng_tpu.ops.nat44 import SV_NAT_IP, SV_NAT_PORT
from bng_tpu.ops.qos import QST_PKTS_DROPPED
from bng_tpu.ops.table import nbuckets_for
from bng_tpu.runtime.hostpath import fnv1a32_cols
from bng_tpu.utils.jaxenv import enable_compilation_cache
from bng_tpu.utils.net import ip_to_u32, parse_mac, u32_to_ip

MAC_BASE = 0x02AA00000000  # provisioned subscriber i = MAC_BASE + i
NEW_MAC_BASE = 0x02CC00000000  # MACs nobody provisioned
POOL_CIDR = "10.0.0.0/11"  # 2M addresses: the slow path allocates upward
SUB_IP_BASE = (10 << 24) | (16 << 16)  # 10.16.0.0 + i: the pool's top half
REMOTE_BASE = (93 << 24) | (184 << 16)  # internet-side peers
PUBLIC_BASE = (198 << 24) | (18 << 16)  # NAT public block (RFC 2544 range)
ROUTER_MAC = bytes.fromhex("02ee00000001")  # network-side next hop
PORTS_PER_SUB = 1024  # BNGConfig.nat_ports_per_subscriber default
BLOCKS_PER_PUBLIC_IP = (65535 - 1024 + 1) // PORTS_PER_SUB


class Sizes(NamedTuple):
    """What is loaded and offered. The defaults are the reference's
    1M-entry maps (bpf/maps.h:10); its 4M-session NAT capacity
    (bpf/nat44.c:38-40) is cut to 1M flows to keep host set-up short."""

    subscribers: int = 1_000_000
    nat_subscribers: int = 250_000
    flows_per_nat_subscriber: int = 4
    batch: int = 8192  # bulk lane lanes per device step
    discovers: int = 256
    requests: int = 128
    new_macs: int = 8
    nat_probes: int = 256
    qos_burst_frames: int = 8
    spoofed_frames: int = 4

    @property
    def nat_flows(self) -> int:
        return self.nat_subscribers * self.flows_per_nat_subscriber


class SmokeError(AssertionError):
    """A phase's outcome differed from the host expectation."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def say(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------------
# build: the app `bng run` builds
# --------------------------------------------------------------------------

def run_argv(sizes: Sizes, shards: int = 1) -> list[str]:
    """The `bng run` command line this smoke stands for."""
    n_public = -(-sizes.nat_subscribers // BLOCKS_PER_PUBLIC_IP) + 1
    argv = ["--pool-cidr", POOL_CIDR, "--batch-size", str(sizes.batch),
            # the in-memory ring is built for a packet source; the
            # generator itself is switched off after the build so that
            # the smoke pushes every frame
            "--synthetic-subs", "1"]
    if shards > 1:
        argv += ["--shards", str(shards), "--shard-nbuckets",
                 str(nbuckets_for(sizes.subscribers // shards))]
    else:
        argv += ["--scheduler-enabled",
                 "--max-subscribers", str(sizes.subscribers),
                 "--max-nat-sessions", str(sizes.nat_flows),
                 "--max-nat-subscribers", str(sizes.nat_subscribers),
                 "--nat-public-ips",
                 *(u32_to_ip(PUBLIC_BASE + i) for i in range(n_public))]
    return argv


def build_app(sizes: Sizes, shards: int = 1):
    parser = argparse.ArgumentParser()
    cli._add_run_flags(parser)
    app = cli.BNGApp(cli._config_from_args(
        parser.parse_args(run_argv(sizes, shards))))
    app.config.synthetic_subs = 0
    return app


def sub_macs(idx):
    return np.asarray(idx, dtype=np.uint64) + np.uint64(MAC_BASE)


def sub_ips(idx):
    return (np.asarray(idx, dtype=np.int64) + SUB_IP_BASE).astype(np.uint32)


def nat_sub_index(sizes: Sizes, j):
    """NAT subscriber j -> its subscriber index (spread over the range)."""
    return j * (sizes.subscribers // sizes.nat_subscribers)


def flow_of(sizes: Sizes, j: int, f: int) -> tuple[int, int, int, int, int]:
    """(src_ip, dst_ip, src_port, dst_port, proto) of NAT subscriber j's
    flow f: UDP and TCP alternate."""
    src = SUB_IP_BASE + nat_sub_index(sizes, j)
    return (src, REMOTE_BASE + (j & 0xFFFF), 40000 + f, 443,
            17 if f % 2 == 0 else 6)


def provision(app, sizes: Sizes) -> dict:
    """Load the table set through the bulk writers, then one full
    upload. Returns seconds per step."""
    c = app.components
    now = int(app.clock())
    took = {}

    def timed(name, t0):
        took[name] = round(time.time() - t0, 2)

    idx = np.arange(sizes.subscribers)
    macs, ips = sub_macs(idx), sub_ips(idx)
    t0 = time.time()
    c["fastpath"].add_subscribers_bulk(macs, pool_ids=1, ips=ips,
                                       lease_expiries=np.uint32(now + 86400))
    timed("subscribers", t0)

    t0 = time.time()
    policy = c["policies"].get(app.config.default_policy)
    c["qos"].bulk_set_subscribers(ips, policy.download_bps, policy.upload_bps)
    timed("qos", t0)

    t0 = time.time()
    c["antispoof"].bulk_add_bindings(macs, ips, MODE_STRICT)
    # strict for unbound MACs too (enforced on the access side only)
    c["antispoof"].set_config(MODE_STRICT, log_violations=True)
    timed("antispoof", t0)

    t0 = time.time()
    j = np.arange(sizes.nat_subscribers)
    nat_ips = sub_ips(nat_sub_index(sizes, j))
    made = c["nat"].bulk_allocate_nat(nat_ips, now)
    check(made == sizes.nat_subscribers,
          f"NAT blocks: {made} of {sizes.nat_subscribers}")
    timed("nat_blocks", t0)

    t0 = time.time()
    F = sizes.flows_per_nat_subscriber
    f = np.tile(np.arange(F), sizes.nat_subscribers)
    jj = np.repeat(j, F)
    _, _, ok = c["nat"].bulk_flows(
        np.repeat(nat_ips, F), (REMOTE_BASE + (jj & 0xFFFF)).astype(np.uint32),
        40000 + f, 443, np.where(f % 2 == 0, 17, 6), pkt_len=64, now=now)
    check(bool(ok.all()), f"NAT flows: {int(ok.sum())} of {len(ok)}")
    timed("nat_flows", t0)

    # the one QoS-limited subscriber: 8 kbit/s, a 1500-byte bucket
    c["qos"].set_subscriber(flow_of(sizes, _qos_sub(sizes), 0)[0],
                            down_bps=8000, up_bps=8000,
                            up_burst=1500, down_burst=1500)

    t0 = time.time()
    c["engine"].resync_tables()
    jax.block_until_ready(jax.tree_util.tree_leaves(c["engine"].tables))
    timed("upload", t0)
    return took


def _qos_sub(sizes: Sizes) -> int:
    return sizes.nat_subscribers - 1  # kept out of the NAT probes


# --------------------------------------------------------------------------
# serve: push on the ring, beat the loop, pop the replies
# --------------------------------------------------------------------------

def _idle(app) -> bool:
    c = app.components
    if c["ring"].rx_pending():
        return False
    if "scheduler" in c:
        snap = c["scheduler"].stats_snapshot()
        return not any(snap[lane]["queue_depth"] or snap[lane]["inflight"]
                       for lane in ("express", "bulk"))
    return c["cluster"]._inflight is None


def serve(app, frames, from_access: bool = True,
          limit_s: float = 300.0) -> list[bytes]:
    """`ring.rx_push` the frames, beat `app.drive_once()` until the ring
    and the lanes drain, return every frame the ring gives back
    (`tx_pop`; the sharded loop queues forwarded frames on `fwd_pop`)."""
    ring = app.components["ring"]
    out: list[bytes] = []
    deadline = time.time() + limit_s
    wave = 512  # under the ring's RX depth
    for at in range(0, len(frames), wave):
        for fr in frames[at:at + wave]:
            check(ring.rx_push(fr, from_access=from_access),
                  "ring refused a frame")
        while True:
            app.drive_once()
            for pop in (ring.tx_pop, ring.fwd_pop):
                while (got := pop()) is not None:
                    out.append(got[0])
            if _idle(app):
                break
            check(time.time() < deadline, "the ring did not drain in time")
    return out


def _l4_checksum_ok(raw: bytes) -> bool:
    d = packets.decode(raw)
    if d.proto == 17 and d.l4_checksum == 0:
        return True  # UDP over IPv4: checksum not used
    seg = raw[34:14 + d.ip_total_len]
    pseudo = struct.pack("!IIBBH", d.src_ip, d.dst_ip, 0, d.proto, len(seg))
    return packets.checksum16(pseudo + seg) == 0


def _stats(app) -> dict:
    """Folded device counters + the host slow path's own."""
    c = app.components
    if "engine" in c:
        st = c["engine"].stats
        dev = {"dhcp": st.dhcp.copy(), "nat": st.nat.copy(),
               "qos": st.qos.copy(), "spoof": st.spoof.copy()}
    else:
        st = c["cluster"].stats
        dev = {k: np.asarray(st.get(k, np.zeros(16, np.uint64))).copy()
               for k in ("dhcp", "nat", "qos", "spoof")}
    host = c["dhcp"].stats
    dev["host_dhcp"] = host.discover + host.request
    return dev


# --------------------------------------------------------------------------
# the host-side expectation for DHCP: a reference server no device feeds
# --------------------------------------------------------------------------

class ReferenceDHCP:
    """The slow path's codec-built reply for a subscriber whose binding
    is known — a host-only DHCPServer over the same pool settings."""

    def __init__(self, app):
        cfg = app.config
        net = ipaddress.ip_network(cfg.pool_cidr)
        pools = PoolManager()
        pools.add_pool(Pool(
            pool_id=1, network=int(net.network_address),
            prefix_len=net.prefixlen,
            gateway=int(net.network_address) + 1,
            dns_primary=ip_to_u32(cfg.dns_primary),
            dns_secondary=ip_to_u32(cfg.dns_secondary),
            lease_time=cfg.lease_time))
        self.server = DHCPServer(parse_mac(cfg.server_mac),
                                 ip_to_u32(cfg.server_ip), pools,
                                 clock=app.clock)

    def reply(self, frame: bytes, mac_u64: int, ip: int) -> bytes:
        self.server._offers[mac_u64] = (ip, 1)
        out = self.server.handle_frame(frame)
        check(out is not None, "reference server gave no reply")
        return out


def dhcp_frame(mac_u64: int, msg_type: int, xid: int,
               requested_ip: int = 0, server_id: int = 0) -> bytes:
    mac = int(mac_u64).to_bytes(6, "big")
    p = dhcp_codec.build_request(mac, msg_type, xid=xid,
                                 requested_ip=requested_ip,
                                 server_id=server_id)
    p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
    return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                              p.encode().ljust(320, b"\x00"))


def _by_xid(replies: list[bytes]) -> dict[int, bytes]:
    out = {}
    for raw in replies:
        d = packets.decode(raw)
        if d.proto == 17 and d.src_port == 67:
            out[dhcp_codec.decode(d.payload).xid] = raw
    return out


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_dhcp_known(app, sizes: Sizes, ref: ReferenceDHCP) -> str:
    """DISCOVERs and REQUESTs from provisioned MACs over the whole key
    range: OFFER/ACK byte-identical to the reference server's, from the
    device (the host slow path sees none of them)."""
    n = sizes.discovers + sizes.requests
    picks = np.linspace(0, sizes.subscribers - 1, n).astype(np.int64)
    server_ip = ip_to_u32(app.config.server_ip)
    frames, want = [], {}
    for k, i in enumerate(picks):
        mac, ip, xid = MAC_BASE + int(i), SUB_IP_BASE + int(i), 0x51000000 + k
        if k < sizes.discovers:
            fr = dhcp_frame(mac, dhcp_codec.DISCOVER, xid)
        else:
            fr = dhcp_frame(mac, dhcp_codec.REQUEST, xid, requested_ip=ip,
                            server_id=server_ip)
        frames.append(fr)
        want[xid] = (ref.reply(fr, mac, ip), ip)
    before = _stats(app)
    got = _by_xid(serve(app, frames))
    after = _stats(app)
    check(len(got) == n, f"DHCP replies: {len(got)} of {n}")
    for xid, (expect, ip) in want.items():
        raw = got.get(xid)
        check(raw is not None, f"no reply for xid {xid:#x}")
        d = packets.decode(raw)
        p = dhcp_codec.decode(d.payload)
        check(p.yiaddr == ip and p.server_id == server_ip,
              f"xid {xid:#x}: yiaddr/server-id differ from the binding")
        check(d.ip_checksum_ok and _l4_checksum_ok(raw),
              f"xid {xid:#x}: bad IP/UDP checksum")
        check(raw == expect,
              f"xid {xid:#x}: reply differs from the reference server's")
    hits = int(after["dhcp"][ST_HIT] - before["dhcp"][ST_HIT])
    check(hits == n, f"device DHCP hits {hits}, expected {n}")
    check(after["host_dhcp"] == before["host_dhcp"],
          "the host slow path answered a provisioned MAC")
    return (f"{sizes.discovers} OFFER + {sizes.requests} ACK from the device, "
            f"byte-identical to the reference server")


def phase_dhcp_new(app, sizes: Sizes) -> str:
    """MACs nobody provisioned: the slow path allocates and writes the
    table (the update drain runs on the device); the same MAC's next
    DISCOVER is answered from the device."""
    c = app.components
    server_ip = ip_to_u32(app.config.server_ip)
    macs = [NEW_MAC_BASE + k for k in range(sizes.new_macs)]

    def exchange(msg, base_xid, ips=None):
        frames = [dhcp_frame(m, msg, base_xid + k,
                             requested_ip=ips[k] if ips else 0,
                             server_id=server_ip if ips else 0)
                  for k, m in enumerate(macs)]
        got = _by_xid(serve(app, frames))
        check(len(got) == len(macs),
              f"{len(got)} replies for {len(macs)} new MACs")
        return [dhcp_codec.decode(packets.decode(got[base_xid + k]).payload)
                for k in range(len(macs))]

    before = _stats(app)
    offers = exchange(dhcp_codec.DISCOVER, 0x52000000)
    ips = [p.yiaddr for p in offers]
    check(all(p.msg_type == dhcp_codec.OFFER for p in offers), "not OFFERs")
    check(len(set(ips)) == len(ips)
          and all(0 < ip < SUB_IP_BASE for ip in ips),
          "slow-path addresses collide or leave the pool's free half")
    acks = exchange(dhcp_codec.REQUEST, 0x53000000, ips)
    check(all(p.msg_type == dhcp_codec.ACK and p.yiaddr == ip
              for p, ip in zip(acks, ips)), "not ACKs of the offered address")
    mid = _stats(app)
    check(int(mid["dhcp"][ST_HIT] - before["dhcp"][ST_HIT]) == 0,
          "the device answered a MAC before the slow path wrote it")
    for m, ip in zip(macs, ips):
        lease = c["dhcp"].leases.get(m)
        check(lease is not None and lease.ip == ip, "no host lease")
    again = exchange(dhcp_codec.DISCOVER, 0x54000000)
    after = _stats(app)
    check(all(p.msg_type == dhcp_codec.OFFER and p.yiaddr == ip
              for p, ip in zip(again, ips)), "re-DISCOVER: another address")
    check(int(after["dhcp"][ST_HIT] - mid["dhcp"][ST_HIT]) == len(macs)
          and after["host_dhcp"] == mid["host_dhcp"],
          "re-DISCOVER was not answered from the device")
    return (f"{len(macs)} new MACs: DORA through the slow path, then the "
            f"re-DISCOVER answered from the device table")


def _data_frame(src_mac: bytes, dst_mac: bytes, flow, payload: bytes) -> bytes:
    src, dst, sport, dport, proto = flow
    build = packets.udp_packet if proto == 17 else packets.tcp_packet
    return build(src_mac, dst_mac, src, dst, sport, dport, payload)


def _nat_mapping(nat, flow) -> tuple[int, int]:
    """The mapping the host NATManager holds for a provisioned flow."""
    src, dst, sport, dport, proto = flow
    row = nat.sessions.lookup([src, dst, (sport << 16) | dport, proto])
    check(row is not None, "flow missing from the host session mirror")
    return int(row[SV_NAT_IP]), int(row[SV_NAT_PORT])


def phase_nat(app, sizes: Sizes, nat_of=None) -> str:
    """Upstream frames of provisioned flows SNAT to the host's mapping;
    the matching downstream frames DNAT back. Checksums valid."""
    nat_of = nat_of or (lambda _ip: app.components["nat"])
    server_mac = parse_mac(app.config.server_mac)
    js = np.linspace(0, sizes.nat_subscribers - 2,
                     sizes.nat_probes).astype(np.int64)  # not _qos_sub
    flows = [flow_of(sizes, int(j), k % sizes.flows_per_nat_subscriber)
             for k, j in enumerate(js)]
    flows = list(dict.fromkeys(flows))
    maps = [_nat_mapping(nat_of(fl[0]), fl) for fl in flows]
    payloads = [b"up-%06d" % k for k in range(len(flows))]

    up = [_data_frame(int(MAC_BASE + fl[0] - SUB_IP_BASE).to_bytes(6, "big"),
                      server_mac, fl, pl) for fl, pl in zip(flows, payloads)]
    got = {}
    for raw in serve(app, up, from_access=True):
        d = packets.decode(raw)
        got[(d.src_ip, d.src_port, d.proto)] = (raw, d)
    for fl, (nat_ip, nat_port), pl in zip(flows, maps, payloads):
        hit = got.get((nat_ip, nat_port, fl[4]))
        check(hit is not None, f"no SNAT output for flow {fl}")
        raw, d = hit
        check((d.dst_ip, d.dst_port, d.payload) == (fl[1], fl[3], pl),
              f"SNAT changed more than the source: {fl}")
        check(d.ip_checksum_ok and _l4_checksum_ok(raw),
              f"SNAT checksum invalid: {fl}")
    check(len(got) == len(flows), f"SNAT outputs: {len(got)} of {len(flows)}")

    down = [_data_frame(ROUTER_MAC, server_mac,
                        (fl[1], nat_ip, fl[3], nat_port, fl[4]), b"dn" + pl)
            for fl, (nat_ip, nat_port), pl in zip(flows, maps, payloads)]
    got = {}
    for raw in serve(app, down, from_access=False):
        d = packets.decode(raw)
        got[(d.dst_ip, d.dst_port, d.proto)] = (raw, d)
    for fl, pl in zip(flows, payloads):
        hit = got.get((fl[0], fl[2], fl[4]))
        check(hit is not None, f"no DNAT output for flow {fl}")
        raw, d = hit
        check((d.src_ip, d.src_port, d.payload) == (fl[1], fl[3], b"dn" + pl),
              f"DNAT changed more than the destination: {fl}")
        check(d.ip_checksum_ok and _l4_checksum_ok(raw),
              f"DNAT checksum invalid: {fl}")
    check(len(got) == len(flows), f"DNAT outputs: {len(got)} of {len(flows)}")
    return (f"{len(flows)} flows SNAT to the host's mapping and DNAT back, "
            f"UDP and TCP, checksums valid")


def phase_qos_spoof(app, sizes: Sizes, nat_of=None) -> str:
    """One subscriber offered more than its 1500-byte bucket, and one
    spoofed source: dropped on the device and counted."""
    nat_of = nat_of or (lambda _ip: app.components["nat"])
    server_mac = parse_mac(app.config.server_mac)
    fl = flow_of(sizes, _qos_sub(sizes), 0)
    nat_ip, nat_port = _nat_mapping(nat_of(fl[0]), fl)
    mac = int(MAC_BASE + fl[0] - SUB_IP_BASE).to_bytes(6, "big")
    burst = [_data_frame(mac, server_mac, fl, b"q" * 400)
             ] * sizes.qos_burst_frames
    fits = 1500 // len(burst[0])  # sequential token bucket, no refill
    # a bound MAC sending from its neighbour's address
    victim = flow_of(sizes, 0, 0)
    liar = int(MAC_BASE + victim[0] - SUB_IP_BASE + 1).to_bytes(6, "big")
    spoofed = [_data_frame(liar, server_mac, victim, b"spoof")
               ] * sizes.spoofed_frames

    before = _stats(app)
    out = [packets.decode(raw) for raw in serve(app, burst + spoofed)]
    after = _stats(app)
    passed = [d for d in out if (d.src_ip, d.src_port) == (nat_ip, nat_port)]
    check(len(passed) == fits and len(out) == fits,
          f"QoS let {len(passed)} of {len(burst)} through, expected {fits}; "
          f"{len(out) - len(passed)} other frames came out")
    q = int(after["qos"][QST_PKTS_DROPPED] - before["qos"][QST_PKTS_DROPPED])
    check(q == len(burst) - fits, f"QoS counted {q} drops")
    s = int(after["spoof"][AST_DROPPED] - before["spoof"][AST_DROPPED])
    v = int(after["spoof"][AST_V4_VIOL] - before["spoof"][AST_V4_VIOL])
    check(s == len(spoofed) and v == len(spoofed),
          f"antispoof counted {s} drops, {v} v4 violations")
    return (f"QoS passed {fits} of {len(burst)} and counted {q} drops; "
            f"antispoof dropped and counted {s} spoofed frames")


def nothing_gave_way(app, platform: str) -> list[str]:
    """The facts of section 2.4: assert each, return them as lines."""
    c = app.components
    sched, eng = c["scheduler"], c["engine"]
    snap = sched.stats_snapshot()["express"]
    check(not sched.express_fallbacks,
          f"express fallbacks: {sched.express_fallbacks}")
    check(sched._aot_ready and snap["aot_dispatches"] > 0
          and snap["jit_dispatches"] == 0 and snap["aot_misses"] == 0,
          f"the AOT express program did not serve every dispatch: {snap}")
    leaves = jax.tree_util.tree_leaves(eng.tables)
    homes = set().union(*(leaf.devices() for leaf in leaves))
    check(all(d.platform == platform for d in homes)
          and homes <= set(jax.devices()),
          f"table leaves on {homes}")
    return [
        "express fallbacks: none",
        f"AOT express program ready; dispatches aot={snap['aot_dispatches']} "
        f"jit=0 misses=0",
        f"table impl: {eng.table_impl}; QoS prefix impl: "
        f"{qos_mod.PREFIX_IMPL}; host path: {eng.host_path}",
        f"ring: {type(c['ring']).__name__}",
        f"{len(leaves)} table leaves on {sorted(str(d) for d in homes)}",
    ]


def run_one_chip(sizes: Sizes, platform: str = "tpu") -> None:
    say(f"build: bng run {' '.join(run_argv(sizes)[:13])} ... "
        f"(synthetic generator off: the smoke pushes every frame)")
    say(f"sizes: {sizes.subscribers} subscribers, {sizes.nat_flows} NAT flows "
        f"over {sizes.nat_subscribers} NAT subscribers (the reference's 4M "
        f"NAT sessions, bpf/nat44.c:38-40, cut to keep host set-up short); "
        f"QoS rows and antispoof bindings for every subscriber")
    t0 = time.time()
    app = build_app(sizes)
    try:
        say(f"app built in {time.time() - t0:.1f} s (AOT express compile "
            f"included)")
        took = provision(app, sizes)
        say(f"provisioned through the bulk writers, seconds: {took}")
        eng = app.components["engine"]
        resident = sum(x.nbytes for x in
                       jax.tree_util.tree_leaves(eng.tables))
        ref = ReferenceDHCP(app)
        t0 = time.time()
        say("dhcp (provisioned): " + phase_dhcp_known(app, sizes, ref))
        say(f"  first express dispatches took {time.time() - t0:.1f} s")
        say("dhcp (new MACs): " + phase_dhcp_new(app, sizes))
        t0 = time.time()
        say("nat: " + phase_nat(app, sizes))
        say(f"  first bulk dispatches took {time.time() - t0:.1f} s "
            f"(fused-step compile included)")
        say("qos + antispoof: " + phase_qos_spoof(app, sizes))
        t0 = time.time()
        app.tick()
        say(f"tick: one maintenance heartbeat in {time.time() - t0:.1f} s")
        for line in nothing_gave_way(app, platform):
            say("fact: " + line)
        stats = jax.devices()[0].memory_stats() or {}
        say(f"device memory: tables {resident} bytes resident; "
            f"bytes_in_use {stats.get('bytes_in_use', 'n/a')}; "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")
    finally:
        app.close()


# --------------------------------------------------------------------------
# four chips: the sharded serving path (`bng run --shards N`)
# --------------------------------------------------------------------------

# A shard's NAT pool is one public IP today (parallel/sharded.py), which
# is 63 port blocks: the sharded phase holds the same 1M subscribers but
# a NAT set that fits, and says so.
SHARDED_NAT_SUBSCRIBERS = 128


def provision_sharded(app, sizes: Sizes) -> dict:
    """The sharded twin of provision(): subscribers hash-sharded by MAC,
    QoS rows / antispoof bindings / NAT state on each subscriber's
    affinity shard."""
    cl = app.components["cluster"]
    now = int(app.clock())
    took = {}
    idx = np.arange(sizes.subscribers)
    macs, ips = sub_macs(idx), sub_ips(idx)
    t0 = time.time()
    cl.add_subscribers_bulk(macs, pool_ids=1, ips=ips,
                            lease_expiries=np.uint32(now + 86400))
    took["subscribers"] = round(time.time() - t0, 2)

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
        # strict for unbound MACs too: a shard holds the bindings of ITS
        # subscribers' addresses, and a spoofed source steers the frame
        # to the address's shard, where the liar's MAC is unbound
        cl.spoof[sh].set_config(MODE_STRICT, log_violations=True)
    took["qos+antispoof"] = round(time.time() - t0, 2)

    t0 = time.time()
    for j in range(sizes.nat_subscribers):
        ip = SUB_IP_BASE + nat_sub_index(sizes, j)
        check(cl.affinity_shard_ip(ip) == int(owner[ip - SUB_IP_BASE]),
              "vectorized affinity differs from the cluster's")
        check(cl.allocate_nat(ip, now)[1] is not None,
              f"shard {cl.affinity_shard_ip(ip)} has no NAT block left")
        for f in range(sizes.flows_per_nat_subscriber):
            src, dst, sport, dport, proto = flow_of(sizes, j, f)
            check(cl.handle_new_flow(src, dst, sport, dport, proto, 64,
                                     now)[1] is not None, "NAT flow refused")
    took["nat"] = round(time.time() - t0, 2)

    cl.set_qos(flow_of(sizes, _qos_sub(sizes), 0)[0], down_bps=8000,
               up_bps=8000, up_burst=1500, down_burst=1500)

    t0 = time.time()
    cl.sync_tables()
    jax.block_until_ready(jax.tree_util.tree_leaves(cl.tables))
    took["upload"] = round(time.time() - t0, 2)
    return took


def sharded_facts(app, shards: int, platform: str) -> list[str]:
    cl = app.components["cluster"]
    leaves = jax.tree_util.tree_leaves(cl.tables)
    for leaf in leaves:
        homes = {sh.device for sh in leaf.addressable_shards}
        check(len(homes) == shards
              and all(d.platform == platform for d in homes),
              f"a table leaf lives on {homes}, not on {shards} devices")
    snap = cl.telemetry.snapshot()
    frames = [sh["frames"] for sh in snap["per_shard"]]
    check(all(n > 0 for n in frames), f"an idle shard: frames {frames}")
    check(snap["missteer_total"] == 0,
          f"{snap['missteer_total']} missteered frames")
    check(cl.stats["slow_errors"] == 0, "slow-path errors")
    return [
        f"{len(leaves)} table leaves, each split over {shards} distinct "
        f"devices: {sorted(str(d) for d in cl.mesh.devices.flat)}",
        f"per_shard_frames: {frames}",
        "missteers: 0",
        f"table impl: {cl.table_impl}; ring: "
        f"{type(app.components['ring']).__name__}",
    ]


def run_sharded(sizes: Sizes, shards: int, platform: str = "tpu") -> None:
    sizes = sizes._replace(
        nat_subscribers=min(sizes.nat_subscribers, SHARDED_NAT_SUBSCRIBERS),
        nat_probes=min(sizes.nat_probes, SHARDED_NAT_SUBSCRIBERS))
    say(f"build: bng run {' '.join(run_argv(sizes, shards))} "
        f"(synthetic generator off: the smoke pushes every frame)")
    say(f"sizes: {sizes.subscribers} subscribers hash-sharded over {shards} "
        f"chips, QoS rows and antispoof bindings for every subscriber; NAT "
        f"cut to {sizes.nat_flows} flows over {sizes.nat_subscribers} "
        f"subscribers, because a shard's NAT pool is one public IP "
        f"({BLOCKS_PER_PUBLIC_IP} port blocks) today")
    t0 = time.time()
    app = build_app(sizes, shards)
    try:
        say(f"app built in {time.time() - t0:.1f} s")
        say(f"provisioned, seconds: {provision_sharded(app, sizes)}")
        cl = app.components["cluster"]
        nat_of = lambda ip: cl.nat[cl.affinity_shard_ip(ip)]  # noqa: E731
        t0 = time.time()
        say("dhcp (provisioned): "
            + phase_dhcp_known(app, sizes, ReferenceDHCP(app)))
        say(f"  first sharded DHCP steps took {time.time() - t0:.1f} s "
            f"(compile included)")
        say("dhcp (new MACs): " + phase_dhcp_new(app, sizes))
        t0 = time.time()
        say("nat: " + phase_nat(app, sizes, nat_of))
        say(f"  first sharded fused steps took {time.time() - t0:.1f} s "
            f"(compile included)")
        say("qos + antispoof: " + phase_qos_spoof(app, sizes, nat_of))
        t0 = time.time()
        app.tick()
        say(f"tick: one maintenance heartbeat in {time.time() - t0:.1f} s")
        for line in sharded_facts(app, shards, platform):
            say("fact: " + line)
        for d in jax.devices()[:shards]:
            stats = d.memory_stats() or {}
            say(f"device memory {d}: peak_bytes_in_use "
                f"{stats.get('peak_bytes_in_use', 'n/a')}")
    finally:
        app.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the sharded path (bng run --shards 4)")
    args = ap.parse_args()

    devs = jax.devices()
    say(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); this machine has "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3

    say(f"compile cache: {enable_compilation_cache()}")
    try:
        if args.chips == 4:
            run_sharded(Sizes(), shards=4)
        else:
            run_one_chip(Sizes())
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
