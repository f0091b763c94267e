"""The system under test and its plain reference.

Copied from chip_smoke.py (PR 22), which proved these calls on the chip:
build the app `bng run` builds from a configuration's argv, fill its
tables through the bulk writers, and hold what comes out against
expectations that no device code computed -- a host-only DHCPServer for
DHCP replies, the host NATManager's session mirror for translations.

The benchmark may not import chip_smoke.py (a later PR may edit it), so
the functions live here. What varies with `--seed` is the `Layout`: the
MAC block and the remote-peer block the tables are filled with.
"""

from __future__ import annotations

import argparse
import ipaddress
import json
import os
import struct
import time

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)

SUB_IP_BASE = (10 << 24) | (16 << 16)  # 10.16.0.0 + i: the pool's top half
ROUTER_MAC = bytes.fromhex("02ee00000001")  # network-side next hop
REMOTE_PORT = 443
FLOW_PORT_BASE = 40000


class BenchError(RuntimeError):
    """Set-up did not reach the state the cell states."""


def load_named(kind: str, name: str, bench_dir: str = BENCH_DIR) -> dict:
    """configs/<name>.json, traffic/<name>.json or layers/<name>.json."""
    path = os.path.join(bench_dir, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


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
# build: the app `bng run` builds
# --------------------------------------------------------------------------

def run_argv(config: dict) -> list[str]:
    from bng_tpu.utils.net import ip_to_u32, u32_to_ip

    argv = list(config["argv"])
    pub = config.get("nat_public_ips")
    if pub:
        base = ip_to_u32(pub["base"])
        argv += ["--nat-public-ips",
                 *(u32_to_ip(base + i) for i in range(int(pub["count"])))]
    return argv


def build_app(config: dict):
    from bng_tpu import cli

    parser = argparse.ArgumentParser()
    cli._add_run_flags(parser)
    app = cli.BNGApp(cli._config_from_args(parser.parse_args(run_argv(config))))
    # the in-memory ring is built for a packet source; the generator
    # itself is switched off so that the benchmark pushes every frame
    app.config.synthetic_subs = 0
    return app


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
    """Fill the tables through the bulk writers, then one full upload.
    Returns seconds per step and the NAT mapping of every flow id."""
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


def table_leaves(app):
    c = app.components
    owner = c["cluster"] if "cluster" in c else c["engine"]
    return jax.tree_util.tree_leaves(owner.tables)


def idle(app) -> bool:
    c = app.components
    if c["ring"].rx_pending():
        return False
    if "cluster" in c:
        return c["cluster"]._inflight is None
    snap = c["scheduler"].stats_snapshot()
    return not any(snap[lane]["queue_depth"] or snap[lane]["inflight"]
                   for lane in ("express", "bulk"))


def counters(app) -> dict:
    """Everything a `counter` layer metric or the count check reads, as
    one nested dict of plain numbers."""
    from bng_tpu.ops.dhcp import ST_HIT
    from bng_tpu.ops.qos import QST_PKTS_DROPPED

    c = app.components
    out = {"ring": dict(c["ring"].stats())}
    if "cluster" in c:
        cl = c["cluster"]
        st = {k: np.asarray(cl.stats.get(k, np.zeros(16, np.uint64)))
              for k in ("dhcp", "qos")}
        out["sharded"] = cl.telemetry.snapshot()
        out["sharded"].pop("merged_stages", None)
        out["engine"] = {"dropped": int(out["ring"].get("drop", 0)),
                         "slow_errors": int(cl.stats["slow_errors"])}
    else:
        es = c["engine"].stats
        st = {"dhcp": es.dhcp, "qos": es.qos}
        out["engine"] = {k: int(getattr(es, k)) for k in
                         ("batches", "tx", "fwd", "dropped", "passed",
                          "slow_errors")}
        out["sched"] = c["scheduler"].stats_snapshot()
        # the snapshot's occupancy_avg is over the process's life; the sum
        # beside `batches` lets a layer file take the window's own
        out["sched"]["bulk"]["occupancy_sum"] = float(
            c["scheduler"].bulk.stats.occupancy_sum)
    host = c["dhcp"].stats
    # the antispoof kernel's own drop counter is not here: it counts
    # network-side and padding lanes too, which the pipeline never drops
    out["device"] = {"dhcp_hit": int(st["dhcp"][ST_HIT]),
                     "qos_dropped": int(st["qos"][QST_PKTS_DROPPED])}
    out["host"] = {"dhcp_handled": int(host.discover + host.request)}
    return out


def selectors(app) -> str:
    """The implementation selectors this run was served by."""
    import bng_tpu.ops.qos as qos_mod

    c = app.components
    ring = type(c["ring"]).__name__
    if "cluster" in c:
        return f"table={c['cluster'].table_impl} ring={ring} sharded"
    eng, sched = c["engine"], c["scheduler"]
    return (f"table={eng.table_impl} qos_prefix={qos_mod.PREFIX_IMPL} "
            f"host_path={eng.host_path} express_loop={sched.express_loop} "
            f"aot_ready={sched._aot_ready} ring={ring}")


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
    if "cluster" in c:
        return c["cluster"].nat[c["cluster"].affinity_shard_ip(src_ip)]
    return c["nat"]
