"""Sharded NAT at a deployment's shape (PR 42), small, on the CPU mesh.

`bng run --shards N` with CGNAT sized as a one-chip deployment sizes it:
every address of the public pool is used and owned by one shard (dealt in
contiguous runs), the two NAT capacities size each shard's tables, the
ring steers a frame from the core to the owner of its destination for
every address of a pool larger than the exact map holds, blocks and flows
are provisioned in bulk a shard, and the share ties to the whole: one
un-sharded `Engine` given the same subscribers, blocks and flows returns
the same bytes. `benchmark/kits/shardnat.py Plain`, the un-sharded plain
reference, is held against the cluster here too.

One geometry a shard count (2 and 4), so the mesh programs compile once.
"""

import json
import math
import os
import struct
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import app as applib  # noqa: E402
from benchmark.lib import gen  # noqa: E402
from bng_tpu.control.nat import NATManager  # noqa: E402
from bng_tpu.ops.antispoof import MODE_STRICT  # noqa: E402
from bng_tpu.ops.nat44 import NATGeom  # noqa: E402
from bng_tpu.ops.table import TableGeom, nbuckets_for  # noqa: E402
from bng_tpu.parallel.sharded import ShardedCluster  # noqa: E402
from bng_tpu.runtime import ring as ringmod  # noqa: E402
from bng_tpu.runtime.engine import AntispoofTables, Engine, QoSTables  # noqa: E402
from bng_tpu.runtime.ring import (FLAG_FROM_ACCESS, VERDICT_FWD,  # noqa: E402
                                  VERDICT_PASS, NativeRing, PyRing)
from bng_tpu.runtime.tables import FastPathTables  # noqa: E402
from bng_tpu.utils.net import fnv1a32, ip_to_u32, parse_mac  # noqa: E402

pytestmark = pytest.mark.sharded

NOW = 1_753_000_000
SERVER_MAC = parse_mac("02:aa:bb:cc:dd:01")
ROUTER_MAC = bytes.fromhex("02ee00000001")
PUB_BASE = ip_to_u32("198.18.0.0")
SUB_BASE = ip_to_u32("10.16.0.0")
REMOTE = ip_to_u32("93.184.0.0")
B = 64  # lanes a shard
PER_SHARD_ADDRS = 12
PER_SHARD_SUBS = 600
FLOWS_PER = 2
SLOT = 512
GEOM = dict(batch_per_shard=B, sub_nbuckets=256, vlan_nbuckets=64,
            cid_nbuckets=64, qos_nbuckets=1024, spoof_nbuckets=1024,
            nat_sessions_nbuckets=nbuckets_for(4 * PER_SHARD_SUBS),
            nat_sub_nbuckets=1024, garden_enabled=False)
native_available = ringmod.load_native() is not None
kit = applib.load_kit({"kit": "shardnat"})


def pool(k: int, base: int = PUB_BASE) -> list[int]:
    return [base + i for i in range(k)]


def sub_mac(i) -> np.ndarray:
    return np.asarray(i, np.uint64) + np.uint64(0x02AA00000000)


class World:
    """N shards and one un-sharded engine holding the same subscribers,
    port blocks and flows."""

    def __init__(self, n: int):
        self.n = n
        self.pub = pool(PER_SHARD_ADDRS * n)
        self.cl = cl = ShardedCluster(n, public_ips=self.pub, **GEOM)
        subs = PER_SHARD_SUBS * n
        idx = np.arange(subs)
        self.ips = ips = (SUB_BASE + idx).astype(np.uint32)
        self.macs = macs = sub_mac(idx)
        self.owner = owner = cl.affinity_shards(ips)
        for s in range(n):
            m = owner == s
            cl.qos[s].bulk_set_subscribers(ips[m], 10**9, 10**9)
            cl.spoof[s].bulk_add_bindings(macs[m], ips[m], MODE_STRICT)
            cl.spoof[s].set_config(MODE_STRICT, log_violations=True)
        self.made = cl.bulk_allocate_nat(ips, NOW)
        k = np.arange(subs * FLOWS_PER)
        j, f = k // FLOWS_PER, k % FLOWS_PER
        self.flows = (ips[j], (REMOTE + (j & 0xFF)).astype(np.uint32),
                      (40000 + f).astype(np.uint32),
                      np.full(len(k), 443, np.uint32),
                      np.where(f % 2 == 0, 17, 6).astype(np.uint32))
        self.nat_ip, self.nat_port, self.ok = cl.bulk_flows(
            *self.flows, pkt_len=64, now=NOW)
        cl.sync_tables()

        # the whole: one NATManager over the whole pool, every block
        # restored where the cluster carved it, the same flows in bulk
        nat = NATManager(public_ips=self.pub,
                         sessions_nbuckets=nbuckets_for(2 * FLOWS_PER * subs),
                         sub_nat_nbuckets=nbuckets_for(subs))
        for s in range(n):
            for priv, blk in cl.nat[s].blocks.items():
                assert nat.restore_block(priv, blk["public_ip"],
                                         blk["port_start"], blk["port_end"],
                                         NOW)
        self.one_ip, self.one_port, one_ok = nat.bulk_flows(
            *self.flows, pkt_len=64, now=NOW)
        assert bool(one_ok.all())
        qos = QoSTables(nbuckets=4096)
        qos.bulk_set_subscribers(ips, 10**9, 10**9)
        spoof = AntispoofTables(nbuckets=4096)
        spoof.bulk_add_bindings(macs, ips, MODE_STRICT)
        spoof.set_config(MODE_STRICT, log_violations=True)
        fp = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64,
                            cid_nbuckets=64, max_pools=16)
        self.engine = Engine(fp, nat, qos, spoof, batch_size=n * B,
                             pkt_slot=SLOT, clock=lambda: float(NOW))
        self.engine.resync_tables()

    def traffic(self, seed: int, n_up: int = 700):
        """Seeded frames: upstream of random flows, and the matching
        downstream of half of them; ids in the last four bytes."""
        rng = np.random.default_rng([seed, 0x5A7])
        src, dst, sport, dport, proto = self.flows
        up = rng.integers(0, len(src), n_up)
        down = up[: n_up // 2]
        sub = src[up].astype(np.int64) - SUB_BASE
        ids = np.arange(n_up + len(down))
        frames = gen.row_bytes(gen.data_frames(
            gen.mac_cols(self.macs[sub]), np.frombuffer(SERVER_MAC, np.uint8),
            src[up], dst[up], sport[up], dport[up], proto[up], ids[:n_up]))
        frames += gen.row_bytes(gen.data_frames(
            np.frombuffer(ROUTER_MAC, np.uint8),
            np.frombuffer(SERVER_MAC, np.uint8), dst[down], self.nat_ip[down],
            dport[down], self.nat_port[down], proto[down], ids[n_up:]))
        return frames, np.concatenate([up, down]), n_up


@pytest.fixture(scope="module", params=[2, 4])
def world(request):
    import jax

    if len(jax.devices()) < request.param:
        pytest.skip("needs the CPU mesh")
    return World(request.param)


def _frame_id(raw: bytes) -> int:
    return int.from_bytes(raw[-4:], "big")


def _drain(ring) -> dict[int, bytes]:
    out = {}
    while True:
        got = ring.fwd_pop() or ring.tx_pop()
        if got is None:
            return out
        assert _frame_id(got[0]) not in out  # one answer a frame
        out[_frame_id(got[0])] = got[0]


def _serve_cluster(cl, ring, frames, n_up) -> dict[int, bytes]:
    out = {}
    at = [0, n_up]
    ends = [n_up, len(frames)]
    while at[0] < ends[0] or at[1] < ends[1] or ring.rx_pending():
        for side, fa in ((0, True), (1, False)):
            take = frames[at[side]: min(at[side] + 3 * B // 2, ends[side])]
            at[side] += ring.rx_push_batch(take, from_access=fa)
        cl.process_ring(ring, NOW, 0, pkt_slot=SLOT)
        out.update(_drain(ring))
    return out


# -- (a) the pool is dealt whole, each address to one shard -----------------

@pytest.mark.parametrize("n,k", [(2, 16), (4, 35), (4, 4), (3, 10)])
def test_every_address_is_used_and_owned_by_one_shard(n, k):
    pub = pool(k)
    cl = ShardedCluster(n, public_ips=pub, **GEOM)
    owners = cl.pub_ip_map()
    assert sorted(owners) == pub  # every address, once
    for s in range(n):
        mine = pub[s * k // n: (s + 1) * k // n]  # a contiguous run, in order
        assert cl.nat[s].public_ips == mine and mine
        assert all(owners[ip] == s for ip in mine)
    snap = cl.telemetry.snapshot()["per_shard"]
    assert [p["nat_pool"]["addresses"] for p in snap] == [
        len(cl.nat[s].public_ips) for s in range(n)]
    assert all(p["nat_pool"]["blocks_free"] == 63 * p["nat_pool"]["addresses"]
               and p["nat_sessions"] == 0 and p["nat_blocks"] == 0
               for p in snap)


def test_a_shared_or_missing_address_is_refused():
    with pytest.raises(ValueError, match="need >= 4 public IPs"):
        ShardedCluster(4, public_ips=pool(3), **GEOM)
    a, b, c = pool(3)
    cl = ShardedCluster(2, public_ips=[a, b, a, c], **GEOM)
    with pytest.raises(ValueError, match="owned by shards 0 and 1"):
        cl.pub_ip_map()
    with pytest.raises(ValueError, match="owned by shards 0 and 1"):
        cl.make_ring(nframes=64, frame_size=SLOT, depth=16)


# -- (b) the ring steers by ownership over a pool no exact map holds --------

def _down(dst_ip: int, port: int = 2000, fid: int = 7) -> bytes:
    return gen.row_bytes(gen.data_frames(
        np.frombuffer(ROUTER_MAC, np.uint8), np.frombuffer(SERVER_MAC, np.uint8),
        [REMOTE], [dst_ip], [443], [port], [17], [fid]))[0]


def _up(src_ip: int, fid: int = 9) -> bytes:
    return gen.row_bytes(gen.data_frames(
        gen.mac_cols(sub_mac([1])), np.frombuffer(SERVER_MAC, np.uint8),
        [src_ip], [REMOTE], [40000], [443], [17], [fid]))[0]


def _rings(cl):
    rings = [("py-scalar", PyRing(nframes=8192, frame_size=SLOT, depth=4096,
                                  n_shards=cl.n, host_path="scalar")),
             ("py-vector", PyRing(nframes=8192, frame_size=SLOT, depth=4096,
                                  n_shards=cl.n, host_path="vector"))]
    if native_available:
        rings.append(("native", NativeRing(nframes=8192, frame_size=SLOT,
                                           depth=4096, n_shards=cl.n)))
    return [(name, cl.steer_ring(r)) for name, r in rings]


@pytest.mark.parametrize("n", [2, 4])
def test_the_ring_steers_every_address_of_a_large_pool_to_its_owner(n):
    k = 600 * n + 7  # more than the exact map's 1,024 slots, uneven runs
    cl = ShardedCluster(n, public_ips=pool(k), **GEOM)
    owners = cl.pub_ip_map()
    outside = [PUB_BASE - 1, PUB_BASE + k, ip_to_u32("203.0.113.9")]
    down = [_down(ip) for ip in list(owners) + outside]
    want = list(owners.values()) + [
        fnv1a32(ip.to_bytes(4, "big")) % n for ip in outside]
    rng = np.random.default_rng(n)
    srcs = (SUB_BASE + rng.integers(0, 1 << 20, 512)).astype(np.uint32)
    up = [_up(int(ip)) for ip in srcs]
    stats = {}
    for name, ring in _rings(cl):
        # a range a shard, whatever the pool holds; no exact entry
        if name.startswith("py"):
            assert len(ring._pub_ranges) == n and not ring._pub_ips
        got = [ring.shard_of(f, 0) for f in down]
        assert got == want, name
        assert got == [ringmod.shard_of(f, 0, n, None,
                                        sorted(_runs(owners))) for f in down]
        # upstream: by the source, whatever the pool
        assert [ring.shard_of(f, FLAG_FROM_ACCESS) for f in up] == [
            cl.affinity_shard_ip(int(ip)) for ip in srcs], name
        assert (cl.affinity_shards(srcs)
                == [cl.affinity_shard_ip(int(ip)) for ip in srcs]).all()
        # the always-on counters: frames the queues took, by what the
        # tables said of a frame from the core
        assert ring.rx_push_batch(down, from_access=False) == len(down)
        assert ring.rx_push_batch(up, from_access=True) == len(up)
        st = ring.stats()
        assert (st["steer_pub_hit"], st["steer_pub_miss"]) == (k, len(outside))
        stats[name] = (st["steer_pub_hit"], st["steer_pub_miss"])
        if hasattr(ring, "close"):
            ring.close()
    assert len(set(stats.values())) == 1  # native and both twins alike


def _runs(owners: dict[int, int]):
    """(lo, hi, shard) of maximal runs, as `steer_ring` coalesces them."""
    out = []
    for ip, s in sorted(owners.items()):
        if out and out[-1][1] + 1 == ip and out[-1][2] == s:
            out[-1][1] = ip
        else:
            out.append([ip, ip, s])
    return [tuple(r) for r in out]


def test_lone_addresses_keep_the_exact_map_and_ranges_do_not_overlap():
    # today's cells: one address a shard, consecutive, different owners
    cl = ShardedCluster(4, public_ips=pool(4), **GEOM)
    ring = cl.steer_ring(PyRing(nframes=64, frame_size=SLOT, depth=16,
                                n_shards=4))
    assert ring._pub_ips == cl.pub_ip_map() and not ring._pub_ranges
    for name, r in _rings(cl):
        assert r.steer_pub_range(PUB_BASE + 100, PUB_BASE + 199, 1), name
        assert not r.steer_pub_range(PUB_BASE + 199, PUB_BASE + 300, 2), name
        assert not r.steer_pub_range(PUB_BASE + 50, PUB_BASE + 100, 2), name
        assert not r.steer_pub_range(PUB_BASE + 400, PUB_BASE + 399, 0), name
        assert not r.steer_pub_range(PUB_BASE + 400, PUB_BASE + 500, 4), name
        assert r.steer_pub_range(PUB_BASE + 200, PUB_BASE + 200, 3), name
        # a range is looked up before the exact map
        assert r.steer_pub_ip(PUB_BASE + 150, 0)
        assert r.shard_of(_down(PUB_BASE + 150), 0) == 1, name
        assert r.shard_of(_down(PUB_BASE + 200), 0) == 3, name
        assert r.shard_of(_down(PUB_BASE + 2), 0) == 2, name
        if hasattr(r, "close"):
            r.close()


def test_a_pool_the_ring_cannot_steer_is_refused():
    # 70 runs of two addresses, a gap between them: more ranges than the
    # ring's table holds (64)
    pub = [PUB_BASE + 3 * i + d for i in range(70) for d in (0, 1)]
    cl = ShardedCluster(2, public_ips=pub, **GEOM)
    with pytest.raises(RuntimeError, match="steering tables rejected"):
        cl.make_ring(nframes=64, frame_size=SLOT, depth=16)
    with pytest.raises(RuntimeError, match="steering tables rejected"):
        cl.make_ring(nframes=64, frame_size=SLOT, depth=16,
                     prefer_native=False)


# -- (c) the share ties to the whole ------------------------------------------

def test_bulk_provisioning_lands_each_subscriber_on_its_owner(world):
    w, cl = world, world.cl
    assert int(w.made.sum()) == len(w.ips) and bool(w.ok.all())
    assert (w.made == np.bincount(w.owner, minlength=w.n)).all()
    for s in range(w.n):
        nat = cl.nat[s]
        assert set(nat.blocks) == set(w.ips[w.owner == s].tolist())
        assert nat.sessions.count == FLOWS_PER * len(nat.blocks)
        assert {b["public_ip"] for b in nat.blocks.values()} <= set(
            nat.public_ips)
    snap = cl.telemetry.snapshot()["per_shard"]
    assert [p["nat_blocks"] for p in snap] == w.made.tolist()
    assert [p["nat_sessions"] for p in snap] == (FLOWS_PER * w.made).tolist()
    assert all(p["nat_pool"]["blocks_used"] + p["nat_pool"]["blocks_free"]
               == 63 * PER_SHARD_ADDRS for p in snap)
    # the mapping is the whole's: one manager over the whole pool, given
    # the same blocks, gives every flow the same external endpoint
    assert (w.nat_ip == w.one_ip).all() and (w.nat_port == w.one_port).all()
    # one call a flow, as before PR 42, lands where the bulk path did
    o, got = cl.handle_new_flow(int(w.flows[0][5]), int(w.flows[1][5]),
                                int(w.flows[2][5]), 443, int(w.flows[4][5]),
                                64, NOW)
    assert o == w.owner[5 // FLOWS_PER]
    assert got == (int(w.nat_ip[5]), int(w.nat_port[5]))


def test_the_cluster_returns_the_bytes_one_unsharded_engine_returns(world):
    w, cl = world, world.cl
    frames, flow_of, n_up = w.traffic(seed=42 + w.n)
    ring = cl.make_ring(nframes=4096, frame_size=SLOT, depth=256)
    frames0 = cl.telemetry.frames.copy()
    got = _serve_cluster(cl, ring, frames, n_up)
    assert sorted(got) == list(range(len(frames)))  # each frame, once
    served = cl.telemetry.frames - frames0
    assert int(served.sum()) == len(frames) and (served > 0).all()
    snap = cl.telemetry.snapshot()
    assert snap["nat_punt_total"] == 0 and snap["missteer_total"] == 0
    assert snap["pass_total"] == 0
    st = ring.stats()
    assert st["steer_pub_hit"] == len(frames) - n_up
    assert st["steer_pub_miss"] == 0 and st["slow"] == 0

    one = ringmod.make_ring(nframes=4096, frame_size=SLOT, depth=2048)
    assert one.rx_push_batch(frames[:n_up], from_access=True) == n_up
    assert one.rx_push_batch(frames[n_up:], from_access=False) \
        == len(frames) - n_up
    whole = {}
    while one.rx_pending():
        w.engine.process_ring(one, now=float(NOW))
        whole.update(_drain(one))
    assert sorted(whole) == sorted(got)
    assert all(got[i] == whole[i] for i in got)  # byte for byte
    assert all(got[i] != frames[i] for i in got)  # and translated
    for r in (ring, one):
        if hasattr(r, "close"):
            r.close()


# -- (d) a frame on the wrong shard is punted, never mistranslated -----------

def test_a_missteered_downstream_frame_is_punted_untranslated(world):
    w, cl = world, world.cl
    k = int(np.nonzero(w.owner[np.arange(len(w.nat_ip)) // FLOWS_PER] == 1)[0][0])
    frame = gen.row_bytes(gen.data_frames(
        np.frombuffer(ROUTER_MAC, np.uint8), np.frombuffer(SERVER_MAC, np.uint8),
        [w.flows[1][k]], [w.nat_ip[k]], [w.flows[3][k]], [w.nat_port[k]],
        [w.flows[4][k]], [77]))[0]
    assert cl.pub_ip_map()[int(w.nat_ip[k])] == 1
    lanes = cl.n * B
    for shard, verdict in ((0, VERDICT_PASS), (1, VERDICT_FWD)):
        pkt = np.zeros((lanes, SLOT), np.uint8)
        length = np.zeros(lanes, np.uint32)
        row = shard * B + 3
        pkt[row, :len(frame)] = np.frombuffer(frame, np.uint8)
        length[row] = len(frame)
        res = cl.step(pkt, length, np.zeros(lanes, bool), NOW, 0)
        out = bytes(np.asarray(res["out_pkt"])[row, :len(frame)])
        assert int(res["verdict"][row]) == verdict
        if shard == 0:
            assert out == frame  # as it came: nothing was rewritten
        else:
            dst, dport = struct.unpack_from("!I", out, 30)[0], \
                struct.unpack_from("!H", out, 36)[0]
            assert (dst, dport) == (int(w.flows[0][k]), int(w.flows[2][k]))
    # through a ring whose steering is wrong, the loop counts the missteer
    bad = PyRing(nframes=256, frame_size=SLOT, depth=64, n_shards=cl.n)
    assert bad.steer_pub_range(w.pub[0], w.pub[-1], 0)  # all to shard 0
    t = cl.telemetry
    miss0, pass0 = int(t.missteers.sum()), int(t.verdicts[:, 0].sum())
    assert bad.rx_push(frame, from_access=False)
    cl.process_ring(bad, NOW, 0, pkt_slot=SLOT)
    assert int(t.missteers.sum()) == miss0 + 1
    assert int(t.verdicts[:, 0].sum()) == pass0 + 1
    assert bad.fwd_pop() is None and bad.tx_pop() is None


# -- (e) the two capacities size a shard's tables; unset, today's program ----

def _app(argv):
    import argparse

    from bng_tpu import cli

    parser = argparse.ArgumentParser()
    cli._add_run_flags(parser)
    return cli.BNGApp(cli._config_from_args(parser.parse_args(argv)))


def test_the_nat_capacities_size_each_shards_tables_and_unset_they_stand():
    from bng_tpu import cli

    cfg = applib.load_named("configs", "ipoe-sharded4-1M")
    assert "--max-nat-sessions" not in cfg["argv"]
    argv = [a if a != "131072" else "4096" for a in cfg["argv"]]
    unset = _app(argv)
    sized = _app(argv + ["--max-nat-sessions", "40000",
                         "--max-nat-subscribers", "10000",
                         "--nat-public-ips", "198.18.0.0", "198.18.0.1",
                         "198.18.0.2", "198.18.0.3", "198.18.0.4"])
    try:
        cl = unset.components["cluster"]
        # unset: the session table gets --shard-nbuckets and the block
        # table 256 buckets, the sizes of `sharded4-1M.flood-64B`'s program
        assert cl.geom.nat == NATGeom(sessions=TableGeom(4096, 64),
                                      reverse=TableGeom(4096, 64),
                                      sub_nat=TableGeom(256, 64))
        twin = ShardedCluster(
            4, batch_per_shard=2048, sub_nbuckets=4096, vlan_nbuckets=1024,
            cid_nbuckets=1024, nat_sessions_nbuckets=4096, qos_nbuckets=4096,
            spoof_nbuckets=4096, public_ips=pool(4))
        assert twin.geom == cl.geom and twin._step is cl._step  # one program
        assert [len(m.public_ips) for m in cl.nat] == [1, 1, 1, 1]
        cs = sized.components["cluster"]
        share = 1 + cli.SHARD_HEADROOM
        assert cs.geom.nat.sessions.nbuckets == nbuckets_for(
            math.ceil(40000 / 4 * share)) == 8192
        assert cs.geom.nat.reverse == cs.geom.nat.sessions
        assert cs.geom.nat.sub_nat.nbuckets == nbuckets_for(
            math.ceil(10000 / 4 * share)) == 2048
        assert cs.geom._replace(nat=cl.geom.nat) == cl.geom  # nothing else
        assert [len(m.public_ips) for m in cs.nat] == [1, 1, 1, 2]
        got = sized.stats()["sharded"]
        assert [p["nat_pool"]["addresses"] for p in got["per_shard_nat"]] == [
            1, 1, 1, 2]
        assert got["nat_fwd"] == 0 and got["steering"] == {
            "steer_pub_hit": 0, "steer_pub_miss": 0}
    finally:
        unset.close()
        sized.close()
    # the cell's configuration is the old one's argv and the two capacities
    new = applib.load_named("configs", "ipoe-cgnat-sharded4-1M")
    assert new["argv"] == cfg["argv"] + ["--max-nat-sessions", "4000000",
                                         "--max-nat-subscribers", "1000000"]
    assert cli._shard_sized(4_000_000, 4, 7) == nbuckets_for(1_000_000)
    assert cli._shard_sized(1_000_000, 4, 7) == nbuckets_for(250_000)
    assert cli._shard_sized(0, 4, 7) == 7


def test_the_fullest_shard_at_a_million_addresses():
    """What the headroom is taken against: the layout's 1,000,000
    consecutive addresses split exactly evenly under FNV-1a32 mod 4 (the
    last byte's low bits survive the odd multiplier), and a million drawn
    at random stay within half a percent."""
    from bng_tpu import cli

    cl = ShardedCluster(4, public_ips=pool(4), **GEOM)
    ips = (SUB_BASE + np.arange(1_000_000)).astype(np.uint32)
    assert np.bincount(cl.affinity_shards(ips)).tolist() == [250_000] * 4
    rnd = np.random.default_rng(4).integers(0, 1 << 32, 1_000_000,
                                            dtype=np.uint64)
    share = np.bincount(cl.affinity_shards(rnd.astype(np.uint32))) / 250_000
    assert share.max() < 1.005 < 1 + cli.SHARD_HEADROOM


def test_one_shards_session_rows_are_read_from_its_own_chip(world):
    """`fetch_session_vals` / `expire`: a shard's piece of the mesh-stacked
    session array, not the whole array once a shard."""
    from bng_tpu.telemetry import spans as tele

    cl = world.cl
    cl.quiesce()
    whole = np.asarray(cl.tables.nat.sessions.vals)
    for s in range(cl.n):
        got = cl.fetch_session_vals(s)
        assert got.shape == whole.shape[1:] and (got == whole[s]).all()
    assert cl.expire(NOW) == 0  # nothing idle: every flow was just made
    assert sum(m.sessions.count for m in cl.nat) == len(world.nat_ip)
    assert tele.trace_sums()["nat_punt"] == 0


# -- (f) the plain reference against the cluster -------------------------------

def _fix_checksums(raw: bytearray) -> bytes:
    """Recompute both checksums of an untagged IPv4 UDP / TCP frame."""
    proto = raw[23]
    raw[24:26] = b"\x00\x00"
    raw[24:26] = struct.pack("!H", 0xFFFF - kit.ones_sum(bytes(raw[14:34])))
    at = kit.L4_CSUM[proto]
    raw[at] = b"\x00\x00"
    seg = bytes(raw[34:])
    pseudo = bytes(raw[26:34]) + struct.pack("!BBH", 0, proto, len(seg))
    raw[at] = struct.pack("!H", (0xFFFF - kit.ones_sum(pseudo + seg)) or 0xFFFF)
    return bytes(raw)


def _bump(raw: bytes, at: int, width: int, fix: bool) -> bytes:
    b = bytearray(raw)
    v = int.from_bytes(b[at:at + width], "big") + 1
    b[at:at + width] = (v % (1 << 8 * width)).to_bytes(width, "big")
    return _fix_checksums(b) if fix else bytes(b)


def test_plain_holds_the_clusters_frames_and_fails_one_off_by_one(world):
    w, cl = world, world.cl
    plain = kit.Plain(*w.flows, w.nat_ip, w.nat_port,
                      owner=w.owner[np.arange(len(w.nat_ip)) // FLOWS_PER])
    counted = plain.check_partition()
    assert counted["flows"] == counted["external_endpoints"] == len(w.nat_ip)
    assert counted["owners"] == w.n
    assert counted["public_addresses"] <= len(w.pub)
    frames, _flow_of, n_up = w.traffic(seed=7 + w.n, n_up=300)
    ring = cl.make_ring(nframes=4096, frame_size=SLOT, depth=256)
    got = _serve_cluster(cl, ring, frames, n_up)
    assert len(got) == len(frames)
    for i, raw in got.items():
        up = i < n_up
        assert plain.holds(frames[i], raw, up), i
        assert not plain.holds(frames[i], raw, not up)
        assert not plain.holds(frames[i], frames[i], up)  # untranslated
        # the rewritten endpoint: address and port, off by one, with both
        # checksums made valid again, then each checksum alone
        ip_at, port_at = (26, 34) if up else (30, 36)
        l4 = kit.L4_CSUM[raw[23]].start
        for at, width, fix in ((ip_at, 4, True), (port_at, 2, True),
                               (24, 2, False), (l4, 2, False),
                               (len(raw) - 5, 1, True), (22, 1, True)):
            assert not plain.holds(frames[i], _bump(raw, at, width, fix),
                                   up), (i, at)
        assert plain.holds(frames[i], _fix_checksums(bytearray(raw)), up)
    if hasattr(ring, "close"):
        ring.close()


def test_plain_refuses_a_partition_that_does_not_hold():
    src = np.array([10, 10, 11, 12], np.uint32)
    dst = np.full(4, 99, np.uint32)
    sport = np.array([1, 2, 1, 1], np.uint32)
    dport = np.full(4, 443, np.uint32)
    proto = np.full(4, 17, np.uint32)
    nat_ip = np.array([500, 500, 500, 501], np.uint32)
    nat_port = np.array([1024, 1025, 2048, 1024], np.uint32)
    ok = kit.Plain(src, dst, sport, dport, proto, nat_ip, nat_port,
                   owner=[0, 0, 0, 1])
    assert ok.check_partition() == {"flows": 4, "external_endpoints": 4,
                                    "public_addresses": 2, "owners": 2}
    assert ok.external_of(10, 99, 2, 443, 17) == (500, 1025)
    assert ok.external_of(10, 98, 2, 443, 17) is None
    assert ok.internal_of(500, 2048, 17) == (11, 1)
    assert ok.internal_of(500, 2048, 6) is None
    # two internal endpoints behind one external endpoint
    clash = kit.Plain(src, dst, sport, dport, proto, nat_ip,
                      np.array([1024, 1025, 1024, 1024], np.uint32))
    with pytest.raises(ValueError, match="not injective"):
        clash.check_partition()
    # a public address under two owners; a subscriber under two owners
    with pytest.raises(ValueError, match="public address 0x1f4 appears"):
        kit.Plain(src, dst, sport, dport, proto, nat_ip, nat_port,
                  owner=[0, 0, 1, 1]).check_partition()
    with pytest.raises(ValueError, match="subscriber 0xa appears"):
        kit.Plain(src, dst, sport, dport, proto,
                  np.array([500, 502, 500, 501], np.uint32), nat_port,
                  owner=[0, 2, 0, 1]).check_partition()
    # EIM: two flows of one internal endpoint share its external endpoint
    eim = kit.Plain([10, 10], [98, 99], [1, 1], [443, 443], [17, 17],
                    [500, 500], [1024, 1024], owner=[0, 0])
    assert eim.check_partition()["external_endpoints"] == 1
    assert eim.external_of(10, 98, 1, 443, 17) == (500, 1024)


def test_the_kits_pool_check_names_the_shard_before_any_insert():
    """On a program that gives a shard one address (the parent of PR 42)
    the cell fails at once: the kit's message, and nothing inserted."""
    cl = ShardedCluster(2, public_ips=pool(2), **GEOM)  # one address each
    owner = cl.affinity_shards((SUB_BASE + np.arange(200)).astype(np.uint32))
    with pytest.raises(applib.BenchError,
                       match=r"shard 0 owns 1 public address\(es\), 63 port "
                             r"blocks of 1024, and 100 NAT subscribers"):
        kit.pool_room(cl, owner)
    assert all(not m.blocks and m.sessions.count == 0 for m in cl.nat)
    rooms = kit.pool_room(cl, owner[:100])
    assert [r["subscribers"] for r in rooms] == [50, 50]
    assert json.dumps(rooms)  # plain numbers: the run prints them


def test_a_zero_tcp_checksum_verifies_and_a_zero_udp_one_is_none():
    """One's-complement zero: a TCP checksum of 0x0000 is a checksum like
    any other (the rehearsal met one in 20,000 frames); a UDP one of
    0x0000 says "no checksum", which no frame of the cell was sent with."""
    tcp, udp = gen.row_bytes(gen.data_frames(
        gen.mac_cols(sub_mac([1, 1])), np.frombuffer(SERVER_MAC, np.uint8),
        [SUB_BASE, SUB_BASE], [REMOTE, REMOTE], [40001, 40000], [443, 443],
        [6, 17], [5, 6]))
    assert kit.Plain.checksums_ok(tcp) and kit.Plain.checksums_ok(udp)
    # add the checksum into a payload word: the sum closes with 0x0000
    c = int.from_bytes(tcp[50:52], "big")
    w = int.from_bytes(tcp[54:56], "big") + c
    zero = bytearray(tcp)
    zero[54:56] = ((w & 0xFFFF) + (w >> 16)).to_bytes(2, "big")
    zero[50:52] = b"\x00\x00"
    assert kit.Plain.checksums_ok(bytes(zero))
    assert _fix_checksums(bytearray(zero))[50:52] in (b"\x00\x00", b"\xff\xff")
    assert not kit.Plain.checksums_ok(udp[:40] + b"\x00\x00" + udp[42:])
    assert not kit.Plain.checksums_ok(_bump(tcp, 50, 2, False))
