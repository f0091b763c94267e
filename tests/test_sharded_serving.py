"""The ICI-sharded dataplane as the SERVING path (ISSUE 12).

Covers the promotion contract end to end:

* exact missteer accounting — wrong-shard punts split out of
  ShardTelemetry's PASS class (bng_shard_missteer_total), zero on a
  steered ring, nonzero when steering is sabotaged;
* sharded checkpoints — same-topology slot-exact round-trip, N->M and
  N->1->N re-shard round-trips audit-clean, reject-to-cold-start on
  geometry/CRC mismatch and on cross-topology (engine<->sharded) loads;
* sharded blue/green swap — audited flip, crash-at-flip keeps the
  active cluster;
* `bng run --shards N` — the composed app serves DORA through the
  steered ring with zero missteers, checkpoints, swaps, audits.

Every cluster here shares ONE geometry (the cli --shards default at
shard_nbuckets=64) so the mesh programs compile once per suite run.
"""

import numpy as np
import pytest

from bng_tpu.control import packets
from bng_tpu.control.dhcp_server import DHCPServer
from bng_tpu.parallel.sharded import ShardedCluster, ShardedFastPathSink
from bng_tpu.runtime.checkpoint import (CheckpointError,
                                        build_sharded_checkpoint,
                                        decode_checkpoint,
                                        encode_checkpoint,
                                        restore_checkpoint,
                                        restore_sharded_checkpoint)
from bng_tpu.utils.net import fnv1a32, ip_to_u32, parse_mac

pytestmark = pytest.mark.sharded

NOW = 1_753_000_000
SERVER_MAC = parse_mac("02:aa:bb:cc:dd:01")
SERVER_IP = ip_to_u32("10.0.0.1")
GEOM = dict(batch_per_shard=8, sub_nbuckets=64, vlan_nbuckets=64,
            cid_nbuckets=64, nat_sessions_nbuckets=64, qos_nbuckets=64,
            spoof_nbuckets=64)


def make_cluster(n: int = 2, **over) -> ShardedCluster:
    kw = {**GEOM, **over}
    cl = ShardedCluster(n, **kw)
    cl.set_server_config_all(SERVER_MAC, SERVER_IP)
    cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 24, SERVER_IP,
                    lease_time=3600)
    return cl


def mac_i(i: int) -> bytes:
    return (0x02D0 << 32 | i).to_bytes(6, "big")


def populate(cl: ShardedCluster, n_subs: int = 8) -> list[bytes]:
    macs = [mac_i(i) for i in range(n_subs)]
    for i, m in enumerate(macs):
        cl.add_subscriber(m, pool_id=1, ip=ip_to_u32(f"10.0.0.{50 + i}"),
                          lease_expiry=NOW + 600)
    cl.allocate_nat(ip_to_u32("10.0.0.50"), NOW)
    cl.set_qos(ip_to_u32("10.0.0.50"), down_bps=8_000, up_bps=8_000,
               down_burst=1000, up_burst=1000)
    cl.add_spoof_binding(macs[0], ip_to_u32("10.0.0.50"), 1)
    if cl.garden is not None:
        cl.set_gardened(ip_to_u32("10.0.0.51"), True)
    return macs


def discover(mac: bytes, xid: int) -> bytes:
    from bng_tpu.control import dhcp_codec

    p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER, xid=xid)
    p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
    return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                              p.encode().ljust(320, b"\x00"))


def audit_clean(cl, dhcp=None):
    from bng_tpu.chaos.invariants import audit_invariants

    rep = audit_invariants(cluster=cl, dhcp=dhcp, check_roundtrip=False)
    assert rep.ok, rep.to_dict()
    return rep


# ---------------------------------------------------------------------------
# missteer accounting
# ---------------------------------------------------------------------------

class TestMissteer:
    def test_steered_ring_counts_zero_missteers(self):
        """Ring-steered owner batches: cached renewals TX on device,
        slow-path DHCP misses stay legit PASSes, missteer == 0."""
        cl = make_cluster()
        macs = populate(cl)
        cl.sync_tables()
        ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)
        # cached subscriber -> device TX; unknown subscriber -> legit punt
        assert ring.rx_push(discover(macs[0], 1), from_access=True)
        assert ring.rx_push(discover(mac_i(900), 2), from_access=True)
        served = {}

        def slow(frame):
            served["punt"] = True
            return None

        got = cl.process_ring(ring, NOW, 0, slow_path=slow)
        assert got == 2
        snap = cl.telemetry.snapshot()
        assert snap["missteer_total"] == 0
        assert snap["pass_total"] == 1  # the unknown MAC's legit punt
        assert served.get("punt")
        assert snap["psum_dhcp_hits"] >= 1

    def test_sabotaged_steering_counts_missteer_exactly(self):
        """A downstream frame for shard-owned NAT state landing on the
        WRONG shard (unsteered ring) is a missteer; the classifier
        counts it apart from legit slow-path punts."""
        from bng_tpu.runtime.ring import make_ring

        cl = make_cluster()
        populate(cl)
        nat_priv = ip_to_u32("10.0.0.50")
        owner = cl.affinity_shard_ip(nat_priv)
        _o, flow = cl.handle_new_flow(nat_priv, ip_to_u32("1.2.3.4"),
                                      40000, 443, 17, 600, NOW)
        assert flow is not None
        pub_ip, pub_port = flow
        cl.sync_tables()
        # an UNSTEERED ring (no pub-IP registration): downstream frames
        # fall back to dst-IP hashing — force the wrong shard
        ring = make_ring(nframes=256, frame_size=2048, depth=64,
                         prefer_native=True, n_shards=cl.n)
        down = packets.udp_packet(SERVER_MAC, mac_i(0),
                                  ip_to_u32("1.2.3.4"), pub_ip,
                                  443, pub_port, b"r" * 32)
        hashed = fnv1a32(int(pub_ip).to_bytes(4, "big")) % cl.n
        if hashed == owner:
            pytest.skip("dst-hash happens to match the owner for this "
                        "geometry — sabotage not expressible")
        assert ring.rx_push(down, from_access=False)
        got = cl.process_ring(ring, NOW + 1, 1000)
        assert got == 1
        snap = cl.telemetry.snapshot()
        assert snap["missteer_total"] == 1
        assert snap["pass_total"] == 0  # split OUT of the PASS class
        assert snap["per_shard"][hashed]["missteers"] == 1

    def test_metrics_export_missteer_family(self):
        from bng_tpu.control.metrics import BNGMetrics

        cl = make_cluster()
        cl.telemetry.missteers[1] = 3
        m = BNGMetrics()
        m.collect_sharded(cl)
        text = m.expose()
        assert 'bng_shard_missteer_total{shard="1"} 3' in text


# ---------------------------------------------------------------------------
# sharded checkpoints: round-trips, re-shard, rejects
# ---------------------------------------------------------------------------

class TestShardedLoopOnTheTracer:
    """PR 25: the sharded loop has no clock of its own. Armed, its stages
    are the Tracer's `sharded` lane; disarmed, it reads no clock at all."""

    def _beats(self, cl, ring, macs, n=3):
        out = 0
        for k in range(n):
            for i, mac in enumerate(macs[:4]):
                assert ring.rx_push(discover(mac, 10 * k + i),
                                    from_access=True)
            out += cl.process_ring_pipelined(ring, NOW + k, k * 1000)
        return out + cl.flush_pipeline()

    def test_disarmed_loop_reads_no_clock(self, monkeypatch):
        import time

        import bng_tpu.parallel.sharded as sharded_mod
        from bng_tpu.telemetry import spans as tele

        assert not hasattr(sharded_mod, "time")
        cl = make_cluster()
        macs = populate(cl)
        cl.sync_tables()
        ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)
        self._beats(cl, ring, macs, n=1)  # compile outside the stub
        reads = []
        for name in ("perf_counter", "perf_counter_ns", "monotonic",
                     "monotonic_ns", "time", "time_ns"):
            real = getattr(time, name)
            monkeypatch.setattr(
                time, name, lambda real=real, name=name:
                (reads.append(name), real())[1])
        stub = tele.Tracer(clock=lambda: reads.append("tracer") or 0)
        assert not tele.enabled()
        assert self._beats(cl, ring, macs) == 12
        cl.dhcp_step(*self._dhcp_batch(cl, macs), NOW)
        monkeypatch.undo()
        assert reads == [], reads
        assert stub.sums()["beats"] == 0
        snap = cl.telemetry.snapshot()
        assert snap["steps"] >= 5 and "trace" in snap
        assert "stages" not in snap["per_shard"][0]

    def _dhcp_batch(self, cl, macs):
        B = cl.n * cl.b
        pkt = np.zeros((B, 2048), dtype=np.uint8)
        length = np.zeros((B,), dtype=np.uint32)
        f = discover(macs[0], 99)
        pkt[0, : len(f)] = np.frombuffer(f, dtype=np.uint8)
        length[0] = len(f)
        return pkt, length

    def test_armed_loop_stamps_the_sharded_lane(self):
        from bng_tpu.telemetry import spans as tele

        cl = make_cluster()
        macs = populate(cl)
        cl.sync_tables()
        ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)
        self._beats(cl, ring, macs, n=1)
        with tele.armed(keep_events=1 << 12) as tr:
            for k in range(3):
                tele.beat_begin()
                for i, mac in enumerate(macs[:4]):
                    ring.rx_push(discover(mac, 50 + 10 * k + i),
                                 from_access=True)
                cl.process_ring_pipelined(ring, NOW + k, k * 1000)
                tele.beat_end()
            tele.beat_begin()
            cl.flush_pipeline()
            tele.beat_end()
            snap = cl.telemetry.snapshot()
        lane = tele.LANE_SHARDED
        for stage in (tele.RING, tele.PACK, tele.DRAIN, tele.DISPATCH,
                      tele.DEVICE, tele.DEVICE_WAIT, tele.REPLY, tele.TX,
                      tele.TOTAL):
            h = tr.lane_hist(lane, stage)
            # pack is two laps a window: flag columns, then the staging
            want = 6 if stage == tele.PACK else 3
            assert h.n == want, tele.STAGE_NAMES[stage]
        assert not tr._dev and len(tr._free) == tr.OPEN_SLOTS
        t = snap["trace"]
        assert t["beats"] == 4 and t["batches"] == 3
        assert sum(t["starved_ns"].values()) == \
            t["beat_starved_ns"] + t["starved_ns"]["outside"]
        # the loop's stages tile its beats: little is left unclaimed
        assert t["beat_self_ns"] < 0.5 * t["stage_ns"]["beat"]
        beats = [b for e, b in zip(tr.events, tr.event_beats)
                 if e[1] == lane and e[0] == tele.DISPATCH]
        assert beats == [0, 1, 2]  # one dispatch a beat, the flush none


def test_an_armed_sharded_window_counts_the_crossings_the_code_makes():
    """`upload` / `fetch` (PR 37) on the sharded loop: one fused window
    over two shards whose tables are clean. The drain hands the step the
    update batch that is already placed over the mesh (PR 41: it asks
    each table for its dirty count and compares the dense arrays' bytes),
    so the literals are `pack`'s three placements and the retire's ten
    reads, started at the dispatch, and nothing of the drain's; a PR that
    merges the retire's reads lowers them here."""
    from bng_tpu.control import packets
    from bng_tpu.telemetry import spans as tele

    cl = make_cluster()
    macs = populate(cl)
    cl.sync_tables()
    ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)

    def beat(k):
        for i, mac in enumerate(macs[:3]):
            assert ring.rx_push(discover(mac, 70 + 10 * k + i),
                                from_access=True)
        assert ring.rx_push(packets.udp_packet(
            macs[0], SERVER_MAC, ip_to_u32("10.0.0.50"),
            ip_to_u32("93.184.216.34"), 40000, 443, b"x" * 64),
            from_access=True)  # a data frame: the window rides the fused step
        return cl.process_ring_pipelined(ring, NOW + k, k * 1000)

    import jax

    for k in range(3):  # compiled; the new flow punted, created and shipped
        beat(k)
    cl.flush_pipeline()
    assert cl.pending_dirty() == 0
    assert cl.garden is not None and cl.pppoe is None and cl.edge is None
    before = jax.tree.leaves(cl._drain_updates())
    with tele.armed(keep_events=1 << 12) as tr:
        tele.beat_begin()
        beat(3)
        cl.flush_pipeline()
        tele.beat_end()
        snap = cl.telemetry.snapshot()["trace"]
    batch = jax.tree.leaves(cl._drain_updates())  # clean: ships nothing
    assert (cl.n, len(batch)) == (2, 62)
    assert all(a is b for a, b in zip(before, batch))
    assert snap["batches"] == 1
    # ten tables a shard, every one clean: the placed batch served
    assert (snap["drain_built"], snap["drain_cached"]) == (0, 10 * cl.n)
    x = snap["xfer"]
    # `pack`: packet slots, lengths, access flags. The drain: none
    assert x["upload_calls"] == 3
    # the retire reads ten outputs (verdict, punt, violation, five stats
    # blocks with the garden's, out_pkt, out_len), and the copy of every
    # one was started at the window's dispatch (PR 44): the reads cross
    # nothing. The drain: none
    assert x["prefetch_calls"] == 10
    assert (x["fetch_calls"], x["fetch_bytes"]) == (0, 0)
    B = cl.n * cl.b
    assert x["upload_bytes"] == B * (2048 + 4 + 1)
    by_stage = {}
    for stage, _lane, _t0, _dur in tr.events:
        by_stage[stage] = by_stage.get(stage, 0) + 1
    # the retire's reads under one `fetch` lap, `pack`'s three placements
    # under one `upload` lap, one `drain` lap with no child
    assert by_stage[tele.FETCH] == 1
    assert by_stage[tele.UPLOAD] == 1
    assert by_stage[tele.DRAIN] == 1
    assert snap["stage_ns"]["fetch"] + snap["stage_ns"]["upload"] <= \
        snap["stage_ns"]["device_wait"] + snap["stage_ns"]["pack"]
    assert sum(snap["starved_ns"].values()) == \
        snap["beat_starved_ns"] + snap["starved_ns"]["outside"]


def save_bytes(cl, dhcp=None) -> bytes:
    return encode_checkpoint(
        build_sharded_checkpoint(cl, 1, float(NOW), dhcp=dhcp))


class TestShardedCheckpoint:
    def test_same_topology_roundtrip_audit_clean(self):
        cl = make_cluster()
        macs = populate(cl)
        cl.sync_tables()
        data = save_bytes(cl)

        fresh = make_cluster()
        rows = restore_sharded_checkpoint(decode_checkpoint(data), fresh,
                                          now=NOW)
        assert any(k.startswith("shard0.") for k in rows)
        for m in macs:
            assert fresh.get_subscriber(m) is not None
        # NAT block survived slot-exact on its owner shard
        owner = fresh.affinity_shard_ip(ip_to_u32("10.0.0.50"))
        assert ip_to_u32("10.0.0.50") in fresh.nat[owner].blocks
        audit_clean(fresh)

    def test_reshard_n_to_m_and_back_audit_clean(self):
        """2 -> 1 -> 2: every subscriber row and every piece of
        affinity state lands on its owner under each topology, audits
        clean at every step (the N->M and N->1->N satellite)."""
        cl = make_cluster(2)
        macs = populate(cl)
        cl.sync_tables()
        data2 = save_bytes(cl)

        cl1 = make_cluster(1)
        rows = restore_sharded_checkpoint(decode_checkpoint(data2), cl1,
                                          now=NOW)
        assert rows["resharded_from"] == 2 and rows["resharded_to"] == 1
        assert rows["dhcp_rows"] == len(macs)
        for m in macs:
            assert cl1.get_subscriber(m) is not None
        audit_clean(cl1)

        data1 = save_bytes(cl1)
        cl2 = make_cluster(2)
        rows = restore_sharded_checkpoint(decode_checkpoint(data1), cl2,
                                          now=NOW)
        assert rows["resharded_from"] == 1 and rows["resharded_to"] == 2
        for m in macs:
            assert cl2.get_subscriber(m) is not None
        # affinity state on its owner under the final topology
        nat_priv = ip_to_u32("10.0.0.50")
        owner = cl2.affinity_shard_ip(nat_priv)
        assert nat_priv in cl2.nat[owner].blocks
        assert cl2.qos[owner].up.lookup(nat_priv) is not None
        audit_clean(cl2)

    def test_reshard_serves_on_device_after_restore(self):
        """Post-re-shard, a cached DISCOVER must be answered BY THE
        MESH on the new topology (rows reachable via owner routing)."""
        cl = make_cluster(2)
        macs = populate(cl)
        cl.sync_tables()
        data = save_bytes(cl)
        cl1 = make_cluster(1)
        restore_sharded_checkpoint(decode_checkpoint(data), cl1, now=NOW)
        ring = cl1.make_ring(nframes=256, frame_size=2048, depth=64)
        assert ring.rx_push(discover(macs[3], 9), from_access=True)
        cl1.process_ring(ring, NOW, 0)
        assert ring.tx_pop() is not None
        assert cl1.telemetry.psum_dhcp_hits >= 1

    def test_crc_corruption_rejects(self):
        cl = make_cluster()
        populate(cl)
        data = bytearray(save_bytes(cl))
        data[-5] ^= 0xFF
        with pytest.raises(CheckpointError):
            decode_checkpoint(bytes(data))

    def test_geometry_mismatch_rejects_to_cold_start(self):
        cl = make_cluster()
        populate(cl)
        data = save_bytes(cl)
        shrunk = make_cluster(2, sub_nbuckets=128)  # different geometry
        with pytest.raises(CheckpointError):
            restore_sharded_checkpoint(decode_checkpoint(data), shrunk,
                                       now=NOW)

    def test_cross_topology_loads_reject_both_ways(self):
        """A single-engine snapshot cannot hydrate a cluster and a
        sharded snapshot cannot hydrate a single-engine process."""
        from bng_tpu.runtime.checkpoint import build_checkpoint
        from bng_tpu.runtime.tables import FastPathTables

        cl = make_cluster()
        populate(cl)
        sharded_ckpt = decode_checkpoint(save_bytes(cl))
        with pytest.raises(CheckpointError, match="single-engine"):
            restore_checkpoint(sharded_ckpt,
                               fastpath=FastPathTables(sub_nbuckets=64))

        flat = build_checkpoint(1, float(NOW),
                                fastpath=FastPathTables(sub_nbuckets=64))
        with pytest.raises(CheckpointError, match="sharded"):
            restore_sharded_checkpoint(flat, make_cluster(), now=NOW)


# ---------------------------------------------------------------------------
# sharded blue/green swap
# ---------------------------------------------------------------------------

class TestShardedSwap:
    def test_clean_swap_flips_and_serves(self):
        from bng_tpu.runtime.ops import sharded_blue_green_swap

        cl = make_cluster()
        macs = populate(cl)
        cl.sync_tables()
        comps = {"cluster": cl}
        rep = sharded_blue_green_swap(comps)
        assert rep["outcome"] == "ok", rep
        assert rep["audit_ok"]
        assert comps["cluster"] is not cl
        # the standby serves the hydrated rows on device
        standby = comps["cluster"]
        ring = standby.make_ring(nframes=256, frame_size=2048, depth=64)
        assert ring.rx_push(discover(macs[0], 5), from_access=True)
        standby.process_ring(ring, NOW, 0)
        assert ring.tx_pop() is not None
        assert standby.telemetry.psum_dhcp_hits >= 1

    def test_crash_at_flip_keeps_active(self):
        from bng_tpu.chaos.faults import FAIL, FaultPlan, FaultSpec, armed
        from bng_tpu.runtime.ops import sharded_blue_green_swap

        cl = make_cluster()
        populate(cl)
        cl.sync_tables()
        comps = {"cluster": cl}
        plan = FaultPlan(3, [FaultSpec("ops.swap", FAIL, at_hit=1)])
        with armed(plan, log=False):
            rep = sharded_blue_green_swap(comps)
        assert rep["outcome"] == "failed"
        assert comps["cluster"] is cl
        audit_clean(cl)


# ---------------------------------------------------------------------------
# the composed serving path: bng run --shards N
# ---------------------------------------------------------------------------

class TestShardedApp:
    @pytest.fixture()
    def app(self):
        from bng_tpu.cli import BNGApp, BNGConfig

        cfg = BNGConfig(shards=2, shard_nbuckets=64, batch_size=16,
                        synthetic_subs=8, dhcpv6_enabled=False,
                        slaac_enabled=False, metrics_enabled=True)
        app = BNGApp(cfg)
        yield app
        app.close()

    def test_run_shards_end_to_end(self, app):
        """`bng run --shards 2` on the forced host-device CPU mesh:
        ring-steered batches reach owner shards with zero missteers,
        the slow path serves OFFERs, a sharded swap flips live, and the
        full app audit is clean (the acceptance-criteria path)."""
        c = app.components
        assert "cluster" in c and "engine" not in c
        for _ in range(20):
            app.drive_once()
        c["cluster"].flush_pipeline(app._slow_path)
        s = app.stats()
        assert s["dhcp"]["offer"] > 0
        assert s["sharded"]["missteers"] == 0
        assert s["sharded"]["frames"] > 0

        rep = app.engine_swap()
        assert rep["outcome"] == "ok", rep
        for _ in range(5):
            app.drive_once()
        c["cluster"].flush_pipeline(app._slow_path)

        # post-swap control-plane writes must follow the flip: a NEW
        # DORA's subscriber row lands on the SERVING cluster's shards
        # (the sink resolves the live reference, never the retired one)
        from bng_tpu.control import dhcp_codec

        dhcp = c["dhcp"]
        m = mac_i(321)
        offer = dhcp.handle_frame(discover(m, 0x71))
        assert offer is not None
        op = dhcp_codec.decode(packets.decode(offer).payload)
        req = dhcp_codec.build_request(m, dhcp_codec.REQUEST, xid=0x72,
                                       requested_ip=op.yiaddr,
                                       server_id=SERVER_IP)
        fr = packets.udp_packet(m, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                req.encode().ljust(320, b"\x00"))
        assert dhcp.handle_frame(fr) is not None
        assert c["cluster"].get_subscriber(m) is not None

        from bng_tpu.chaos.invariants import audit_app

        audit = audit_app(app)
        assert audit.ok, audit.to_dict()

    def test_full_dora_renewal_hits_device(self, app):
        """A full DORA through the composed app's steered ring, then a
        renewal DISCOVER answered ON DEVICE (psum hit) — the promoted
        path's fast-path proof with the missteer counter at 0."""
        from bng_tpu.control import dhcp_codec

        c = app.components
        ring = c["ring"]
        cl = c["cluster"]
        m = mac_i(77)

        def beat():
            app.drive_once()
            app.drive_once()
            cl.flush_pipeline(app._slow_path)
            return ring.tx_pop()

        assert ring.rx_push(discover(m, 0x51), from_access=True)
        offer = None
        for _ in range(6):
            got = beat()
            if got is not None:
                offer = got[0]
                break
        assert offer is not None
        od = packets.decode(offer)
        op = dhcp_codec.decode(od.payload)
        req = dhcp_codec.build_request(m, dhcp_codec.REQUEST, xid=0x52,
                                       requested_ip=op.yiaddr,
                                       server_id=od.src_ip)
        fr = packets.udp_packet(m, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                req.encode().ljust(320, b"\x00"))
        assert ring.rx_push(fr, from_access=True)
        for _ in range(6):
            if beat() is not None:
                break
        hits_before = cl.telemetry.psum_dhcp_hits
        assert ring.rx_push(discover(m, 0x53), from_access=True)
        reply = None
        for _ in range(6):
            got = beat()
            if got is not None:
                reply = got[0]
                break
        assert reply is not None
        assert cl.telemetry.psum_dhcp_hits > hits_before
        assert cl.telemetry.snapshot()["missteer_total"] == 0


# ---------------------------------------------------------------------------
# the sink facade: owner routing for the DHCP server's writes
# ---------------------------------------------------------------------------

class TestShardedSink:
    def test_sink_routes_rows_to_owner_shards(self):
        cl = make_cluster()
        sink = ShardedFastPathSink(cl)
        macs = [mac_i(100 + i) for i in range(8)]
        for i, m in enumerate(macs):
            sink.add_subscriber(m, pool_id=1, ip=ip_to_u32(f"10.0.1.{i}"),
                                lease_expiry=NOW + 60)
        placed = 0
        for m in macs:
            o = cl.dhcp_sub_shard(m)
            assert cl.fastpath[o].get_subscriber(m) is not None
            other = (o + 1) % cl.n
            assert cl.fastpath[other].get_subscriber(m) is None
            placed += 1
        assert placed == len(macs)
        assert sink.remove_subscriber(macs[0])
        assert cl.get_subscriber(macs[0]) is None

    def test_sink_feeds_dhcp_server(self):
        """The DHCP server's _update_fastpath writes land on owner
        shards through the sink (the serving path's control plane)."""
        from bng_tpu.control.pool import Pool, PoolManager

        cl = make_cluster()
        sink = ShardedFastPathSink(cl)
        pools = PoolManager(fastpath_tables=sink)
        pools.add_pool(Pool(pool_id=2, network=ip_to_u32("10.9.0.0"),
                            prefix_len=24, gateway=ip_to_u32("10.9.0.1"),
                            dns_primary=ip_to_u32("1.1.1.1"),
                            lease_time=120))
        server = DHCPServer(SERVER_MAC, SERVER_IP, pools,
                            fastpath_tables=sink)
        m = mac_i(500)
        offer = server.handle_frame(discover(m, 0x99))
        assert offer is not None
        from bng_tpu.control import dhcp_codec

        op = dhcp_codec.decode(packets.decode(offer).payload)
        req = dhcp_codec.build_request(m, dhcp_codec.REQUEST, xid=0x9A,
                                       requested_ip=op.yiaddr,
                                       server_id=SERVER_IP)
        fr = packets.udp_packet(m, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                req.encode().ljust(320, b"\x00"))
        assert server.handle_frame(fr) is not None
        o = cl.dhcp_sub_shard(m)
        assert cl.fastpath[o].get_subscriber(m) is not None
