"""Latency-tiered scheduler (runtime/scheduler.py + runtime/lanes.py).

Covers the ISSUE-1 acceptance surface on the CPU backend:
- deadline-close semantics: a partial express batch dispatches at
  max-wait, not before;
- express-never-behind-bulk: an express dispatch while a bulk step is in
  flight has no data dependency on it (the dhcp chain is never rebound
  by bulk), runs on its own device when one is available, and completes
  while the bulk step is still in flight;
- pipelining depth: never more than N bulk dispatches in flight;
- update-drain cadence: bulk host-table drains happen every
  `drain_every` dispatches only, express drains the fastpath every
  dispatch;
- bng_sched_* metric families exported;
- slow-path exceptions are logged (rate-limited), not swallowed.

Table geometry mirrors tests/test_e2e.py so the fused-pipeline compile
is shared across modules within one pytest process.
"""

from __future__ import annotations

import logging

import pytest

import jax

from bng_tpu.control import dhcp_codec, packets
from bng_tpu.control.dhcp_server import DHCPServer
from bng_tpu.control.metrics import BNGMetrics
from bng_tpu.control.nat import NATManager
from bng_tpu.control.pool import Pool, PoolManager
from bng_tpu.ops.express import XD_WORDS
from bng_tpu.runtime import engine as engine_mod
from bng_tpu.runtime import hostpath
from bng_tpu.runtime.engine import AntispoofTables, Engine, QoSTables
from bng_tpu.runtime.lanes import (CLOSE_DEADLINE, CLOSE_FULL, CompletionRing,
                                   InflightEntry, Lane, LaneConfig)
from bng_tpu.runtime.scheduler import (LANE_BULK, LANE_EXPRESS,
                                       SchedulerConfig, TieredScheduler)
from bng_tpu.runtime.tables import FastPathTables
from bng_tpu.utils.net import ip_to_u32, parse_mac
from bng_tpu.utils.structlog import RateLimiter

SERVER_MAC = parse_mac("02:aa:bb:cc:dd:01")
SERVER_IP = ip_to_u32("10.0.0.1")


class FakeClock:
    def __init__(self, t=1_700_000_000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def build_stack(batch_size=8, clock=None, slow_path="server"):
    clock = clock or FakeClock()
    fastpath = FastPathTables(sub_nbuckets=512, vlan_nbuckets=64,
                              cid_nbuckets=64, max_pools=16)
    fastpath.set_server_config(SERVER_MAC, SERVER_IP)
    pools = PoolManager(fastpath)
    pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.0.0.0"),
                        prefix_len=24, gateway=SERVER_IP,
                        dns_primary=ip_to_u32("1.1.1.1"), lease_time=3600))
    nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                     sessions_nbuckets=256, sub_nat_nbuckets=64)
    qos = QoSTables(nbuckets=256)
    spoof = AntispoofTables(nbuckets=256)
    server = DHCPServer(SERVER_MAC, SERVER_IP, pools,
                        fastpath_tables=fastpath, clock=clock)
    sp = server.handle_frame if slow_path == "server" else slow_path
    engine = Engine(fastpath, nat, qos, spoof, batch_size=batch_size,
                    slow_path=sp, clock=clock)
    return engine, server, clock


def spy_bulk_drains(engine):
    """([what each bulk drain returned], [each non-empty batch that went
    through the packet-free apply program]) from now on."""
    drains, applied = [], []
    make, apply_now = engine._make_bulk_updates, engine.apply_updates_now
    engine._make_bulk_updates = lambda: (drains.append(make()), drains[-1])[1]
    engine.apply_updates_now = lambda upd: (
        applied.append(upd) if upd else None, apply_now(upd))[1]
    return drains, applied


def bulk_dispatch(engine, drain: bool, replica=None):
    """One inert window through the scheduler's bulk road; the replica as
    the step left it."""
    import numpy as np

    if replica is None:
        replica = engine.dhcp_replica(jax.numpy.copy)
    pkt = np.zeros((engine.B, engine.L), np.uint8)
    length = np.zeros((engine.B,), np.uint32)
    _res, replica = engine.dispatch_scheduled_bulk(
        pkt, length, np.zeros((engine.B,), bool), 1_753_000_000.0, replica,
        drain=drain)
    return replica


def discover(mac: bytes, xid: int) -> bytes:
    p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER, xid=xid)
    p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
    return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                              p.encode().ljust(300, b"\x00"))


def data_frame(i: int) -> bytes:
    mac = (0x02C0 << 32 | i).to_bytes(6, "big")
    return packets.udp_packet(mac, SERVER_MAC, ip_to_u32("10.0.0.9") + i,
                              ip_to_u32("93.184.216.34"), 40000 + i, 443,
                              b"x" * 64)


def mac_of(i: int) -> bytes:
    return (0x02B0 << 32 | i).to_bytes(6, "big")


def np_key(mac: bytes):
    """The sub table's key words of a MAC (hi, lo)."""
    k = int.from_bytes(mac, "big")
    return [k >> 32, k & 0xFFFFFFFF]


# ---------------------------------------------------------------------------
# lanes: pure host-side policy (no device)
# ---------------------------------------------------------------------------

class TestLanePolicy:
    def test_full_close(self):
        lane = Lane(LaneConfig("x", batch=4, max_wait_us=1000, depth=2))
        now = 100.0
        for i in range(4):
            assert lane.push(b"f%d" % i, True, now, tag=i)
        assert lane.close_reason(now) == CLOSE_FULL
        pend, reason = lane.close_batch(now)
        assert reason == CLOSE_FULL and len(pend) == 4
        assert lane.stats.batches_full == 1

    def test_deadline_close_only_after_max_wait(self):
        lane = Lane(LaneConfig("x", batch=4, max_wait_us=200, depth=2))
        lane.push(b"f", True, 100.0)
        assert lane.close_reason(100.0 + 100e-6) is None  # 100us < 200us
        assert lane.close_reason(100.0 + 250e-6) == CLOSE_DEADLINE
        pend, reason = lane.close_batch(100.0 + 250e-6)
        assert reason == CLOSE_DEADLINE and len(pend) == 1
        assert lane.stats.batches_deadline == 1
        assert lane.stats.occupancy_avg() == pytest.approx(0.25)

    def test_overflow_drops(self):
        lane = Lane(LaneConfig("x", batch=2, max_wait_us=10, depth=1,
                               max_queue=3))
        assert all(lane.push(b"f", True, 1.0) for _ in range(3))
        assert not lane.push(b"f", True, 1.0)
        assert lane.stats.dropped_overflow == 1

    def test_completion_ring_overflow_is_fifo(self):
        ring = CompletionRing(depth=2)
        e = [InflightEntry(None, [], float(i), "full") for i in range(4)]
        assert ring.push(e[0]) is None
        assert ring.push(e[1]) is None
        assert ring.push(e[2]) is e[0]  # overflow hands back the OLDEST
        assert ring.push(e[3]) is e[1]
        assert len(ring) == 2


# ---------------------------------------------------------------------------
# scheduler over a live engine (CPU backend)
# ---------------------------------------------------------------------------

class TestClassification:
    def test_access_dhcp_express_else_bulk(self):
        engine, _, clock = build_stack()
        sched = TieredScheduler(engine, SchedulerConfig(), clock=clock)
        d = discover(mac_of(1), 0x11)
        assert sched.classify(d, from_access=True) == LANE_EXPRESS
        # core-side port-67 transit must NOT ride the express lane
        assert sched.classify(d, from_access=False) == LANE_BULK
        assert sched.classify(data_frame(1), from_access=True) == LANE_BULK


class TestOversizeFrames:
    def test_frame_over_pkt_slot_dropped_not_crash(self):
        """Rings admit frames up to frame_size (2048) but the engine slot
        is smaller; the scheduler must drop-and-count at submit, not blow
        up _pack_frames at dispatch (a wire frame must never kill the
        drive loop)."""
        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(bulk_batch=8),
                                clock=clock)
        big = data_frame(0) + b"\x00" * engine.L  # > pkt_slot
        assert sched.submit(big) is None
        assert sched.oversize_dropped == 1
        assert len(sched.bulk) == 0
        sched.poll()  # nothing queued, nothing raises


class TestDeadlineClose:
    def test_partial_express_batch_ships_at_max_wait(self):
        engine, _, clock = build_stack()
        sched = TieredScheduler(engine, SchedulerConfig(
            express_batch=64, express_max_wait_us=200.0), clock=clock)
        for i in range(3):
            assert sched.submit(discover(mac_of(i), 0x20 + i)) == LANE_EXPRESS
        sched.poll()
        assert sched.express.stats.batches == 0  # neither full nor aged
        clock.advance(100e-6)
        sched.poll()
        assert sched.express.stats.batches == 0  # 100us < max_wait
        clock.advance(150e-6)
        sched.poll()
        assert sched.express.stats.batches == 1  # deadline close fired
        assert sched.express.stats.batches_deadline == 1
        assert sched.express.stats.frames_dispatched == 3
        done = sched.drain_completions()
        assert len(done) == 3  # OFFERs from the slow path (fresh MACs)
        assert {c.lane for c in done} == {LANE_EXPRESS}
        replies = [c.frame for c in done if c.frame is not None]
        assert replies, "slow path should have produced OFFERs"


@pytest.mark.hotpath
class TestExpressNeverBehindBulk:
    def test_express_completes_while_bulk_in_flight(self):
        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            express_batch=64, bulk_batch=8, bulk_depth=2), clock=clock)

        # fill + dispatch exactly one bulk batch (manually, so nothing
        # retires it behind our back)
        for i in range(8):
            assert sched.submit(data_frame(i)) == LANE_BULK
        dhcp_before = jax.tree_util.tree_leaves(engine.tables.dhcp)
        now = clock()
        pend, reason = sched.bulk.close_batch(now)
        assert reason == CLOSE_FULL
        assert sched._dispatch_bulk(pend, now, reason) is None
        assert len(sched._bulk_ring) == 1  # bulk step in flight

        # the bulk dispatch must NOT have rebound the dhcp chain: that is
        # the data-dependency the replica design removes
        dhcp_after = jax.tree_util.tree_leaves(engine.tables.dhcp)
        assert all(a is b for a, b in zip(dhcp_before, dhcp_after))

        # express dispatch + retire with the bulk step still in flight
        for i in range(64):
            sched.submit(discover(mac_of(100 + i), 0x3000 + i))
        retired = sched._pump_express(clock())
        assert retired == 64
        done = sched.drain_completions()
        assert len(done) == 64
        assert {c.lane for c in done} == {LANE_EXPRESS}
        # ...and the bulk step is STILL in flight: express completion did
        # not wait for (or retire) it
        assert len(sched._bulk_ring) == 1

        # multi-device mesh: the express program ran on its own device,
        # so it did not even share an execution stream with bulk
        if len(jax.devices()) > 1:
            express_devs = {d for leaf in
                            jax.tree_util.tree_leaves(engine.tables.dhcp)
                            for d in leaf.devices()}
            bulk_entry = sched._bulk_ring._ring[0]
            bulk_devs = set(bulk_entry.res.verdict.devices())
            assert express_devs == {sched._express_dev}
            assert express_devs.isdisjoint(bulk_devs)

        # the flush barrier retires the bulk step
        sched.flush()
        bulk_done = sched.drain_completions()
        assert len(bulk_done) == 8
        assert {c.lane for c in bulk_done} == {LANE_BULK}

    def test_poll_services_express_before_bulk(self):
        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            express_batch=8, bulk_batch=8, bulk_depth=2), clock=clock)
        # both lanes have a full batch queued; one poll must dispatch
        # express first (completion order proves service order)
        for i in range(8):
            sched.submit(data_frame(i))
        for i in range(8):
            sched.submit(discover(mac_of(200 + i), 0x4000 + i))
        sched.poll()
        sched.flush()
        lanes_in_order = [c.lane for c in sched.drain_completions()]
        assert lanes_in_order.index(LANE_EXPRESS) < lanes_in_order.index(LANE_BULK)

    def test_young_express_frames_ship_ahead_of_a_waiting_bulk_close(self):
        """Express frames younger than their deadline, a bulk batch aged
        past its own: the bulk close takes milliseconds of this thread, so
        the express batch ships in the same poll and AHEAD of it, not a
        beat later behind that step. With no bulk close waiting the
        deadline still decides."""
        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            express_batch=64, express_max_wait_us=200.0, bulk_batch=8,
            bulk_max_wait_us=2000.0, bulk_depth=2), clock=clock)
        for i in range(3):
            sched.submit(data_frame(i))
        clock.advance(3000e-6)  # the bulk frames are past their deadline
        for i in range(2):
            sched.submit(discover(mac_of(300 + i), 0x5000 + i))
        clock.advance(50e-6)  # the express frames are not past theirs
        sched.poll()
        assert sched.express.stats.batches == 1
        assert sched.express.stats.batches_deadline == 1
        assert sched.bulk.stats.batches == 1
        sched.flush()
        lanes_in_order = [c.lane for c in sched.drain_completions()]
        assert lanes_in_order == [LANE_EXPRESS] * 2 + [LANE_BULK] * 3
        # no bulk close waiting: young express frames keep filling
        sched.submit(discover(mac_of(310), 0x5100))
        clock.advance(50e-6)
        sched.poll()
        assert sched.express.stats.batches == 1


@pytest.mark.hotpath
class TestPipelineDepth:
    def test_no_more_than_depth_in_flight(self):
        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            bulk_batch=8, bulk_depth=2, drain_every=1), clock=clock)
        max_seen = 0
        orig_push = sched._bulk_ring.push

        def spy_push(entry):
            nonlocal max_seen
            out = orig_push(entry)
            max_seen = max(max_seen, len(sched._bulk_ring))
            return out

        sched._bulk_ring.push = spy_push
        for i in range(5 * 8):  # five full bulk batches
            sched.submit(data_frame(i))
        retired = sched.poll()
        assert sched.bulk.stats.batches == 5
        # the ring may transiently hold depth+1 inside push(); what the
        # scheduler leaves in flight is bounded by depth
        assert max_seen <= 3
        assert len(sched._bulk_ring) <= 2
        retired += sched.flush()
        assert retired == 40


class TestTracedLoop:
    """PR 25: the scheduler's stamps tile, the occupancy calls pair up,
    the always-on integers count, and the `trace` subtree freezes."""

    def _drive(self, sched, clock, base, n_bulk=24, n_dhcp=2):
        for i in range(n_bulk):
            sched.submit(data_frame(base + i))
        for i in range(n_dhcp):
            sched.submit(discover(mac_of(base + i), 100 + base + i))
        clock.advance(0.01)
        return sched.poll()

    def test_trace_subtree_is_the_same_before_and_after_the_drain(self):
        from bng_tpu.telemetry import spans as tele

        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            bulk_batch=8, bulk_depth=2, express_batch=4,
            express_device_index=-1), clock=clock)
        assert "beats" in sched.stats_snapshot()["trace"]  # always present
        tr = tele.arm(tele.Tracer(keep_events=1 << 12))
        try:
            c0 = sched.stats_snapshot()
            assert c0["trace"]["beats"] == 0 == c0["trace"]["batches"]
            for k in range(3):
                tele.beat_begin()
                self._drive(sched, clock, 100 * k)
                tele.beat_end()
        finally:
            tele.disarm()
        frozen = sched.stats_snapshot()["trace"]
        # more traffic and the drain, disarmed: nothing moves the sums
        self._drive(sched, clock, 900)
        sched.flush()
        c1 = sched.stats_snapshot()
        assert c1["trace"] == frozen
        assert frozen["beats"] == 3 and frozen["batches"] >= 9
        for stage in ("pack", "dispatch", "drain", "device", "device_wait",
                      "reply", "sojourn", "lane_wait"):
            assert frozen["stage_ns"][stage] > 0, stage
        assert 0 < frozen["beat_self_ns"] < frozen["stage_ns"]["beat"]
        assert sum(frozen["starved_ns"].values()) == \
            frozen["beat_starved_ns"] + frozen["starved_ns"]["outside"]
        # all that went up came down, but what was still in flight at
        # disarm: the drain retires those unseen (at most the bulk depth)
        assert len(tr._dev) <= 2
        assert len(tr._free) == tr.OPEN_SLOTS - len(tr._dev)
        # sojourns: one per frame retired while armed, by lane
        soj = [e for e in tr.events if e[0] == tele.SOJOURN]
        assert {e[1] for e in soj} == {tele.LANE_EXPRESS_L, tele.LANE_BULK_L}
        assert len(tr.events) == len(tr.event_beats)
        assert all(b >= 0 for e, b in zip(tr.events, tr.event_beats)
                   if e[0] == tele.SOJOURN)  # retired inside a beat
        assert tr.lane_hist(tele.LANE_BULK_L, tele.DEVICE).n >= 1

    def test_one_armed_poll_counts_the_crossings_the_code_makes(self):
        """`upload` / `fetch` (PR 37) on the scheduler's loop: one bulk and
        one express dispatch, each retired. The literals are the crossings
        the code makes: a PR that merges reads lowers them here."""
        from bng_tpu.telemetry import spans as tele

        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            bulk_batch=8, bulk_depth=2, express_batch=4,
            express_device_index=-1), clock=clock)
        assert set(sched.stats_snapshot()["trace"]["xfer"]) == {
            "upload_calls", "upload_bytes", "fetch_calls", "fetch_bytes",
            "prefetch_calls"}
        for k in range(2):  # compile, and place the dense arrays once
            self._drive(sched, clock, 100 * k, n_bulk=8)
        sched.flush()
        with tele.armed(keep_events=1 << 10) as tr:
            tele.beat_begin()
            self._drive(sched, clock, 300, n_bulk=8)
            sched.flush()
            tele.beat_end()
            snap = sched.stats_snapshot()["trace"]
        assert snap["batches"] == 2  # one bulk step, one express batch
        x = snap["xfer"]
        L = engine.L
        # bulk: the window's one block (packet slots, and the planes of
        # the lengths and access flags behind them); express: the
        # descriptor rows alone (the clock word crosses inside the call);
        # every table clean
        assert x["upload_calls"] == 1 + 1
        assert x["upload_bytes"] == (
            hostpath.window_rows(8, L) * L + 4 * XD_WORDS * 4)
        # bulk retire: verdict, out_len, punt, violation inside
        # `device_wait`, then _fold_stats' four blocks: since PR 43 the
        # copy of each was started at dispatch (out_pkt's too, which no
        # lane of these frames needs: none is TX or FWD), so they cross
        # nothing at the retire. Express retire: the verdict block
        # (written over the descriptor rows), and the one stats block the
        # program returns, forced as before
        assert x["prefetch_calls"] == 5 + 4
        assert x["fetch_calls"] == 1 + 1
        assert x["fetch_bytes"] == (4 * XD_WORDS * 4
                                    + 4 * len(engine.stats.dhcp))
        lanes = {}
        for stage, lane, _t0, _dur in tr.events:
            if stage in (tele.UPLOAD, tele.FETCH):
                lanes.setdefault((stage, lane), []).append(1)
        assert {k: len(v) for k, v in lanes.items()} == {
            (tele.UPLOAD, tele.LANE_BULK_L): 1,
            (tele.UPLOAD, tele.LANE_EXPRESS_L): 1,
            (tele.FETCH, tele.LANE_BULK_L): 2,
            (tele.FETCH, tele.LANE_EXPRESS_L): 2}
        assert sum(snap["starved_ns"].values()) == \
            snap["beat_starved_ns"] + snap["starved_ns"]["outside"]
        # the children lie inside their parents
        assert snap["stage_ns"]["upload"] <= snap["stage_ns"]["dispatch"]

    def test_always_on_integers_count_disarmed(self):
        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            bulk_batch=8, bulk_depth=2, express_batch=4,
            express_device_index=-1), clock=clock)
        # five full bulk batches in one poll: depth 2, so three retires block
        for i in range(40):
            sched.submit(data_frame(i))
        sched.poll()
        snap = sched.stats_snapshot()
        assert snap["bulk"]["blocked_retires"] == 3
        assert snap["express"]["behind_bulk"] == 0
        # an express batch dispatched while bulk steps are in flight on
        # the device it shares
        assert len(sched._bulk_ring) == 2
        for i in range(4):
            sched.submit(discover(mac_of(i), 7 + i))
        sched.poll()
        snap = sched.stats_snapshot()
        assert snap["express"]["behind_bulk"] == 1
        assert snap["express"]["batches"] == 1
        sched.flush()
        # what an operator reads them from
        metrics = BNGMetrics()
        metrics.collect_scheduler(sched)
        text = metrics.expose()
        assert "bng_sched_bulk_blocked_retires_total 3" in text
        assert "bng_sched_express_behind_bulk_total 1" in text


@pytest.mark.hotpath
class TestUpdateDrainCadence:
    def test_bulk_drains_every_n_dispatches(self):
        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            bulk_batch=8, bulk_depth=2, drain_every=3,
            overlap_drain=False), clock=clock)
        drains, applied = spy_bulk_drains(engine)
        for i in range(6 * 8):  # six bulk dispatches under sustained load
            sched.submit(data_frame(i))
        sched.poll()
        sched.flush()
        assert sched.bulk.stats.batches == 6
        # drains at bulk_seq 0, 3 — every third dispatch only
        assert len(drains) == 2
        assert sched._drains_applied == 2
        assert sched._drains_prefetched == 0
        # nothing was dirty at either: no drain built a batch, and no step
        # of the six was preceded by an apply call
        assert drains == [(), ()] and applied == []

    def test_overlap_drain_prefetches_next_scatter(self):
        """overlap_drain (default): the drain-due step's scatter is built
        right after the PREVIOUS dispatch (overlapping step N's device
        execution), the in-dispatch cadence is unchanged, and a trailing
        prefetch that no batch consumed reaches the device at flush —
        never stranded (host dirty sets were already drained into it)."""
        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            bulk_batch=8, bulk_depth=2, drain_every=3), clock=clock)
        drains, applied = spy_bulk_drains(engine)
        for i in range(6 * 8):
            sched.submit(data_frame(i))
        sched.poll()
        sched.flush()
        assert sched.bulk.stats.batches == 6
        # drains: in-dispatch at seq 0, prefetched for seq 3 and seq 6;
        # seq 6 never dispatched, so its (empty) batch is settled at flush
        assert len(drains) == 3 and applied == []
        assert sched._drains_prefetched == 2
        assert sched._drains_applied == 3  # seq 0, seq 3, flush-applied
        assert sched._prefetched_upd is None

    def test_overlap_drain_flush_ships_pending_delta(self):
        """A host write drained into a prefetched batch must be ON the
        device after flush even when no later bulk batch ever runs —
        the dangling-prefetch divergence hazard, pinned end-to-end."""
        import numpy as np

        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            bulk_batch=8, bulk_depth=2, drain_every=1), clock=clock)
        for i in range(8):
            sched.submit(data_frame(i))
        sched.poll()
        sched.flush()  # drains consumed; a prefetched batch may linger
        # new host delta -> consumed by the NEXT prefetch, no more frames
        engine.qos.set_subscriber(ip_to_u32("10.9.9.9"), 8_000_000, 8_000_000)
        for i in range(8):
            sched.submit(data_frame(100 + i))
        sched.poll()
        sched.flush()
        assert engine.qos.up.dirty_count() == 0  # drained somewhere...
        slot = engine.qos.up._find(ip_to_u32("10.9.9.9"))
        assert slot is not None
        from bng_tpu.ops.qtable import way_rows
        dev_row = way_rows(engine.tables.qos_up.rows, engine.qos.up.nbuckets)[slot]
        assert np.array_equal(dev_row, engine.qos.up.rows[slot])  # ...and on device

    def test_no_drain_steps_carry_live_dense_config(self):
        """A step that drains nothing must still read the dense config
        arrays as the host holds them: a no-drain bulk step after a
        config change has it in its tables (and the all-padding batch the
        apply program is built with still carries it, not a build-time
        snapshot)."""
        engine, _, clock = build_stack()
        engine._empty_updates()  # primes the scatter caches
        engine.antispoof.add_allowed_range(ip_to_u32("172.16.0.0"), 12)
        after = engine._empty_updates()
        import numpy as np

        # upd layout: spoof ranges ride at index 5
        sp_ranges = np.asarray(after[5])
        assert (sp_ranges[:, 1] == ip_to_u32("172.16.0.0")).any()
        bulk_dispatch(engine, drain=False)
        on_chip = np.asarray(engine.tables.spoof_ranges)
        assert (on_chip[:, 1] == ip_to_u32("172.16.0.0")).any()

    @pytest.mark.parametrize("drain", ["no_drain_bulk", "_drain_updates",
                                       "drain_bulk"])
    @pytest.mark.parametrize("array", ["spoof_ranges", "nat_hairpin",
                                       "nat_config", "pools", "server"])
    def test_every_batch_carries_live_dense_config(self, drain, array):
        """The twin of the test above for every road to a step and for the
        nat / fastpath arrays (PR 35: a dense array is placed once and
        placed again when its bytes changed; PR 50: into the tables, on the
        host, with no program). A change made before a dispatch is in the
        tables its step reads (the bulk replica's for pools and server on
        the bulk roads), and an unchanged array crosses nothing."""
        import numpy as np

        engine, _, clock = build_stack()
        replica = [engine.dhcp_replica(jax.numpy.copy)]

        def bulk(drain_flag):
            replica[0] = bulk_dispatch(engine, drain_flag, replica[0])

        sync = {"no_drain_bulk": lambda: bulk(False),
                "drain_bulk": lambda: bulk(True),
                "_drain_updates": engine._drain_updates}[drain]

        def dhcp():
            return (engine.tables.dhcp if drain == "_drain_updates"
                    else replica[0])

        write, leaf, seen = {
            "spoof_ranges": (
                lambda: engine.antispoof.add_allowed_range(
                    ip_to_u32("172.16.0.0"), 12),
                lambda: engine.tables.spoof_ranges,
                lambda a: (a[:, 1] == ip_to_u32("172.16.0.0")).any()),
            "nat_hairpin": (
                lambda: engine.nat.add_hairpin_ip(ip_to_u32("203.0.113.9")),
                lambda: engine.tables.nat.hairpin_ips,
                lambda a: (a == ip_to_u32("203.0.113.9")).any()),
            # config_array() is a fresh array a call: the compare is on
            # bytes, not on the array's identity
            "nat_config": (
                lambda: setattr(engine.nat, "ports_per_subscriber", 77),
                lambda: engine.tables.nat.config,
                lambda a: int(a[3]) == 77),
            "pools": (
                lambda: engine.fastpath.add_pool(
                    3, ip_to_u32("10.3.0.0"), 24, ip_to_u32("10.3.0.1")),
                lambda: dhcp().pools,
                lambda a: (a[3] != 0).any()),
            "server": (
                lambda: engine.fastpath.set_server_config(
                    SERVER_MAC, ip_to_u32("10.0.0.2")),
                lambda: dhcp().server,
                lambda a: (a == ip_to_u32("10.0.0.2")).any()),
        }[array]
        placed = []  # the fields each sync put into the tables
        orig = engine._fresh_dense

        def fresh(chain, node, **kw):
            out = orig(chain, node, **kw)
            placed.extend(f for f in node._fields
                          if getattr(out, f) is not getattr(node, f))
            return out

        engine._fresh_dense = fresh
        sync()
        assert not seen(np.asarray(leaf()))
        assert placed == []  # unchanged since the upload: nothing crosses
        write()
        sync()
        assert seen(np.asarray(leaf()))
        assert len(placed) == 1  # this array, and no other for this write
        sync()
        assert seen(np.asarray(leaf())) and len(placed) == 1

    def test_express_drains_fastpath_every_dispatch(self, monkeypatch):
        """The drain is LOGICALLY per-dispatch, and only what changed is
        uploaded (PR 35; PR 13 had a shortcut of its own here): clean
        tables cost nothing, so a dispatch with nothing dirty builds no
        table batch (`drain_built` 0) and makes no apply call (PR 50: no
        step takes a batch), while ANY dirty slot is built, shipped and
        applied ahead of the very next dispatch (lease visibility pinned
        by the next test)."""
        import numpy as np

        from bng_tpu.telemetry import spans

        engine, _, clock = build_stack()
        sched = TieredScheduler(engine, SchedulerConfig(
            express_batch=8), clock=clock)
        fp, drained, applied = engine.fastpath, [], []
        orig = engine._drain_fastpath_updates
        engine._drain_fastpath_updates = (
            lambda device=None: (drained.append(1), orig(device))[1])
        monkeypatch.setattr(
            engine_mod, "_apply_fastpath_jit",
            lambda t, u, _f=engine_mod._apply_fastpath_jit:
            (applied.append(u), _f(t, u))[1])
        n = fp.update_slots
        with spans.armed() as tr:
            for i in range(16):
                sched.submit(discover(mac_of(300 + i), 0x5000 + i))
            sched.poll()
            assert sched.express.stats.batches == 2
            # a drain a dispatch, and nothing was dirty at either: no
            # batch was built or uploaded, and no apply call was made
            assert len(drained) == 2 and applied == []
            assert tr.sums()["drain_built"] == 0
            assert tr.sums()["drain_cached"] == 2 * 3
            # a host-side table write makes the NEXT dispatch drain for real
            fp.add_subscriber(mac_of(390), pool_id=1,
                              ip=ip_to_u32("10.0.0.90"),
                              lease_expiry=int(clock()) + 600)
            for i in range(8):
                sched.submit(discover(mac_of(320 + i), 0x5100 + i))
            sched.poll()
            assert sched.express.stats.batches == 3
            assert len(drained) == 3 and len(applied) == 1
            # (placed on the express lane's device: compare what they hold)
            assert (np.asarray(applied[0].sub.bidx) < fp.sub.nbuckets).any()
            assert np.array_equal(applied[0].vlan.bidx,  # still clean
                                  fp.vlan.empty_update(n).bidx)
            assert tr.sums()["drain_built"] == 1
            assert tr.sums()["drain_cached"] == 2 * 3 + 2
        assert fp.dirty_count() == 0  # delta shipped
        slot = fp.sub._find_slot(np_key(mac_of(390)))
        assert np.asarray(engine.tables.dhcp.sub.vals)[slot].any()  # on device

    def test_pending_lease_reaches_device_via_express_drain(self):
        """A lease installed host-side between steps is visible to the
        very next express dispatch (the OFFER-correctness invariant the
        always-drain express rule protects)."""
        engine, _, clock = build_stack()
        sched = TieredScheduler(engine, SchedulerConfig(express_batch=8),
                                clock=clock)
        mac = mac_of(400)
        engine.fastpath.add_subscriber(mac, pool_id=1,
                                       ip=ip_to_u32("10.0.0.77"),
                                       lease_expiry=int(clock()) + 3600)
        out = sched.process([discover(mac, 0x6001)])
        assert len(out["tx"]) == 1  # on-device OFFER: the update landed


@pytest.mark.hotpath
class TestSchedulerDHCPCorrectness:
    def test_dora_then_fastpath_hit(self):
        engine, server, clock = build_stack()
        sched = TieredScheduler(engine, SchedulerConfig(express_batch=8),
                                clock=clock)
        mac = mac_of(500)
        out = sched.process([discover(mac, 0x7001)])
        assert len(out["slow"]) == 1
        offer = out["slow"][0][1]
        assert offer is not None
        od = packets.decode(offer)
        op = dhcp_codec.decode(od.payload)
        assert op.msg_type == dhcp_codec.OFFER
        req = dhcp_codec.build_request(mac, dhcp_codec.REQUEST, xid=0x7002,
                                       requested_ip=op.yiaddr,
                                       server_id=od.src_ip)
        req.options.append((dhcp_codec.OPT_PARAM_REQ_LIST,
                            bytes([1, 3, 6, 51, 54])))
        rf = packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                req.encode().ljust(300, b"\x00"))
        out2 = sched.process([rf])
        ack = out2["slow"][0][1]
        assert ack is not None
        assert dhcp_codec.decode(packets.decode(ack).payload).msg_type \
            == dhcp_codec.ACK
        # the lease is now in the device cache: next DISCOVER answers
        # on-device through the express lane (TX, no slow path)
        out3 = sched.process([discover(mac, 0x7003)])
        assert len(out3["tx"]) == 1 and not out3["slow"]

    def test_mixed_batch_fans_out_to_both_lanes(self):
        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            express_batch=8, bulk_batch=8), clock=clock)
        frames = [discover(mac_of(600 + i), 0x8000 + i) for i in range(3)]
        frames += [data_frame(700 + i) for i in range(5)]
        out = sched.process(frames)
        done = {i for lst in (out["tx"], out["slow"], out["fwd"])
                for i, _ in lst} | set(out["dropped"])
        assert done == set(range(8))
        assert sched.express.stats.frames_dispatched == 3
        assert sched.bulk.stats.frames_dispatched == 5


class TestSchedulerMetrics:
    def test_bng_sched_families_exported(self):
        engine, _, clock = build_stack(batch_size=8)
        metrics = BNGMetrics()
        sched = TieredScheduler(engine, SchedulerConfig(
            express_batch=8, bulk_batch=8), metrics=metrics, clock=clock)
        for i in range(8):
            sched.submit(discover(mac_of(800 + i), 0x9000 + i))
        for i in range(8):
            sched.submit(data_frame(900 + i))
        sched.poll()
        sched.flush()
        metrics.collect_scheduler(sched)
        text = metrics.expose()
        assert 'bng_sched_dispatches_total{lane="express",close="full"} 1' in text
        assert 'bng_sched_dispatches_total{lane="bulk",close="full"} 1' in text
        assert 'bng_sched_queue_depth{lane="express"} 0' in text
        assert 'bng_sched_frames_total{lane="bulk"} 8' in text
        assert "bng_sched_batch_occupancy_ratio_bucket" in text
        assert "bng_sched_dispatch_latency_seconds_bucket" in text


class TestSlowPathErrorsLogged:
    def _capture(self):
        records = []

        class H(logging.Handler):
            def emit(self, record):
                records.append(record)

        h = H()
        logging.getLogger("bng.slowpath").addHandler(h)
        return records, h

    def test_engine_process_logs_not_swallows(self):
        def boom(frame):
            raise ValueError("poisoned frame")

        engine, _, clock = build_stack(slow_path=boom)
        records, h = self._capture()
        try:
            out = engine.process([data_frame(0)])
            assert len(out["slow"]) == 1
            assert engine.stats.slow_errors == 1
            assert len(records) == 1
            assert records[0].bng_fields["error"].startswith("ValueError")
            assert records[0].exc_info is not None  # traceback preserved
        finally:
            logging.getLogger("bng.slowpath").removeHandler(h)

    def test_scheduler_lanes_log_and_rate_limit(self):
        def boom(frame):
            raise RuntimeError("handler down")

        engine, _, clock = build_stack(slow_path=boom)
        # deterministic limiter: 2-token bucket, no refill w/ fake clock
        engine._slow_err_log._limit = RateLimiter(rate=1.0, burst=2,
                                                  clock=clock)
        sched = TieredScheduler(engine, SchedulerConfig(express_batch=8),
                                clock=clock)
        records, h = self._capture()
        try:
            sched.process([discover(mac_of(950 + i), 0xA100 + i)
                           for i in range(8)])
            assert engine.stats.slow_errors == 8  # every failure counted
            assert len(records) == 2  # ...but the log is rate-limited
        finally:
            logging.getLogger("bng.slowpath").removeHandler(h)


class TestRateLimiter:
    def test_burst_then_refill(self):
        clock = FakeClock(0.0)
        rl = RateLimiter(rate=1.0, burst=2, clock=clock)
        assert rl.allow() == (True, 0)
        assert rl.allow() == (True, 0)
        ok, _ = rl.allow()
        assert not ok
        ok, _ = rl.allow()
        assert not ok
        clock.advance(1.0)  # one token refilled
        ok, suppressed = rl.allow()
        assert ok and suppressed == 2  # the two denied events reported


class TestLoadtestHarnessScheduler:
    def test_harness_routes_through_scheduler(self):
        from bng_tpu.loadtest import BenchmarkConfig, DHCPBenchmark

        engine, _, clock = build_stack(batch_size=8)
        sched = TieredScheduler(engine, SchedulerConfig(
            express_batch=8, bulk_batch=8), clock=clock)
        cfg = BenchmarkConfig(batch_size=8, duration_s=0.05, warmup_s=0.02,
                              unique_macs=8, enable_renewals=False)
        import time as _t

        bench = DHCPBenchmark(sched, cfg, clock=_t.perf_counter)
        res = bench.run()
        assert res.program == "tiered_scheduler"
        assert res.requests > 0
        assert res.responses > 0
