"""Telemetry subsystem tests (bng_tpu/telemetry): disarmed-overhead
bound, histogram merge laws, flight-recorder wrap + anomaly triggers
(incl. forced backend fallback), Chrome-trace export schema, and DORA
through tracing — host-only through the fleet, and the full engine +
scheduler + fleet.

`make verify-telemetry` runs the 'telemetry and not slow' set less
TestDoraTracingE2E with BNG_TELEMETRY=1 in the environment (< 30 s — no
XLA compiles there).
"""

from __future__ import annotations

import json
import os
import sys
import timeit

import numpy as np
import pytest

from bng_tpu.chaos.faults import FaultPlan, FaultSpec, SimClock, armed
from bng_tpu.chaos.invariants import audit_invariants
from bng_tpu.chaos.scenarios import _mac, build_fleet, dora_with_retries
from bng_tpu.telemetry import (FlightRecorder, LatencyHist, RecorderConfig,
                               Tracer, chrome_trace)
from bng_tpu.telemetry import spans

pytestmark = pytest.mark.telemetry

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    spans.disarm()


# ---------------------------------------------------------------------------
# disarmed overhead: the production state must stay near-free
# ---------------------------------------------------------------------------

class TestDisarmedOverhead:
    def test_hooks_disarmed_ns_per_call_bounded(self):
        """Each disarmed hook is one module-global load + is-None
        compare. Measured 77-84 ns/call on the dev container (PERF_NOTES
        §8); the bound here is deliberately loose for noisy CI — what it
        pins is the ORDER (ns, not us): an accidental dict lookup or
        allocation on the disarmed path would blow through it."""
        assert not spans.enabled()
        n = 200_000
        for fn, args in ((spans.t, ()), (spans.stamp, (spans.DISPATCH,)),
                         (spans.lap, (spans.DISPATCH, None)),
                         (spans.xfer, (spans.UPLOAD, None, 0)),
                         (spans.fetched, (None, None)),
                         (spans.ready, (None,))):
            ns = (timeit.Timer(lambda: fn(*args)).timeit(n) / n) * 1e9
            assert ns < 2_000, f"{fn.__name__}: {ns:.0f} ns/call"

    def test_disarmed_hooks_are_noops(self):
        assert spans.t() is None
        assert spans.begin_batch(spans.LANE_ENGINE, 8) is None
        spans.lap(spans.DISPATCH, None)
        spans.xfer(spans.FETCH, None, 1 << 20, calls=3)
        assert spans.ready(object()) is None
        assert spans.trace_sums()["xfer"] == spans._ZERO_SUMS["xfer"]
        spans.end_batch(None)
        spans.add(shed=5)
        assert spans.trigger("worker_death") is None
        with spans.span(spans.SLOW):
            pass  # the no-op singleton


# ---------------------------------------------------------------------------
# histograms: accuracy, merge laws, wire round-trip
# ---------------------------------------------------------------------------

class TestLatencyHist:
    def test_percentiles_track_numpy_within_bucket_error(self):
        rng = np.random.default_rng(7)
        vals = rng.lognormal(3.0, 1.5, 50_000)  # us, heavy tail
        h = LatencyHist()
        h.record_many(vals)
        for q in (50, 90, 99, 99.9):
            exact = float(np.percentile(vals, q))
            got = h.percentile(q)
            assert abs(got - exact) / exact < 0.126, (q, got, exact)

    def test_scalar_and_vector_record_agree(self):
        rng = np.random.default_rng(8)
        vals = rng.lognormal(2.0, 2.0, 2_000)
        a, b = LatencyHist(), LatencyHist()
        for v in vals:
            a.record(float(v))
        b.record_many(vals)
        assert (a.counts == b.counts).all()
        assert a.n == b.n

    def test_merge_is_associative_and_commutative(self):
        """The property that makes per-worker/per-shard histograms
        mergeable in ANY gather order: counts are plain addition."""
        rng = np.random.default_rng(9)
        parts = [rng.lognormal(3, 1, 5_000) for _ in range(3)]
        a, b, c = (LatencyHist() for _ in range(3))
        for h, p in zip((a, b, c), parts):
            h.record_many(p)
        ab_c = a.copy().merge(b.copy()).merge(c.copy())
        a_bc = a.copy().merge(b.copy().merge(c.copy()))
        cba = c.copy().merge(b.copy()).merge(a.copy())
        for m in (a_bc, cba):
            assert (ab_c.counts == m.counts).all()
            assert ab_c.n == m.n
            assert ab_c.sum_us == pytest.approx(m.sum_us)
        whole = LatencyHist()
        whole.record_many(np.concatenate(parts))
        assert (whole.counts == ab_c.counts).all()

    def test_wire_roundtrip(self):
        h = LatencyHist()
        h.record_many(np.random.default_rng(1).lognormal(4, 1, 1_000))
        rt = LatencyHist.from_dict(json.loads(json.dumps(h.to_dict())))
        assert (rt.counts == h.counts).all()
        assert rt.n == h.n and rt.max_us == h.max_us
        assert rt.percentile(99) == h.percentile(99)

    def test_empty_hist(self):
        h = LatencyHist()
        assert h.percentile(99) == 0.0
        assert h.summary()["count"] == 0


# ---------------------------------------------------------------------------
# flight recorder: wrap + anomaly triggers
# ---------------------------------------------------------------------------

def _traced_batches(tracer, n, total_sleep_us=0.0, shed=0):
    for _ in range(n):
        tok = tracer.begin(spans.LANE_ENGINE, 16)
        t0 = tracer.clock()
        tracer.lap(spans.DISPATCH, t0, tok)
        if shed:
            tracer.add(tok, shed=shed)
        tracer.end(tok)


class TestFlightRecorder:
    def test_ring_wraps_keeping_last_n(self, tmp_path):
        rec = FlightRecorder(RecorderConfig(capacity=16,
                                            out_dir=str(tmp_path)))
        tr = Tracer(recorder=rec)
        _traced_batches(tr, 50)
        meta = rec.snapshot_meta()
        assert meta["valid_records"] == 16
        records = rec.records()
        assert len(records) == 16
        # oldest-first, exactly the LAST 16 of the 50
        assert [r["seq"] for r in records] == list(range(34, 50))
        assert all(r["stages_us"].get("total", 0) >= 0 for r in records)

    def test_latency_excursion_trigger_dumps(self, tmp_path):
        rec = FlightRecorder(RecorderConfig(
            capacity=8, latency_budget_us=0.000001,
            out_dir=str(tmp_path)))
        tr = Tracer(recorder=rec)
        _traced_batches(tr, 1)
        assert rec.triggers.get("latency_excursion") == 1
        assert len(rec.dump_paths) == 1
        d = json.load(open(rec.dump_paths[0]))
        assert d["reason"] == "latency_excursion"
        assert d["meta"]["backend"] == "unknown"

    def test_shed_burst_trigger_dumps(self, tmp_path):
        rec = FlightRecorder(RecorderConfig(capacity=8, shed_burst=4,
                                            out_dir=str(tmp_path)))
        tr = Tracer(recorder=rec)
        _traced_batches(tr, 1, shed=10)
        assert rec.triggers.get("shed_burst") == 1
        # and the token-less path (fleet outside a traced batch)
        rec.note_shed(10)
        assert rec.triggers["shed_burst"] == 2

    def test_worker_death_trigger_via_module_hook(self, tmp_path):
        rec = FlightRecorder(RecorderConfig(capacity=8,
                                            out_dir=str(tmp_path)))
        with spans.armed(Tracer(recorder=rec)):
            path = spans.trigger("worker_death", "worker 2 lost a batch")
        assert path is not None
        d = json.load(open(path))
        assert d["reason"] == "worker_death"
        assert d["detail"] == "worker 2 lost a batch"

    def test_dump_rate_limit_and_cap(self, tmp_path):
        rec = FlightRecorder(RecorderConfig(
            capacity=4, min_dump_interval_s=3600.0,
            out_dir=str(tmp_path)))
        with spans.armed(Tracer(recorder=rec)):
            assert spans.trigger("worker_death") is not None
            assert spans.trigger("worker_death") is None  # rate-limited
        assert rec.triggers["worker_death"] == 2  # counted regardless

    def test_invariant_violation_triggers_dump(self, tmp_path):
        """A planted double-lease must land a flight dump the moment the
        auditor proves it (the chaos <-> telemetry wiring)."""
        clock = SimClock()
        fleet, pools, fastpath = build_fleet(2, clock)
        macs = [_mac(i) for i in range(8)]
        leased = dora_with_retries(fleet, macs, clock)
        victim_ip = next(iter(leased.values()))
        fleet._inline[0].restore_state({"session_seq": 0, "leases": [{
            "mac": _mac(999).hex(), "ip": victim_ip, "pool_id": 1,
            "expiry": 2_000_000_000, "circuit_id": "", "remote_id": "",
            "s_tag": 0, "c_tag": 0, "session_id": "forged",
            "client_class": 0, "username": "", "qos_policy": ""}]})
        rec = FlightRecorder(RecorderConfig(capacity=8,
                                            out_dir=str(tmp_path)))
        with spans.armed(Tracer(recorder=rec)):
            report = audit_invariants(pools=pools, fleet=fleet,
                                      fastpath=fastpath)
        assert not report.ok
        assert rec.triggers.get("invariant_violation") == 1
        d = json.load(open(rec.dump_paths[0]))
        assert "double-lease" in d["detail"]
        fleet.close()


# ---------------------------------------------------------------------------
# Chrome-trace export schema
# ---------------------------------------------------------------------------

class TestChromeTrace:
    def test_export_schema(self):
        tr = Tracer(keep_events=100)
        with spans.armed(tr):
            for _ in range(4):
                tok = spans.begin_batch(spans.LANE_EXPRESS_L, 8)
                t0 = spans.t()
                spans.lap(spans.DISPATCH, t0, tok)
                spans.end_batch(tok)
        trace = json.loads(json.dumps(chrome_trace(tr)))
        assert set(trace) >= {"traceEvents", "displayTimeUnit"}
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        ms = [e for e in trace["traceEvents"] if e["ph"] == "M"]
        assert xs and ms
        for e in xs:
            assert {"name", "cat", "pid", "tid", "ts", "dur"} <= set(e)
            assert e["dur"] > 0 and e["ts"] >= 0
            assert e["name"] in spans.STAGE_NAMES
        assert {"total", "dispatch"} <= {e["name"] for e in xs}
        # lane thread metadata names the express lane
        assert any(e["name"] == "thread_name"
                   and "express" in e["args"]["name"] for e in ms)

    def test_export_without_events_refuses(self):
        with pytest.raises(ValueError):
            chrome_trace(Tracer())


# ---------------------------------------------------------------------------
# DORA through tracing — host-only fleet tier (no XLA compile)
# ---------------------------------------------------------------------------

class TestFleetTracing:
    def test_dora_through_fleet_records_stages(self, tmp_path):
        """Full DORA through 2 inline workers with the tracer armed:
        admit/fleet stages populate from the parent, and the workers'
        per-frame histograms merge into the `worker` stage — the
        cross-worker histogram merge, live."""
        rec = FlightRecorder(RecorderConfig(capacity=32,
                                            out_dir=str(tmp_path)))
        with spans.armed(Tracer(recorder=rec)) as tr:
            clock = SimClock()
            fleet, pools, fastpath = build_fleet(2, clock)
            macs = [_mac(i) for i in range(16)]
            leased = dora_with_retries(fleet, macs, clock)
            assert len(leased) == len(macs)
            bd = tr.breakdown()
        assert {"admit", "fleet", "worker"} <= set(bd)
        assert bd["worker"]["count"] >= 2 * len(macs)  # DISCOVER+REQUEST
        assert bd["worker"]["p99_us"] > 0
        fleet.close()

    def test_worker_hists_merge_across_both_workers(self):
        """Both shards' workers must contribute to the merged worker
        stage — the per-worker deltas fold through _absorb."""
        with spans.armed(Tracer()) as tr:
            clock = SimClock()
            fleet, _pools, _fastpath = build_fleet(2, clock)
            macs = [_mac(i) for i in range(32)]
            dora_with_retries(fleet, macs, clock)
            from bng_tpu.control.fleet import shard_for_mac
            shards = {shard_for_mac(m, 2) for m in macs}
            assert shards == {0, 1}  # both workers saw traffic
            assert tr.stage_hist(spans.WORKER).n >= 2 * len(macs)
        fleet.close()

    def test_chaos_worker_kill_dumps_flight_record(self, tmp_path):
        """A chaos-killed worker (fleet.scatter kill) must both count a
        worker failure AND leave a flight dump."""
        rec = FlightRecorder(RecorderConfig(capacity=16,
                                            out_dir=str(tmp_path)))
        with spans.armed(Tracer(recorder=rec)):
            clock = SimClock()
            fleet, pools, fastpath = build_fleet(2, clock)
            plan = FaultPlan(1, [FaultSpec("fleet.scatter", "kill",
                                           at_hit=1)])
            with armed(plan, log=False):
                dora_with_retries(fleet, [_mac(i) for i in range(8)],
                                  clock)
        assert fleet.worker_failures >= 1
        assert rec.triggers.get("worker_death", 0) >= 1
        assert rec.dump_paths
        fleet.close()


# ---------------------------------------------------------------------------
# metrics export
# ---------------------------------------------------------------------------

class TestDispatchFailureSlotSafety:
    def test_chaos_dispatch_failure_releases_record_slot(self):
        """A chaos-injected dispatch failure (engine.dispatch `fail`,
        raised BEFORE the jit call) must cancel the open batch record —
        a leaked slot per failure would exhaust the pool exactly during
        the failure storms the flight recorder exists to capture."""
        from bng_tpu.control.nat import NATManager
        from bng_tpu.runtime.engine import Engine, FaultInjectedError
        from bng_tpu.runtime.tables import FastPathTables
        from bng_tpu.utils.net import ip_to_u32

        fp = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64,
                            cid_nbuckets=64, max_pools=4)
        fp.set_server_config(b"\x02" * 6, ip_to_u32("10.0.0.1"))
        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         sessions_nbuckets=256, sub_nat_nbuckets=64)
        engine = Engine(fp, nat, batch_size=8)
        tr = Tracer()
        with spans.armed(tr):
            n_fails = tr.OPEN_SLOTS + 4  # more failures than slots
            plan = FaultPlan(1, [FaultSpec("engine.dispatch", "fail",
                                           at_hit=1, count=n_fails)])
            with armed(plan, log=False):
                for _ in range(n_fails):
                    with pytest.raises(FaultInjectedError):
                        engine.process([b"\x00" * 64])
            assert len(tr._free) == tr.OPEN_SLOTS
            assert tr.records_dropped == 0


class TestMetricsExport:
    def test_stage_latency_family_and_counters(self, tmp_path):
        from bng_tpu.control.metrics import BNGMetrics

        rec = FlightRecorder(RecorderConfig(capacity=8,
                                            out_dir=str(tmp_path)))
        tr = Tracer(recorder=rec)
        _traced_batches(tr, 5)
        with spans.armed(tr):
            spans.trigger("worker_death", "x")
        m = BNGMetrics()
        m.attach_telemetry(tr)
        m.attach_telemetry(tr)  # idempotent
        m.collect_telemetry(tr)
        text = m.expose()
        assert 'bng_stage_latency_us_bucket{stage="total",le="+Inf"} 5' \
            in text
        assert 'bng_stage_latency_us_count{stage="dispatch"} 5' in text
        assert 'bng_flight_dumps_total{reason="worker_death"} 1' in text
        assert "bng_telemetry_batch_records_total 5" in text


# ---------------------------------------------------------------------------
# full engine + scheduler + fleet e2e (XLA compiles: slow tier)
# ---------------------------------------------------------------------------

def _build_engine_stack(workers: int = 2, scheduler: bool = True):
    from bng_tpu.control.admission import AdmissionConfig
    from bng_tpu.control.dhcp_server import DHCPServer
    from bng_tpu.control.fleet import FleetSpec, SlowPathFleet
    from bng_tpu.control.nat import NATManager
    from bng_tpu.control.pool import Pool, PoolManager
    from bng_tpu.runtime.engine import Engine
    from bng_tpu.runtime.scheduler import SchedulerConfig, TieredScheduler
    from bng_tpu.runtime.tables import FastPathTables
    from bng_tpu.utils.net import ip_to_u32, parse_mac

    smac = parse_mac("02:aa:bb:cc:dd:01")
    sip = ip_to_u32("10.0.0.1")
    fp = FastPathTables(sub_nbuckets=1 << 10, vlan_nbuckets=64,
                        cid_nbuckets=64, max_pools=4, update_slots=256)
    fp.set_server_config(smac, sip)
    pools = PoolManager(fp)
    pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.0.0.0"),
                        prefix_len=16, gateway=sip,
                        dns_primary=ip_to_u32("1.1.1.1"), lease_time=3600))
    nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                     sessions_nbuckets=256, sub_nat_nbuckets=64)
    server = DHCPServer(smac, sip, pools, fastpath_tables=fp)
    engine = Engine(fp, nat, batch_size=64, slow_path=server.handle_frame)
    fleet = SlowPathFleet(
        FleetSpec.from_pool_manager(smac, sip, pools),
        n_workers=workers, pools=pools, mode="inline",
        # compile-cold first batches must not be deadline-shed
        admission=AdmissionConfig(inbox_capacity=512, deadline_ms=60_000.0),
        table_sink=fp)
    engine.slow_path_batch = fleet.handle_batch
    target = engine
    if scheduler:
        target = TieredScheduler(engine, SchedulerConfig(
            express_batch=16, bulk_batch=64))
    return target, fleet


def _dora_frames():
    from bng_tpu.control import dhcp_codec, packets

    def discover(mac, xid):
        p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER, xid=xid)
        return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                  p.encode().ljust(320, b"\x00"))

    def request(mac, offer_frame, xid):
        od = packets.decode(offer_frame)
        off = dhcp_codec.decode(od.payload)
        p = dhcp_codec.build_request(mac, dhcp_codec.REQUEST, xid=xid,
                                     requested_ip=off.yiaddr,
                                     server_id=od.src_ip)
        return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                  p.encode().ljust(320, b"\x00"))

    return discover, request


class TestDoraTracingE2E:
    def test_dora_through_scheduler_and_fleet(self, tmp_path):
        """The tentpole e2e: DORA for 32 subscribers through the tiered
        scheduler (express lane), the slow-path fleet (2 inline workers)
        and back — with the tracer armed the whole way. Every lifecycle
        stage the scheduler path exercises must land samples, the flight
        recorder must hold per-batch records, and the span log must
        export a valid Chrome trace."""
        rec = FlightRecorder(RecorderConfig(capacity=64,
                                            out_dir=str(tmp_path)))
        tr = Tracer(recorder=rec, keep_events=1 << 12)
        sched, fleet = _build_engine_stack(workers=2, scheduler=True)
        discover, request = _dora_frames()
        macs = [(0x02D0 << 32 | i).to_bytes(6, "big") for i in range(32)]
        with spans.armed(tr):
            res = sched.process([discover(m, 0x100 + i)
                                 for i, m in enumerate(macs)])
            offers = {i: f for i, f in res["slow"] if f is not None}
            assert len(offers) == len(macs)
            res2 = sched.process([request(m, offers[i], 0x200 + i)
                                  for i, m in enumerate(macs)])
            assert sum(1 for _i, f in res2["slow"] if f is not None) \
                == len(macs)
            # renewal DISCOVERs answered on device (express lane TX)
            res3 = sched.process([discover(m, 0x300 + i)
                                  for i, m in enumerate(macs)])
            assert len(res3["tx"]) == len(macs)
            bd = tr.breakdown()
        for stage in ("lane_wait", "dispatch", "device_wait", "fleet",
                      "worker", "slow_path", "reply", "total"):
            assert stage in bd, (stage, sorted(bd))
            assert bd[stage]["count"] > 0
        assert tr.seq >= 3  # at least one record per exchange batch
        assert rec.snapshot_meta()["valid_records"] == min(tr.seq, 64)
        trace = chrome_trace(tr)
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert len(xs) >= tr.seq  # every batch contributes spans
        assert {"dispatch", "device_wait", "total"} <= {e["name"]
                                                        for e in xs}
        fleet.close()

    def test_engine_pipelined_ring_tracing(self):
        """The ring stage: the pipelined engine loop over a PyRing must
        attribute ring assemble time and keep records balanced (every
        begun batch ends — the open-slot pool never leaks)."""
        from bng_tpu.runtime.ring import PyRing

        engine, fleet = _build_engine_stack(workers=1, scheduler=False)
        discover, _request = _dora_frames()
        ring = PyRing(nframes=256, frame_size=2048)
        with spans.armed(Tracer()) as tr:
            for i in range(32):
                ring.rx_push(discover(
                    (0x02D1 << 32 | i).to_bytes(6, "big"), 0x400 + i),
                    from_access=True)
            engine.process_ring_pipelined(ring)
            engine.process_ring_pipelined(ring)
            engine.flush_pipeline()
            bd = tr.breakdown()
            assert "ring" in bd and bd["ring"]["count"] >= 1
            assert "reply" in bd
            # the open-slot pool drained back: all begun records ended
            assert len(tr._free) == tr.OPEN_SLOTS
        fleet.close()

    def test_loadtest_harness_reports_histogram_percentiles(self):
        from bng_tpu.loadtest import BenchmarkConfig, DHCPBenchmark

        engine, fleet = _build_engine_stack(workers=1, scheduler=False)
        cfg = BenchmarkConfig(batch_size=32, duration_s=0.5, warmup_s=0.5,
                              unique_macs=64)
        res = DHCPBenchmark(engine, cfg).run()
        assert res.requests > 0
        assert res.request_p50_us > 0
        assert res.request_p999_us >= res.request_p99_us \
            >= res.request_p50_us
        assert res.latency_p999_us >= res.latency_p99_us
        d = res.to_dict()
        assert "request_p999_us" in d and "latency_p999_us" in d
        fleet.close()

    def test_process_fleet_restores_telemetry_env(self):
        """Spawning a process fleet under an armed tracer must not leak
        BNG_TELEMETRY=1 into the parent environment — a leaked flag
        would force-arm every later BNGApp in this process and make
        every later fleet's workers pay armed per-frame costs."""
        from bng_tpu.control.fleet import FleetSpec, SlowPathFleet
        from bng_tpu.control.pool import Pool, PoolManager
        from bng_tpu.utils.net import ip_to_u32

        before = os.environ.get("BNG_TELEMETRY")
        sip = ip_to_u32("10.9.0.1")
        pools = PoolManager(None)
        pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.9.0.0"),
                            prefix_len=24, gateway=sip,
                            dns_primary=ip_to_u32("1.1.1.1"),
                            lease_time=3600))
        with spans.armed(Tracer()) as tr:
            fleet = SlowPathFleet(
                FleetSpec.from_pool_manager(b"\x02" * 6, sip, pools),
                n_workers=1, pools=pools, mode="process")
            try:
                assert os.environ.get("BNG_TELEMETRY") == before
                # and the child DID inherit it: its per-frame histogram
                # arrives in the stats payload and merges
                from bng_tpu.control import dhcp_codec, packets

                mac = (0x02E0 << 32).to_bytes(6, "big")
                p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER,
                                             xid=1)
                frame = packets.udp_packet(
                    mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                    p.encode().ljust(320, b"\x00"))
                out = fleet.handle_batch([(0, frame)])
                assert out[0][1] is not None
                assert tr.stage_hist(spans.WORKER).n >= 1
            finally:
                fleet.close()

    def test_trace_cli_export_chrome(self, tmp_path):
        from bng_tpu import cli

        out = tmp_path / "dora.json"
        rc = cli.main(["trace", "export", "--format", "chrome",
                       "--out", str(out), "--macs", "16",
                       "--trace-dir", str(tmp_path)])
        assert rc == 0
        d = json.load(open(out))
        xs = [e for e in d["traceEvents"] if e["ph"] == "X"]
        assert xs and all(e["dur"] > 0 for e in xs)
        # and `trace status` sees the dir
        rc = cli.main(["trace", "status", "--trace-dir", str(tmp_path)])
        assert rc == 0


# ---------------------------------------------------------------------------
# one clock inside the loop (PR 25): beats tile, events carry ids beside
# them, device occupancy and starvation by readiness, per-lane histograms
# ---------------------------------------------------------------------------

class _Clock:
    """A scripted ns clock: the test sets `.now`, the Tracer reads it."""

    def __init__(self):
        self.now = 0

    def __call__(self):
        return self.now


def _lap(tr, clk, stage, a, b, tok=None):
    clk.now = b
    tr.lap(stage, a, tok)


class TestBeatTiling:
    def test_children_plus_self_is_the_beat_and_nested_laps_count_once(self):
        clk = _Clock()
        tr = Tracer(clock=clk, keep_events=64)
        clk.now = 1_000
        tr.beat_begin()
        _lap(tr, clk, spans.RING, 1_100, 1_300)         # 200
        # drain [1500,1600] nested inside dispatch [1400,1900]: union 500
        _lap(tr, clk, spans.DRAIN, 1_500, 1_600)
        _lap(tr, clk, spans.DISPATCH, 1_400, 1_900)
        # two laps that overlap by 50: union 250
        _lap(tr, clk, spans.REPLY, 2_000, 2_150)
        _lap(tr, clk, spans.TX, 2_100, 2_250)
        clk.now = 3_000
        tr.beat_end()
        # a lap outside any beat claims nothing of a beat
        _lap(tr, clk, spans.OPS, 3_100, 3_500)
        clk.now = 4_000
        tr.beat_begin()
        _lap(tr, clk, spans.RING, 3_900, 4_400)         # clipped to 400
        clk.now = 4_500
        tr.beat_end()
        s = tr.sums()
        assert s["beats"] == 2
        assert s["stage_ns"]["beat"] == 2_000 + 500
        # what no lap claims: the beats less the union of their children
        assert s["beat_self_ns"] == 2_500 - (200 + 500 + 250 + 400)
        # the per-stage sums are plain sums of samples (the nested lap's
        # 100 ns is in `drain` AND inside `dispatch`'s 500)
        assert s["stage_ns"]["drain"] == 100
        assert s["stage_ns"]["dispatch"] == 500
        assert s["stage_ns"]["ring"] == 200 + 500
        assert tr.stage_hist(spans.BEAT).n == 2

    def test_a_child_xfer_lap_is_in_the_union_once_and_self_time_stands(self):
        """`upload` / `fetch` are children of the laps that enclose them:
        the beat's union counts the child once, so `beat_self_ns` is what it
        was without the child; a crossing under no parent (`_fold_stats`)
        takes its time out of `beat_self_ns`."""
        def beat(children: bool, orphan: bool) -> dict:
            clk = _Clock()
            tr = Tracer(clock=clk, keep_events=64)
            clk.now = 1_000
            tr.beat_begin()
            if children:
                clk.now = 1_300                       # drain [1200,1300]
                tr.lap(spans.DRAIN, 1_200)
                clk.now = 1_450                       # upload [1300,1450]
                tr.xfer(spans.UPLOAD, 1_300, 4_096, calls=3)
            _lap(tr, clk, spans.DISPATCH, 1_100, 1_600)
            if children:
                clk.now = 1_900                       # fetch [1800,1900]
                tr.xfer(spans.FETCH, 1_800, 512, calls=2)
            _lap(tr, clk, spans.DEVICE_WAIT, 1_700, 1_900)
            if orphan:
                clk.now = 2_100                       # under no parent
                tr.xfer(spans.FETCH, 2_000, 64, calls=4)
            clk.now = 3_000
            tr.beat_end()
            return tr.sums()

        bare, nested, both = beat(False, False), beat(True, False), \
            beat(True, True)
        assert bare["beat_self_ns"] == 2_000 - (500 + 200)
        assert nested["beat_self_ns"] == bare["beat_self_ns"]
        assert both["beat_self_ns"] == bare["beat_self_ns"] - 100
        # the parents' own sums do not move; the children's are beside them
        for s in (nested, both):
            assert (s["stage_ns"]["dispatch"], s["stage_ns"]["device_wait"]) \
                == (500, 200) == (bare["stage_ns"]["dispatch"],
                                  bare["stage_ns"]["device_wait"])
            assert s["stage_ns"]["upload"] == 150
            assert s["stage_ns"]["drain"] == 100
        assert (nested["stage_ns"]["fetch"], both["stage_ns"]["fetch"]) == \
            (100, 200)
        assert nested["xfer"] == {"upload_calls": 3, "upload_bytes": 4_096,
                                  "fetch_calls": 2, "fetch_bytes": 512,
                                  "prefetch_calls": 0}
        assert both["xfer"]["fetch_calls"] == 6
        assert both["xfer"]["fetch_bytes"] == 512 + 64

    def test_xfer_is_a_lap_with_an_event_a_lane_and_a_flight_record(self):
        clk = _Clock()
        rec = FlightRecorder(RecorderConfig(capacity=4))
        tr = Tracer(recorder=rec, clock=clk, keep_events=16)
        clk.now = 100
        tr.beat_begin()
        tok = tr.begin(spans.LANE_RING_L, 8)
        clk.now = 400
        tr.xfer(spans.UPLOAD, 150, 1 << 20, calls=3, tok=tok)
        clk.now = 900
        tr.xfer(spans.FETCH, 600, 1 << 10, tok=tok)
        tr.end(tok)
        tr.beat_end()
        rows = list(zip(tr.events, tr.event_beats))
        assert ((spans.UPLOAD, spans.LANE_RING_L, 150, 250), 0) in rows
        assert ((spans.FETCH, spans.LANE_RING_L, 600, 300), 0) in rows
        assert tr.lane_hist(spans.LANE_RING_L, spans.UPLOAD).n == 1
        assert tr.lane_hist(spans.LANE_RING_L, spans.FETCH).n == 1
        assert not tr.lane_hist(spans.LANE_ENGINE, spans.FETCH).n
        assert rec._dur[0, spans.UPLOAD] == pytest.approx(0.25)
        assert rec._dur[0, spans.FETCH] == pytest.approx(0.3)
        # TOTAL stays the last stage: the recorder indexes it so
        assert spans.STAGE_NAMES[-1] == "total" == \
            spans.STAGE_NAMES[spans.TOTAL]
        assert spans.STAGE_NAMES[spans.UPLOAD] == "upload"
        assert spans.STAGE_NAMES[spans.FETCH] == "fetch"

    def test_events_stay_4_tuples_with_beat_ids_beside_them(self):
        clk = _Clock()
        tr = Tracer(clock=clk, keep_events=64)
        _lap(tr, clk, spans.OPS, 10, 20)                # no batch, no beat
        clk.now = 100
        tr.beat_begin()
        tok = tr.begin(spans.LANE_BULK_L, 3)
        _lap(tr, clk, spans.DISPATCH, 110, 150, tok)
        tok2 = tr.begin(spans.LANE_EXPRESS_L, 1)
        _lap(tr, clk, spans.DISPATCH, 160, 170, tok2)
        tr.observe_many(spans.SOJOURN, [5.0, 7.0], tok)
        clk.now = 200
        tr.end(tok)
        tr.beat_end()
        assert all(len(e) == 4 for e in tr.events)
        assert len(tr.events) == len(tr.event_beats)
        rows = list(zip(tr.events, tr.event_beats))
        by = lambda stage: [(e, i) for e, i in rows if e[0] == stage]  # noqa: E731
        assert by(spans.OPS)[0][1] == -1
        (e_b, beat_b), (e_x, beat_x) = by(spans.DISPATCH)
        assert (e_b[1], beat_b) == (spans.LANE_BULK_L, 0)
        assert (e_x[1], beat_x) == (spans.LANE_EXPRESS_L, 0)
        soj = by(spans.SOJOURN)
        assert [e[3] for e, _ in soj] == [5_000, 7_000]
        assert all(i == 0 and e[1] == spans.LANE_BULK_L for e, i in soj)
        assert by(spans.TOTAL)[0][1] == 0
        assert by(spans.BEAT)[0] == ((spans.BEAT, 0, 100, 100), 0)
        # a lap after the beat's end, and the next beat's
        _lap(tr, clk, spans.OPS, 210, 220)
        clk.now = 300
        tr.beat_begin()
        _lap(tr, clk, spans.RING, 310, 320)
        assert list(tr.event_beats)[-2:] == [-1, 1]
        # the exporters unpack 4-tuples
        assert len(chrome_trace(tr)["traceEvents"]) >= len(tr.events)


class TestDeviceOccupancy:
    def test_depth_two_samples_run_from_the_previous_ready(self):
        clk = _Clock()
        tr = Tracer(clock=clk, keep_events=64)
        a = tr.begin(spans.LANE_BULK_L, 8)
        clk.now = 1_000
        tr.device_up(a)
        b = tr.begin(spans.LANE_BULK_L, 8)
        clk.now = 1_500
        tr.device_up(b)                      # queued behind a
        clk.now = 237_000
        tr.device_down(a)                    # first seen ready
        tr.device_down(a)                    # the retire says it again
        clk.now = 473_000
        tr.device_down(b)
        dev = [e for e in tr.events if e[0] == spans.DEVICE]
        # a: from its own dispatch end; b: from when a was seen ready
        assert [e[3] for e in dev] == [236_000, 236_000]
        assert [e[2] for e in dev] == [1_000, 237_000]
        h = tr.lane_hist(spans.LANE_BULK_L, spans.DEVICE)
        assert h.n == 2 and not tr.lane_hist(spans.LANE_EXPRESS_L,
                                             spans.DEVICE).n

    def test_express_behind_bulk_gives_no_sample_and_keeps_the_bulk_one(self):
        clk = _Clock()
        tr = Tracer(clock=clk)
        bulk = tr.begin(spans.LANE_BULK_L, 8)
        clk.now = 1_000
        tr.device_up(bulk)
        ex = tr.begin(spans.LANE_EXPRESS_L, 2)
        clk.now = 100_000
        tr.device_up(ex, sample=False)       # queued behind the bulk step
        clk.now = 238_000
        tr.device_down(ex)                   # forced: the bulk step is done
        clk.now = 238_100
        tr.device_down(bulk)                 # seen at the next pop_ready
        assert tr.lane_hist(spans.LANE_EXPRESS_L, spans.DEVICE).n == 0
        assert tr.lane_hist(spans.LANE_BULK_L, spans.DEVICE).max_us == \
            pytest.approx(237.1)
        # two seen ready at one look: the second finished nobody knows when
        c = tr.begin(spans.LANE_BULK_L, 8)
        d = tr.begin(spans.LANE_BULK_L, 8)
        clk.now = 300_000
        tr.device_up(c)
        tr.device_up(d)
        clk.now = 900_000
        tr.device_down(c)
        tr.device_down(d, clean=False)
        assert tr.lane_hist(spans.LANE_BULK_L, spans.DEVICE).n == 2

    def test_starvation_is_charged_to_the_stage_whose_lap_overlaps_it(self):
        clk = _Clock()
        tr = Tracer(clock=clk)
        a = tr.begin(spans.LANE_BULK_L, 8)
        clk.now = 1_000
        tr.device_up(a)
        clk.now = 5_000
        tr.device_down(a)                    # idle from 5,000
        # between beats, under no lap: the caller's
        clk.now = 6_000
        tr.beat_begin()                      # outside: 1,000
        _lap(tr, clk, spans.RING, 6_100, 6_400)          # ring 300
        # pack nested in dispatch; the device goes up at dispatch's end
        b = tr.begin(spans.LANE_BULK_L, 8)
        _lap(tr, clk, spans.PACK, 6_600, 6_700, b)       # pack 100
        _lap(tr, clk, spans.DISPATCH, 6_500, 7_000, b)   # dispatch 400
        tr.device_up(b)                      # window [5,000, 7,000] closes
        _lap(tr, clk, spans.DRAIN, 7_000, 7_500, b)      # busy: no charge
        clk.now = 8_000
        tr.device_down(b)                    # idle again from 8,000
        _lap(tr, clk, spans.REPLY, 7_900, 8_600, b)      # reply 600
        clk.now = 9_000
        tr.beat_end()                        # in the beat, under no lap
        clk.now = 9_500
        tr.finish()                          # outside: 500 more
        s = tr.sums()
        st = s["starved_ns"]
        assert (st["ring"], st["pack"], st["dispatch"], st["reply"]) == \
            (300, 100, 400, 600)
        assert st["drain"] == 0
        # the beat's own: [6000,7000] less 800 claimed, [8000,9000] less 600
        assert st["beat"] == 200 + 400
        assert st["outside"] == 1_000 + 500
        # the invariant: by stage + `outside` = all the time nothing was in
        # flight, [5000,7000] + [8000,9500]. What a layer metric reads is
        # all but `outside` (the caller's time between beats; a profiler's
        # stop alone can take seconds there)
        assert sum(st.values()) == 2_000 + 1_500
        assert s["beat_starved_ns"] == 2_000 + 1_500 - st["outside"]

    def test_starvation_under_a_child_is_the_childs_the_parent_keeps_the_rest(
            self):
        """A child closes before its parent, so it takes the starvation
        under it and the parent keeps what is left: a parent's `starved_ns`
        is its self share, and parent + children is what the parent was
        charged without them. `beat_starved_ns` does not move."""
        def run(children: bool) -> dict:
            clk = _Clock()
            tr = Tracer(clock=clk)
            a = tr.begin(spans.LANE_RING_L, 8)
            clk.now = 1_000
            tr.device_up(a)
            clk.now = 5_000
            tr.beat_begin()
            clk.now = 6_000
            tr.device_down(a)                   # idle from 6,000
            b = tr.begin(spans.LANE_RING_L, 8)
            if children:
                clk.now = 6_300                 # drain [6200,6300]
                tr.lap(spans.DRAIN, 6_200, b)
                clk.now = 6_700                 # upload [6300,6700]
                tr.xfer(spans.UPLOAD, 6_300, 1 << 20, calls=3, tok=b)
            _lap(tr, clk, spans.DISPATCH, 6_100, 7_000, b)
            tr.device_up(b)                     # window [6000,7000] closes
            clk.now = 8_000
            tr.device_down(b)                   # idle again from 8,000
            if children:
                clk.now = 8_400                 # fetch [8100,8400] in reply
                tr.xfer(spans.FETCH, 8_100, 1 << 10, tok=b)
            _lap(tr, clk, spans.REPLY, 8_000, 8_500, b)
            clk.now = 9_000
            tr.beat_end()
            tr.finish()
            return tr.sums()

        bare, split = run(False), run(True)
        st, ch = bare["starved_ns"], split["starved_ns"]
        assert (st["dispatch"], st["reply"]) == (900, 500)
        assert (st["upload"], st["fetch"], st["drain"]) == (0, 0, 0)
        assert (ch["drain"], ch["upload"], ch["fetch"]) == (100, 400, 300)
        assert ch["dispatch"] == 900 - 100 - 400     # its self share
        assert ch["reply"] == 500 - 300
        assert ch["dispatch"] + ch["drain"] + ch["upload"] == st["dispatch"]
        assert ch["reply"] + ch["fetch"] == st["reply"]
        assert ch["beat"] == st["beat"] and ch["outside"] == st["outside"]
        assert split["beat_starved_ns"] == bare["beat_starved_ns"]
        assert sum(ch.values()) == split["beat_starved_ns"] + ch["outside"]

    def test_xfer_counts_are_always_served_armed_and_frozen_at_disarm(self):
        keys = {"upload_calls", "upload_bytes", "fetch_calls", "fetch_bytes",
                "prefetch_calls"}
        assert set(spans._ZERO_SUMS["xfer"]) == keys
        assert not any(spans._ZERO_SUMS["xfer"].values())
        clk = _Clock()
        tr = spans.arm(Tracer(clock=clk))
        assert set(spans.trace_sums()["xfer"]) == keys
        assert not any(spans.trace_sums()["xfer"].values())
        t0 = spans.t()
        clk.now = 50
        spans.xfer(spans.UPLOAD, t0, 12_623_872, calls=3)
        t0 = spans.t()
        clk.now = 80
        spans.xfer(spans.FETCH, t0, 8_192)
        armed = spans.trace_sums()
        assert armed["xfer"] == {"upload_calls": 3,
                                 "upload_bytes": 12_623_872,
                                 "fetch_calls": 1, "fetch_bytes": 8_192,
                                 "prefetch_calls": 0}
        assert armed["stage_ns"]["upload"] == 50
        assert armed["stage_ns"]["fetch"] == 30
        spans.disarm()
        frozen = spans.trace_sums()["xfer"]
        spans.xfer(spans.FETCH, spans.t(), 1 << 20, calls=9)  # disarmed
        tr.xfer(spans.FETCH, 0, 1 << 20)  # even on the tracer itself
        assert spans.trace_sums()["xfer"] == frozen == armed["xfer"]

    def test_ready_blocks_armed_only_and_fetched_counts_device_arrays(self):
        """`ready`: armed it waits on the output, says the dispatch was
        seen ready and opens the `fetch` lap; disarmed nothing is forced.
        `fetched` closes it: host arrays among a step's outputs cross
        nothing."""
        class Out:
            nbytes = 96

            def __init__(self):
                self.waits = 0

            def block_until_ready(self):
                self.waits += 1

        out = Out()
        assert spans.ready(out, 3) is None and out.waits == 0
        clk = _Clock()
        tr = spans.arm(Tracer(clock=clk))
        tok = tr.begin(spans.LANE_BULK_L, 8)
        clk.now = 1_000
        spans.device_up(tok)
        clk.now = 4_000
        assert spans.ready(out, tok) == 4_000 and out.waits == 1
        assert not tr.device_pending(tok)
        assert tr.lane_hist(spans.LANE_BULK_L, spans.DEVICE).n == 1
        assert spans.ready(np.zeros(4), tok) == 4_000  # a host array: no wait
        clk.now = 4_500
        spans.fetched(4_000, out, np.zeros(8, np.uint32), None, out, tok=tok)
        assert tr.sums()["xfer"] == {"upload_calls": 0, "upload_bytes": 0,
                                     "fetch_calls": 2, "fetch_bytes": 192,
                                     "prefetch_calls": 0}
        assert tr.sums()["stage_ns"]["fetch"] == 500
        spans.disarm()
        spans.fetched(4_000, out, out)  # disarmed: a no-op
        spans.fetched(None, out)
        assert spans.trace_sums()["xfer"]["fetch_calls"] == 2

    def test_sums_freeze_at_disarm_and_stay_readable(self):
        clk = _Clock()
        zero = spans.trace_sums()
        assert zero["beats"] == 0 and set(zero["stage_ns"]) == \
            set(spans.STAGE_NAMES)
        tr = spans.arm(Tracer(clock=clk))
        assert spans.trace_sums()["beats"] == 0      # c0: right after arm
        clk.now = 100
        spans.beat_begin()
        spans.lap(spans.RING, 120)
        clk.now = 300
        spans.beat_end()
        spans.disarm()
        frozen = spans.trace_sums()
        assert frozen["beats"] == 1 and frozen["stage_ns"]["beat"] == 200
        # the drain after the window: disarmed hooks add nothing
        spans.beat_begin()
        spans.lap(spans.RING, spans.t())
        spans.beat_end()
        spans.device_up(None)
        assert spans.trace_sums() == frozen == tr.sums()

    def test_per_lane_histograms_keep_lanes_apart(self):
        tr = Tracer()
        bulk = tr.begin(spans.LANE_BULK_L, 8)
        ex = tr.begin(spans.LANE_EXPRESS_L, 2)
        for _ in range(40):
            tr.observe(spans.DEVICE, 236_000.0, bulk)
            tr.observe(spans.DEVICE, 30.0, ex)
        assert tr.stage_hist(spans.DEVICE).n == 80        # merged view
        bd = tr.breakdown(lanes=True)
        assert bd["device"]["p99_us"] > 200_000
        assert bd["device@express"]["p99_us"] < 40
        assert bd["device@bulk"]["count"] == 40
        assert "device@express" not in tr.breakdown()


class TestOneClockWithTheDeviceTrace:
    def test_beats_anchor_the_tracer_clock_in_a_profiler_session(
            self, tmp_path, monkeypatch):
        """While a `jax.profiler` session runs, every beat leaves a
        `bng.beat` annotation that carries the Tracer clock's reading at
        its entry; the reducer over the recorded directory finds them
        (no device plane on the CPU: the device parts stay empty)."""
        import jax
        import jax.numpy as jnp

        from bng_tpu.utils import profiling

        f = jax.jit(lambda a: (a @ a).sum())
        x = jnp.ones((64, 64))
        f(x).block_until_ready()
        events = tmp_path / "events.json"
        monkeypatch.setenv("BNG_TRACE_EVENTS", str(events))
        tr = spans.arm(Tracer(keep_events=256))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for _ in range(3):
                spans.beat_begin()
                t0 = spans.t()
                f(x).block_until_ready()
                spans.lap(spans.DISPATCH, t0)
                spans.beat_end()
        finally:
            jax.profiler.stop_trace()
            spans.disarm()
        out = profiling.reduce_trace(str(tmp_path), str(events))
        assert out["anchors"] == 3 and out["devices"] == 0
        log = json.loads(events.read_text())
        assert len(log["events"]) == len(log["beats"]) == len(tr.events)
        assert log["sums"]["beats"] == 3
        beats = [e for e in log["events"] if log["stages"][e[0]] == "beat"]
        assert len(beats) == 3

    def test_a_gap_under_a_child_lap_is_named_by_the_child(self):
        """The program's reducer names an idle gap by the shortest lap
        over its midpoint: `fetch` inside `device_wait` or under no parent,
        `upload` inside `dispatch`; and both are host laps it keeps."""
        from bng_tpu.utils.profiling import HOST_LAPS, _lap_over

        assert {"upload", "fetch"} <= set(HOST_LAPS)
        assert set(HOST_LAPS) <= set(spans.STAGE_NAMES)
        laps = [(100.0, 900.0, "dispatch"), (150.0, 50.0, "drain"),
                (200.0, 300.0, "upload"), (2_000.0, 1_000.0, "device_wait"),
                (2_600.0, 400.0, "fetch"), (3_100.0, 200.0, "fetch")]
        assert _lap_over(laps, 160.0)[2] == "drain"
        assert _lap_over(laps, 350.0)[2] == "upload"
        assert _lap_over(laps, 800.0)[2] == "dispatch"
        assert _lap_over(laps, 2_300.0)[2] == "device_wait"  # the wait alone
        assert _lap_over(laps, 2_700.0)[2] == "fetch"
        assert _lap_over(laps, 3_200.0)[2] == "fetch"        # _fold_stats
        assert _lap_over(laps, 1_500.0) is None

    def test_scope_is_the_first_named_scope_on_the_op_path(self):
        from bng_tpu.utils.profiling import _scope_of

        assert _scope_of("jit(step)/dhcp/jit(take_along_axis)/gather:") == \
            "dhcp"
        assert _scope_of("jit(step)/jit(main)/nat44/while/body/x") == "nat44"
        assert _scope_of("jit(step)/updates/scatter") == "updates"
        assert _scope_of("jit(copy)/copy") == "(no scope)"
        assert _scope_of("") == "(no scope)"
