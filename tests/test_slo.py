"""SLO engine tests (telemetry/slo.py): spec validation, one-shot
evaluation, the storm-budget re-home (verdicts byte-identical to the
PR-8 originals), the live burn-rate monitor firing the slo_breach
flight dump, and the sharded-path ShardTelemetry counters + histogram
merge laws. `make verify-perf` runs the `perf` marker."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bng_tpu.telemetry import FlightRecorder, RecorderConfig
from bng_tpu.telemetry import spans as tele
from bng_tpu.telemetry import slo

pytestmark = pytest.mark.perf


# ---------------------------------------------------------------------------
# spec + registry
# ---------------------------------------------------------------------------

class TestSpec:
    def test_unknown_stage_rejected(self):
        with pytest.raises(ValueError, match="unknown stage"):
            slo.SLOSpec("warp_drive", 100.0)
        with pytest.raises(ValueError, match="unknown stage"):
            slo.BudgetLine("warp_drive", 100.0)

    def test_nonpositive_limits_rejected(self):
        with pytest.raises(ValueError):
            slo.SLOSpec("dispatch", 0.0)
        with pytest.raises(ValueError):
            slo.SLOSpec("dispatch", 10.0, per=0.0)

    def test_default_registry_covers_every_stage(self):
        # Dapper's lesson machine-checked: the shipped registry budgets
        # EVERY stage of the fixed vocabulary, not just the headline
        budgeted = {s.stage for s in slo.DEFAULT_SLOS}
        assert budgeted == set(tele.STAGE_NAMES)

    def test_device_budget_is_the_paper_target(self):
        dev = [s for s in slo.DEFAULT_SLOS if s.stage == "device"]
        assert dev[0].p99_limit_us == \
            slo.HEADLINE_TARGETS["offer_device_only_p99_us"] == 50.0

    def test_device_spec_reads_the_express_lane_only(self, tmp_path):
        """A 236 ms bulk step's `device` sample must neither breach nor
        dilute the 50 us OFFER budget: the spec is bound to the express
        lane, in the one-shot verdict and in the live monitor."""
        dev = [s for s in slo.DEFAULT_SLOS if s.stage == "device"][0]
        assert dev.lane == "express"
        with pytest.raises(ValueError, match="unknown lane"):
            slo.SLOSpec("device", 50.0, lane="warp")
        tr = tele.Tracer()
        bulk = tr.begin(tele.LANE_BULK_L, 8)
        ex = tr.begin(tele.LANE_EXPRESS_L, 2)
        mon = slo.SLOMonitor(tr, slos=(dev,), window_s=10.0, burn_windows=1)
        mon.tick(0.0)
        for _ in range(64):
            tr.observe(tele.DEVICE, 236_000.0, bulk)
            tr.observe(tele.DEVICE, 30.0, ex)
        # samples fed to another lane neither dilute nor breach the
        # served path's
        tr.observe_many(tele.DEVICE, [829.0] * 64, lane=tele.LANE_BENCH)
        assert tr.lane_hist(tele.LANE_EXPRESS_L, tele.DEVICE).n == 64
        assert slo.evaluate(tr.breakdown(lanes=True), (dev,))["ok"]
        # a breakdown without lane entries is read merged, as before
        assert not slo.evaluate(tr.breakdown(), (dev,))["ok"]
        assert mon.tick(11.0) == []
        assert mon.snapshot()["window_p99_us"]["device"] < 50.0
        for _ in range(64):
            tr.observe(tele.DEVICE, 900.0, ex)       # the express lane's own
        assert mon.tick(22.0) == ["device"]
        assert not slo.evaluate(tr.breakdown(lanes=True), (dev,))["ok"]
        # no express sample at all: nothing to judge, not a breach
        quiet = tele.Tracer()
        quiet.observe(tele.DEVICE, 236_000.0, quiet.begin(tele.LANE_BULK_L, 1))
        assert slo.evaluate(quiet.breakdown(lanes=True), (dev,))["ok"]

    def test_parse_budgets(self):
        specs = slo.parse_budgets(["dispatch:1000", "fleet:2000:64"])
        assert specs[0].stage == "dispatch"
        assert specs[0].p99_limit_us == 1000.0 and specs[0].per == 1.0
        assert specs[1].per == 64.0
        # an override moves the limit and keeps the lane the spec judges
        dev = slo.parse_budgets(["device:5000"])[0]
        assert (dev.p99_limit_us, dev.lane) == (5000.0, "express")
        assert specs[0].lane == ""
        with pytest.raises(ValueError, match="bad SLO budget"):
            slo.parse_budgets(["dispatch"])
        with pytest.raises(ValueError, match="unknown stage"):
            slo.parse_budgets(["nope:10"])


class TestEvaluate:
    def test_ok_and_breach(self):
        bd = {"dispatch": {"p99_us": 10.0}, "reply": {"p99_us": 999.0}}
        specs = (slo.SLOSpec("dispatch", 100.0), slo.SLOSpec("reply", 100.0))
        v = slo.evaluate(bd, specs)
        assert v == {"ok": False, "breaches": ["reply"]}
        v = slo.evaluate({"dispatch": {"p99_us": 10.0}},
                         (slo.SLOSpec("dispatch", 100.0),))
        assert v == {"ok": True, "breaches": []}

    def test_required_missing_is_a_coverage_hole(self):
        v = slo.evaluate({}, (slo.SLOSpec("fleet", 100.0, required=True),))
        assert v == {"ok": False, "breaches": ["fleet:missing"]}

    def test_optional_missing_skipped(self):
        v = slo.evaluate({}, (slo.SLOSpec("fleet", 100.0),))
        assert v["ok"]

    def test_per_amortization(self):
        bd = {"fleet": {"p99_us": 6400.0}}
        assert slo.evaluate(bd, (slo.SLOSpec("fleet", 200.0, per=64),))["ok"]
        assert not slo.evaluate(
            bd, (slo.SLOSpec("fleet", 50.0, per=64),))["ok"]


# ---------------------------------------------------------------------------
# the storm-budget re-home: byte-identical verdicts
# ---------------------------------------------------------------------------

class TestBudgetRehome:
    def test_storms_import_is_the_slo_objects(self):
        import bng_tpu.chaos.storms as storms

        assert storms.BudgetLine is slo.BudgetLine
        assert storms.check_budget is slo.check_budget

    def test_verdict_bytes_identical_to_pr8_semantics(self):
        """The PR-8 check_budget contract, replayed against the re-homed
        evaluator: mean-based, `per` amortization, required-missing as
        `stage:missing`, breaches sorted — and the serialized verdict
        (what lands in the bit-compared storm reports) byte-equal to the
        hand-built expectation."""
        tr = tele.Tracer()
        for _ in range(4):
            tr.observe(tele.FLEET, 1000.0)   # mean 1000
            tr.observe(tele.ADMIT, 10.0)     # mean 10
        lines = (
            slo.BudgetLine("admit", limit_us=50.0),            # ok
            slo.BudgetLine("fleet", limit_us=100.0, per=5.0),  # 200 > 100
            slo.BudgetLine("worker", limit_us=1.0),            # missing
            slo.BudgetLine("device", limit_us=1.0, required=False),
        )
        v = slo.check_budget(tr, lines)
        expected = {"ok": False, "breaches": ["fleet", "worker:missing"]}
        assert v == expected
        assert json.dumps(v, sort_keys=True) == \
            json.dumps(expected, sort_keys=True)

    def test_clean_budget_verdict(self):
        tr = tele.Tracer()
        tr.observe(tele.ADMIT, 1.0)
        v = slo.check_budget(tr, (slo.BudgetLine("admit", 100.0),))
        assert v == {"ok": True, "breaches": []}

    def test_breach_fires_slo_breach_trigger(self, tmp_path):
        rec = FlightRecorder(RecorderConfig(out_dir=str(tmp_path)))
        with tele.armed(recorder=rec) as tr:
            tr.observe(tele.FLEET, 1000.0)
            slo.check_budget(tr, (slo.BudgetLine("fleet", 1.0),))
        assert rec.triggers.get("slo_breach") == 1


# ---------------------------------------------------------------------------
# live burn-rate monitor
# ---------------------------------------------------------------------------

def _feed(tr, stage, us, n=64):
    for _ in range(n):
        tr.observe(stage, us)


class TestMonitor:
    def _mon(self, tmp_path, **kw):
        rec = FlightRecorder(RecorderConfig(out_dir=str(tmp_path)))
        tr = tele.Tracer(recorder=rec)
        mon = slo.SLOMonitor(tr, slos=(slo.SLOSpec("dispatch", 100.0),),
                             window_s=10.0, burn_windows=2, **kw)
        return rec, tr, mon

    def test_burn_rate_breach_fires_flight_dump(self, tmp_path):
        rec, tr, mon = self._mon(tmp_path)
        prev = tele.tracer()
        tele.arm(tr)
        try:
            t = 0.0
            mon.tick(t)
            _feed(tr, tele.DISPATCH, 50.0)
            t += 11
            assert mon.tick(t) == []          # healthy window
            _feed(tr, tele.DISPATCH, 500.0)
            t += 11
            assert mon.tick(t) == []          # first bad window: burning
            assert mon.snapshot()["burning"]["dispatch"] == 1
            _feed(tr, tele.DISPATCH, 500.0)
            t += 11
            assert mon.tick(t) == ["dispatch"]  # second: breach
        finally:
            tele.disarm()
            if prev is not None:
                tele.arm(prev)
        assert mon.breaches["dispatch"] == 1
        assert rec.triggers.get("slo_breach") == 1
        assert rec.dump_paths, "breach must dump the flight ring"
        body = json.loads(open(rec.dump_paths[0]).read())
        assert body["reason"] == "slo_breach"
        assert "dispatch" in body["detail"]

    def test_windowed_not_cumulative(self, tmp_path):
        """Hours of healthy history must not dilute a fresh regression:
        the windowed p99 comes from bucket-count deltas only."""
        _rec, tr, mon = self._mon(tmp_path)
        t = 0.0
        mon.tick(t)
        _feed(tr, tele.DISPATCH, 10.0, n=10_000)  # long healthy history
        t += 11
        mon.tick(t)
        _feed(tr, tele.DISPATCH, 500.0, n=64)     # fresh regression
        t += 11
        mon.tick(t)
        p99 = mon.snapshot()["window_p99_us"]["dispatch"]
        assert p99 > 400.0, f"window p99 {p99} diluted by history"

    def test_quiet_window_skipped_and_resets_burn(self, tmp_path):
        _rec, tr, mon = self._mon(tmp_path)
        t = 0.0
        mon.tick(t)
        _feed(tr, tele.DISPATCH, 500.0)
        t += 11
        mon.tick(t)
        assert mon.snapshot()["burning"]["dispatch"] == 1
        # silence (below min_samples) is not a breach — and resets burn
        t += 11
        assert mon.tick(t) == []
        assert mon.snapshot()["burning"]["dispatch"] == 0

    def test_healthy_window_resets_burn(self, tmp_path):
        _rec, tr, mon = self._mon(tmp_path)
        t = 0.0
        mon.tick(t)
        _feed(tr, tele.DISPATCH, 500.0)
        t += 11
        mon.tick(t)
        _feed(tr, tele.DISPATCH, 10.0)
        t += 11
        assert mon.tick(t) == []
        assert mon.snapshot()["burning"]["dispatch"] == 0
        assert mon.breaches["dispatch"] == 0

    def test_snapshot_shape(self, tmp_path):
        _rec, _tr, mon = self._mon(tmp_path)
        snap = mon.snapshot()
        assert snap["budgets_us"] == {"dispatch": 100.0}
        assert snap["ok"] is True
        assert set(snap) >= {"windows", "window_s", "burn_windows",
                             "burning", "breaches", "window_p99_us"}


class TestCountsPercentile:
    def test_matches_latencyhist_geometry(self):
        from bng_tpu.telemetry.hist import LatencyHist

        rng = np.random.default_rng(3)
        vals = rng.uniform(10.0, 5000.0, size=500)
        h = LatencyHist()
        h.record_many(vals)
        got = slo._counts_percentile(h.counts, 99.0)
        ref = float(np.percentile(vals, 99))
        assert abs(got - ref) / ref < 0.15  # bucket-midpoint error bound

    def test_empty_counts(self):
        assert slo._counts_percentile(np.zeros(8, dtype=np.int64), 99) == 0.0


# ---------------------------------------------------------------------------
# sharded-path telemetry (parallel/sharded.py ShardTelemetry)
# ---------------------------------------------------------------------------

class TestShardTelemetry:
    def _rec(self, st, seed):
        rng = np.random.default_rng(seed)
        n, b = st.n, st.b
        length = rng.integers(0, 2, size=n * b).astype(np.uint32) * 100
        verdict = rng.integers(0, 4, size=n * b).astype(np.uint8)
        punt = rng.integers(0, 2, size=n * b).astype(bool)
        viol = np.zeros(n * b, dtype=bool)
        st.record_fused(length, verdict, punt, viol, 7)
        return length, verdict, punt

    def test_counters_from_lane_regions(self):
        from bng_tpu.parallel.sharded import ShardTelemetry

        st = ShardTelemetry(2, 4)
        length = np.array([100, 100, 0, 0, 100, 100, 100, 100],
                          dtype=np.uint32)
        verdict = np.array([2, 0, 1, 1, 3, 3, 1, 0], dtype=np.uint8)
        punt = np.array([0, 1, 0, 0, 0, 0, 0, 0], dtype=bool)
        viol = np.array([0, 0, 0, 0, 0, 0, 1, 0], dtype=bool)
        st.record_fused(length, verdict, punt, viol, 5)
        snap = st.snapshot()
        s0, s1 = snap["per_shard"]
        # shard 0: 2 real lanes (tx, pass); padding lanes never counted
        assert s0["frames"] == 2
        assert s0["verdicts"] == {"pass": 1, "drop": 0, "tx": 1, "fwd": 0}
        assert s0["nat_punts"] == 1
        # shard 1: fwd, fwd, drop, pass; one violation
        assert s1["frames"] == 4
        assert s1["verdicts"] == {"pass": 1, "drop": 1, "tx": 0, "fwd": 2}
        assert s1["violations"] == 1
        assert snap["psum_dhcp_hits"] == 5
        assert snap["pass_total"] == 2

    def test_dhcp_lane_counts(self):
        from bng_tpu.parallel.sharded import ShardTelemetry

        st = ShardTelemetry(2, 2)
        length = np.array([100, 100, 100, 0], dtype=np.uint32)
        is_reply = np.array([True, False, True, False])
        st.record_dhcp(length, is_reply, 2)
        snap = st.snapshot()
        assert snap["per_shard"][0]["dhcp_replies"] == 1
        assert snap["per_shard"][0]["verdicts"]["pass"] == 1
        assert snap["per_shard"][1]["dhcp_replies"] == 1
        # the padding lane on shard 1 is not a punt
        assert snap["per_shard"][1]["verdicts"]["pass"] == 0

    def test_merge_laws(self):
        """The sharded loop's stage times live on the Tracer's `sharded`
        lane; a stage's merged histogram is plain counter addition over
        its lanes — associative and commutative, the same law the
        fleet's worker-histogram merge is pinned to."""
        from bng_tpu.telemetry.hist import LatencyHist

        tr = tele.Tracer()
        toks = {lane: tr.begin(lane, 4) for lane in
                (tele.LANE_SHARDED, tele.LANE_BULK_L, tele.LANE_EXPRESS_L)}
        rng = np.random.default_rng(5)
        for stage in (tele.DISPATCH, tele.DEVICE_WAIT, tele.REPLY):
            for lane, tok in toks.items():
                for us in rng.integers(1, 5000, size=7 + lane):
                    tr.observe(stage, float(us), tok)
            lanes = [tr.lane_hist(lane, stage) for lane in toks]
            fwd, rev = LatencyHist(), LatencyHist()
            for h in lanes:
                fwd.merge(h)
            for h in reversed(lanes):
                rev.merge(h)
            assert np.array_equal(fwd.counts, rev.counts)
            assert np.array_equal(tr.stage_hist(stage).counts, fwd.counts)
            assert tr.stage_hist(stage).n == sum(h.n for h in lanes)
            assert tr.lane_hist(tele.LANE_SHARDED, stage).n == \
                7 + tele.LANE_SHARDED

    def test_idle_shard_records_nothing(self):
        from bng_tpu.parallel.sharded import ShardTelemetry

        st = ShardTelemetry(2, 2)
        length = np.array([100, 100, 0, 0], dtype=np.uint32)
        st.record_fused(length, np.zeros(4, np.uint8), None, None, 0)
        snap = st.snapshot()
        assert snap["steps"] == 1  # one program over the mesh: one step
        assert snap["per_shard"][0]["frames"] == 2
        assert snap["per_shard"][1]["frames"] == 0  # idle shard: no lane
        assert sum(snap["per_shard"][1]["verdicts"].values()) == 0
        # counters only: the loop's times are the Tracer's
        assert "stages" not in snap["per_shard"][0]
        assert "merged_stages" not in snap

    def test_snapshot_is_json_serializable(self):
        from bng_tpu.parallel.sharded import ShardTelemetry

        st = ShardTelemetry(2, 4)
        self._rec(st, 1)
        json.dumps(st.snapshot())


# ---------------------------------------------------------------------------
# metrics export
# ---------------------------------------------------------------------------

class TestMetricsExport:
    def test_collect_slo_families(self, tmp_path):
        from bng_tpu.control.metrics import BNGMetrics

        m = BNGMetrics()
        tr = tele.Tracer()
        mon = slo.SLOMonitor(tr, slos=(slo.SLOSpec("dispatch", 100.0),),
                             window_s=10.0, burn_windows=1)
        t = 0.0
        mon.tick(t)
        _feed(tr, tele.DISPATCH, 500.0)
        t += 11
        mon.tick(t)
        m.collect_slo(mon)
        text = m.expose()
        assert 'bng_slo_breaches_total{stage="dispatch"} 1' in text
        assert 'bng_slo_budget_us{stage="dispatch"} 100' in text
        assert "bng_slo_ok 1" in text  # breach re-armed -> not burning

    def test_collect_sharded_families(self):
        from bng_tpu.control.metrics import BNGMetrics
        from bng_tpu.parallel.sharded import ShardTelemetry

        class _FakeCluster:
            telemetry = ShardTelemetry(2, 2)

        cl = _FakeCluster()
        length = np.array([100, 100, 100, 0], dtype=np.uint32)
        verdict = np.array([2, 0, 3, 0], dtype=np.uint8)
        cl.telemetry.record_fused(length, verdict, None, None, 3)
        m = BNGMetrics()
        with tele.armed() as tr:
            tok = tr.begin(tele.LANE_SHARDED, 3)
            tr.observe(tele.DISPATCH, 20.0, tok)
            tr.observe(tele.DISPATCH, 900.0, tr.begin(tele.LANE_BULK_L, 1))
            m.collect_sharded(cl)
        text = m.expose()
        assert "bng_shard_psum_dhcp_hits_total 3" in text
        assert ('bng_shard_frames_total{shard="0",verdict="tx"} 1'
                in text)
        # one program over the mesh: one value a stage, from the
        # Tracer's sharded lane (the bulk lane's 900 us is not in it)
        line = [ln for ln in text.splitlines() if ln.startswith(
            'bng_sharded_stage_p99_us{stage="dispatch"}')]
        assert line and float(line[0].split()[-1]) < 30.0
        assert "bng_shard_stage_p99_us" not in text


class TestLoadtestResultField:
    def test_slo_field_rides_to_dict(self):
        from bng_tpu.loadtest.harness import BenchmarkResult

        res = BenchmarkResult()
        res.slo = {"ok": True, "breaches": []}
        assert res.to_dict()["slo"] == {"ok": True, "breaches": []}
