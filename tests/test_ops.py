"""Zero-downtime operations: live fleet elasticity, rolling worker
restart, blue/green engine swap, the ops control wire and the
autoscaler — plus the checkpoint N->M worker-count transition matrix
(runtime/checkpoint.py's never-cold-start promise beyond the
fleet<->fleetless directions test_fleet already covers)."""

import json
import threading
import urllib.request

import pytest

from bng_tpu.chaos.faults import (FAIL, IO_ERROR, KILL, FaultPlan, FaultSpec,
                                  SimClock, armed)
from bng_tpu.chaos.invariants import audit_invariants
from bng_tpu.chaos.scenarios import (_discover, _renew, _reply, _request,
                                     build_fleet, dora_with_retries, _mac)
from bng_tpu.control import dhcp_codec
from bng_tpu.control.opsctl import (AutoscaleConfig, FleetAutoscaler,
                                    OpsController, OpsServer, ctl_request)
from bng_tpu.runtime import checkpoint as ckpt_mod

pytestmark = pytest.mark.ops


def _ack_of(rep, want_ip):
    if rep is None:
        return False
    p = _reply(rep)
    return p.msg_type == dhcp_codec.ACK and p.yiaddr == want_ip


def _renew_all(fleet, clock, leased, xid=0x100):
    macs = sorted(leased)
    out = fleet.handle_batch(
        [(i, _renew(m, leased[m], xid + i)) for i, m in enumerate(macs)],
        now=clock.advance(30.0))
    return sum(1 for (_l, rep), m in zip(out, macs)
               if _ack_of(rep, leased[m]))


# ---------------------------------------------------------------------------
# live fleet elasticity
# ---------------------------------------------------------------------------

class TestFleetResize:
    def test_shrink_and_grow_keep_every_lease_and_offer(self):
        clock = SimClock()
        fleet, pools, fastpath = build_fleet(4, clock)
        macs = [_mac(100 + i) for i in range(20)]
        leased = dora_with_retries(fleet, macs, clock)
        assert len(leased) == 20
        # in-flight DORAs: DISCOVER sent, OFFER out, no REQUEST yet
        inflight = [_mac(900 + i) for i in range(5)]
        out = fleet.handle_batch(
            [(i, _discover(m, 50 + i)) for i, m in enumerate(inflight)],
            now=clock())
        offers = {m: _reply(rep).yiaddr for (_l, rep), m in zip(out, inflight)}

        rep = fleet.resize(2)
        assert rep["outcome"] == "ok"
        assert rep["leases_moved"] == 20 and rep["offers_moved"] == 5
        assert fleet.n == 2 and len(fleet._inline) == 2

        # the un-ACKed OFFERs complete on their NEW owners at the
        # offered address — zero dropped in-flight DORAs
        out = fleet.handle_batch(
            [(i, _request(m, offers[m], 60 + i))
             for i, m in enumerate(inflight)], now=clock())
        assert all(_ack_of(rep, offers[m])
                   for (_l, rep), m in zip(out, inflight))
        assert _renew_all(fleet, clock, leased) == 20

        # grow past the original count; everything still renews
        assert fleet.resize(5)["outcome"] == "ok"
        assert _renew_all(fleet, clock, leased, xid=0x200) == 20
        audit = audit_invariants(pools=pools, fleet=fleet,
                                 fastpath=fastpath)
        assert audit.ok, audit.violations_by_kind()

    def test_resize_releases_unheld_slices(self):
        """Shrinking must hand un-leased slice addresses back to the
        parent pool, or repeated resizes leak the pool dry."""
        clock = SimClock()
        fleet, pools, _ = build_fleet(4, clock, slice_size=32)
        leased = dora_with_retries(fleet, [_mac(i) for i in range(8)], clock)
        pool = pools.pools[1]
        used_before = pool.used
        rep = fleet.resize(2)
        assert rep["slices_freed"] > 0
        # after resize: parent usage = leases + the new fleet's carves;
        # repeated resizes must not grow it monotonically
        for n in (3, 2, 4, 2):
            assert fleet.resize(n)["outcome"] == "ok"
        assert pool.used <= used_before
        assert _renew_all(fleet, clock, leased) == 8

    def test_resize_noop_and_validation(self):
        clock = SimClock()
        fleet, _pools, _ = build_fleet(2, clock)
        assert fleet.resize(2)["outcome"] == "noop"
        with pytest.raises(ValueError):
            fleet.resize(0)

    def test_admission_protection_survives_resize(self):
        """REQUEST-after-OFFER must never shed ACROSS a transition: the
        admission controller's known-client set is parent-side state."""
        clock = SimClock()
        fleet, _pools, _ = build_fleet(3, clock)
        m = _mac(77)
        out = fleet.handle_batch([(0, _discover(m, 1))], now=clock())
        ip = _reply(out[0][1]).yiaddr
        mac_u64 = int.from_bytes(m, "big")
        assert fleet.admission.is_known(mac_u64, clock())
        fleet.resize(5)
        assert fleet.admission.is_known(mac_u64, clock())
        out = fleet.handle_batch([(0, _request(m, ip, 2))], now=clock())
        assert _ack_of(out[0][1], ip)

    def test_chaos_fail_aborts_with_old_fleet_serving(self):
        clock = SimClock()
        fleet, pools, fastpath = build_fleet(3, clock)
        leased = dora_with_retries(fleet, [_mac(i) for i in range(9)], clock)
        with armed(FaultPlan(1, [FaultSpec("fleet.resize", FAIL)]),
                   log=False):
            rep = fleet.resize(2)
        assert rep["outcome"] == "aborted"
        assert fleet.n == 3  # untouched, still serving
        assert _renew_all(fleet, clock, leased) == 9
        assert audit_invariants(pools=pools, fleet=fleet,
                                fastpath=fastpath).ok

    @pytest.mark.parametrize("fails,expect_n", [(1, 3), (2, 1)])
    def test_salvage_past_commit_point(self, fails, expect_n):
        """Past phase 2 the old fleet is gone and the exported books are
        the ONLY copy of every lease — a spawn/grant failure there must
        salvage them into SOME worker set (retry at target, then shrink
        to 1), never abandon them."""
        clock = SimClock()
        fleet, pools, fastpath = build_fleet(2, clock)
        leased = dora_with_retries(fleet, [_mac(i) for i in range(10)],
                                   clock)
        calls = {"n": 0}
        orig = fleet._initial_grant

        def flaky_grant():
            calls["n"] += 1
            if calls["n"] <= fails:
                raise RuntimeError("injected: grant infra down")
            return orig()

        fleet._initial_grant = flaky_grant
        rep = fleet.resize(3)
        assert rep["outcome"] == "salvaged", rep
        assert rep["to"] == expect_n and fleet.n == expect_n
        assert "RuntimeError" in rep["error"]
        assert rep["leases_moved"] == 10
        # every lease survived into the salvaged fleet
        assert _renew_all(fleet, clock, leased) == 10
        audit = audit_invariants(pools=pools, fleet=fleet,
                                 fastpath=fastpath)
        assert audit.ok, audit.violations_by_kind()

    def test_chaos_kill_mid_resize_heals_inline_shard(self):
        clock = SimClock()
        fleet, pools, fastpath = build_fleet(4, clock)
        leased = dora_with_retries(fleet, [_mac(i) for i in range(16)],
                                   clock)
        with armed(FaultPlan(1, [FaultSpec("fleet.resize", KILL,
                                           at_hit=2)]), log=False) as inj:
            rep = fleet.resize(2)
        assert inj.injected and rep["outcome"] == "ok"
        # the killed worker's book was still knowable inline: no loss
        assert rep["leases_moved"] == 16 and not rep["lost_workers"]
        assert not fleet._dead  # fresh fleet, all alive
        assert _renew_all(fleet, clock, leased) == 16
        assert audit_invariants(pools=pools, fleet=fleet,
                                fastpath=fastpath).ok


class TestRollingRestart:
    def test_books_offers_and_slices_move_verbatim(self):
        clock = SimClock()
        fleet, pools, fastpath = build_fleet(3, clock)
        leased = dora_with_retries(fleet, [_mac(i) for i in range(12)],
                                   clock)
        m = _mac(800)
        out = fleet.handle_batch([(0, _discover(m, 9))], now=clock())
        offered = _reply(out[0][1]).yiaddr
        rep = fleet.rolling_restart()
        assert rep["outcome"] == "ok"
        assert rep["replaced"] == [0, 1, 2] and not rep["lost"]
        out = fleet.handle_batch([(0, _request(m, offered, 10))],
                                 now=clock())
        assert _ack_of(out[0][1], offered)
        assert _renew_all(fleet, clock, leased) == 12
        assert audit_invariants(pools=pools, fleet=fleet,
                                fastpath=fastpath).ok

    def test_restart_heals_a_chaos_killed_worker(self):
        clock = SimClock()
        fleet, pools, fastpath = build_fleet(3, clock)
        leased = dora_with_retries(fleet, [_mac(i) for i in range(12)],
                                   clock)
        fleet._kill_worker(1)
        assert 1 in fleet._dead
        rep = fleet.rolling_restart()
        assert rep["outcome"] == "ok" and rep["healed"] == [1]
        assert not fleet._dead
        assert _renew_all(fleet, clock, leased) == 12
        assert audit_invariants(pools=pools, fleet=fleet,
                                fastpath=fastpath).ok


# ---------------------------------------------------------------------------
# checkpoint restore across --slowpath-workers N -> M (never-cold-start)
# ---------------------------------------------------------------------------

class TestCheckpointWorkerCountMatrix:
    def _leased_fleet(self, n, n_macs=18):
        clock = SimClock()
        fleet, pools, fastpath = build_fleet(n, clock)
        leased = dora_with_retries(
            fleet, [_mac(i) for i in range(n_macs)], clock)
        assert len(leased) == n_macs
        return clock, fleet, pools, fastpath, leased

    def _roundtrip(self, fleet):
        ck = ckpt_mod.build_checkpoint(1, 1.0, fleet=fleet)
        return ckpt_mod.decode_checkpoint(ckpt_mod.encode_checkpoint(ck))

    @pytest.mark.parametrize("n_from,n_to", [(4, 2), (2, 5), (3, 3)])
    def test_fleet_to_fleet_n_to_m(self, n_from, n_to):
        _clock, fleet, _pools, _fp, leased = self._leased_fleet(n_from)
        dec = self._roundtrip(fleet)
        clock2 = SimClock()
        fleet2, pools2, fastpath2 = build_fleet(n_to, clock2)
        rows = ckpt_mod.restore_checkpoint(dec, fleet=fleet2)
        assert rows["fleet.leases"] == len(leased)
        assert _renew_all(fleet2, clock2, leased) == len(leased)
        audit = audit_invariants(pools=pools2, fleet=fleet2,
                                 fastpath=fastpath2)
        assert audit.ok, audit.violations_by_kind()

    def test_n_to_1_to_n_chain(self):
        """The full round trip the promise covers: fleet -> fleetless
        single worker -> fleet again, leases surviving every hop."""
        from bng_tpu.control.dhcp_server import DHCPServer
        from bng_tpu.chaos.scenarios import (SERVER_IP, SERVER_MAC,
                                             _make_pools)

        _clock, fleet, _pools, _fp, leased = self._leased_fleet(4)
        dec = self._roundtrip(fleet)
        # hop 1: N -> 1 (fleetless): worker books merge into the parent
        pools_b = _make_pools()
        server = DHCPServer(SERVER_MAC, SERVER_IP, pools_b)
        rows = ckpt_mod.restore_checkpoint(dec, dhcp=server)
        assert rows["dhcp.leases"] == len(leased)
        # hop 2: 1 -> N: the parent book re-shards into a NEW fleet
        dec2 = ckpt_mod.decode_checkpoint(ckpt_mod.encode_checkpoint(
            ckpt_mod.build_checkpoint(2, 2.0, dhcp=server)))
        clock3 = SimClock()
        fleet3, pools3, fastpath3 = build_fleet(3, clock3)
        rows = ckpt_mod.restore_checkpoint(dec2, fleet=fleet3)
        assert rows["fleet.leases"] == len(leased)
        assert _renew_all(fleet3, clock3, leased) == len(leased)
        audit = audit_invariants(pools=pools3, fleet=fleet3,
                                 fastpath=fastpath3)
        assert audit.ok, audit.violations_by_kind()

    def test_live_resize_then_checkpoint_roundtrip(self):
        """A fleet that has been live-resized checkpoints/restores like
        any other — the two transition paths share one hydration core."""
        clock, fleet, _pools, _fp, leased = self._leased_fleet(4)
        assert fleet.resize(2)["outcome"] == "ok"
        dec = self._roundtrip(fleet)
        clock2 = SimClock()
        fleet2, pools2, fastpath2 = build_fleet(4, clock2)
        assert ckpt_mod.restore_checkpoint(
            dec, fleet=fleet2)["fleet.leases"] == len(leased)
        assert _renew_all(fleet2, clock2, leased) == len(leased)
        assert audit_invariants(pools=pools2, fleet=fleet2,
                                fastpath=fastpath2).ok


# ---------------------------------------------------------------------------
# blue/green engine swap (compiles the fused pipeline once per session)
# ---------------------------------------------------------------------------

def _engine_stack():
    from bng_tpu.chaos.scenarios import _build_server_stack
    from bng_tpu.runtime.engine import Engine

    clock = SimClock()
    server, pools, fastpath, nat = _build_server_stack(clock)
    eng = Engine(fastpath, nat, batch_size=32,
                 slow_path=server.handle_frame, clock=clock)
    leased = {}
    for i in range(5):
        m = _mac(300 + i)
        out = eng.process([_discover(m, 100 + i)])
        ip = _reply((out["slow"] or out["tx"])[0][1]).yiaddr
        eng.process([_request(m, ip, 200 + i)])
        leased[m] = ip
    return clock, server, pools, fastpath, nat, eng, leased


class TestBlueGreenSwap:
    def test_swap_flips_and_serves_on_device(self):
        from bng_tpu.runtime.ops import blue_green_swap

        clock, server, pools, _fp, nat, eng, leased = _engine_stack()
        components = {"engine": eng, "pools": pools, "dhcp": server}
        rep = blue_green_swap(components)
        assert rep["outcome"] == "ok" and rep["audit_ok"]
        standby = components["engine"]
        assert standby is not eng
        assert standby.stats is eng.stats  # counter continuity
        # renewals answered ON DEVICE from the hydrated standby chain
        m = next(iter(sorted(leased)))
        out = standby.process([_renew(m, leased[m], 0xA01)],
                              now=clock.advance(30.0))
        assert out["tx"] and _ack_of(out["tx"][0][1], leased[m])
        assert audit_invariants(engine=standby, pools=pools, dhcp=server,
                                nat=nat).ok

    def test_crash_mid_swap_rolls_back(self):
        from bng_tpu.runtime.ops import blue_green_swap

        clock, server, pools, _fp, nat, eng, leased = _engine_stack()
        components = {"engine": eng, "pools": pools, "dhcp": server}
        with armed(FaultPlan(1, [FaultSpec("ops.swap", FAIL)]), log=False):
            rep = blue_green_swap(components)
        assert rep["outcome"] == "rolled_back"
        assert components["engine"] is eng  # active untouched
        m = next(iter(sorted(leased)))
        out = eng.process([_renew(m, leased[m], 0xA02)],
                          now=clock.advance(30.0))
        assert _ack_of((out["tx"] or out["slow"])[0][1], leased[m])
        assert audit_invariants(engine=eng, pools=pools, dhcp=server,
                                nat=nat).ok

    def test_unexpected_error_after_delta_still_heals_active(self, monkeypatch):
        """The rollback heal must run for ANY exception once the replay
        consumed dirty marks into the discarded standby — an XLA runtime
        error is a plain RuntimeError, not one of the expected types, and
        escaping without eng.resync_tables() would leave the active
        device chain silently missing those rows."""
        from bng_tpu import chaos
        from bng_tpu.runtime.ops import blue_green_swap

        clock, server, pools, _fp, nat, eng, leased = _engine_stack()
        components = {"engine": eng, "pools": pools, "dhcp": server}

        def exploding_audit(*a, **kw):
            raise RuntimeError("injected: device backend fell over")

        monkeypatch.setattr(chaos.invariants, "audit_invariants",
                            exploding_audit)
        rep = blue_green_swap(components)
        monkeypatch.undo()
        assert rep["outcome"] == "rolled_back", rep
        assert "RuntimeError" in rep["error"]
        assert components["engine"] is eng  # active untouched
        # the heal ran: host == device on the ACTIVE chain, still serving
        m = next(iter(sorted(leased)))
        out = eng.process([_renew(m, leased[m], 0xA05)],
                          now=clock.advance(30.0))
        assert _ack_of((out["tx"] or out["slow"])[0][1], leased[m])
        assert audit_invariants(engine=eng, pools=pools, dhcp=server,
                                nat=nat).ok

    def test_snapshot_io_error_fails_before_standby(self):
        from bng_tpu.runtime.ops import blue_green_swap

        _clock, server, pools, _fp, _nat, eng, _leased = _engine_stack()
        components = {"engine": eng, "pools": pools, "dhcp": server}
        with armed(FaultPlan(1, [FaultSpec("ops.snapshot", IO_ERROR)]),
                   log=False):
            rep = blue_green_swap(components)
        assert rep["outcome"] == "failed"
        assert "OSError" in rep["error"]
        assert components["engine"] is eng

    def test_delta_replay_ships_post_snapshot_rows(self):
        from bng_tpu.runtime.engine import Engine
        from bng_tpu.runtime.ops import clone_mirrors, replay_delta_since

        clock, server, pools, fastpath, nat, eng, _leased = _engine_stack()
        eng.quiesce()
        eng.fold_device_authoritative()
        ck = ckpt_mod.roundtrip_checkpoint(ckpt_mod.build_checkpoint(
            0, clock(), fastpath=fastpath, nat=nat, qos=eng.qos,
            antispoof=eng.antispoof))
        # mutate AFTER the snapshot: one more subscriber leases
        m = _mac(999)
        out = eng.process([_discover(m, 0xB00)])
        ip = _reply((out["slow"] or out["tx"])[0][1]).yiaddr
        eng.process([_request(m, ip, 0xB01)])
        eng.quiesce()
        tmp = clone_mirrors(eng)
        ckpt_mod.restore_checkpoint(ck, **tmp)
        hydrator = Engine(tmp["fastpath"], tmp["nat"], qos=tmp["qos"],
                          antispoof=tmp["antispoof"], batch_size=eng.B,
                          clock=clock)
        standby = Engine(fastpath, nat, qos=eng.qos,
                         antispoof=eng.antispoof, batch_size=eng.B,
                         slow_path=server.handle_frame, clock=clock)
        standby.adopt_device_tables(hydrator.tables)
        d = replay_delta_since(standby, ck.arrays)
        assert d["rows"] > 0 and not d["resync"]
        assert standby.pending_dirty() == 0
        # host == device bit-exact after the replay (the mirror audit)
        audit = audit_invariants(engine=standby, pools=pools, dhcp=server,
                                 nat=nat)
        assert audit.ok, audit.violations_by_kind()

    def test_swap_with_scheduler_repoints_lanes(self):
        from bng_tpu.runtime.ops import blue_green_swap
        from bng_tpu.runtime.scheduler import SchedulerConfig, TieredScheduler

        clock, server, pools, _fp, nat, eng, leased = _engine_stack()
        sched = TieredScheduler(eng, SchedulerConfig(bulk_batch=32),
                                clock=clock)
        components = {"engine": eng, "scheduler": sched, "pools": pools,
                      "dhcp": server}
        rep = blue_green_swap(components)
        assert rep["outcome"] == "ok"
        assert sched.engine is components["engine"]
        m = next(iter(sorted(leased)))
        res = sched.process([_renew(m, leased[m], 0xA03)],
                            now=clock.advance(30.0))
        got = res["tx"] or res["slow"]
        assert got and _ack_of(got[0][1], leased[m])


# ---------------------------------------------------------------------------
# the ops control wire (`bng ctl`) + app-level transitions
# ---------------------------------------------------------------------------

class TestOpsControl:
    def _app(self, **kw):
        from bng_tpu.cli import BNGApp, BNGConfig

        cfg = BNGConfig(slowpath_workers=2, slowpath_worker_mode="inline",
                        dhcpv6_enabled=False, slaac_enabled=False,
                        metrics_enabled=True, ctl_listen="", **kw)
        return BNGApp(cfg)

    def test_app_fleet_resize_and_status(self):
        app = self._app()
        try:
            assert app.components["fleet"].n == 2
            rep = app.fleet_resize(4)
            assert rep["outcome"] == "ok"
            assert app.components["fleet"].n == 4
            st = app.ops_status()
            assert st["fleet"]["workers"] == 4
            assert st["fleet"]["resizes"] == 1
            # transition metrics recorded
            m = app.components["metrics"]
            assert m.ops_transitions.value(op="fleet_resize",
                                           outcome="ok") == 1
        finally:
            app.close()

    def test_app_rejects_resize_without_fleet(self):
        from bng_tpu.cli import BNGApp, BNGConfig

        app = BNGApp(BNGConfig(slowpath_workers=4, pppoe_enabled=True,
                               dhcpv6_enabled=False, slaac_enabled=False,
                               metrics_enabled=True))
        try:
            assert app.fleet_blockers == ["pppoe"]
            assert "slowpath_fleet_blocked" in app.stats()
            rep = app.fleet_resize(8)
            assert rep["outcome"] == "rejected" and "pppoe" in rep["error"]
            # the degradation is a labeled gauge, not just a log line
            m = app.components["metrics"]
            assert m.slowpath_fleet_blocked.value(blocker="pppoe") == 1
        finally:
            app.close()

    def test_ha_active_composes_with_fleet(self):
        """`ha` left the blocker list: an active-role app with a
        configured fleet builds BOTH, and worker lease events reach the
        ActiveSyncer store through the fleet's lease_hook relay."""
        from bng_tpu.cli import BNGApp, BNGConfig
        from bng_tpu.control import dhcp_codec, packets

        app = BNGApp(BNGConfig(slowpath_workers=2, ha_role="active",
                               dhcpv6_enabled=False, slaac_enabled=False,
                               metrics_enabled=True))
        try:
            assert app.fleet_blockers == []
            fleet = app.components["fleet"]
            assert fleet.n == 2
            ha_store = app.components["ha_store"]
            assert len(ha_store) == 0

            mac = bytes.fromhex("02aa00000042")
            disc = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER,
                                            xid=1)
            frame = packets.udp_packet(
                mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                disc.encode().ljust(300, b"\x00"))
            (_l, rep), = fleet.handle_batch([(0, frame)], now=1.0)
            off = dhcp_codec.decode(packets.decode(rep).payload)
            assert off.msg_type == dhcp_codec.OFFER
            req = dhcp_codec.build_request(
                mac, dhcp_codec.REQUEST, xid=2, requested_ip=off.yiaddr,
                server_id=off.server_id)
            frame = packets.udp_packet(
                mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                req.encode().ljust(300, b"\x00"))
            (_l, rep), = fleet.handle_batch([(0, frame)], now=1.0)
            ack = dhcp_codec.decode(packets.decode(rep).payload)
            assert ack.msg_type == dhcp_codec.ACK

            # the worker's lease event crossed the single-writer drain
            # into the active's replicated session store
            assert len(ha_store) == 1
            (sess,) = ha_store.all()
            assert sess.mac == mac.hex() and sess.ip == ack.yiaddr
        finally:
            app.close()

    def test_ctl_http_roundtrip(self):
        """The full wire: OpsServer -> OpsController queue -> run-loop
        pump -> fleet.resize -> report back over HTTP."""
        app = self._app()
        srv = None
        stop = threading.Event()
        try:
            ops = app.components["ops"]
            srv = OpsServer(ops, "127.0.0.1", 0).start()
            addr = f"{srv.addr[0]}:{srv.addr[1]}"

            def pump():
                while not stop.is_set():
                    ops.run_pending()
                    stop.wait(0.01)

            t = threading.Thread(target=pump, daemon=True)
            t.start()
            code, doc = ctl_request(addr, "fleet/resize", {"n": 3},
                                    timeout_s=30)
            assert code == 200 and doc["outcome"] == "ok"
            assert app.components["fleet"].n == 3
            code, doc = ctl_request(addr, "status")
            assert code == 200 and doc["fleet"]["workers"] == 3
            code, doc = ctl_request(addr, "fleet/rolling-restart", {})
            assert code == 200 and doc["outcome"] == "ok"
            # unknown op rejects without touching the queue
            code, doc = ctl_request(addr, "bogus", {})
            assert code == 409 and doc["outcome"] == "rejected"
        finally:
            stop.set()
            if srv is not None:
                srv.close()
            app.close()

    def test_timeout_race_with_executing_loop_returns_real_report(self):
        """When the run loop claims an op right at the client's
        deadline, the client must NOT be told 'timeout' (it would retry
        and double the transition) — the atomic claim makes exactly one
        side win, and the losing client waits for the real report."""
        import time as _time

        app = self._app()
        try:
            ops = app.components["ops"]
            orig = app.fleet_resize

            def slow_resize(n):
                _time.sleep(0.4)  # loop holds the op past the deadline
                return orig(n)

            app.fleet_resize = slow_resize
            stop = threading.Event()

            def pump():
                while not stop.is_set():
                    ops.run_pending()
                    _time.sleep(0.001)

            t = threading.Thread(target=pump, daemon=True)
            t.start()
            # the pump dequeues within ~1ms and executes for 0.4s; the
            # 0.1s client deadline expires mid-execution — the loop owns
            # the claim, so submit waits it out and returns the report
            rep = ops.submit("fleet/resize", {"n": 3}, timeout_s=0.1)
            stop.set()
            t.join(timeout=5)
            assert rep["outcome"] == "ok", rep
            assert app.components["fleet"].n == 3
            # exactly ONE transition executed
            assert app.components["fleet"].resizes == 1
        finally:
            app.close()

    def test_run_pending_skips_client_claimed_entry(self):
        """The loop side of the same claim: an entry the client already
        claimed (timed out) must be skipped, never executed."""
        app = self._app()
        try:
            ops = app.components["ops"]
            done = threading.Event()
            ops._q.put_nowait(("fleet_resize", {"n": 3}, done,
                               {"owner": "client"}))
            assert ops.run_pending() == 0
            assert done.is_set()  # the skip still releases the waiter
            assert app.components["fleet"].n == 2
        finally:
            app.close()

    def test_controller_timeout_when_nothing_pumps(self):
        app = self._app()
        try:
            ops = app.components["ops"]
            fleet = app.components["fleet"]
            rep = ops.submit("fleet/resize", {"n": 3}, timeout_s=0.05)
            assert rep["outcome"] == "timeout"
            # the timed-out op was CANCELLED, not abandoned: when the
            # loop finally drains, it must not fire (an operator retry
            # after a timeout would otherwise double the transition)
            assert ops.run_pending() == 0
            assert fleet.n == 2
            assert ops.stats_snapshot()["rejected"] == 1
        finally:
            app.close()


class TestAutoscaler:
    def _fleet(self):
        clock = SimClock()
        fleet, _pools, _ = build_fleet(2, clock)
        return clock, fleet

    def test_scales_up_on_shed(self):
        clock, fleet = self._fleet()
        auto = FleetAutoscaler(fleet, AutoscaleConfig(max_workers=4,
                                                      cooldown_s=0.0),
                               clock=clock)
        assert auto.target(clock()) is None  # first look only baselines
        fleet.admission.stats.shed["inbox_full"] = 5
        clock.advance(1.0)
        assert auto.target(clock()) == 3

    def test_scales_down_only_after_hold(self):
        clock, fleet = self._fleet()
        auto = FleetAutoscaler(
            fleet, AutoscaleConfig(min_workers=1, max_workers=4, hold=3,
                                   cooldown_s=0.0), clock=clock)
        auto.target(clock())
        downs = []
        for _ in range(6):
            clock.advance(1.0)
            got = auto.target(clock())
            if got is not None:
                downs.append(got)
        # calm fleet: exactly one step down per `hold` calm looks
        assert downs and downs[0] == 1

    def test_cooldown_blocks_thrash(self):
        clock, fleet = self._fleet()
        auto = FleetAutoscaler(fleet, AutoscaleConfig(max_workers=8,
                                                      cooldown_s=60.0),
                               clock=clock)
        auto.target(clock())
        fleet.admission.stats.shed["inbox_full"] = 5
        clock.advance(1.0)
        assert auto.target(clock()) == 3
        fleet.admission.stats.shed["inbox_full"] = 50
        clock.advance(1.0)
        assert auto.target(clock()) is None  # inside the cooldown

    def test_transition_reset_never_credits_calm(self):
        """resize/rolling_restart zero the per-worker stats payloads, so
        busy_seconds_total() goes BACKWARD across a transition — that
        look must re-baseline and decide nothing, not bank a bogus
        'calm' hysteresis credit while the fleet may be saturated."""
        clock, fleet = self._fleet()
        auto = FleetAutoscaler(
            fleet, AutoscaleConfig(min_workers=1, max_workers=4, hold=2,
                                   cooldown_s=0.0), clock=clock)
        auto.target(clock())  # baseline
        # busy fleet: mid-band fraction (no decision, calm resets)
        fleet._last_stats = [{"busy_s": 1.0}, {"busy_s": 1.0}]
        clock.advance(2.0)
        assert auto.target(clock()) is None and auto._calm == 0
        # a transition resets the stats: counter goes backward
        fleet._last_stats = [{}, {}]
        clock.advance(1.0)
        assert auto.target(clock()) is None
        assert auto._calm == 0  # the reset look banked NO calm credit
        # from the fresh baseline, exactly `hold` genuinely-calm looks
        # are still required before a scale-down fires
        clock.advance(1.0)
        assert auto.target(clock()) is None and auto._calm == 1
        clock.advance(1.0)
        assert auto.target(clock()) == 1

    def test_transition_reset_still_grows_on_shed(self):
        """The look after a transition knows nothing of busy time, but
        the shed counter never resets: shedding grows the fleet whether
        or not the fresh busy sum has passed the old one (that race with
        the wall clock made `bng chaos run` print 5 or 6 workers)."""
        for busy_after in (0.0, 5.0):  # behind the old sum / past it
            clock, fleet = self._fleet()
            auto = FleetAutoscaler(
                fleet, AutoscaleConfig(min_workers=1, max_workers=4,
                                       busy_hi=1e18, busy_lo=-1.0,
                                       cooldown_s=0.0), clock=clock)
            fleet._last_stats = [{"busy_s": 1.0}, {"busy_s": 1.0}]
            auto.target(clock())  # baseline
            fleet._last_stats = [{"busy_s": busy_after}, {}]
            fleet.admission.stats.shed["inbox_full"] = 7
            clock.advance(1.0)
            assert auto.target(clock()) == 3

    def test_autoscaler_resize_failure_keeps_tick_alive(self):
        """An autoscaler-triggered resize that raises must be contained
        by the tick loop — crashing the dataplane process on a failed
        grow is the outage the zero-downtime layer exists to prevent."""
        from bng_tpu.cli import BNGApp, BNGConfig

        app = BNGApp(BNGConfig(
            slowpath_workers=2, slowpath_worker_mode="inline",
            slowpath_autoscale=True, slowpath_max_workers=4,
            dhcpv6_enabled=False, slaac_enabled=False))
        try:
            fleet = app.components["fleet"]
            app.components["autoscaler"].cfg.cooldown_s = 0.0

            def exploding_resize(n):
                raise RuntimeError("injected: cannot spawn workers")

            fleet.resize = exploding_resize
            app.tick(1000.0)  # baseline look
            fleet.admission.stats.shed["inbox_full"] = 9
            app.tick(1001.0)  # recommends a grow; resize raises inside
            assert fleet.n == 2  # unchanged, and the loop survived
            app.tick(1002.0)  # loop still ticking
        finally:
            app.close()

    def test_app_tick_drives_autoscaler(self):
        from bng_tpu.cli import BNGApp, BNGConfig

        app = BNGApp(BNGConfig(
            slowpath_workers=2, slowpath_worker_mode="inline",
            slowpath_autoscale=True, slowpath_max_workers=4,
            dhcpv6_enabled=False, slaac_enabled=False))
        try:
            auto = app.components["autoscaler"]
            auto.cfg.cooldown_s = 0.0
            app.tick(1000.0)  # baseline look
            app.components["fleet"].admission.stats.shed["inbox_full"] = 9
            app.tick(1001.0)
            assert app.components["fleet"].n == 3
        finally:
            app.close()


# ---------------------------------------------------------------------------
# the acceptance bar: live transitions on a RUNNING composed app —
# traffic before, transitions at the boundary, traffic after, audit-clean
# epilogue, one process throughout
# ---------------------------------------------------------------------------

class TestLiveAppTransitions:
    def test_resize_and_swap_on_a_driving_app(self):
        from bng_tpu.chaos.invariants import audit_app
        from bng_tpu.cli import BNGApp, BNGConfig

        app = BNGApp(BNGConfig(
            synthetic_subs=32, batch_size=32,
            slowpath_workers=2, slowpath_worker_mode="inline",
            dhcpv6_enabled=False, slaac_enabled=False, ctl_listen=""))
        try:
            fleet = app.components["fleet"]
            engine_before = app.components["engine"]

            def drive(beats):
                moved = 0
                for _ in range(beats):
                    moved += app.drive_once()
                return moved

            assert drive(12) > 0
            served_before = app.components["dhcp"].stats.offer \
                + sum(w.server.stats.offer for w in fleet._inline)
            assert served_before > 0

            # live resize between beats — the batch boundary the run
            # loop's ops pump uses
            rep = app.fleet_resize(3)
            assert rep["outcome"] == "ok" and fleet.n == 3
            assert drive(12) > 0

            # blue/green engine swap on the same still-running process
            rep = app.engine_swap()
            assert rep["outcome"] == "ok", rep
            assert app.components["engine"] is not engine_before
            assert drive(12) > 0

            rep = app.fleet_rolling_restart()
            assert rep["outcome"] == "ok"
            assert drive(12) > 0

            # audit-clean epilogue over the live, post-transition app
            audit = audit_app(app)
            assert audit.ok, audit.violations_by_kind()
            # traffic kept flowing across every transition (no restart:
            # the same engine stats object accumulated throughout)
            assert app.components["engine"].stats.batches > 0
        finally:
            app.close()


# ---------------------------------------------------------------------------
# the requeue satellite: public pending-queue API
# ---------------------------------------------------------------------------

class TestRequeue:
    def test_demux_requeue_order(self):
        from bng_tpu.control.slowpath import SlowPathDemux

        d = SlowPathDemux()
        d.requeue([b"b", b"c"])
        d.requeue([b"a"], front=True)
        assert d.drain_pending() == [b"a", b"b", b"c"]
        assert d.drain_pending() == []

    def test_fleet_requeue_order(self):
        clock = SimClock()
        fleet, _pools, _ = build_fleet(2, clock)
        fleet.requeue([b"y"])
        fleet.requeue([b"x"], front=True)
        assert fleet.drain_pending() == [b"x", b"y"]
