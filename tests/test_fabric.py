"""Cluster control fabric tests (ISSUE 19).

Covers the fabric subsystem end to end: the authenticated UDP transport
(sign/replay/skew/malformed rejection, replay-floor reset), the
deterministic SimTransport (seeded drops, delivery delay, directed
partial partitions), the partition-aware failure detector (suspicion,
accusation quorum, gray serving-word stall, startup grace, reset), the
carve plan's host axis, the RADIUS/CoA fan-out through the slow-path
fleet (MAC-affine auth, relay accounting, degraded cache), the
accounting spool across failover, the resilience probe wall-time fix,
the bng_fabric_* metric families, and the two fabric chaos scenarios'
byte-determinism.
"""

import json

import pytest

from bng_tpu.cluster.fabric import (FailureDetector, SimTransport,
                                    UDPTransport)
from bng_tpu.control.deviceauth import PSKAuthenticator
from bng_tpu.utils.net import ip_to_u32

pytestmark = pytest.mark.fabric

PSK = "fabric-test-psk-0123456789"


class FakeClock:
    def __init__(self, now=1_700_000_000.0):
        self.now = now

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


def udp_pair(clock=None, psk=PSK, psk_b=None):
    """Two UDP endpoints on loopback, peered both ways."""
    kw = {"clock": clock} if clock is not None else {}
    a = UDPTransport("node-a", PSKAuthenticator(psk=psk), **kw)
    b = UDPTransport("node-b", PSKAuthenticator(psk=psk_b or psk), **kw)
    a.add_peer("node-b", b.addr)
    b.add_peer("node-a", a.addr)
    return a, b


def drain(ep, tries=50):
    """Poll until messages arrive (UDP delivery is async-ish even on
    loopback) or the budget runs out."""
    import time

    for _ in range(tries):
        got = ep.poll()
        if got:
            return got
        time.sleep(0.01)
    return []


class TestUDPTransport:
    def test_signed_beat_roundtrip(self):
        a, b = udp_pair()
        try:
            assert a.send("node-b", "beat", {"served": 3, "work": 7})
            got = drain(b)
            assert len(got) == 1
            msg = got[0]
            assert (msg.src, msg.kind) == ("node-a", "beat")
            assert msg.body == {"served": 3, "work": 7}
            assert msg.seq == 1
            assert b.stats["rx"] == 1
        finally:
            a.close()
            b.close()

    def test_bad_psk_rejected(self):
        a, b = udp_pair(psk_b="a-different-psk-9876543210")
        try:
            a.send("node-b", "beat", {})
            assert drain(b, tries=20) == []
            assert b.stats["rx_bad_sig"] == 1
            assert b.stats["rx"] == 0
        finally:
            a.close()
            b.close()

    def _wire(self, transport, src, seq, ts, kind="beat", body=None):
        """A raw datagram signed with the receiver's own PSK (what a
        legitimate sender with that seq/ts would put on the wire)."""
        from bng_tpu.cluster.fabric.transport import (FABRIC_VERSION,
                                                      _canonical)

        body = body or {}
        sig = transport.authenticator.sign_message(
            _canonical(src, seq, ts, kind, body))
        return json.dumps({"v": FABRIC_VERSION, "src": src, "seq": seq,
                           "ts": ts, "kind": kind, "body": body,
                           "sig": sig}).encode()

    def test_replay_skew_malformed_counted(self):
        clock = FakeClock()
        rx = UDPTransport("rx", PSKAuthenticator(psk=PSK), clock=clock)
        try:
            now = clock()
            fresh = self._wire(rx, "peer", 5, now)
            assert rx._verify(fresh) is not None
            # same seq again = replay; an OLDER seq is also a replay
            assert rx._verify(fresh) is None
            assert rx._verify(self._wire(rx, "peer", 4, now)) is None
            assert rx.stats["rx_replay"] == 2
            # timestamp outside the skew window
            assert rx._verify(
                self._wire(rx, "peer", 6, now - 10_000.0)) is None
            assert rx.stats["rx_skew"] == 1
            # garbage and schema-violating datagrams
            assert rx._verify(b"not json at all") is None
            assert rx._verify(b'{"v":1,"src":"x"}') is None
            assert rx.stats["rx_malformed"] == 2
            assert rx.stats["rx"] == 1
        finally:
            rx.close()

    def test_reset_peer_clears_replay_floor(self):
        """Standby promotion: the slot's new process restarts seq at 1.
        Without the reset every fresh beat would read as a replay."""
        clock = FakeClock()
        rx = UDPTransport("rx", PSKAuthenticator(psk=PSK), clock=clock)
        try:
            assert rx._verify(self._wire(rx, "peer", 9, clock())) is not None
            assert rx._verify(self._wire(rx, "peer", 1, clock())) is None
            rx.reset_peer("peer")
            assert rx._verify(self._wire(rx, "peer", 1, clock())) is not None
        finally:
            rx.close()


class TestSimTransport:
    def test_seeded_drops_deterministic(self):
        def run(seed):
            clock = FakeClock()
            hub = SimTransport(clock, seed=seed)
            a, b = hub.endpoint("a"), hub.endpoint("b")
            a.add_peer("b")
            hub.set_drop("a", "b", 0.5)
            pattern = []
            for i in range(50):
                a.send("b", "beat", {"i": i})
                pattern.extend(m.body["i"] for m in b.poll())
            return pattern, hub.stats["dropped"]

        p1, d1 = run(11)
        p2, d2 = run(11)
        p3, _ = run(12)
        assert p1 == p2 and d1 == d2
        assert 0 < d1 < 50
        assert p1 != p3  # a different seed drops differently

    def test_delay_holds_until_clock_passes(self):
        clock = FakeClock()
        hub = SimTransport(clock, seed=0)
        a, b = hub.endpoint("a"), hub.endpoint("b")
        a.add_peer("b")
        hub.set_delay("a", "b", 2.0)
        a.send("b", "beat", {})
        assert b.poll() == []
        clock.advance(1.0)
        assert b.poll() == []
        clock.advance(1.5)
        assert len(b.poll()) == 1

    def test_partial_partition_is_per_link(self):
        """partition(a, b) severs exactly a<->b; both still reach c —
        the NEAT shape, not a binary netsplit."""
        clock = FakeClock()
        hub = SimTransport(clock, seed=0)
        eps = {n: hub.endpoint(n) for n in ("a", "b", "c")}
        for n, ep in eps.items():
            for p in eps:
                if p != n:
                    ep.add_peer(p)
        hub.partition("a", "b")
        for src in ("a", "b", "c"):
            for dst in eps[src].peers:
                eps[src].send(dst, "beat", {})
        got = {n: sorted(m.src for m in eps[n].poll()) for n in eps}
        assert got == {"a": ["c"], "b": ["c"], "c": ["a", "b"]}
        assert hub.stats["cut"] == 2
        hub.heal("a", "b")
        eps["a"].send("b", "beat", {})
        assert [m.src for m in eps["b"].poll()] == ["a"]

    def test_oneway_partition(self):
        clock = FakeClock()
        hub = SimTransport(clock, seed=0)
        a, b = hub.endpoint("a"), hub.endpoint("b")
        a.add_peer("b")
        b.add_peer("a")
        hub.partition_oneway("a", "b")
        a.send("b", "beat", {})
        b.send("a", "beat", {})
        assert b.poll() == []
        assert len(a.poll()) == 1


def mesh(clock, seed=0, n=3, **det_kw):
    """An n-node detector mesh over one sim hub, everyone watching
    everyone (quorum defaults: majority of observers)."""
    hub = SimTransport(clock, seed=seed)
    ids = [f"n{i}" for i in range(n)]
    dets = {}
    for nid in ids:
        ep = hub.endpoint(nid)
        for p in ids:
            if p != nid:
                ep.add_peer(p)
        kw = dict(clock=clock, beat_interval_s=0.5,
                  suspicion_threshold=3, startup_grace_s=0.0)
        kw.update(det_kw)
        dets[nid] = FailureDetector(nid, ep, **kw)
    for nid in ids:
        for p in ids:
            if p != nid:
                dets[nid].watch(p, now=clock())
    return hub, ids, dets


def beat_rounds(clock, dets, rounds, silent=(), bodies=None):
    for _ in range(rounds):
        for nid, d in dets.items():
            if nid in silent:
                continue
            body = (bodies or {}).get(nid, {})
            d.beat(served=body.get("served", 0), work=body.get("work", 0))
        for d in dets.values():
            d.tick(clock())
        clock.advance(0.5)


class TestFailureDetector:
    def test_suspect_then_recover_counts_partition(self):
        clock = FakeClock()
        _, _, dets = mesh(clock, n=2)
        beat_rounds(clock, dets, 3)
        assert dets["n0"].views["n1"].state == "up"
        beat_rounds(clock, dets, 5, silent=("n1",))
        # 2-node mesh: observers of n1 = just n0, quorum 1 -> down...
        # unless n0 withholds? observers//2+1 = 1, so silence IS fatal
        assert dets["n0"].views["n1"].state == "down"
        assert dets["n0"].verdicts["down"] == 1

    def test_no_quorum_no_down_in_partial_partition(self):
        clock = FakeClock()
        hub, _, dets = mesh(clock, n=3)
        beat_rounds(clock, dets, 3)
        hub.partition("n0", "n1")
        beat_rounds(clock, dets, 8)
        # each split side suspects the other, the common neighbour
        # vouches (by not accusing): 1 accuser < quorum 2
        assert dets["n0"].views["n1"].state == "suspect"
        assert dets["n1"].views["n0"].state == "suspect"
        assert dets["n2"].views["n0"].state == "up"
        assert dets["n2"].views["n1"].state == "up"
        assert sum(d.verdicts["down"] for d in dets.values()) == 0
        # accusations piggybacked on beats reached the neighbour
        assert dets["n2"].views["n1"].accused_by == {"n0"}
        hub.heal_all()
        beat_rounds(clock, dets, 6)
        assert dets["n0"].views["n1"].state == "up"
        assert dets["n0"].views["n1"].partitions_observed == 1

    def test_gray_needs_no_quorum(self):
        """work advances, served stalls, beats keep flowing: GRAY off
        the member's own signed beats, no accusation round needed."""
        clock = FakeClock()
        _, _, dets = mesh(clock, n=3, gray_beats=4)
        ctr = {"n": 0}

        def round_(wedge):
            ctr["n"] += 8
            bodies = {nid: {"served": ctr["n"], "work": ctr["n"]}
                      for nid in dets}
            if wedge:
                bodies["n1"]["served"] = 32  # frozen after round 4
            beat_rounds(clock, dets, 1, bodies=bodies)

        for _ in range(4):
            round_(wedge=False)
        assert dets["n0"].views["n1"].state == "up"
        for _ in range(6):
            round_(wedge=True)
        assert dets["n0"].views["n1"].state == "gray"
        assert dets["n0"].probe("n1") is False
        assert dets["n0"].probe("n2") is True
        # the healthy members never flap
        assert dets["n0"].views["n2"].state == "up"

    def test_startup_grace_shields_never_beaten_peer(self):
        clock = FakeClock()
        ep = SimTransport(clock, seed=0).endpoint("solo")
        det = FailureDetector("solo", ep, clock=clock,
                              beat_interval_s=0.5, suspicion_threshold=3,
                              startup_grace_s=10.0, quorum=1)
        det.watch("spawning", now=clock())
        clock.advance(5.0)  # 10 missed beats, but inside the grace
        assert det.tick(clock()) == []
        assert det.views["spawning"].state == "up"
        clock.advance(6.0)  # grace expired, still never beaten
        assert det.tick(clock()) == [("spawning", "down")]

    def test_reset_wipes_history_and_rearms_grace(self):
        clock = FakeClock()
        ep = SimTransport(clock, seed=0).endpoint("solo")
        det = FailureDetector("solo", ep, clock=clock,
                              beat_interval_s=0.5, suspicion_threshold=3,
                              startup_grace_s=10.0, quorum=1)
        det.watch("m", now=clock())
        clock.advance(20.0)
        det.tick(clock())
        assert det.views["m"].state == "down"
        assert det.probe("m") is False
        det.reset("m", now=clock())
        assert det.views["m"].state == "up"
        assert det.probe("m") is True
        clock.advance(5.0)  # fresh grace window for the promoted slot
        assert det.tick(clock()) == []

    def test_status_deterministic_shape(self):
        clock = FakeClock()
        _, _, dets = mesh(clock, n=2)
        beat_rounds(clock, dets, 2)
        st = dets["n0"].status()
        assert st["node_id"] == "n0"
        assert st["beats_tx"] == 2 and st["beats_rx"] == 2
        assert set(st["peers"]) == {"n1"}
        assert json.dumps(st, sort_keys=True)  # JSON-serializable


class TestPlanHostAxis:
    def test_hosts_interleave_the_deal(self):
        from bng_tpu.cluster.plan import initial_plan

        plan = initial_plan(ip_to_u32("10.0.0.0"), 16, ["a", "b", "c"],
                            hosts={"a": "h1", "b": "h1", "c": "h2"})
        dealt = {i: [blk.index for blk in p.blocks]
                 for i, p in plan.members.items()}
        # round-robin across sorted host groups: h1(a,b) x h2(c)
        assert dealt == {"a": [0, 3], "b": [2], "c": [1]}
        assert plan.n_hosts == 2
        assert plan.hosts() == {"a": "h1", "b": "h1", "c": "h2"}

    def test_no_hosts_is_exactly_the_legacy_deal(self):
        from bng_tpu.cluster.plan import initial_plan

        legacy = initial_plan(ip_to_u32("10.0.0.0"), 16, ["a", "b", "c"])
        blank = initial_plan(ip_to_u32("10.0.0.0"), 16, ["a", "b", "c"],
                             hosts={"a": "", "b": "", "c": ""})
        assert {i: [blk.index for blk in p.blocks]
                for i, p in legacy.members.items()} \
            == {"a": [0, 3], "b": [1], "c": [2]} \
            == {i: [blk.index for blk in p.blocks]
                for i, p in blank.members.items()}
        assert legacy.n_hosts == 1

    def test_serialization_and_legacy_restore(self):
        from bng_tpu.cluster.plan import ClusterPlan, initial_plan

        plan = initial_plan(ip_to_u32("10.0.0.0"), 16, ["a", "b"],
                            hosts={"a": "h1", "b": "h2"})
        back = ClusterPlan.from_dict(plan.to_dict())
        assert back.hosts() == {"a": "h1", "b": "h2"}
        # a pre-host-axis checkpoint restores to the unplaced legacy
        d = plan.to_dict()
        for p in d["members"].values():
            p.pop("host")
        legacy = ClusterPlan.from_dict(d)
        assert legacy.hosts() == {"a": "", "b": ""}
        assert legacy.n_hosts == 1

    def test_replan_carries_hosts_and_survivors_pinned(self):
        from bng_tpu.cluster.plan import initial_plan, replan

        plan = initial_plan(ip_to_u32("10.0.0.0"), 16, ["a", "b"],
                            hosts={"a": "h1", "b": "h2"})
        before = {i: [blk.index for blk in p.blocks]
                  for i, p in plan.members.items()}
        # unchanged membership -> the SAME plan object (no new epoch)
        assert replan(plan, ["a", "b"]) is plan
        # a joiner on a new host deals from the free list only
        grown = replan(plan, ["a", "b", "c"], hosts={"c": "h3"})
        after = {i: [blk.index for blk in p.blocks]
                 for i, p in grown.members.items()}
        assert after["a"] == before["a"] and after["b"] == before["b"]
        assert grown.hosts() == {"a": "h1", "b": "h2", "c": "h3"}
        assert grown.n_hosts == 3


# ---------------------------------------------------------------------------
# RADIUS/CoA fan-out through the slow-path fleet
# ---------------------------------------------------------------------------

from bng_tpu.control.fleet import shard_for_mac  # noqa: E402
from bng_tpu.control.radius import packet as rp  # noqa: E402
from bng_tpu.control.radius.client import (RadiusClient,  # noqa: E402
                                           RadiusServerConfig)
from tests.test_fleet import (SERVER_IP, discover, dora,  # noqa: E402
                              make_pools, mac_of, reply_packet, request)
from tests.test_radius import SECRET, FakeRadiusServer  # noqa: E402


def make_radius_fleet(n=2, users=None):
    from bng_tpu.control.fleet import FleetSpec, SlowPathFleet

    pools = make_pools()
    spec = FleetSpec.from_pool_manager(
        bytes.fromhex("02aabbccdd01"), SERVER_IP, pools)
    spec.radius_servers = [RadiusServerConfig(
        "10.0.0.5", secret=SECRET, timeout_s=0.05, retries=1)]
    spec.radius_nas_id = "bng-test"
    from bng_tpu.control.fleet import SlowPathFleet as _F

    fleet = _F(spec, n, pools, mode="inline")
    users = users if users is not None else {
        "": {"password": "", "attrs": [(rp.FILTER_ID, "gold"),
                                       (rp.SESSION_TIMEOUT, 600)]}}
    servers = []
    for w in fleet._inline:
        assert w.radius is not None
        srv = FakeRadiusServer(users=users)
        w.radius.transport = srv
        servers.append(srv)
    return fleet, servers


class TestRadiusFanout:
    def test_auth_lands_on_mac_affine_worker(self):
        fleet, servers = make_radius_fleet(n=2)
        try:
            macs = [mac_of(i) for i in range(16)]
            leased = dora(fleet, macs)
            assert len(leased) == 16
            # every worker authenticated exactly its steered MACs —
            # auth affinity IS dhcp affinity (same FNV-1a32 hash)
            want = {0: 0, 1: 0}
            for m in macs:
                want[shard_for_mac(m, 2)] += 1
            assert {w: fleet._inline[w].auth_requests
                    for w in (0, 1)} == want
            assert all(want[w] > 0 for w in (0, 1))
            # the worker's own client socket served them (no parent)
            for w, srv in enumerate(servers):
                auths = [r for _, _, r in srv.requests
                         if r.code == rp.ACCESS_REQUEST]
                assert len(auths) == want[w]
            # Session-Timeout capped the lease via the profile
            lease = next(iter(fleet._inline[0].server.leases.values()))
            assert lease.qos_policy == "gold"
        finally:
            fleet.close()

    def test_reject_naks_and_degraded_cache_serves_outage(self):
        fleet, _ = make_radius_fleet(n=2)
        try:
            m = mac_of(3)
            w = shard_for_mac(m, 2)
            leased = dora(fleet, [m])
            assert len(leased) == 1
            # outage: every auth times out from here on
            fleet._inline[w].radius.transport = lambda *a: None
            # the known subscriber's lease expires; re-auth times out;
            # the worker-local degraded cache answers instead
            fleet._inline[w].server.leases.clear()
            fleet._inline[w].server._offers.clear()
            out = dora(fleet, [m], xid_base=500)
            assert len(out) == 1
            assert fleet._inline[w].auth_degraded == 1
            # a NEVER-seen subscriber has no cached profile: NAK
            m2 = next(mm for mm in (mac_of(100 + i) for i in range(32))
                      if shard_for_mac(mm, 2) == w)
            got = fleet.handle_batch([(0, discover(m2, 900))])
            offer = got[0][1]
            if offer is not None:  # OFFER precedes auth (auth on REQUEST)
                o = reply_packet(offer)
                got = fleet.handle_batch(
                    [(0, request(m2, o.yiaddr, SERVER_IP, 901))])
                from bng_tpu.control import dhcp_codec
                assert reply_packet(got[0][1]).msg_type == dhcp_codec.NAK
        finally:
            fleet.close()

    def test_coa_qos_on_owner_and_disconnect(self):
        from bng_tpu.control import dhcp_codec

        fleet, _ = make_radius_fleet(n=2)
        try:
            m = mac_of(5)
            leased = dora(fleet, [m])
            ip = leased[m]
            w = shard_for_mac(m, 2)
            r = fleet.handle_coa("qos", mac=m, policy_name="premium")
            assert r == {"found": True, "ip": ip, "worker": w,
                         "relayed": False}
            assert fleet.coa_handled == 1 and fleet.coa_relayed == 0
            import bng_tpu.utils.net as _net
            lease = next(iter(fleet._inline[w].server.leases.values()))
            assert lease.qos_policy == "premium"
            # disconnect force-expires; the next REQUEST is a fresh DORA
            r = fleet.handle_coa("disconnect", ip=ip)
            assert r["found"] and r["worker"] == w
            assert fleet._inline[w].server.leases == {}
            # unknown target: counted miss
            r = fleet.handle_coa("locate", ip=ip_to_u32("10.9.9.9"))
            assert not r["found"] and fleet.coa_misses == 1
        finally:
            fleet.close()

    def test_coa_relay_counted_when_lease_off_steer(self):
        fleet, _ = make_radius_fleet(n=2)
        try:
            m = mac_of(7)
            leased = dora(fleet, [m])
            w = shard_for_mac(m, 2)
            other = 1 - w
            # the lease moved off its steered shard (a resize shape):
            # the steered probe misses, the scan finds it, relay counted
            from bng_tpu.utils.net import mac_to_u64
            lease = fleet._inline[w].server.leases.pop(mac_to_u64(m))
            fleet._inline[other].server.leases[mac_to_u64(m)] = lease
            r = fleet.handle_coa("locate", mac=m)
            assert r == {"found": True, "ip": leased[m], "worker": other,
                         "relayed": True}
            assert fleet.coa_relayed == 1
        finally:
            fleet.close()

    def test_worker_stats_carry_radius_lane(self):
        fleet, _ = make_radius_fleet(n=2)
        try:
            dora(fleet, [mac_of(i) for i in range(8)])
            fleet.handle_coa("locate", mac=mac_of(0))
            snap = fleet.stats_snapshot()
            assert snap["coa_handled"] == 1
            per = [w for w in snap["per_worker"] if w]
            assert sum(w["auth_requests"] for w in per) == 8
            assert all("radius" in w and w["radius"]["auth_ok"] >= 0
                       for w in per)
        finally:
            fleet.close()


class TestAccountingSpoolFailover:
    def test_promoted_standby_replays_spool_once(self, tmp_path):
        """The active's RADIUS dies mid-session; its stop spools. The
        active then dies; the promoted standby recovers the spool and
        replays it — each record lands exactly once, octets never
        double-count."""
        from bng_tpu.control.radius.accounting import AccountingManager

        spool = str(tmp_path / "acct.spool")
        clock = FakeClock()
        live = FakeRadiusServer()
        client = RadiusClient(
            [RadiusServerConfig("10.0.0.5", secret=SECRET,
                                timeout_s=0.05, retries=1)],
            transport=live, clock=clock)
        active = AccountingManager(client, interim_interval_s=60,
                                   spool_path=spool, clock=clock)
        assert active.start("s1", "alice", ip_to_u32("10.0.0.9"))
        active.update_counters("s1", 1111, 2222)
        clock.advance(61.0)
        assert active.interim_tick(clock()) == 1
        active.update_counters("s1", 5555, 7777)
        # the RADIUS server goes dark: the stop spools instead of sending
        client.transport = lambda *a: None
        assert active.stop("s1") is False
        assert len(active.pending) == 1
        # ACTIVE DIES here (no more ticks). The standby promotes with
        # the same spool path and a healthy server:
        client2 = RadiusClient(
            [RadiusServerConfig("10.0.0.5", secret=SECRET,
                                timeout_s=0.05, retries=1)],
            transport=live, clock=clock)
        standby = AccountingManager(client2, interim_interval_s=60,
                                    spool_path=spool, clock=clock)
        assert standby.retry_tick() == 1
        assert standby.retry_tick() == 0  # nothing left to replay
        stops = [r for _, _, r in live.requests
                 if r.code == rp.ACCOUNTING_REQUEST
                 and r.get_int(rp.ACCT_STATUS_TYPE) == rp.ACCT_STOP]
        assert len(stops) == 1
        assert stops[0].get_int(rp.ACCT_INPUT_OCTETS) == 5555
        assert stops[0].get_int(rp.ACCT_OUTPUT_OCTETS) == 7777

    def test_orphaned_session_closed_with_lost_carrier(self, tmp_path):
        from bng_tpu.control.radius.accounting import AccountingManager

        spool = str(tmp_path / "acct.spool")
        clock = FakeClock()
        live = FakeRadiusServer()

        def client():
            return RadiusClient(
                [RadiusServerConfig("10.0.0.5", secret=SECRET,
                                    timeout_s=0.05, retries=1)],
                transport=live, clock=clock)

        active = AccountingManager(client(), spool_path=spool, clock=clock)
        active.start("s2", "bob", ip_to_u32("10.0.0.10"))
        # crash with the session open: the standby must close it out
        standby = AccountingManager(client(), spool_path=spool, clock=clock)
        assert standby.retry_tick() == 1
        stops = [r for _, _, r in live.requests
                 if r.code == rp.ACCOUNTING_REQUEST
                 and r.get_int(rp.ACCT_STATUS_TYPE) == rp.ACCT_STOP]
        assert len(stops) == 1
        assert stops[0].get_int(rp.ACCT_TERMINATE_CAUSE) \
            == rp.TERM_LOST_CARRIER


class TestResilienceProbeWallTime:
    def test_stalling_probe_credits_elapsed_ticks(self):
        """A radius probe that blocks for multiple check intervals
        (socket timeout against a black-holed server) must credit the
        burned wall-time, or detection takes threshold * stall."""
        from bng_tpu.control.resilience import ResilienceManager

        wall = FakeClock(0.0)

        def stalling_resolver():
            wall.advance(12.0)  # each probe eats 12s of wall-time
            return False

        mgr = ResilienceManager(
            nexus_healthy=lambda: True,
            radius_healthy=stalling_resolver,
            check_interval_s=5.0, failure_threshold=3,
            probe_clock=wall)
        mgr.tick(10.0)
        # one stalled probe = 1 + 12//5 = 3 ticks >= threshold: down NOW
        assert mgr.radius_down is True
        assert mgr.degraded_auth_active

    def test_fast_probe_still_needs_threshold_ticks(self):
        from bng_tpu.control.resilience import ResilienceManager

        wall = FakeClock(0.0)
        mgr = ResilienceManager(
            nexus_healthy=lambda: True,
            radius_healthy=lambda: False,
            check_interval_s=5.0, failure_threshold=3,
            probe_clock=wall)
        mgr.tick(10.0)
        mgr.tick(20.0)
        assert mgr.radius_down is False
        mgr.tick(30.0)
        assert mgr.radius_down is True

    def test_recovery_resets_the_count(self):
        from bng_tpu.control.resilience import ResilienceManager

        wall = FakeClock(0.0)
        healthy = {"v": False}
        mgr = ResilienceManager(
            nexus_healthy=lambda: True,
            radius_healthy=lambda: healthy["v"],
            check_interval_s=5.0, failure_threshold=3,
            probe_clock=wall)
        mgr.tick(10.0)
        mgr.tick(20.0)
        healthy["v"] = True
        mgr.tick(30.0)
        assert mgr._radius_fails == 0 and not mgr.radius_down


class TestFabricMetrics:
    def _status(self, state="up", accusers=()):
        return {"node_id": "coordinator", "beats_tx": 10, "beats_rx": 20,
                "verdicts": {"suspect": 1, "gray": 0, "down": 2},
                "partitions_observed": 3,
                "peers": {"bng-a": {"state": state, "beats_rx": 20,
                                    "stalled_beats": 0,
                                    "accused_by": list(accusers),
                                    "served": 5, "work": 5}},
                "transport": {"tx": 10, "rx": 20, "rx_bad_sig": 1,
                              "rx_replay": 2, "rx_skew": 0,
                              "rx_malformed": 4}}

    def test_collect_fabric_families(self):
        from bng_tpu.control.metrics import BNGMetrics

        m = BNGMetrics()
        m.collect_fabric(self._status(state="gray",
                                      accusers=("coordinator",)))
        assert m.fabric_beats_tx.value() == 10
        assert m.fabric_beats_rx.value() == 20
        assert m.fabric_verdicts.value(verdict="down") == 2
        assert m.fabric_partitions.value() == 3
        assert m.fabric_member_state.value(member="bng-a", state="gray") == 1
        assert m.fabric_member_state.value(member="bng-a", state="up") == 0
        assert m.fabric_member_suspicion.value(member="bng-a") == 1
        assert m.fabric_rx_rejected.value(reason="bad_sig") == 1
        assert m.fabric_rx_rejected.value(reason="malformed") == 4

    def test_departed_member_labels_drop(self):
        from bng_tpu.control.metrics import BNGMetrics

        m = BNGMetrics()
        m.collect_fabric(self._status())
        gone = self._status()
        gone["peers"] = {}
        m.collect_fabric(gone)
        assert m.fabric_member_suspicion.labeled() == []
        assert m.fabric_member_state.labeled() == []

    def test_record_cluster_routes_fabric_block(self):
        from bng_tpu.control.metrics import BNGMetrics

        m = BNGMetrics()
        m.record_cluster({"members": {}, "recarves": 0, "failovers": 0,
                          "shed_frames": 0, "refused_removes": 0,
                          "fabric": self._status()})
        assert m.fabric_beats_rx.value() == 20

    def test_fleet_scrape_carries_fanout_counters(self):
        from bng_tpu.control.metrics import BNGMetrics

        fleet, _ = make_radius_fleet(n=2)
        try:
            dora(fleet, [mac_of(i) for i in range(8)])
            fleet.handle_coa("locate", mac=mac_of(1))
            m = BNGMetrics()
            m.collect_fleet(fleet)
            per = {w: fleet._inline[w].auth_requests for w in (0, 1)}
            for w, n in per.items():
                assert m.fabric_auth_shard.value(worker=str(w)) == n
            assert m.fabric_coa_relayed.value() == 0
        finally:
            fleet.close()


class TestFabricChaosScenarios:
    def test_partial_partition_ok_and_deterministic(self):
        from bng_tpu.chaos.scenarios import cluster_partial_partition

        a = cluster_partial_partition(7)
        assert a["ok"], a
        assert a["down_verdicts"] == 0 and a["failovers"] == 0
        b = cluster_partial_partition(7)
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)

    def test_gray_member_ok_and_deterministic(self):
        from bng_tpu.chaos.scenarios import cluster_gray_member

        a = cluster_gray_member(5)
        assert a["ok"], a
        assert a["failovers"] == 1 and a["gray_verdicts"] >= 1
        b = cluster_gray_member(5)
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)

    def test_scenarios_registered(self):
        from bng_tpu.chaos.scenarios import SCENARIOS

        assert "cluster_partial_partition" in SCENARIOS
        assert "cluster_gray_member" in SCENARIOS


@pytest.mark.slow
class TestProcessFabric:
    def test_udp_beats_and_sigkill_failover(self):
        """The ISSUE 19 acceptance shape: a process-mode cluster whose
        members beat over the UDP fabric; SIGKILL one member and the
        fabric detector (not a pipe flag) drives exactly one failover,
        after which the promoted slot's beats resume."""
        import os
        import signal
        import time

        from bng_tpu.cluster.coordinator import ClusterCoordinator

        coord = ClusterCoordinator(
            mode="process", fabric=True, n_workers=1,
            fabric_beat_interval_s=0.1, fabric_suspicion_threshold=3,
            ha_probe_interval_s=0.1, ha_failover_delay_s=0.2,
            ha_failure_threshold=2)
        try:
            coord.add_instances(["bng-a", "bng-b"])
            deadline = time.time() + 60
            while time.time() < deadline:
                coord.tick()
                st = coord.fabric_detector.status()
                if st["peers"] and all(p["beats_rx"] >= 3
                                       for p in st["peers"].values()):
                    break
                time.sleep(0.05)
            peers = coord.fabric_detector.status()["peers"]
            assert all(v["beats_rx"] >= 3 for v in peers.values()), peers

            os.kill(coord.members["bng-a"].instance.pid, signal.SIGKILL)
            deadline = time.time() + 60
            while time.time() < deadline and coord.failovers == 0:
                coord.tick()
                time.sleep(0.05)
            assert coord.failovers == 1
            assert ("bng-a", "down") in coord.fabric_events
            assert coord.members["bng-a"].role == "promoted"

            # the promoted slot's fresh process beats again (the replay
            # floor was reset, or its seq=1 beats would all drop)
            deadline = time.time() + 60
            ok = False
            while time.time() < deadline:
                coord.tick()
                v = coord.fabric_detector.views["bng-a"]
                if v.beats_rx >= 2 and v.state == "up":
                    ok = True
                    break
                time.sleep(0.05)
            assert ok, coord.fabric_detector.status()
        finally:
            coord.close()
