"""Cluster-of-BNGs (bng_tpu/cluster): carve-plan law, coordinator
lifecycle, HA failover, the `_audit_cluster` planted-violation proofs,
checkpoint interop for the carve plan, the chaos scenario + 4M storm
determinism, and the dormant-L4 modules the cluster now leans on
(nexus watch, peerpool carve/return, resilience probes).

`make verify-cluster` runs this file (`cluster` marker, <60s); the
tier-1 Makefile line deselects the marker so the suite runs once."""

import copy
import json

import pytest

from bng_tpu.chaos.faults import SimClock
from bng_tpu.chaos.invariants import audit_invariants
from bng_tpu.chaos.scenarios import (_mac, _renew, _reply,
                                     dora_with_retries)
from bng_tpu.cluster import (ClusterCoordinator, ClusterPlan,
                             InlineInstance, InstanceSpec, elect_carver,
                             initial_plan, instance_for_mac, replan,
                             steer_macs_u48)
from bng_tpu.control import dhcp_codec
from bng_tpu.utils.net import fnv1a32, ip_to_u32

pytestmark = pytest.mark.cluster

SPACE = ip_to_u32("10.64.0.0")


def _coord(**kw):
    kw.setdefault("clock", SimClock())
    kw.setdefault("space_network", SPACE)
    kw.setdefault("space_prefix_len", 16)
    kw.setdefault("sub_nbuckets", 0)
    kw.setdefault("slice_size", 64)
    return ClusterCoordinator(**kw)


# ---------------------------------------------------------------------------
# the carve plan law
# ---------------------------------------------------------------------------

class TestPlan:
    def test_initial_carve_partitions_the_space(self):
        plan = initial_plan(SPACE, 16, ["c", "a", "b", "d"])
        assert plan.epoch == 1 and not plan.free
        seen = set()
        for p in plan.members.values():
            for b in p.blocks:
                assert b.network not in seen
                seen.add(b.network)
        assert plan.total_addresses() == 1 << 16
        # round-robin over SORTED ids: every carver computes the same
        assert [len(plan.members[i].blocks) for i in plan.member_ids()] \
            == [1, 1, 1, 1]

    def test_small_cluster_keeps_free_growth_blocks(self):
        plan = initial_plan(SPACE, 16, ["a", "b"])
        # minimum 4 blocks: 2 members x 2 blocks, none free but blocks
        # stay whole-power-of-two so a leaver's return is dealable
        assert plan.n_blocks == 4
        assert all(len(p.blocks) == 2 for p in plan.members.values())

    def test_replan_never_moves_a_survivor_block(self):
        plan = initial_plan(SPACE, 16, ["a", "b", "c", "d"])
        before = {i: list(p.blocks) for i, p in plan.members.items()}
        plan2 = replan(plan, ["a", "b", "c"])
        for iid in ("a", "b", "c"):
            assert plan2.members[iid].blocks == before[iid]
        assert plan2.epoch == plan.epoch + 1
        assert [b.index for b in plan2.free] \
            == sorted(b.index for b in before["d"])

    def test_replan_deals_free_blocks_only_to_empty_joiners(self):
        plan = initial_plan(SPACE, 16, ["a", "b", "c", "d"])
        plan = replan(plan, ["a", "b", "c"])          # d leaves -> free
        plan2 = replan(plan, ["a", "b", "c", "x"])    # x joins
        assert plan2.members["x"].blocks  # joiner built from the free list
        assert not plan2.free
        # serving members kept exactly their carve
        for iid in ("a", "b", "c"):
            assert plan2.members[iid].blocks == plan.members[iid].blocks

    def test_joiner_without_free_blocks_stays_pending(self):
        plan = initial_plan(SPACE, 16, ["a", "b", "c", "d"])
        plan2 = replan(plan, ["a", "b", "c", "d", "e"])
        assert not plan2.members["e"].blocks
        assert "e" not in plan2.serving_ids()
        assert "e" in plan2.member_ids()

    def test_replan_unchanged_membership_is_the_same_object(self):
        plan = initial_plan(SPACE, 16, ["a", "b"])
        assert replan(plan, ["b", "a"]) is plan

    def test_roundtrip_and_nat_slices(self):
        plan = initial_plan(SPACE, 16, ["a", "b"],
                            nat_base=ip_to_u32("100.64.0.0"),
                            nat_total=1024)
        plan2 = ClusterPlan.from_dict(
            json.loads(json.dumps(plan.to_dict())))
        assert plan2.to_dict() == plan.to_dict()
        per = 1024 // plan.n_blocks
        starts = set()
        for p in plan.members.values():
            for b in p.blocks:
                start, count = plan.nat_range(b)
                assert count == per and start not in starts
                starts.add(start)

    def test_steering_vector_matches_scalar_bit_exact(self):
        import numpy as np

        ids = ("a", "b", "c", "d", "e")
        u48 = (np.uint64(0x02C5) << np.uint64(32)) + np.arange(
            4096, dtype=np.uint64) * np.uint64(2654435761)
        idx = steer_macs_u48(u48 & np.uint64((1 << 48) - 1), len(ids))
        for j in range(0, 4096, 37):
            mac = int(u48[j]) & ((1 << 48) - 1)
            mb = mac.to_bytes(6, "big")
            assert ids[int(idx[j])] == instance_for_mac(mb, ids)
            assert int(idx[j]) == fnv1a32(mb) % len(ids)

    def test_elect_carver_is_lowest_sorted(self):
        assert elect_carver(["b", "a", "c"]) == "a"
        assert elect_carver([]) is None

    def test_space_too_small_raises(self):
        with pytest.raises(ValueError):
            initial_plan(SPACE, 29, ["a", "b", "c", "d", "e", "f", "g",
                                     "h", "i"])


# ---------------------------------------------------------------------------
# coordinator lifecycle
# ---------------------------------------------------------------------------

class TestCoordinator:
    def test_founding_carve_and_dora_through_front_door(self):
        clock = SimClock()
        coord = _coord(clock=clock)
        try:
            coord.add_instances(["bng-a", "bng-b", "bng-c"])
            assert coord.plan.epoch == 1
            macs = [_mac(100 + i) for i in range(30)]
            leased = dora_with_retries(coord, macs, clock)
            assert len(leased) == 30
            assert len(set(leased.values())) == 30
            # every lease landed inside its serving member's carve
            for m, ip in leased.items():
                owner = instance_for_mac(m, coord.member_ids())
                assert coord.plan.owner_of(ip) == owner
            st = coord.status()
            assert st["instances"] == 3
            assert sum(e["leases"] for e in st["members"].values()) == 30
        finally:
            coord.close()

    def test_remove_with_live_book_refused_then_forced(self):
        clock = SimClock()
        coord = _coord(clock=clock)
        try:
            coord.add_instances(["bng-a", "bng-b"])
            leased = dora_with_retries(
                coord, [_mac(200 + i) for i in range(12)], clock)
            assert leased
            victim = coord.member_ids()[0]
            assert coord.remove_instance(victim) is False
            assert coord.refused_removes == 1
            assert victim in coord.member_ids()
            assert coord.remove_instance(victim, force=True) is True
            assert victim not in coord.plan.member_ids()
        finally:
            coord.close()

    def test_elastic_join_builds_from_freed_blocks(self):
        clock = SimClock()
        coord = _coord(clock=clock)
        try:
            coord.add_instances(["bng-a", "bng-b", "bng-c", "bng-d"])
            # a drained member leaves cleanly; its blocks hit the free
            # list and the next joiner builds from them
            gone = coord.member_ids()[-1]
            assert coord.remove_instance(gone) is True
            assert coord.plan.free
            coord.add_instance("bng-x")
            m = coord.members["bng-x"]
            assert not m.pending and m.instance is not None
            leased = dora_with_retries(
                coord, [_mac(300 + i) for i in range(40)], clock)
            assert len(leased) == 40
            audit = audit_invariants(bng_cluster=coord)
            assert audit.ok, audit.violations_by_kind()
        finally:
            coord.close()

    def test_checkpoint_roundtrip_restores_the_carve(self):
        from bng_tpu.runtime.checkpoint import (build_checkpoint,
                                                decode_checkpoint,
                                                encode_checkpoint,
                                                restore_checkpoint)

        coord = _coord()
        try:
            coord.add_instances(["bng-a", "bng-b", "bng-c"])
            want = coord.checkpoint_plan()
            ck = decode_checkpoint(encode_checkpoint(
                build_checkpoint(7, 100.0, cluster_plan=coord)))
            coord2 = _coord()
            try:
                rows = restore_checkpoint(ck, cluster_coord=coord2)
                assert rows["cluster_plan.members"] == 3
                assert coord2.checkpoint_plan() == want
                # restored members are pending until their processes
                # register; a member that joins with its old id adopts
                # its carve instead of re-carving
                coord2.add_instances(["bng-a", "bng-b", "bng-c"])
                assert coord2.plan.epoch == want["epoch"]
                assert not any(m.pending
                               for m in coord2.members.values())
            finally:
                coord2.close()
        finally:
            coord.close()

    def test_corrupt_carve_plan_refuses_restore(self):
        from bng_tpu.runtime.checkpoint import (CheckpointError,
                                                build_checkpoint,
                                                decode_checkpoint,
                                                encode_checkpoint,
                                                restore_checkpoint)

        coord = _coord()
        try:
            coord.add_instances(["bng-a", "bng-b"])
            ck = decode_checkpoint(encode_checkpoint(
                build_checkpoint(7, 100.0, cluster_plan=coord)))
            ck.meta["components"]["cluster_plan"]["members"] = "garbage"
            coord2 = _coord()
            try:
                with pytest.raises(CheckpointError, match="cluster_plan"):
                    restore_checkpoint(ck, cluster_coord=coord2)
                # all-or-nothing: the refused restore touched nothing
                assert coord2.plan is None
            finally:
                coord2.close()
        finally:
            coord.close()

    def test_process_mode_smoke(self):
        clock = SimClock()
        coord = _coord(clock=clock, mode="process")
        try:
            coord.add_instances(["bng-a", "bng-b"])
            leased = dora_with_retries(
                coord, [_mac(400 + i) for i in range(8)], clock)
            assert len(leased) == 8
            st = coord.status()
            assert sum(e["leases"] for e in st["members"].values()) == 8
        finally:
            coord.close()


# ---------------------------------------------------------------------------
# HA failover
# ---------------------------------------------------------------------------

class TestFailover:
    def test_kill_promotes_standby_and_renewals_stick(self):
        clock = SimClock()
        coord = _coord(clock=clock)
        try:
            coord.add_instances(["bng-a", "bng-b", "bng-c"])
            macs = [_mac(500 + i) for i in range(24)]
            leased = dora_with_retries(coord, macs, clock)
            victim = coord.member_ids()[1]
            vmacs = [m for m in macs
                     if instance_for_mac(m, coord.member_ids()) == victim]
            assert vmacs
            coord.kill_instance(victim)
            # outage: the dead member's subscribers shed, others serve
            out = coord.handle_batch(
                [(i, _renew(m, leased[m], 0x6000 + i))
                 for i, m in enumerate(macs)], now=clock())
            shed = [m for (_l, rep), m in zip(out, macs) if rep is None]
            assert sorted(shed) == sorted(vmacs)
            assert coord.shed_frames == len(vmacs)

            for _ in range(16):
                if coord.members[victim].role == "promoted":
                    break
                clock.advance(1.0)
                coord.tick()
            assert coord.members[victim].role == "promoted"
            assert coord.failovers == 1

            # stickiness: renewals ACK with the ORIGINAL addresses
            out = coord.handle_batch(
                [(i, _renew(m, leased[m], 0x7000 + i))
                 for i, m in enumerate(vmacs)], now=clock())
            for (_l, rep), m in zip(out, vmacs):
                p = _reply(rep)
                assert p.msg_type == dhcp_codec.ACK
                assert p.yiaddr == leased[m]
            audit = audit_invariants(bng_cluster=coord)
            assert audit.ok, audit.violations_by_kind()
        finally:
            coord.close()


# ---------------------------------------------------------------------------
# _audit_cluster: planted violations
# ---------------------------------------------------------------------------

def _leased_cluster(clock, n=24):
    coord = _coord(clock=clock)
    coord.add_instances(["bng-a", "bng-b", "bng-c"])
    leased = dora_with_retries(
        coord, [_mac(700 + i) for i in range(n)], clock)
    assert len(leased) == n
    return coord, leased


def _books(coord, iid):
    return coord.members[iid].instance.fleet._inline


class TestAuditCluster:
    def test_clean_cluster_audits_clean(self):
        clock = SimClock()
        coord, _ = _leased_cluster(clock)
        try:
            rep = audit_invariants(bng_cluster=coord)
            assert rep.ok
            assert rep.checks["cluster_members"] == 3
            assert rep.checks["cluster_leases"] == 24
        finally:
            coord.close()

    def test_no_plan_is_a_finding(self):
        coord = _coord()
        try:
            # an empty coordinator is vacuously clean...
            assert audit_invariants(bng_cluster=coord).ok
            # ...but members with a LOST plan document are a finding
            coord.add_instances(["a", "b"])
            coord.plan = None
            rep = audit_invariants(bng_cluster=coord)
            assert not rep.ok
            assert "cluster-no-plan" in rep.violations_by_kind()
        finally:
            coord.close()

    def test_planted_foreign_ip_detected(self):
        clock = SimClock()
        coord, _ = _leased_cluster(clock)
        try:
            iid = coord.member_ids()[0]
            w = _books(coord, iid)[0]
            k, lease = next(iter(w.server.leases.items()))
            # point the lease at an address OUTSIDE the owner's carve
            other = coord.plan.members[coord.member_ids()[1]].blocks[0]
            lease.ip = other.network + 7
            rep = audit_invariants(bng_cluster=coord)
            assert not rep.ok
            assert rep.violations_by_kind().get("cluster-foreign-ip")
        finally:
            coord.close()

    def test_planted_double_ownership_detected(self):
        clock = SimClock()
        coord, _ = _leased_cluster(clock)
        try:
            a, b = coord.member_ids()[0], coord.member_ids()[1]
            wa = _books(coord, a)[0]
            k, lease = next(iter(wa.server.leases.items()))
            # the DESTINI clause one level up: the same (mac, ip) lease
            # surfacing in TWO instances' books
            _books(coord, b)[0].server.leases[k] = copy.copy(lease)
            rep = audit_invariants(bng_cluster=coord)
            assert not rep.ok
            kinds = rep.violations_by_kind()
            assert kinds.get("cluster-double-ownership")
        finally:
            coord.close()

    def test_planted_missteer_detected(self):
        clock = SimClock()
        coord, _ = _leased_cluster(clock)
        try:
            # move one lease's book entry to a member the steering
            # function would never pick for that MAC
            src = None
            for iid in coord.member_ids():
                w = _books(coord, iid)[0]
                if w.server.leases:
                    src, (k, lease) = iid, next(
                        iter(w.server.leases.items()))
                    break
            wrong = next(i for i in coord.member_ids()
                         if i != instance_for_mac(lease.mac,
                                                  coord.member_ids()))
            if wrong != src:
                del _books(coord, src)[0].server.leases[k]
                # keep it inside `wrong`'s carve so only the steering
                # check fires, not the carve one
                lease.ip = coord.plan.members[wrong].blocks[0].network + 9
                _books(coord, wrong)[0].server.leases[k] = lease
            rep = audit_invariants(bng_cluster=coord)
            assert not rep.ok
            assert rep.violations_by_kind().get("cluster-missteer")
        finally:
            coord.close()


# ---------------------------------------------------------------------------
# the chaos scenario + the 4M storm (reduced scale; full scale runs in
# the `bng chaos run` determinism gate)
# ---------------------------------------------------------------------------

class TestChaosIntegration:
    def test_failover_scenario_ok_and_deterministic(self):
        from bng_tpu.chaos.scenarios import cluster_failover_redora

        a = cluster_failover_redora(3)
        b = cluster_failover_redora(3)
        assert a["ok"], a
        assert json.dumps(a, sort_keys=True) == json.dumps(b,
                                                           sort_keys=True)

    def test_scale_storm_ok_and_deterministic(self):
        from bng_tpu.chaos.storms import cluster_scale_storm

        a = cluster_scale_storm(3, scale=0.01)
        b = cluster_scale_storm(3, scale=0.01)
        assert a["ok"], a
        assert a["instances"] >= 4
        assert set(a["slo"]) == set(a["leased"])
        assert all(v["ok"] for v in a["slo"].values())
        assert json.dumps(a, sort_keys=True) == json.dumps(b,
                                                           sort_keys=True)

    def test_storm_registered_in_runner_catalog(self):
        from bng_tpu.chaos.runner import scenario_catalog

        names = {n for n, _d in scenario_catalog()}
        assert "cluster_failover_redora" in names
        assert "cluster_scale_storm" in names


# ---------------------------------------------------------------------------
# instance spec / carve application edges
# ---------------------------------------------------------------------------

class TestInstance:
    def test_empty_carve_refused(self):
        with pytest.raises(ValueError):
            InlineInstance(InstanceSpec(
                instance_id="x", server_mac=b"\x02" * 6,
                server_ip=ip_to_u32("10.0.0.1"), blocks=[]),
                SimClock())

    def test_shrinking_an_undrained_block_refused(self):
        clock = SimClock()
        coord = _coord(clock=clock)
        try:
            coord.add_instances(["bng-a", "bng-b"])
            leased = dora_with_retries(
                coord, [_mac(800 + i) for i in range(10)], clock)
            assert leased
            iid = next(i for i in coord.member_ids()
                       if coord.members[i].instance.lease_count())
            inst = coord.members[iid].instance
            smaller = copy.deepcopy(coord.plan.members[iid])
            smaller.blocks = []
            before = list(inst.spec.blocks)
            # half-drained shrink is refused; the instance keeps serving
            # the OLD carve untouched
            assert inst.apply_plan(smaller) is False
            assert inst.spec.blocks == before
            assert inst.lease_count() > 0
        finally:
            coord.close()


# ---------------------------------------------------------------------------
# dormant L4 modules the cluster leans on
# ---------------------------------------------------------------------------

class TestMemoryStoreWatch:
    def test_notify_order_and_prefix_filter(self):
        from bng_tpu.control.nexus import MemoryStore

        store = MemoryStore()
        calls = []
        store.watch("a/", lambda k, v: calls.append(("first", k, v)))
        store.watch("a/", lambda k, v: calls.append(("second", k, v)))
        store.watch("b/", lambda k, v: calls.append(("other", k, v)))
        store.put("a/x", b"1")
        # registration order, prefix-filtered
        assert calls == [("first", "a/x", b"1"), ("second", "a/x", b"1")]
        calls.clear()
        store.delete("a/x")
        assert calls == [("first", "a/x", None), ("second", "a/x", None)]

    def test_cancel_is_idempotent_and_scoped(self):
        from bng_tpu.control.nexus import MemoryStore

        store = MemoryStore()
        got1, got2 = [], []
        cancel1 = store.watch("k/", lambda k, v: got1.append(k))
        store.watch("k/", lambda k, v: got2.append(k))
        store.put("k/1", b"x")
        cancel1()
        cancel1()  # idempotent: second cancel must not unhook others
        store.put("k/2", b"y")
        assert got1 == ["k/1"]
        assert got2 == ["k/1", "k/2"]

    def test_unsubscribe_during_notify_is_safe(self):
        from bng_tpu.control.nexus import MemoryStore

        store = MemoryStore()
        seen = []
        cancels = {}

        def once(key, value):
            seen.append(key)
            cancels["self"]()

        cancels["self"] = store.watch("", once)
        store.watch("", lambda k, v: seen.append("tail:" + k))
        store.put("p", b"1")  # cancel mid-notify: the tail still fires
        store.put("q", b"2")
        assert seen == ["p", "tail:p", "tail:q"]

    def test_typed_store_watch_cancel(self):
        from bng_tpu.control.nexus import (MemoryStore, SubscriberEntity,
                                           TypedStore)

        subs = TypedStore(MemoryStore(), "subscribers", SubscriberEntity)
        got = []
        cancel = subs.watch(lambda id_, obj: got.append((id_, obj)))
        subs.put("s1", SubscriberEntity(id="s1", mac="02aa"))
        cancel()
        subs.put("s2", SubscriberEntity(id="s2"))
        assert len(got) == 1
        assert got[0][0] == "s1" and got[0][1].mac == "02aa"


class TestPeerPoolEdges:
    def _pool(self):
        from bng_tpu.control.peerpool import PeerPool, PoolRange

        return PeerPool("n1", ["n1"], PoolRange(ip_to_u32("10.9.0.0"), 8))

    def test_allocate_is_idempotent_per_subscriber(self):
        p = self._pool()
        ip = p.allocate("sub-1")
        assert p.allocate("sub-1") == ip
        assert p.stats["local_allocs"] == 1

    def test_release_returns_the_address_for_reuse(self):
        from bng_tpu.control.peerpool import PeerPoolError

        p = self._pool()
        ips = {p.allocate(f"s{i}") for i in range(8)}
        assert len(ips) == 8
        with pytest.raises(PeerPoolError):
            p.allocate("overflow")
        assert p.release("s3") is True
        assert p.release("s3") is False  # double return: counted once
        assert p.allocate("late") in ips  # the freed address reused

    def test_release_unknown_subscriber_is_false(self):
        p = self._pool()
        assert p.release("ghost") is False


class TestResilienceProbes:
    def test_probe_interval_gates_the_checks(self):
        from bng_tpu.control.resilience import ResilienceManager

        probes = []

        def nexus_ok():
            probes.append(1)
            return True

        mgr = ResilienceManager(nexus_ok, check_interval_s=5.0)
        mgr.tick(10.0)
        mgr.tick(11.0)  # within the interval: probe NOT re-fired
        mgr.tick(14.9)
        assert len(probes) == 1
        mgr.tick(15.0)
        assert len(probes) == 2

    def test_raising_probe_folds_to_unhealthy_and_partitions(self):
        from bng_tpu.control.resilience import (PartitionState,
                                                ResilienceManager)

        def bad_probe():
            raise ConnectionError("nexus gone")

        mgr = ResilienceManager(bad_probe, check_interval_s=1.0,
                                failure_threshold=3)
        t = 0.0
        for _ in range(2):
            t += 1.0
            assert mgr.tick(t) == PartitionState.NORMAL
        t += 1.0
        assert mgr.tick(t) == PartitionState.PARTITIONED

    def test_recovery_after_partition(self):
        from bng_tpu.control.resilience import (PartitionState,
                                                ResilienceManager)

        healthy = {"ok": False}
        mgr = ResilienceManager(lambda: healthy["ok"],
                                check_interval_s=1.0,
                                failure_threshold=2)
        assert mgr.tick(1.0) == PartitionState.NORMAL
        assert mgr.tick(2.0) == PartitionState.PARTITIONED
        healthy["ok"] = True
        state = mgr.tick(3.0)
        assert state in (PartitionState.RECOVERING, PartitionState.NORMAL)
        assert mgr.tick(4.0) == PartitionState.NORMAL


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

class TestClusterMetrics:
    def test_record_cluster_families_and_reconciliation(self):
        from bng_tpu.control.metrics import BNGMetrics

        coord = _coord()
        try:
            coord.add_instances(["a", "b"])
            m = BNGMetrics()
            m.record_cluster(coord.status())
            assert m.cluster_instances.value(state="up") == 2
            assert m.cluster_plan_epoch.value() == 1
            assert m.cluster_addresses.value(instance="a") > 0
            coord.remove_instance("b")
            m.record_cluster(coord.status())
            # the departed member's gauge labels DROP (no stale rows)
            labels = {d["instance"]
                      for d in m.cluster_addresses.labeled()}
            assert labels == {"a"}
            assert m.cluster_recarves.value() == 2
        finally:
            coord.close()

    def test_fleet_blocked_gauge_clears_removed_blockers(self):
        from bng_tpu.control.metrics import BNGMetrics

        m = BNGMetrics()
        # the full blocker vocabulary after ISSUE 20 shrank it again:
        # nexus joined radius and peer-pool off the list (each shard
        # allocates against the shared store through its own
        # HTTPAllocator), so a config reload from the old set to the
        # new one must DROP the retired labels
        m.record_fleet_blocked(["nexus", "radius", "peer-pool"])
        assert m.slowpath_fleet_blocked.value(blocker="radius") == 1
        m.record_fleet_blocked(["pppoe", "sharded"])
        # the satellite fix: a blocker that disappeared must leave the
        # scrape, not freeze at 1
        assert {d["blocker"]
                for d in m.slowpath_fleet_blocked.labeled()} \
            == {"pppoe", "sharded"}
        m.record_fleet_blocked([])
        assert m.slowpath_fleet_blocked.labeled() == []
