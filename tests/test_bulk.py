"""Bulk table-build paths (reference scale: 1M subscribers, bpf/maps.h:10).

Round-1 verdict: the per-subscriber Python insert loop made 1M infeasible;
these tests pin the vectorized bulk paths to the per-key semantics.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from bng_tpu.control.nat import NATManager
from bng_tpu.ops.table import HostTable, device_lookup
from bng_tpu.runtime.tables import FastPathTables
from bng_tpu.utils.net import ip_to_u32

NOW = 1_753_000_000


class TestHostTableBulkInsert:
    def test_matches_per_key_insert(self):
        nb = 1 << 10
        a = HostTable(nb, key_words=2, val_words=3, stash=64, name="a")
        b = HostTable(nb, key_words=2, val_words=3, stash=64, name="b")
        n = 1500
        keys = np.stack([np.arange(n, dtype=np.uint32),
                         np.arange(n, dtype=np.uint32) * 13 + 7], axis=1)
        vals = np.stack([np.arange(n, dtype=np.uint32)] * 3, axis=1)
        a.bulk_insert(keys, vals)
        for i in range(n):
            b.insert(keys[i], vals[i])
        assert a.count == b.count == n
        # every key resolves to the same value through both tables
        got_a = a.lookup_batch_host(keys)
        got_b = b.lookup_batch_host(keys)
        np.testing.assert_array_equal(got_a, vals)
        np.testing.assert_array_equal(got_b, vals)

    def test_device_lookup_agreement(self):
        nb = 1 << 12
        t = HostTable(nb, key_words=2, val_words=4, stash=128, name="d")
        n = 6000
        keys = np.stack([np.arange(n, dtype=np.uint32) + 5,
                         np.arange(n, dtype=np.uint32) * 3], axis=1)
        vals = np.tile(np.arange(n, dtype=np.uint32)[:, None], (1, 4))
        t.bulk_insert(keys, vals)
        res = device_lookup(t.device_state(), jnp.asarray(keys), nb, 128)
        assert bool(res.found.all())
        np.testing.assert_array_equal(np.asarray(res.vals), vals)
        # misses stay misses
        missk = np.stack([np.arange(64, dtype=np.uint32) + 1_000_000,
                          np.zeros(64, dtype=np.uint32)], axis=1)
        res2 = device_lookup(t.device_state(), jnp.asarray(missk), nb, 128)
        assert not bool(res2.found.any())

    def test_large_bulk_requires_full_upload(self):
        t = HostTable(1 << 10, key_words=1, val_words=1, stash=16)
        keys = np.arange(100, dtype=np.uint32)[:, None]
        t.bulk_insert(keys, keys)
        assert t._dirty_all
        with pytest.raises(RuntimeError, match="full upload"):
            t.make_update(32)
        t.device_state()  # full upload clears the flag
        t.insert([5000], [1])
        upd = t.make_update(32)
        # exactly one non-padding bucket row rides the update
        assert int((np.asarray(upd.bidx) < t.nbuckets).sum()) == 1

    def test_small_bulk_keeps_delta_sync(self):
        t = HostTable(1 << 10, key_words=1, val_words=1, stash=64)
        keys = np.arange(10, dtype=np.uint32)[:, None]
        t.bulk_insert(keys, keys)
        assert not t._dirty_all
        assert t.dirty_count() == 10

    def test_high_load_factor_residue_path(self):
        # fill to ~87% of capacity: residue must fall back to cuckoo kicks
        nb = 1 << 8
        cap = nb * 4
        t = HostTable(nb, key_words=1, val_words=1, stash=64)
        n = int(cap * 0.87)
        keys = (np.arange(n, dtype=np.uint32) * 2654435761 % (1 << 30))[:, None]
        keys = np.unique(keys, axis=0)
        t.bulk_insert(keys, keys)
        assert t.count == len(keys)
        got = t.lookup_batch_host(keys)
        np.testing.assert_array_equal(got, keys)


def _rows(rng, n, k, v):
    keys = rng.integers(0, 1 << 32, (n, k), dtype=np.uint64).astype(np.uint32)
    vals = rng.integers(0, 1 << 32, (n, v), dtype=np.uint64).astype(np.uint32)
    return keys, vals


class TestHostTableInsertMany:
    """The batch verbs of a LIVE table (PR 54: a retire's new flows):
    `insert_many` is `insert` of every row in order, `probe` /
    `lookup_many` are `_find_slot` / `lookup` of every key, and every slot
    written is dirty whatever the batch's length."""

    @pytest.mark.parametrize("n", [1, 2, 21, 64, 65, 300])
    def test_matches_per_key_insert_and_stays_on_delta_sync(self, n):
        rng = np.random.default_rng([54, n])
        a = HostTable(1 << 9, key_words=4, val_words=5, stash=64, name="a")
        b = HostTable(1 << 9, key_words=4, val_words=5, stash=64, name="b")
        base_k, base_v = _rows(rng, 1000, 4, 5)  # half full, as provisioned
        for t in (a, b):
            t.bulk_insert(base_k, base_v)
            t.device_state()
        keys, vals = _rows(rng, n, 4, 5)
        walked, failed = a.insert_many(keys, vals)
        for k, v in zip(keys, vals):
            b.insert(k, v)
        assert not failed and a.count == b.count == 1000 + n
        assert not a._dirty_all and a.dirty_count() >= n
        found, got = a.lookup_many(keys)
        assert bool(found.all())
        np.testing.assert_array_equal(got, vals)
        np.testing.assert_array_equal(a.lookup_batch_host(keys), vals)
        np.testing.assert_array_equal(b.lookup_batch_host(keys), vals)
        np.testing.assert_array_equal(a.lookup_batch_host(base_k), base_v)
        # every slot the batch wrote is queued: the chip answers after one
        # bounded drain for a batch under the update's size
        slots = a.probe(keys)[0]
        assert set(slots.tolist()) <= a._dirty
        state = a.device_state()
        res = device_lookup(state, jnp.asarray(keys), a.nbuckets, a.stash)
        assert bool(res.found.all())
        np.testing.assert_array_equal(np.asarray(res.slot), slots)

    def test_full_buckets_send_the_residue_through_the_kick_walk_and_the_stash(self):
        rng = np.random.default_rng(5402)
        t = HostTable(2, key_words=2, val_words=2, stash=8, name="tiny")
        keys, vals = _rows(rng, 8 + 5 + 6, 2, 2)
        assert t.insert_many(keys[:8], vals[:8])[1] == {}
        assert t.count == 8 and not t.used[8:].any()  # both buckets full
        # five more: no way is free, each walks, kicks, and lands in the stash
        walked, failed = t.insert_many(keys[8:13], vals[8:13])
        assert walked.tolist() == [0, 1, 2, 3, 4] and not failed
        assert t.count == 13 and int(t.used[8:].sum()) == 5
        found, got = t.lookup_many(keys[:13])  # stash hits among them
        assert bool(found.all())
        np.testing.assert_array_equal(got, vals[:13])
        np.testing.assert_array_equal(t.lookup_batch_host(keys[:13]), vals[:13])
        # six more into three stash slots: the table is full for three keys
        # alone, they are named, and the rest of the batch is in
        walked, failed = t.insert_many(keys[13:], vals[13:])
        assert walked.tolist() == list(range(6)) and sorted(failed) == [3, 4, 5]
        assert all(isinstance(e, RuntimeError) for e in failed.values())
        assert t.count == 16
        found, got = t.lookup_many(keys)
        assert found.tolist() == [True] * 16 + [False] * 3
        np.testing.assert_array_equal(got[:16], vals[:16])
        np.testing.assert_array_equal(t.lookup_batch_host(keys[:16]), vals[:16])
        assert not got[16:].any() and not t._dirty_all

    def test_present_keys_update_in_place_and_a_repeat_takes_its_last_value(self):
        rng = np.random.default_rng(5403)
        t = HostTable(1 << 8, key_words=3, val_words=4, stash=16)
        keys, vals = _rows(rng, 40, 3, 4)
        t.insert_many(keys[:20], vals[:20])
        slots = t.probe(keys[:20])[0].copy()
        t.device_state()
        # ten held keys with new values, twenty new, one of them three times
        again_k = np.concatenate([keys[5:15], keys[20:], keys[25:26], keys[25:26]])
        again_v = np.concatenate([vals[5:15] + 1, vals[20:], vals[25:26] + 7,
                                  vals[25:26] + 9])
        walked, failed = t.insert_many(again_k, again_v)
        assert not failed and t.count == 40
        np.testing.assert_array_equal(t.probe(keys[:20])[0], slots)  # in place
        want = vals.copy()
        want[5:15] += 1
        want[25] += 9
        found, got = t.lookup_many(keys)
        assert bool(found.all())
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(t.lookup_batch_host(keys), want)
        assert t._dirty == set(t.probe(again_k)[0].tolist())

    @pytest.mark.parametrize("stashed", [0, 3])
    def test_the_vector_probe_is_find_slot_key_by_key(self, stashed):
        rng = np.random.default_rng([5404, stashed])
        t = HostTable(1 << 6, key_words=4, val_words=2, stash=8)
        keys, vals = _rows(rng, 200, 4, 2)
        t.insert_many(keys[:150], vals[:150])
        base = t.nbuckets * 4
        for i in range(stashed):  # stash hits, placed there by hand
            t._place(base + 2 * i, keys[150 + i], vals[150 + i])
        slot, b1, b2 = t.probe(keys)
        for k, s, x, y in zip(keys, slot.tolist(), b1.tolist(), b2.tolist()):
            assert (x, y) == t._buckets(k)
            assert s == (-1 if t._find_slot(k) is None else t._find_slot(k))
        assert int((slot >= base).sum()) == stashed
        found, got = t.lookup_many(keys)
        np.testing.assert_array_equal(got, t.lookup_batch_host(keys))
        assert found.tolist() == [t.lookup(k) is not None for k in keys]

    @pytest.mark.parametrize("n, dirty_all", [(63, False), (64, False),
                                              (65, True), (300, True)])
    def test_bulk_insert_keeps_its_provisioning_behaviour(self, n, dirty_all):
        """`bulk_insert` above `stash` rows still abandons the dirty set for
        a full upload (start-up's); the live verb never does."""
        rng = np.random.default_rng([5405, n])
        keys, vals = _rows(rng, n, 2, 2)
        t = HostTable(1 << 9, key_words=2, val_words=2, stash=64)
        t.bulk_insert(keys, vals)
        assert t._dirty_all is dirty_all
        assert t.dirty_count() == (t.S if dirty_all else n)
        assert (t._dirty == set()) is dirty_all
        live = HostTable(1 << 9, key_words=2, val_words=2, stash=64)
        live.insert_many(keys, vals)
        assert not live._dirty_all and live.dirty_count() == n
        np.testing.assert_array_equal(live.keys, t.keys)  # the same passes
        np.testing.assert_array_equal(live.vals, t.vals)


class TestFastPathBulk:
    def test_bulk_subscribers_visible_on_device(self):
        n = 5000
        fp = FastPathTables(sub_nbuckets=1 << 12, vlan_nbuckets=1 << 6,
                            cid_nbuckets=1 << 6, max_pools=4)
        macs = np.arange(n, dtype=np.uint64) + 0x02AA00000000
        idx = np.arange(n, dtype=np.uint64)
        fp.add_subscribers_bulk(macs, pool_ids=1,
                                ips=((10 << 24) + 2 + idx).astype(np.uint32),
                                lease_expiries=np.uint32(NOW + 900))
        assert fp.sub.count == n
        # same entry via the scalar API path
        got = fp.get_subscriber(int(macs[123]))
        assert got is not None and int(got[1]) == (10 << 24) + 2 + 123

    def test_bulk_then_scalar_update(self):
        fp = FastPathTables(sub_nbuckets=1 << 10, vlan_nbuckets=1 << 4,
                            cid_nbuckets=1 << 4, max_pools=4)
        macs = np.arange(200, dtype=np.uint64) + 0x02BB00000000
        fp.add_subscribers_bulk(macs, 1, np.arange(200, dtype=np.uint32) + 1,
                                np.uint32(NOW))
        assert fp.touch_lease(int(macs[7]), NOW + 500)
        got = fp.get_subscriber(int(macs[7]))
        assert int(got[4]) == NOW + 500  # AV_LEASE_EXP


class TestNATBulk:
    def _mgr(self):
        return NATManager(
            public_ips=[ip_to_u32("203.0.113.1"), ip_to_u32("203.0.113.2")],
            ports_per_subscriber=64, sessions_nbuckets=1 << 12,
            sub_nat_nbuckets=1 << 10, stash=64)

    def test_bulk_allocate_matches_scalar(self):
        a, b = self._mgr(), self._mgr()
        ips = [(10 << 24) | (i + 2) for i in range(300)]
        made = a.bulk_allocate_nat(ips)
        for ip in ips:
            assert b.allocate_nat(ip) is not None
        assert made == 300
        for ip in ips:
            ba, bb = a.blocks[ip], b.blocks[ip]
            assert (ba["public_ip"], ba["port_start"], ba["port_end"]) == (
                bb["public_ip"], bb["port_start"], bb["port_end"])
            assert np.array_equal(a.sub_nat.lookup([ip]), b.sub_nat.lookup([ip]))

    def test_bulk_flows_sessions_and_reverse(self):
        m = self._mgr()
        n = 2000
        n_subs = 500
        fi = np.arange(n)
        src = ((10 << 24) + 2 + fi % n_subs).astype(np.uint32)
        dst = (ip_to_u32("93.184.0.0") + fi // n_subs).astype(np.uint32)
        sport = (30000 + fi // n_subs).astype(np.uint32)
        m.bulk_allocate_nat(np.unique(src))
        nip, nport, ok = m.bulk_flows(src, dst, sport, 443, 17, 100, NOW)
        assert bool(ok.all())
        # sessions resolvable; reverse rows point back at the session key
        for i in (0, 999, 1999):
            skey = [int(src[i]), int(dst[i]), (int(sport[i]) << 16) | 443, 17]
            v = m.sessions.lookup(skey)
            assert v is not None and int(v[0]) == nip[i] and int(v[1]) == nport[i]
            rk = [int(dst[i]), int(nip[i]), (443 << 16) | int(nport[i]), 17]
            rv = m.reverse.lookup(rk)
            # key words lead the 8-word gather-fast reverse row
            assert rv is not None and list(rv[:4]) == skey
        # external ports unique per (pub_ip, port)
        pairs = set(zip(nip.tolist(), nport.tolist()))
        assert len(pairs) == n

    def test_live_flow_after_bulk_no_port_collision(self):
        m = self._mgr()
        src = np.full((8,), (10 << 24) | 2, dtype=np.uint32)
        dst = (ip_to_u32("93.184.0.0") + np.arange(8)).astype(np.uint32)
        sport = (40000 + np.arange(8)).astype(np.uint32)
        m.bulk_allocate_nat([int(src[0])])
        _, nport, ok = m.bulk_flows(src, dst, sport, 443, 17, 100, NOW)
        assert bool(ok.all())
        live = m.handle_new_flow(int(src[0]), ip_to_u32("9.9.9.9"), 50000, 443,
                                 17, 100, NOW)
        assert live is not None and live[1] not in set(nport.tolist())

    def test_bulk_flows_eim_shared_endpoint(self):
        # RFC 4787 EIM: flows from one internal endpoint share ONE mapping
        m = self._mgr()
        src = np.full((6,), (10 << 24) | 2, dtype=np.uint32)
        dst = (ip_to_u32("93.184.0.0") + np.arange(6)).astype(np.uint32)
        sport = np.full((6,), 5000, dtype=np.uint32)  # same endpoint
        m.bulk_allocate_nat([int(src[0])])
        nip, nport, ok = m.bulk_flows(src, dst, sport, 443, 17, 100, NOW)
        assert bool(ok.all())
        assert len(set(nport.tolist())) == 1, "EIM endpoint must map to one port"
        k = (int(src[0]), 5000, 17)
        assert m.eim[k][2] == 6  # refcount = number of flows
        # a later bulk batch on the same endpoint reuses the mapping
        nip2, nport2, ok2 = m.bulk_flows(
            src[:2], dst[:2] + 100, sport[:2], 443, 17, 100, NOW)
        assert bool(ok2.all()) and nport2[0] == nport[0]
        assert m.eim[k][2] == 8
        # an existing handle_new_flow mapping is reused too (not clobbered)
        live = m.handle_new_flow(int(src[0]), ip_to_u32("9.9.9.9"), 6000, 443,
                                 17, 100, NOW)
        nip3, nport3, ok3 = m.bulk_flows(
            src[:1], np.array([ip_to_u32("8.8.8.8")], np.uint32),
            np.array([6000], np.uint32), 443, 17, 100, NOW)
        assert nport3[0] == live[1]
        assert m.eim[(int(src[0]), 6000, 17)][2] == 2

    def test_bulk_flows_exhaustion_marks_not_ok(self):
        m = self._mgr()
        src = np.full((80,), (10 << 24) | 2, dtype=np.uint32)  # block holds 64
        dst = (ip_to_u32("93.184.0.0") + np.arange(80)).astype(np.uint32)
        sport = (40000 + np.arange(80)).astype(np.uint32)
        m.bulk_allocate_nat([int(src[0])])
        _, _, ok = m.bulk_flows(src, dst, sport, 443, 17, 100, NOW)
        assert int(ok.sum()) == 64 and not bool(ok[64:].any())


class TestGraftEntry:
    # tier-1 budget (PERF_NOTES §16 round): ~52s of pure compile on the
    # forced 8-host-device mesh — the heaviest single test in the fast
    # tier, moved to the slow tier (verify-slow/verify-all) to keep
    # tier-1 inside its 870s cap; the sharded SERVING path stays
    # tier-1-covered by tests/test_sharded_serving.py
    @pytest.mark.slow
    def test_dryrun_multichip_guarded(self):
        import __graft_entry__ as g

        g.dryrun_multichip(8)  # conftest already forced cpu; guard is idempotent


class TestBulkOnLiveStepLoop:
    """A bulk table build on a LIVE engine/cluster must recover via one
    full re-upload and serve the bulk-inserted entries on the very next
    step (code-review r3: argument evaluation order captured the stale
    pre-resync tables, silently discarding the re-upload)."""

    def _discover(self, mac_u64: int) -> bytes:
        from bng_tpu.control import dhcp_codec, packets

        mac = int(mac_u64).to_bytes(8, "big")[2:]
        p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER)
        p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
        return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                  p.encode().ljust(320, b"\x00"))

    def test_engine_step_after_bulk_serves_new_subscribers(self):
        from bng_tpu.control.nat import NATManager
        from bng_tpu.runtime.engine import Engine
        from bng_tpu.runtime.tables import FastPathTables
        from bng_tpu.utils.net import ip_to_u32

        now = 1_753_000_000
        fp = FastPathTables(sub_nbuckets=1 << 10, vlan_nbuckets=64,
                            cid_nbuckets=64, max_pools=4, stash=64)
        fp.set_server_config(bytes.fromhex("02aabbccdd01"), ip_to_u32("10.0.0.1"))
        fp.add_pool(1, ip_to_u32("10.0.0.0"), 16, ip_to_u32("10.0.0.1"),
                    lease_time=3600)
        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         sessions_nbuckets=256, sub_nat_nbuckets=64)
        eng = Engine(fp, nat, batch_size=8, clock=lambda: float(now))
        # go live (first step uploads tables, clears dirty tracking)
        eng.process([b""])
        # bulk build ON THE LIVE ENGINE — abandons bounded-delta tracking
        n = 200
        macs = np.arange(n, dtype=np.uint64) + 0x02AB00000000
        idx = np.arange(n, dtype=np.uint64)
        fp.add_subscribers_bulk(
            macs, pool_ids=np.full(n, 1, np.uint32),
            ips=((10 << 24) + 2 + idx).astype(np.uint32),
            lease_expiries=np.uint32(now + 600))
        out = eng.process([self._discover(int(macs[0]))])
        assert len(out["tx"]) == 1, "bulk-inserted subscriber not served post-resync"

    def test_cluster_step_after_bulk_serves_new_subscribers(self):
        from bng_tpu.parallel.sharded import ShardedCluster
        from bng_tpu.utils.net import ip_to_u32

        now = 1_753_000_000
        n_dev = 4
        cl = ShardedCluster(n_dev, batch_per_shard=8, sub_nbuckets=1 << 10)
        cl.set_server_config_all(bytes.fromhex("02aabbccdd01"), ip_to_u32("10.0.0.1"))
        cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 16, ip_to_u32("10.0.0.1"),
                        lease_time=3600)
        B = n_dev * cl.b
        zero = np.zeros((B, 512), np.uint8)
        zl = np.zeros((B,), np.uint32)
        fa = np.ones((B,), dtype=bool)
        cl.step(zero, zl, fa, now, 0)  # live
        # bulk build on shard 0's host mirror
        n = 200
        macs = np.arange(n, dtype=np.uint64) + 0x02AC00000000
        idx = np.arange(n, dtype=np.uint64)
        cl.fastpath[0].add_subscribers_bulk(
            macs, pool_ids=np.full(n, 1, np.uint32),
            ips=((10 << 24) + 2 + idx).astype(np.uint32),
            lease_expiries=np.uint32(now + 600))
        # pick a mac OWNED by shard 0 so the sharded lookup resolves it
        owned = next(int(m) for m in macs if cl.dhcp_sub_shard(int(m)) == 0)
        f = self._discover(owned)
        pkt = np.zeros((B, 512), np.uint8)
        ln = np.zeros((B,), np.uint32)
        pkt[0, : len(f)] = np.frombuffer(f, np.uint8)
        ln[0] = len(f)
        out = cl.step(pkt, ln, fa, now + 1, 0)
        assert out["verdict"][0] == 2, "bulk-inserted subscriber not served post-resync"


class TestReferenceCapacityGeometry:
    """The reference's NAT geometry (bpf/nat44.c:38-40 — 4M sessions,
    2M EIM endpoints, i.e. 2 flows per internal endpoint) stands up
    through the bulk path. Scaled 20x down for CPU CI; the STRUCTURE —
    sessions:EIM = 2:1, unique 5-tuples, reverse rows per session — is
    what this pins."""

    def test_4m_geometry_scaled(self):
        n_flows, n_subs, share = 200_000, 50_000, 2
        sess_nb = 1 << (n_flows * 2 // 4).bit_length()
        # 1008 64-port blocks a public IP: a pool that holds n_subs blocks
        nat = NATManager(
            public_ips=[ip_to_u32("203.0.113.1") + i
                        for i in range(-(-n_subs // 1008) + 1)],
            ports_per_subscriber=64, sessions_nbuckets=sess_nb,
            sub_nat_nbuckets=sess_nb, stash=256)
        fi = np.arange(n_flows, dtype=np.int64)
        src_ips = ((10 << 24) + 2 + fi % n_subs).astype(np.uint32)
        dst_ips = (ip_to_u32("93.184.0.0") + fi // n_subs).astype(np.uint32)
        # `share` flows use one internal endpoint (src_ip, src_port); a
        # distinct dst per shared sport keeps the 5-tuples unique
        sports = (20000 + (fi // n_subs) // share).astype(np.uint32)
        assert nat.bulk_allocate_nat(np.unique(src_ips), NOW) == n_subs
        _, _, ok = nat.bulk_flows(src_ips, dst_ips, sports,
                                  np.uint32(443), np.uint32(17), 100, NOW)
        flows = np.stack([src_ips, dst_ips, sports], axis=1)[ok]
        assert len(flows) == n_flows
        assert nat.sessions.count == n_flows
        assert nat.reverse.count == n_flows
        # the reference ratio: half as many EIM endpoints as sessions
        assert len(nat.eim) == n_flows // 2
        # every endpoint carries exactly its two flows
        refs = [m[2] for m in nat.eim.values()]
        assert min(refs) == max(refs) == 2
        # flows sharing an endpoint share ONE external mapping: the
        # device reverse table must still resolve both 5-tuples
        src, dst, sport = (int(x) for x in flows[0])
        k = nat.sessions.lookup(nat._key(src, dst, sport, 443, 17))
        k2 = nat.sessions.lookup(nat._key(src, dst + 1, sport, 443, 17))
        if k2 is not None:  # its pair flow exists in the batch
            assert (k[0], k[1]) == (k2[0], k2[1])  # same nat_ip/port
