"""The sharded update drain (PR 41): what crosses follows what changed.

A clean mesh drains to the batch that is already placed over its chips
(no upload, no read back, the same arrays as the step before); a dirty
table kind is built on the host, stacked and placed, and the other kinds
keep their placed leaves; a dense config array is placed again when its
bytes changed; the bulk-build resync stands; the drain's cache holds
update leaves only.

One geometry for the whole file (two shards, every optional stage on),
so the mesh programs compile once.
"""

import gc
import weakref

import jax
import numpy as np
import pytest

from bng_tpu.ops.qtable import QW_LAST_US, QW_TOKENS, way_rows
from bng_tpu.parallel.sharded import ShardedCluster
from bng_tpu.telemetry import spans as tele
from bng_tpu.utils.net import ip_to_u32, parse_mac

pytestmark = pytest.mark.sharded

NOW = 1_753_000_000
SERVER_MAC = parse_mac("02:aa:bb:cc:dd:01")
SERVER_IP = ip_to_u32("10.0.0.1")
N = 2
GEOM = dict(batch_per_shard=8, sub_nbuckets=64, vlan_nbuckets=64,
            cid_nbuckets=64, nat_sessions_nbuckets=64, qos_nbuckets=64,
            spoof_nbuckets=64, pppoe_enabled=True, pppoe_nbuckets=64,
            edge_enabled=True, edge_nbuckets=64)
TABLE_LEAVES = 6  # a HostTable's update batch
QTABLE_LEAVES = 3  # a QTable's
STEP_READS = 12  # `step()` reads back: verdict, length, six stats blocks,
#                  punt and violation flags, the mirror column, edge stats.
#                  Since PR 44 its dispatch has started the copy of every
#                  one but the mirror column (no sink is set here) and of
#                  `out_pkt`: twelve starts, and one crossing at the reads


def make_cluster() -> ShardedCluster:
    cl = ShardedCluster(N, **GEOM)
    cl.set_server_config_all(SERVER_MAC, SERVER_IP)
    cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 24, SERVER_IP, lease_time=3600)
    return cl


def idle_step(cl: ShardedCluster) -> None:
    """A fused step over no frames: the drain runs, no lane is real."""
    B = cl.n * cl.b
    cl.step(np.zeros((B, 512), dtype=np.uint8),
            np.zeros((B,), dtype=np.uint32), np.zeros((B,), dtype=bool), 0, 0)


class StepSpy:
    """The update batch each fused step of the block was handed, by leaf."""

    def __init__(self, cl: ShardedCluster):
        self.cl, self.real, self.seen = cl, cl._step, []

    def __enter__(self):
        def step(tables, upd, *rest):
            self.seen.append(jax.tree.leaves(upd))
            return self.real(tables, upd, *rest)

        self.cl._step = step
        return self

    def __exit__(self, *exc) -> None:
        self.cl._step = self.real


def kinds_of(cl: ShardedCluster) -> dict[int, str]:
    """id(placed leaf) -> the shards' attribute it is the batch of."""
    out = {}
    for kind, upd in cl._noop_upd.items():
        for leaf in jax.tree.leaves(upd):
            out[id(leaf)] = kind[1]
    for kind, (_bytes, leaf) in cl._dense_placed.items():
        out[id(leaf)] = kind[1]
    return out


def _table_diff(host, dev, i: int) -> bool:
    return not (
        np.array_equal(host._pack_bucket_rows(np.arange(host.nbuckets)),
                       np.asarray(dev.krows)[i])
        and np.array_equal(host._pack_stash_rows(np.arange(host.stash)),
                           np.asarray(dev.stash_rows)[i])
        and np.array_equal(host.vals, np.asarray(dev.vals)[i]))


def _qtable_diff(host, dev, i: int) -> bool:
    keep = np.ones(host.rows.shape[1], dtype=bool)
    keep[[QW_TOKENS, QW_LAST_US]] = False  # written by the kernel
    got = way_rows(np.asarray(dev.rows)[i], host.nbuckets)
    return not np.array_equal(host.rows[:, keep], got[:, keep])


def mirror_diffs(cl: ShardedCluster) -> list[str]:
    """Every host-authoritative column of the device tables that differs
    from its host mirror, as `shard<i>.<kind>` (drained and quiesced)."""
    assert cl.pending_dirty() == 0
    cl.quiesce()
    d = cl.tables
    bad = []
    for i in range(cl.n):
        fp, nat, sp, g, p, e = (cl.fastpath[i], cl.nat[i], cl.spoof[i],
                                cl.garden[i], cl.pppoe[i], cl.edge[i])
        tables = {
            "sub": (fp.sub, d.dhcp.sub), "vlan": (fp.vlan, d.dhcp.vlan),
            "cid": (fp.cid, d.dhcp.cid),
            "nat.sessions": (nat.sessions, d.nat.sessions),
            "nat.reverse": (nat.reverse, d.nat.reverse),
            "nat.sub_nat": (nat.sub_nat, d.nat.sub_nat),
            "spoof": (sp.bindings, d.spoof),
            "garden": (g.subscribers, d.garden),
            "pppoe.by_sid": (p.by_sid, d.pppoe_by_sid),
            "pppoe.by_ip": (p.by_ip, d.pppoe_by_ip),
            "edge.tap": (e.tap, d.tap), "edge.route": (e.route, d.route),
        }
        bad += [f"shard{i}.{k}" for k, (h, dev) in tables.items()
                if _table_diff(h, dev, i)]
        bad += [f"shard{i}.{k}" for k, h, dev in (
            ("qos.up", cl.qos[i].up, d.qos_up),
            ("qos.down", cl.qos[i].down, d.qos_down))
            if _qtable_diff(h, dev, i)]
        dense = {
            "pools": (fp.pools, d.dhcp.pools),
            "server": (fp.server, d.dhcp.server),
            "nat.hairpin": (nat.hairpin, d.nat.hairpin_ips),
            "nat.alg": (nat.alg, d.nat.alg_ports),
            "nat.config": (nat.config_array(), d.nat.config),
            "spoof.ranges": (sp.ranges, d.spoof_ranges),
            "spoof.config": (sp.config, d.spoof_config),
            "garden.allowed": (g.allowed, d.garden_allowed),
            "edge.tap_filters": (e.tap_filters, d.tap_filters),
            "edge.tap_config": (e.tap_config, d.tap_config),
        }
        bad += [f"shard{i}.{k}" for k, (h, dev) in dense.items()
                if not np.array_equal(h, np.asarray(dev)[i])]
    return bad


class Sess:
    def __init__(self, sid: int, ip: int):
        self.session_id = sid
        self.client_mac = (0x02C0FFEE0000 | sid).to_bytes(6, "big")
        self.assigned_ip = ip


def ip_on_shard(cl: ShardedCluster, k: int, base: int) -> int:
    return next(ip for ip in range(base, base + 64)
                if cl.affinity_shard_ip(ip) == k)


# WRITES: name -> (a write routed to shard k, returning the shard it landed
# on; the shards' attributes whose batch it makes fresh, with their leaves)
def _w_sub(cl, k):
    mac = next(m for m in ((0x02D1 << 32 | i).to_bytes(6, "big")
                           for i in range(64)) if cl.dhcp_sub_shard(m) == k)
    return cl.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.77"),
                             lease_expiry=NOW + 600)


def _w_vlan(cl, k):
    c = next(c for c in range(1, 64) if cl.dhcp_vlan_shard(100, c) == k)
    return cl.add_vlan_subscriber(100, c, pool_id=1,
                                  ip=ip_to_u32("10.0.0.78"),
                                  lease_expiry=NOW + 600)


def _w_cid(cl, k):
    cid = next(c for c in (b"olt-1/%d" % i for i in range(64))
               if cl.dhcp_cid_shard(c) == k)
    return cl.add_circuit_id_subscriber(cid, pool_id=1,
                                        ip=ip_to_u32("10.0.0.79"),
                                        lease_expiry=NOW + 600)


def _w_nat_block(cl, k):
    return cl.allocate_nat(ip_on_shard(cl, k, ip_to_u32("10.0.1.0")), NOW)[0]


def _w_nat_flow(cl, k):
    ip = ip_on_shard(cl, k, ip_to_u32("10.0.2.0"))
    cl.allocate_nat(ip, NOW)
    o, got = cl.handle_new_flow(ip, ip_to_u32("9.9.9.9"), 41000, 443, 17,
                                600, NOW)
    assert got is not None
    return o


def _w_qos(cl, k):
    return cl.set_qos(ip_on_shard(cl, k, ip_to_u32("10.0.3.0")),
                      down_bps=8_000, up_bps=8_000, down_burst=1000,
                      up_burst=1000)


def _w_spoof(cl, k):
    ip = ip_on_shard(cl, k, ip_to_u32("10.0.4.0"))
    return cl.add_spoof_binding((0x02D2 << 32 | k).to_bytes(6, "big"), ip, 1)


def _w_garden(cl, k):
    return cl.set_gardened(ip_on_shard(cl, k, ip_to_u32("10.0.5.0")), True)


def _w_pppoe(cl, k):
    return cl.pppoe_session_up(
        Sess(0x40 + k, ip_on_shard(cl, k, ip_to_u32("10.0.6.0"))))


def _w_tap(cl, k):
    return cl.arm_tap(ip_on_shard(cl, k, ip_to_u32("10.0.7.0")), 7)


def _w_route(cl, k):
    return cl.set_route(ip_on_shard(cl, k, ip_to_u32("10.0.8.0")),
                        bytes.fromhex("02aabbccdd99"), 3)


WRITES = {
    "subscriber": (_w_sub, {"sub": TABLE_LEAVES}),
    "vlan": (_w_vlan, {"vlan": TABLE_LEAVES}),
    "circuit-id": (_w_cid, {"cid": TABLE_LEAVES}),
    "nat-block": (_w_nat_block, {"sub_nat": TABLE_LEAVES}),
    "nat-session": (_w_nat_flow, {"sub_nat": TABLE_LEAVES,
                                  "sessions": TABLE_LEAVES,
                                  "reverse": TABLE_LEAVES}),
    "qos": (_w_qos, {"up": QTABLE_LEAVES, "down": QTABLE_LEAVES}),
    "spoof-binding": (_w_spoof, {"bindings": TABLE_LEAVES}),
    "garden-membership": (_w_garden, {"subscribers": TABLE_LEAVES}),
    "pppoe-session": (_w_pppoe, {"by_sid": TABLE_LEAVES,
                                 "by_ip": TABLE_LEAVES}),
    # the first armed row also flips the shard's dense armed predicate
    "edge-tap": (_w_tap, {"tap": TABLE_LEAVES, "tap_config": 1}),
    "edge-route": (_w_route, {"route": TABLE_LEAVES}),
}


@pytest.fixture(scope="module")
def cl():
    """One live cluster for the file: synced, stepped once (every kind's
    no-op is placed), clean."""
    c = make_cluster()
    c.sync_tables()
    idle_step(c)
    assert c.pending_dirty() == 0
    return c


def test_two_clean_steps_get_the_same_placed_leaves_and_cross_nothing(cl):
    """(i) A clean drain returns the very arrays of the drain before it,
    fastpath lane and fused alike, and an armed window counts no upload
    and no read in it: a step's three placements are `pack`'s."""
    with StepSpy(cl) as spy, tele.armed() as tr:
        idle_step(cl)
        idle_step(cl)
        sums = tr.sums()
    a, b = spy.seen
    assert len(a) == 88 and all(x is y for x, y in zip(a, b))
    assert set(map(id, a)) <= set(kinds_of(cl))
    # 3 fastpath + 3 NAT + 2 QoS + spoof + garden + 2 PPPoE + 2 edge
    assert (sums["drain_built"], sums["drain_cached"]) == (0, 2 * 14 * N)
    assert sums["xfer"]["upload_calls"] == 2 * 3  # pack's, both steps
    assert sums["xfer"]["prefetch_calls"] == 2 * STEP_READS
    assert sums["xfer"]["fetch_calls"] == 2 * 1
    with tele.armed() as tr:
        f1 = jax.tree.leaves(cl._drain_fastpath())
        f2 = jax.tree.leaves(cl._drain_fastpath())
        u = jax.tree.leaves(cl._drain_updates())
        sums = tr.sums()
    assert len(f1) == 20 and all(x is y for x, y in zip(f1, f2))
    assert all(x is y for x, y in zip(f1, u))  # one cache for both lanes
    assert sums["xfer"]["upload_calls"] == sums["xfer"]["fetch_calls"] == 0
    assert (sums["drain_built"], sums["drain_cached"]) == (0, (3 + 3 + 14) * N)


@pytest.mark.parametrize("k", range(N))
@pytest.mark.parametrize("what", list(WRITES))
def test_a_write_is_in_the_next_step_and_only_its_kind_is_placed(cl, what, k):
    """(ii) A write routed to shard k before a step is on shard k's chip
    after that step; the step got fresh leaves for the kinds the write
    dirtied (six a HostTable, three a QTable, each placed once, none
    read back) and the placed leaves of every other kind; the step after
    it is handed the no-op again."""
    write, dirtied = WRITES[what]
    idle_step(cl)
    with StepSpy(cl) as spy:
        idle_step(cl)
        kind = kinds_of(cl)
        assert write(cl, k) == k
        assert cl.pending_dirty() > 0
        with tele.armed() as tr:
            idle_step(cl)
            sums = tr.sums()
        assert cl.pending_dirty() == 0
        idle_step(cl)
    clean, dirty, after = spy.seen
    fresh = [kind[id(c)] for c, d in zip(clean, dirty) if c is not d]
    assert {x: fresh.count(x) for x in set(fresh)} == dirtied
    for c, d, a in zip(clean, dirty, after):
        if c is d or dirtied[kind[id(c)]] > 1:
            assert a is c  # untouched, or a table's no-op again
        else:
            assert a is d  # a dense array: placed once, kept
    n_fresh = sum(dirtied.values())
    assert sums["xfer"]["upload_calls"] == 3 + n_fresh
    assert sums["xfer"]["prefetch_calls"] == STEP_READS
    assert sums["xfer"]["fetch_calls"] == 1
    tables = [x for x in dirtied if dirtied[x] > 1]
    assert sums["drain_built"] == len(tables)  # on one shard
    assert sums["drain_cached"] == 14 * N - len(tables)
    assert mirror_diffs(cl) == []


def _set_ranges(cl):
    for sp in cl.spoof:
        sp.ranges[0] = (ip_to_u32("10.9.0.0"), ip_to_u32("10.9.0.255"))
    return "ranges"


def _set_allowed(cl):
    cl.garden[1].allowed[0] = (ip_to_u32("198.51.100.7"), 443, 6)
    return "allowed"


def _add_pool(cl):
    cl.add_pool_all(2, ip_to_u32("10.8.0.0"), 24, ip_to_u32("10.8.0.1"))
    return "pools"


def _set_server(cl):
    cl.set_server_config_all(parse_mac("02:aa:bb:cc:dd:02"), SERVER_IP)
    return "server"


@pytest.mark.parametrize("write", [_set_ranges, _set_allowed, _add_pool,
                                   _set_server])
def test_a_dense_array_written_in_place_is_placed_once(cl, write):
    """(iii) A dense config array changed before a step (in place, or
    through the cluster's broadcast writers) is on the chips after that
    step, costs that step one placement, and none after it."""
    idle_step(cl)
    with StepSpy(cl) as spy:
        idle_step(cl)
        name = write(cl)
        with tele.armed() as tr:
            idle_step(cl)
            one = tr.sums()["xfer"]["upload_calls"]
        assert mirror_diffs(cl) == []
        with tele.armed() as tr:
            idle_step(cl)
            idle_step(cl)
            two = tr.sums()["xfer"]["upload_calls"]
    assert (one, two) == (3 + 1, 2 * 3)
    before, wrote, after, again = spy.seen[-4:]
    kind = kinds_of(cl)
    assert [kind[id(w)] for b, w in zip(before, wrote) if b is not w] \
        == [name]
    assert all(x is y for x, y in zip(wrote, after))
    assert all(x is y for x, y in zip(after, again))


def test_a_bulk_insert_on_a_live_cluster_resyncs_and_the_no_op_survives(cl):
    """(iv) `bulk_insert` past the stash abandons delta tracking: the
    drain's "full upload" signal is answered with one `sync_tables` and
    a second drain, and the placed no-op (geometry's, not contents')
    serves that step and the ones after it."""
    syncs = []
    real_sync = cl.sync_tables
    cl.sync_tables = lambda: (syncs.append(1), real_sync())[1]
    try:
        with StepSpy(cl) as spy:
            idle_step(cl)
            t = cl.fastpath[1].vlan
            keys = np.arange(0x7000, 0x7000 + t.stash + 8,
                             dtype=np.uint32)[:, None]
            t.bulk_insert(keys, np.ones((len(keys), t.V), dtype=np.uint32))
            assert t._dirty_all
            with pytest.raises(RuntimeError, match="full upload"):
                t.host_update(8)
            idle_step(cl)
            assert syncs == [1] and cl.pending_dirty() == 0
            idle_step(cl)
    finally:
        del cl.sync_tables
    before, resynced, after = spy.seen[-3:]
    assert all(x is y for x, y in zip(before, resynced))
    assert all(x is y for x, y in zip(before, after))
    assert syncs == [1]
    assert mirror_diffs(cl) == []


def test_sync_tables_leaves_no_reference_to_a_shards_tables(cl):
    """(v) `sync_tables` stages whole tables on chip 0 and stacks them:
    none of those arrays outlives it, and what the drain keeps is one
    update batch's leaves over the mesh."""
    refs = []
    real = cl._stack_per_shard

    def stack(per_shard):
        refs.extend(weakref.ref(x) for t in per_shard
                    for x in jax.tree.leaves(t))
        return real(per_shard)

    cl._stack_per_shard = stack
    try:
        cl.sync_tables()
    finally:
        del cl._stack_per_shard
    gc.collect()
    assert len(refs) == N * len(jax.tree.leaves(cl.tables))
    assert all(r() is None for r in refs)
    kept = [leaf for upd in cl._noop_upd.values()
            for leaf in jax.tree.leaves(upd)]
    kept += [leaf for _bytes, leaf in cl._dense_placed.values()]
    batch = jax.tree.leaves(cl._drain_updates())
    assert sorted(map(id, kept)) == sorted(map(id, batch))
    assert sum(a.nbytes for a in batch) < 2 << 20


def test_a_kind_first_drained_dirty_keeps_no_batch_as_its_no_op():
    """A write between `sync_tables` and the first step: the batch that
    ships it is not what later clean steps are handed."""
    c = make_cluster()
    c.sync_tables()
    assert _w_sub(c, 0) == 0 and _w_qos(c, 1) == 1
    with StepSpy(c) as spy:
        idle_step(c)
        assert not {k[1] for k in c._noop_upd} & {"sub", "up", "down"}
        idle_step(c)
        idle_step(c)
    first, second, third = spy.seen
    assert all(x is y for x, y in zip(second, third))
    assert sum(x is not y for x, y in zip(first, second)) \
        == TABLE_LEAVES + 2 * QTABLE_LEAVES
    sub = c._noop_upd["FastPathTables", "sub"]
    up = c._noop_upd["QoSTables", "up"]
    assert (np.asarray(sub.idx) == c.fastpath[0].sub.S).all()
    assert not np.asarray(up.ways).any()
    assert mirror_diffs(c) == []


def test_a_clone_has_a_cache_of_its_own(cl):
    """The blue/green standby shares the mesh and the compiled programs
    and nothing the drain keeps."""
    twin = cl.clone_empty()
    twin.sync_tables()
    idle_step(twin)
    mine, theirs = kinds_of(cl), kinds_of(twin)
    assert set(mine.values()) == set(theirs.values())
    assert not set(mine) & set(theirs)


def test_interleaved_writes_and_steps_leave_every_mirror_equal(cl):
    """(vi) A seeded run of writes of every kind on both shards, a step
    after every few of them, more writes than a batch holds among them:
    afterwards every host-authoritative column of the device tables
    equals its host mirror on every shard."""
    rng = np.random.default_rng(41)
    macs = [(0x02D3 << 32 | i).to_bytes(6, "big") for i in range(200)]
    live: list[bytes] = []
    for r in range(24):
        for _ in range(int(rng.integers(1, 6))):
            ip = ip_to_u32("10.1.0.0") + int(rng.integers(0, 256))
            op = int(rng.integers(0, 10))
            if op == 0 and macs:
                live.append(macs.pop())
                cl.add_subscriber(live[-1], pool_id=1, ip=ip,
                                  lease_expiry=NOW + 600)
            elif op == 1 and live:
                cl.remove_subscriber(
                    live.pop(int(rng.integers(0, len(live)))))
            elif op == 2 and live:
                cl.touch_lease(live[int(rng.integers(0, len(live)))],
                               NOW + 900 + r)
            elif op == 3:
                cl.set_qos(ip, down_bps=1_000 * (r + 1), up_bps=2_000,
                           down_burst=100, up_burst=100)
            elif op == 4:
                cl.add_spoof_binding(macs[op], ip, 1)
            elif op == 5:
                cl.set_gardened(ip, bool(r & 1))
            elif op == 6:
                if cl.allocate_nat(ip, NOW)[1] is not None:
                    cl.handle_new_flow(ip, ip_to_u32("9.9.9.9"),
                                       40000 + r, 443, 17, 100, NOW)
            elif op == 7:
                cl.pppoe_session_up(Sess(0x100 + r, ip))
            elif op == 8:
                cl.arm_tap(ip, 1 + r % 3, [(443, 6, 0)])
                cl.set_route(ip, bytes.fromhex("02aabbccdd98"), r % 4)
            else:
                cl.spoof[r % N].ranges[1] = (ip, ip + r)
        if r == 11:  # more dirty slots than one batch ships
            for i in range(cl.fastpath[0].update_slots + 40):
                cl.add_vlan_subscriber(200, i + 1, pool_id=1, ip=ip,
                                       lease_expiry=NOW + 600)
        idle_step(cl)
    for _ in range(8):
        if not cl.pending_dirty():
            break
        idle_step(cl)
    assert mirror_diffs(cl) == []
    idle_step(cl)
    a = jax.tree.leaves(cl._drain_updates())
    assert all(x is y for x, y in zip(a, jax.tree.leaves(cl._drain_updates())))
    assert set(map(id, a)) <= set(kinds_of(cl))


def test_a_host_update_is_make_updates_batch_without_the_upload():
    """`host_update` is the batch `make_update` uploads: the same
    arrays, the same dirty set consumed, padding when clean; the
    one-chip call pattern stands (six `jnp.asarray` under one `upload`
    lap for a dirty HostTable, three for a QTable, none for a clean
    one)."""
    from bng_tpu.ops.qtable import HostQTable
    from bng_tpu.ops.table import HostTable

    def fill_t(t):
        for i in range(5):
            t.insert([i + 1, 7], np.arange(t.V, dtype=np.uint32) + i)

    for make, fill, n in (
            (lambda: HostTable(64, 2, 8, stash=8, name="t"), fill_t,
             TABLE_LEAVES),
            (lambda: HostQTable(64, name="q"),
             lambda q: [q.insert(ip_to_u32("10.0.0.1") + i,
                                 rate_bps=8_000 + i, burst=100)
                        for i in range(5)], QTABLE_LEAVES)):
        a, b = make(), make()
        fill(a)
        fill(b)
        with tele.armed() as tr:
            dev = a.make_update(16)
            sums = tr.sums()
        host = b.host_update(16)
        assert type(host) is type(dev) and len(host) == n
        assert all(isinstance(h, np.ndarray) for h in host)
        for h, d in zip(host, dev):
            assert h.dtype == d.dtype
            np.testing.assert_array_equal(h, np.asarray(d))
        assert a.dirty_count() == b.dirty_count() == 0
        assert sums["xfer"]["upload_calls"] == n
        with tele.armed() as tr:
            assert a.make_update(16) is a.empty_update(16)
            assert tr.sums()["xfer"]["upload_calls"] == 0
        for h, d in zip(b.host_update(16), a.empty_update(16)):
            assert h.dtype == d.dtype
            np.testing.assert_array_equal(h, np.asarray(d))
