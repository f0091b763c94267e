"""Packed QoS table: host mirror <-> device lookup agreement.

Mirrors tests/test_table.py's strategy for the generic cuckoo table
(SURVEY.md §4: map tests are host/device agreement tests) for the
bucket-packed layout of ops/qtable.py.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from bng_tpu.ops.qtable import (
    QW_BURST, QW_LAST_US, QW_TOKENS, ROW_BUCKETS, ROW_SLOTS, SLOT_W, STORE_W,
    HostQTable, QTableGeom, WAYS, apply_qupdate, qlookup, stored_rows, way_rows,
)


def _set_device_tokens(st, slot, value: float):
    """Simulate the device-side token writeback for one slot."""
    u = np.array(value, dtype=np.float32).view(np.uint32)
    return st._replace(rows=st.rows.at[
        slot // ROW_SLOTS, (slot % ROW_SLOTS) * SLOT_W + QW_TOKENS
    ].set(jnp.uint32(u)))


def _mk(nbuckets=256, n=100, seed=0):
    t = HostQTable(nbuckets, name="t")
    rng = np.random.default_rng(seed)
    ips = rng.choice(1 << 24, size=n, replace=False).astype(np.uint32) + 1
    for i, ip in enumerate(ips):
        t.insert(int(ip), rate_bps=1_000_000 + i, burst=3000 + i, priority=i % 8)
    return t, ips


class TestHostMirror:
    def test_insert_lookup_roundtrip(self):
        t, ips = _mk()
        for i, ip in enumerate(ips):
            got = t.lookup(int(ip))
            assert got is not None
            assert got["rate_bps"] == 1_000_000 + i
            assert got["burst"] == 3000 + i
            assert got["priority"] == i % 8
            assert got["tokens"] == float(3000 + i)

    def test_update_in_place_reseeds_tokens(self):
        t, ips = _mk()
        ip = int(ips[0])
        s0 = t.lookup(ip)["slot"]
        t.insert(ip, rate_bps=5, burst=99, priority=1, start_full=False)
        got = t.lookup(ip)
        assert got["slot"] == s0  # same slot, config replaced
        assert got["rate_bps"] == 5
        assert got["tokens"] == 0.0
        assert t.count == len(ips)  # not double-counted

    def test_delete(self):
        t, ips = _mk()
        assert t.delete(int(ips[3]))
        assert t.lookup(int(ips[3])) is None
        assert not t.delete(int(ips[3]))
        assert t.count == len(ips) - 1

    def test_64bit_rate_split(self):
        t = HostQTable(64)
        t.insert(42, rate_bps=10_000_000_000, burst=1 << 30)
        assert t.lookup(42)["rate_bps"] == 10_000_000_000

    def test_full_table_raises_and_rolls_back(self):
        t = HostQTable(2)  # 8 slots
        installed = []
        with pytest.raises(RuntimeError, match="full"):
            for ip in range(1, 1000):
                t.insert(ip, rate_bps=1, burst=1)
                installed.append(ip)
        # every successfully-installed policy must still resolve
        for ip in installed:
            assert t.lookup(ip) is not None, ip


class TestDeviceLookup:
    def test_agreement_with_host(self):
        t, ips = _mk(n=200, seed=1)
        st = t.device_state()
        g = QTableGeom(t.nbuckets)
        rng = np.random.default_rng(2)
        miss = rng.integers(1 << 24, 1 << 25, size=50).astype(np.uint32)
        q = np.concatenate([ips, miss])
        res = qlookup(st, jnp.asarray(q), g)
        found = np.asarray(res.found)
        assert found[: len(ips)].all()
        assert not found[len(ips):].any()
        for i, ip in enumerate(ips):
            h = t.lookup(int(ip))
            assert int(np.asarray(res.slot)[i]) == h["slot"]
            assert int(np.asarray(res.burst)[i]) == h["burst"]
            got_rate = int(np.asarray(res.rate_lo)[i]) | (int(np.asarray(res.rate_hi)[i]) << 32)
            assert got_rate == h["rate_bps"]
            assert float(np.asarray(res.tokens)[i]) == h["tokens"]

    def test_update_drain_matches_full_upload(self):
        t, ips = _mk(n=60, seed=3)
        st = t.device_state()  # clears dirty
        # mutate: one delete, one update, one fresh insert
        t.delete(int(ips[0]))
        t.insert(int(ips[1]), rate_bps=777, burst=888, priority=3)
        t.insert(0xDEAD, rate_bps=9, burst=10)
        assert t.dirty_count() > 0
        while t.dirty_count():
            st = apply_qupdate(st, t.make_update(4))
        ref = t.device_state()
        np.testing.assert_array_equal(np.asarray(st.rows), np.asarray(ref.rows))
        # tokens: drained slots seeded; untouched slots keep device values
        q = np.asarray([ips[1], 0xDEAD], dtype=np.uint32)
        res = qlookup(st, jnp.asarray(q), QTableGeom(t.nbuckets))
        assert np.asarray(res.found).all()
        assert float(np.asarray(res.tokens)[0]) == 888.0
        assert float(np.asarray(res.tokens)[1]) == 10.0

    def test_update_does_not_clobber_sibling_tokens(self):
        """Device-authoritative tokens of other ways survive a policy sync
        (way-granular updates only touch changed slots)."""
        t = HostQTable(1)  # single bucket: all entries are siblings
        a = t.insert(1, rate_bps=1000, burst=100)
        st = t.device_state()
        # device drains subscriber 1's tokens to 7.0
        st = _set_device_tokens(st, a, 7.0)
        t.insert(2, rate_bps=2000, burst=200)  # same bucket, new way
        while t.dirty_count():
            st = apply_qupdate(st, t.make_update(2))
        res = qlookup(st, jnp.asarray(np.asarray([1, 2], dtype=np.uint32)),
                      QTableGeom(1))
        assert float(np.asarray(res.tokens)[0]) == 7.0  # preserved
        assert float(np.asarray(res.tokens)[1]) == 200.0  # seeded


class TestBulkInsert:
    def test_bulk_matches_serial(self):
        rng = np.random.default_rng(7)
        n = 5000
        ips = rng.choice(1 << 26, size=n, replace=False).astype(np.uint32) + 1
        rates = rng.integers(1_000_000, 100_000_000, size=n).astype(np.uint64)
        bursts = rng.integers(1500, 1 << 20, size=n).astype(np.uint32)
        t = HostQTable(1 << 12)
        t.bulk_insert(ips, rates, bursts)
        assert t.count == n
        st = t.device_state()
        res = qlookup(st, jnp.asarray(ips), QTableGeom(t.nbuckets))
        assert np.asarray(res.found).all()
        np.testing.assert_array_equal(np.asarray(res.burst), bursts)
        got_rate = np.asarray(res.rate_lo).astype(np.uint64) | (
            np.asarray(res.rate_hi).astype(np.uint64) << np.uint64(32))
        np.testing.assert_array_equal(got_rate, rates)

    def test_small_bulk_stays_on_delta_path(self):
        """A <=256-entry bulk insert must reach the device via make_update
        (code-review r3 finding: vectorized placements skipped dirty marks)."""
        t = HostQTable(1 << 8)
        st = t.device_state()
        ips = (np.arange(100) + 1).astype(np.uint32)
        t.bulk_insert(ips, np.full(100, 5, np.uint64), np.full(100, 1500, np.uint32))
        assert t.dirty_count() > 0 and not t._dirty_all
        while t.dirty_count():
            st = apply_qupdate(st, t.make_update(16))
        res = qlookup(st, jnp.asarray(ips), QTableGeom(t.nbuckets))
        assert np.asarray(res.found).all()
        np.testing.assert_array_equal(np.asarray(res.tokens), 1500.0)

    def test_two_ways_same_bucket_both_reseed(self):
        """Two policy changes in one bucket between drains both re-seed
        (code-review r3 finding: dict held only the latest slot)."""
        t = HostQTable(1)  # everything shares bucket 0
        t.insert(1, rate_bps=8, burst=111)
        t.insert(2, rate_bps=8, burst=222)
        st = t.device_state()
        # device token state diverges, then both policies are re-installed
        for s in range(WAYS):
            st = _set_device_tokens(st, s, 3.0)
        t.insert(1, rate_bps=8, burst=111)
        t.insert(2, rate_bps=8, burst=222)
        while t.dirty_count():
            st = apply_qupdate(st, t.make_update(4))
        res = qlookup(st, jnp.asarray(np.asarray([1, 2], dtype=np.uint32)),
                      QTableGeom(1))
        assert float(np.asarray(res.tokens)[0]) == 111.0
        assert float(np.asarray(res.tokens)[1]) == 222.0

    def test_bulk_invalidates_delta_sync(self):
        t = HostQTable(1 << 10)
        ips = (np.arange(2000) + 1).astype(np.uint32)
        t.bulk_insert(ips, np.full(2000, 1, np.uint64), np.full(2000, 1500, np.uint32))
        with pytest.raises(RuntimeError, match="full upload"):
            t.make_update(8)
        t.device_state()  # resync
        t.insert(99999, rate_bps=1, burst=1)
        assert t.dirty_count() == 1


class TestTimestampWrap:
    def test_refill_across_u32_us_wrap(self):
        """The µs clock wraps every ~71.6 minutes; refill must compute the
        elapsed time modulo 2^32 (uint32 wrap-safe diff), not go negative
        or grant a huge refill at the boundary."""
        import jax.numpy as jnp

        from bng_tpu.ops.qos import qos_kernel
        from bng_tpu.runtime.engine import QoSTables

        qos = QoSTables(nbuckets=64)
        # 8 Mbps = 1e6 B/s; burst 10kB
        qos.set_subscriber(0x0A000002, down_bps=8_000_000, up_bps=8_000_000,
                           up_burst=10_000, down_burst=10_000)
        st = qos.up.device_state()
        ips = jnp.full((4,), 0x0A000002, dtype=jnp.uint32)
        lens = jnp.full((4,), 2_000, dtype=jnp.uint32)
        active = jnp.ones((4,), dtype=bool)

        # drain most of the bucket just before the wrap point
        t1 = jnp.uint32(0xFFFFFF00)
        r1 = qos_kernel(ips, lens, active, st, qos.geom, t1)
        assert list(np.asarray(r1.allowed)) == [True] * 4  # 8k of 10k burst
        st = r1.table

        # 2ms later, ACROSS the wrap: refill = 2000us * 1B/us = 2000B.
        # bucket = min(2000 + 2000, burst); exactly two 2000B packets pass
        t2 = jnp.uint32((0xFFFFFF00 + 2_000) & 0xFFFFFFFF)
        assert int(t2) < int(t1)  # genuinely wrapped
        r2 = qos_kernel(ips, lens, active, st, qos.geom, t2)
        assert list(np.asarray(r2.allowed)) == [True, True, False, False], \
            np.asarray(r2.allowed)


def ref_prefix_consumed(limited, slot, lens, avail):
    """O(B^2) numpy reference of `ops/qos.py _prefix_consumed`: a lane
    passes iff the bytes of its bucket's limited lanes up to and
    including it fit the tokens; a dropped lane's bytes stay in the
    prefix."""
    B = len(slot)
    allowed = np.ones((B,), dtype=bool)
    is_head = np.zeros((B,), dtype=bool)
    for i in range(B):
        if not limited[i]:
            continue
        same = limited[: i + 1] & (slot[: i + 1] == slot[i])
        allowed[i] = int(lens[: i + 1][same].sum()) <= int(avail[i])
        is_head[i] = same.sum() == 1
    consumed = np.zeros((B,), dtype=np.int64)
    for i in range(B):
        if limited[i]:
            same = limited & (slot == slot[i])
            consumed[i] = lens[same & allowed].sum()
    return allowed, consumed, is_head


class TestPrefixConsumed:
    """`_prefix_consumed` (the same-bucket aggregation inside
    `qos_kernel`) against the numpy reference."""

    def _check(self, limited, slot, lens, avail):
        from bng_tpu.ops.qos import _prefix_consumed

        allowed, consumed, is_head, _ = _prefix_consumed(
            jnp.asarray(limited), jnp.asarray(slot), jnp.asarray(lens),
            jnp.asarray(avail.astype(np.float32)),
            jnp.zeros((len(slot), 3), dtype=jnp.uint32))
        ref_a, ref_c, ref_h = ref_prefix_consumed(limited, slot, lens, avail)
        np.testing.assert_array_equal(np.asarray(allowed), ref_a)
        np.testing.assert_array_equal(np.asarray(is_head), ref_h)
        np.testing.assert_array_equal(
            np.asarray(consumed)[limited].astype(np.int64), ref_c[limited])

    @pytest.mark.parametrize("B", [64, 256, 768, 1000])
    def test_matches_reference(self, B):
        rng = np.random.default_rng(B)
        nb = max(2, B // 8)
        slot = rng.integers(0, nb, size=B).astype(np.int32)
        lens = rng.integers(64, 1500, size=B).astype(np.uint32)
        # tokens a bucket: some buckets admit everything, some cut mid-batch
        avail = rng.integers(0, 12_000, size=nb)[slot].astype(np.uint32)
        self._check(np.ones((B,), dtype=bool), slot, lens, avail)

    def test_unlimited_lanes_never_group(self):
        """A lane without a limit shares no prefix with anybody, whatever
        its slot number says: it passes, heads nothing, and adds nothing
        to the limited lanes of the same slot."""
        B = 128
        rng = np.random.default_rng(7)
        slot = rng.integers(0, 4, size=B).astype(np.int32)
        limited = rng.random(B) < 0.5
        lens = np.full((B,), 100, dtype=np.uint32)
        avail = np.full((B,), 1_000, dtype=np.uint32)
        self._check(limited, slot, lens, avail)

    def test_sequential_order_within_bucket(self):
        # one bucket, tokens for exactly 2 packets: lanes 0,1 pass, 2+ drop
        from bng_tpu.ops.qos import qos_kernel
        from bng_tpu.runtime.engine import QoSTables

        qos = QoSTables(nbuckets=64)
        qos.set_subscriber(0x0A000002, down_bps=8_000, up_bps=8_000,
                           up_burst=2000, down_burst=2000)
        ips = np.full((8,), 0x0A000002, dtype=np.uint32)
        lens = np.full((8,), 1000, dtype=np.uint32)
        res = qos_kernel(jnp.asarray(ips), jnp.asarray(lens),
                         jnp.ones((8,), dtype=bool),
                         qos.up.device_state(), qos.geom, jnp.uint32(1))
        assert list(np.asarray(res.allowed)) == [True, True] + [False] * 6


def _fill_stored_row(t: HostQTable, r: int, n: int = ROW_SLOTS) -> list[int]:
    """Install n policies in stored row r of t (keys whose first bucket
    lies in it and has a free way). Returns their ips, slot order."""
    ips, ip = [], 1
    while len(ips) < n:
        b1, _ = t._buckets(ip)
        if b1 // ROW_BUCKETS == r and not all(
                t.rows[b1 * WAYS + w][1] & 1 for w in range(WAYS)):
            slot = t.insert(ip, rate_bps=8_000_000, burst=50_000 + ip)
            assert slot // ROW_SLOTS == r
            ips.append(ip)
        ip += 1
    return sorted(ips, key=lambda i: t.lookup(i)["slot"])


def _f32_bits(v) -> np.ndarray:
    return np.asarray(v, dtype=np.float32).view(np.uint32)


class TestStoredRows:
    """The device array is [nbuckets/4, 128], sixteen ways a stored row:
    what one step writes into one stored row is merged into one write."""

    @pytest.mark.parametrize("nbuckets", [1, 2, 4, 256])
    def test_view_round_trips(self, nbuckets):
        t, _ = _mk(nbuckets=nbuckets, n=min(100, nbuckets * 3))
        st = t.device_state()
        assert st.rows.shape == (stored_rows(nbuckets), STORE_W)
        view = way_rows(st.rows, nbuckets)
        assert view.shape == t.rows.shape
        np.testing.assert_array_equal(view, t.rows)
        # a table under one stored row is padded; the tail holds nothing
        assert not np.asarray(st.rows).reshape(-1, SLOT_W)[t.S:].any()
        # and a mesh-stacked array reads the same way, shard by shard
        both = way_rows(jnp.stack([st.rows, st.rows]), nbuckets)
        np.testing.assert_array_equal(both[1], t.rows)

    @pytest.mark.parametrize("n", [2, 4, 16])
    def test_ways_of_one_stored_row_all_land(self, n):
        """n ways of stored row 1 rewritten in one step, their lanes mixed
        with another row's: every way gets its tokens and timestamp, and
        the row's other ways keep all eight words."""
        from bng_tpu.ops.qos import qos_kernel

        t = HostQTable(16)
        row1 = _fill_stored_row(t, 1)
        other = _fill_stored_row(t, 3, 5)
        hit = row1[:n] if n < ROW_SLOTS else row1
        rng = np.random.default_rng(n)
        lanes = np.concatenate([np.repeat(hit, 3), other, other])
        rng.shuffle(lanes)
        lens = rng.integers(64, 1500, size=len(lanes)).astype(np.uint32)
        before = t.rows.copy()
        res = qos_kernel(jnp.asarray(lanes.astype(np.uint32)), jnp.asarray(lens),
                         jnp.ones((len(lanes),), dtype=bool), t.device_state(),
                         QTableGeom(16), jnp.uint32(0))
        assert np.asarray(res.allowed).all()
        got = way_rows(res.table.rows, 16)
        want = before.copy()
        for ip in list(hit) + list(other):
            slot = t.lookup(ip)["slot"]
            spent = int(lens[lanes == ip].sum())
            want[slot, QW_TOKENS] = _f32_bits(
                np.float32(before[slot, QW_BURST]) - np.float32(spent))
            want[slot, QW_LAST_US] = 0
        np.testing.assert_array_equal(got, want)
        # the ways no lane met still hold what the host put there
        idle = [t.lookup(ip)["slot"] for ip in row1 if ip not in hit]
        np.testing.assert_array_equal(got[idle], before[idle])

    def test_update_and_writeback_meet_in_one_stored_row(self):
        """One step: the host installs a policy in a stored row whose
        sibling way the kernel charges. Both land; the sibling's tokens
        are the device's, the new way's the host's."""
        import jax

        from bng_tpu.ops.qos import qos_kernel

        t = HostQTable(16)
        a, = _fill_stored_row(t, 2, 1)
        st = t.device_state()
        b, = [ip for ip in _fill_stored_row(t, 2, 2) if ip != a]
        g = QTableGeom(16)

        @jax.jit
        def step(st, upd, ips, lens):
            return qos_kernel(ips, lens, jnp.ones(ips.shape, dtype=bool),
                              apply_qupdate(st, upd), g, jnp.uint32(7)).table

        out = step(st, t.make_update(4),
                   jnp.asarray(np.asarray([a, a], dtype=np.uint32)),
                   jnp.asarray(np.asarray([1000, 500], dtype=np.uint32)))
        got = way_rows(out.rows, 16)
        sa, sb = t.lookup(a)["slot"], t.lookup(b)["slot"]
        assert sa // ROW_SLOTS == sb // ROW_SLOTS == 2
        np.testing.assert_array_equal(got[sb], t.rows[sb])  # installed, untouched
        # 7 us at 1 B/us refill onto a full bucket: still the burst
        assert got[sa, QW_TOKENS] == _f32_bits(np.float32(50_000 + a) - np.float32(1500))
        assert got[sa, QW_LAST_US] == 7
        np.testing.assert_array_equal(got[sa, :QW_TOKENS], t.rows[sa, :QW_TOKENS])

    def test_dirty_ways_of_one_stored_row_ship_once(self):
        t = HostQTable(16)
        t.device_state()
        ips = _fill_stored_row(t, 1, 5) + _fill_stored_row(t, 3, 2)
        assert t.dirty_count() == 7
        upd = t.make_update(8)
        assert t.dirty_count() == 0
        row, ways = np.asarray(upd.row), np.asarray(upd.ways)
        assert list(row[:2]) == [1, 3] and (row[2:] == stored_rows(16)).all()
        assert bin(int(ways[0])).count("1") == 5 and bin(int(ways[1])).count("1") == 2
        assert not ways[2:].any()
        for k, r in enumerate((1, 3)):
            np.testing.assert_array_equal(
                np.asarray(upd.rows)[k].reshape(ROW_SLOTS, SLOT_W),
                t.rows[r * ROW_SLOTS:(r + 1) * ROW_SLOTS])
        for ip in ips:
            s = t.lookup(ip)["slot"]
            assert ways[list(row).index(s // ROW_SLOTS)] >> (s % ROW_SLOTS) & 1

    def test_update_batch_is_bounded_by_stored_rows(self):
        """make_update(n) drains n stored rows' dirty ways; the rest wait."""
        t = HostQTable(16)
        t.device_state()
        _fill_stored_row(t, 0, 3), _fill_stored_row(t, 1, 3), _fill_stored_row(t, 2, 3)
        upd = t.make_update(2)
        assert list(np.asarray(upd.row)) == [0, 1]
        assert t.dirty_count() == 3
        assert list(np.asarray(t.make_update(2).row)) == [2, stored_rows(16)]

    def test_full_batch_in_64_stored_rows_matches_reference(self):
        """B = 8192, every lane in one of 64 stored rows (1,024 ways):
        verdicts and the table against the plain per-packet reference."""
        from bng_tpu.ops.qos import qos_kernel

        B = 8192
        t, ips = _mk(nbuckets=256, n=700, seed=11)
        assert stored_rows(256) == 64
        rng = np.random.default_rng(12)
        lanes = ips[rng.integers(0, len(ips), size=B)]
        lens = rng.integers(64, 1500, size=B).astype(np.uint32)
        slot = np.asarray([t.lookup(int(ip))["slot"] for ip in lanes], dtype=np.int32)
        before = t.rows.copy()
        avail = before[slot, QW_BURST]
        ref_a, ref_c, _ = ref_prefix_consumed(
            np.ones((B,), dtype=bool), slot, lens, avail)
        res = qos_kernel(jnp.asarray(lanes), jnp.asarray(lens),
                         jnp.ones((B,), dtype=bool), t.device_state(),
                         QTableGeom(256), jnp.uint32(0))
        np.testing.assert_array_equal(np.asarray(res.allowed), ref_a)
        assert not ref_a.all() and ref_a.any()  # some buckets cut mid-batch
        want = before.copy()
        want[slot, QW_TOKENS] = _f32_bits(
            avail.astype(np.float32) - ref_c.astype(np.float32))
        want[slot, QW_LAST_US] = 0
        np.testing.assert_array_equal(way_rows(res.table.rows, 256), want)
