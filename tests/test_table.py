"""Cuckoo table tests — host mirror vs device lookup consistency.

TPU analog of the reference's Go<->eBPF struct layout tests
(test/ebpf/maps_test.go:17-80): the host writer and device reader must agree
on layout and hashing bit-for-bit, or table data is silently corrupted.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bng_tpu.ops.table import HostTable, device_lookup, apply_update, WAYS


class TestPartialDrain:
    def test_half_drained_bucket_hides_undrained_sibling(self):
        """A partial drain must not expose a still-queued sibling insert as
        a hit with stale/zero vals (code-review r3 repro): the sibling
        reads as a MISS until its own drain ships its value row."""
        t = HostTable(1, key_words=1, val_words=2, stash=0, name="pd")
        state = t.device_state()
        sa = t.insert([1], [111, 0])
        sb = t.insert([2], [222, 0])
        assert sa // WAYS == sb // WAYS  # same (only) bucket
        state = apply_update(state, t.make_update(max_slots=1))
        res = device_lookup(state, jnp.asarray([[1], [2]], dtype=jnp.uint32), 1, 0)
        f = np.asarray(res.found)
        v = np.asarray(res.vals)
        # exactly one visible, with its real vals; the other is a clean miss
        assert sorted(f.tolist()) == [False, True]
        assert v[f][0][0] in (111, 222)
        # second drain completes the bucket: both visible, correct vals
        state = apply_update(state, t.make_update(max_slots=1))
        res = device_lookup(state, jnp.asarray([[1], [2]], dtype=jnp.uint32), 1, 0)
        assert np.asarray(res.found).all()
        np.testing.assert_array_equal(np.asarray(res.vals)[:, 0], [111, 222])


def make_queries(keys_list, K):
    return jnp.asarray(np.array(keys_list, dtype=np.uint32).reshape(-1, K))


class TestHostTable:
    def test_insert_lookup_delete(self):
        t = HostTable(nbuckets=64, key_words=2, val_words=4)
        t.insert([1, 2], [10, 20, 30, 40])
        assert t.lookup([1, 2]).tolist() == [10, 20, 30, 40]
        assert t.lookup([9, 9]) is None
        assert t.delete([1, 2])
        assert t.lookup([1, 2]) is None
        assert not t.delete([1, 2])
        assert t.count == 0

    def test_update_existing(self):
        t = HostTable(nbuckets=64, key_words=1, val_words=1)
        t.insert([5], [100])
        t.insert([5], [200])
        assert t.count == 1
        assert t.lookup([5])[0] == 200

    def test_high_load_factor(self):
        # 4-way cuckoo should comfortably hold 90% load.
        t = HostTable(nbuckets=256, key_words=2, val_words=2, stash=64)
        n = int(256 * WAYS * 0.9)
        for i in range(n):
            t.insert([i, i ^ 0xABCD], [i, i + 1])
        assert t.count == n
        for i in range(0, n, 37):
            assert t.lookup([i, i ^ 0xABCD])[0] == i

    def test_full_raises(self):
        t = HostTable(nbuckets=2, key_words=1, val_words=1, stash=2)
        with pytest.raises(RuntimeError):
            for i in range(1, 100):
                t.insert([i], [i])


class TestDeviceLookup:
    def test_matches_host(self):
        t = HostTable(nbuckets=128, key_words=2, val_words=3)
        rng = np.random.default_rng(7)
        keys = rng.integers(0, 2**32, size=(300, 2), dtype=np.uint32)
        keys = np.unique(keys, axis=0)
        for i, k in enumerate(keys):
            t.insert(k, [i, i * 2, i * 3])

        state = t.device_state()
        # present keys + some absent ones
        absent = rng.integers(0, 2**32, size=(50, 2), dtype=np.uint32)
        queries = np.concatenate([keys[:100], absent], axis=0)
        res = device_lookup(state, jnp.asarray(queries), t.nbuckets, t.stash)
        found = np.asarray(res.found)
        vals = np.asarray(res.vals)
        host_vals = t.lookup_batch_host(queries)
        for i in range(100):
            assert found[i], f"key {queries[i]} not found on device"
            assert vals[i].tolist() == host_vals[i].tolist()
        # absent keys: not found unless they collide with a real key (unique'd)
        present = {tuple(k) for k in keys}
        for i in range(100, len(queries)):
            if tuple(queries[i]) not in present:
                assert not found[i]

    def test_stash_entries_visible(self):
        # Force stash use with a tiny table.
        t = HostTable(nbuckets=2, key_words=1, val_words=1, stash=8)
        inserted = []
        try:
            for i in range(1, 50):
                t.insert([i], [i * 10])
                inserted.append(i)
        except RuntimeError:
            pass
        state = t.device_state()
        q = make_queries([[i] for i in inserted], 1)
        res = device_lookup(state, q, t.nbuckets, t.stash)
        assert bool(jnp.all(res.found))
        assert np.asarray(res.vals)[:, 0].tolist() == [i * 10 for i in inserted]

    def test_incremental_update(self):
        t = HostTable(nbuckets=64, key_words=1, val_words=1)
        t.insert([1], [11])
        state = t.device_state()
        assert t.dirty_count() == 0

        t.insert([2], [22])
        t.insert([1], [111])  # update
        upd = t.make_update(max_slots=8)
        state = apply_update(state, upd)
        res = device_lookup(state, make_queries([[1], [2], [3]], 1), t.nbuckets, t.stash)
        assert np.asarray(res.found).tolist() == [True, True, False]
        assert np.asarray(res.vals)[:2, 0].tolist() == [111, 22]

        t.delete([1])
        state = apply_update(state, t.make_update(max_slots=8))
        res = device_lookup(state, make_queries([[1]], 1), t.nbuckets, t.stash)
        assert not bool(res.found[0])

    def test_update_bounded_and_resumable(self):
        t = HostTable(nbuckets=64, key_words=1, val_words=1)
        state = t.device_state()
        for i in range(1, 21):
            t.insert([i], [i])
        assert t.dirty_count() == 20
        state = apply_update(state, t.make_update(max_slots=8))
        assert t.dirty_count() == 12
        state = apply_update(state, t.make_update(max_slots=8))
        state = apply_update(state, t.make_update(max_slots=8))
        assert t.dirty_count() == 0
        q = make_queries([[i] for i in range(1, 21)], 1)
        res = device_lookup(state, q, t.nbuckets, t.stash)
        assert bool(jnp.all(res.found))

    def test_jit_compatible(self):
        t = HostTable(nbuckets=64, key_words=2, val_words=2)
        t.insert([7, 8], [70, 80])
        state = t.device_state()
        f = jax.jit(lambda s, q: device_lookup(s, q, 64, t.stash))
        res = f(state, make_queries([[7, 8]], 2))
        assert bool(res.found[0])
        assert np.asarray(res.vals)[0].tolist() == [70, 80]


def build_table(nbuckets, K, V, stash, n_entries, seed):
    rng = np.random.default_rng(seed)
    t = HostTable(nbuckets, K, V, stash=stash, name="t")
    keys = rng.integers(0, 2**32, size=(n_entries, K), dtype=np.uint32)
    keys = np.unique(keys, axis=0)
    vals = rng.integers(0, 2**32, size=(len(keys), V), dtype=np.uint32)
    for i in range(len(keys)):
        t.insert(keys[i], vals[i])
    return t, keys


def query_mix(keys, K, B, seed, miss_frac=0.3):
    """Hits + misses + in-batch duplicates."""
    rng = np.random.default_rng(seed + 1)
    if len(keys):
        q = keys[rng.integers(0, len(keys), B)].copy()
    else:
        q = np.zeros((B, K), np.uint32)
    miss = rng.random(B) < miss_frac
    q[miss] = rng.integers(0, 2**32, size=(int(miss.sum()), K),
                           dtype=np.uint32)
    return q


# every table geometry the repo ships, plus the edge shapes:
#   (nbuckets, K, V, stash, n_entries, B)
GEOMETRIES = [
    pytest.param(1 << 8, 2, 8, 64, 200, 256, id="dhcp-sub"),
    pytest.param(1 << 6, 1, 8, 64, 100, 64, id="vlan-small-batch"),
    pytest.param(1 << 6, 8, 8, 64, 100, 300, id="cid-k8-kw16"),
    pytest.param(1 << 8, 4, 16, 64, 300, 512, id="nat-sessions-v16"),
    pytest.param(1 << 8, 4, 8, 64, 300, 512, id="nat-reverse-v8"),
    pytest.param(1 << 3, 2, 8, 32, 38, 128, id="overfull-stash-hits"),
    pytest.param(1 << 8, 2, 8, 0, 100, 128, id="no-stash"),
    pytest.param(1 << 6, 2, 8, 64, 0, 128, id="empty-table"),
    # the 1M-subscriber sub-table geometry (K=2, V=8, stash=256) at
    # reduced nbuckets — same shapes/dtypes, CI-sized population
    pytest.param(1 << 12, 2, 8, 256, 6000, 1024, id="1m-geometry-reduced"),
    pytest.param(1 << 6, 1, 8, 64, 120, 96, id="antispoof-garden-k1"),
    pytest.param(1 << 7, 1, 8, 64, 300, 256, id="sub-nat-k1-loaded"),
    pytest.param(1 << 6, 3, 8, 16, 150, 200, id="k3-odd-key"),
    pytest.param(1 << 4, 4, 16, 64, 100, 64, id="sessions-overfull-stash"),
    pytest.param(1 << 8, 2, 8, 64, 400, 4096, id="batch-4096"),
    pytest.param(1 << 2, 2, 8, 8, 20, 33, id="four-buckets-odd-batch"),
]


class TestDeviceLookupGeometries:
    @pytest.mark.parametrize("nbuckets,K,V,stash,n,B", GEOMETRIES)
    def test_device_lookup_equals_host(self, nbuckets, K, V, stash, n, B):
        t, keys = build_table(nbuckets, K, V, stash, n, seed=nbuckets + K)
        q = query_mix(keys, K, B, seed=nbuckets)
        got = device_lookup(t.device_state(), jnp.asarray(q), nbuckets, stash)
        found = np.asarray(got.found)
        assert np.array_equal(
            np.where(found[:, None], np.asarray(got.vals), 0),
            t.lookup_batch_host(q))
        assert np.array_equal(
            found, np.array([t.lookup(k) is not None for k in q]))

    def test_stash_geometry_actually_exercises_stash(self):
        """The overfull geometry must place entries in the stash, or the
        stash-broadcast path of the probe is untested."""
        t, _ = build_table(1 << 3, 2, 8, 32, 38, seed=10)
        assert int(np.count_nonzero(
            np.asarray(t.device_state().stash_rows)[:, 2])) > 0

    def test_nonaligned_batch_padding(self):
        """B not a multiple of 128, below it and straddling it."""
        t, keys = build_table(1 << 6, 2, 8, 64, 80, seed=3)
        state = t.device_state()
        for B in (7, 129):
            q = query_mix(keys, 2, B, seed=B)
            got = device_lookup(state, jnp.asarray(q), t.nbuckets, t.stash)
            found = np.asarray(got.found)
            assert np.array_equal(
                np.where(found[:, None], np.asarray(got.vals), 0),
                t.lookup_batch_host(q)), B

    def test_probe_keeps_wide_row_shape(self):
        """The cascade probes via 2 packed [1,32] row gathers (the
        test_hlo_structure contract, pinned at the probe alone so a
        regression is attributable)."""
        t, keys = build_table(1 << 10, 2, 8, 64, 500, seed=6)

        def look(state, q):
            r = device_lookup(state, q, t.nbuckets, t.stash)
            return r.found, r.slot, r.vals

        hlo = jax.jit(look).lower(t.device_state(),
                                  jnp.asarray(keys[:256])).as_text()
        assert len(re.findall(r"slice_sizes = array<i64: 1, 32>", hlo)) == 2

    def test_slot_values_match_host_placement(self):
        """slot indices agree with the host mirror's physical placement
        (the device-authoritative writers — NAT accounting — scatter by
        these slots, so they must be placement-exact, not just
        found-consistent)."""
        t, keys = build_table(1 << 5, 2, 8, 16, 100, seed=8)
        got = device_lookup(t.device_state(), jnp.asarray(keys[:64]),
                            t.nbuckets, t.stash)
        assert np.asarray(got.found).all()
        assert [int(s) for s in np.asarray(got.slot)] == [
            t._find_slot(k) for k in keys[:64]]


class TestWidenedRowCheckpointCompat:
    """The row widenings (nat reverse 4->8, pppoe 6->8) must not
    cold-start pre-upgrade checkpoints: a declared pure-pad historical
    width restores with the value rows zero-padded; anything undeclared
    still rejects (reject-on-mismatch is the default)."""

    def test_narrow_checkpoint_pads_into_widened_table(self):
        old = HostTable(1 << 5, 4, 4, stash=8, name="nat_reverse")
        key = np.arange(4, dtype=np.uint32)
        old.insert(key, np.asarray([9, 8, 7, 6], dtype=np.uint32))
        arrays = {k: v.copy() for k, v in old.checkpoint_arrays().items()}
        geom = old.checkpoint_geom()

        new = HostTable(1 << 5, 4, 8, stash=8, name="nat_reverse",
                        compat_val_pad_from=(4,))
        assert new.restore_arrays(arrays, geom) == 1
        got = new.lookup(key)
        assert got is not None
        assert list(got) == [9, 8, 7, 6, 0, 0, 0, 0]

    def test_undeclared_width_still_rejects(self):
        old = HostTable(1 << 5, 4, 4, stash=8, name="t")
        arrays = old.checkpoint_arrays()
        geom = old.checkpoint_geom()
        new = HostTable(1 << 5, 4, 8, stash=8, name="t")  # no compat decl
        with pytest.raises(ValueError):
            new.restore_arrays(arrays, geom)

    def test_live_nat_and_pppoe_tables_declare_compat(self):
        from bng_tpu.control.nat import NATManager
        from bng_tpu.runtime.tables import PPPoEFastPathTables
        from bng_tpu.utils.net import ip_to_u32

        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         sessions_nbuckets=1 << 8, sub_nat_nbuckets=1 << 8)
        assert nat.reverse.compat_val_pad_from == (4,)
        pp = PPPoEFastPathTables(nbuckets=1 << 8)
        assert pp.by_sid.compat_val_pad_from == (6,)
        assert pp.by_ip.compat_val_pad_from == (6,)


def _make_update_old_body(t: HostTable, max_slots: int):
    """HostTable.make_update as it was before a clean table answered with
    the batch already on the chip: six fresh arrays and six uploads, dirty
    or not. Kept here as the plain reference the new one is held to."""
    from bng_tpu.ops.table import TableUpdate

    if t._dirty_all:
        raise RuntimeError(f"table {t.name!r}: full upload first")
    take = sorted(t._dirty)[:max_slots]
    t._dirty.difference_update(take)
    base = t.nbuckets * WAYS
    b_take = sorted({s // WAYS for s in take if s < base})
    s_take = [s - base for s in take if s >= base]
    U = max_slots
    bidx = np.full((U,), t.nbuckets, dtype=np.int32)
    brows = np.zeros((U, WAYS * t.KW), dtype=np.uint32)
    sidx = np.full((U,), t.stash, dtype=np.int32)
    srows = np.zeros((U, t.KW), dtype=np.uint32)
    idx = np.full((U,), t.S, dtype=np.int32)
    vv = np.zeros((U, t.V), dtype=np.uint32)
    if b_take:
        bs = np.asarray(b_take, dtype=np.int32)
        bidx[: len(bs)] = bs
        brows[: len(bs)] = t._pack_bucket_rows(bs, mask_dirty=True)
    if s_take:
        ss = np.asarray(s_take, dtype=np.int32)
        sidx[: len(ss)] = ss
        srows[: len(ss)] = t._pack_stash_rows(ss)
    if take:
        ts = np.asarray(take, dtype=np.int32)
        idx[: len(ts)] = ts
        vv[: len(ts)] = t.vals[ts]
    return TableUpdate(bidx=jnp.asarray(bidx), brows=jnp.asarray(brows),
                       sidx=jnp.asarray(sidx), srows=jnp.asarray(srows),
                       idx=jnp.asarray(idx), vals=jnp.asarray(vv))


def _twin_tables(where: str):
    """Two tables with the same rows, uploaded whole and clean; `where`
    says which kind of slot the next write lands in."""
    out = []
    for _ in range(2):
        t = HostTable(nbuckets=2, key_words=1, val_words=2, stash=8, name=where)
        # stash: fill both buckets' ways first, so the write spills
        for i in range(1, 1 + (2 * WAYS if where == "stash" else 3)):
            t.insert([i], [i * 10, i])
        out.append(t)
    return out


@pytest.mark.parametrize("where", ["bucket", "stash"])
class TestCleanTableDrainsTheBatchOnTheChip:
    """Only what changed is uploaded (PR 35): a clean table's make_update
    is empty_update's batch, leaf for leaf the same objects; one dirty
    slot builds and ships what the old body built."""

    def test_clean_make_update_is_the_cached_batch(self, where):
        t, _ = _twin_tables(where)
        t.device_state()
        empty = t.empty_update(8)
        got = t.make_update(8)
        assert all(a is b for a, b in zip(got, empty))
        assert t.make_update(8) is t.empty_update(8)  # and again: no rebuild
        assert t.make_update(4) is t.empty_update(4)  # a cache a size
        assert t.make_update(4) is not empty
        # dirty tracking is left alone: nothing queued, nothing invented
        assert t.dirty_count() == 0 and not t._dirty_all

    def test_one_dirty_slot_builds_what_the_old_body_built(self, where):
        new, old = _twin_tables(where)
        st_new, st_old = new.device_state(), old.device_state()
        key = 100
        for t in (new, old):
            t.insert([key], [7, 9])
            # full buckets: the eviction walk ends in the stash
            assert any(s >= t.nbuckets * WAYS for s in t._dirty) == (
                where == "stash")
        u_new, u_old = new.make_update(16), _make_update_old_body(old, 16)
        assert u_new is not new.empty_update(16)
        for name, a, b in zip(u_new._fields, u_new, u_old):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
        st_new, st_old = apply_update(st_new, u_new), apply_update(st_old, u_old)
        for name, a, b in zip(st_new._fields, st_new, st_old):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
        res = device_lookup(st_new, make_queries([[key]], 1), new.nbuckets,
                            new.stash)
        assert bool(res.found[0]) and np.asarray(res.vals)[0].tolist() == [7, 9]
        # drained: the next one is the cached batch again, and applying it
        # changes nothing
        assert new.dirty_count() == 0
        again = new.make_update(16)
        assert again is new.empty_update(16)
        st_same = apply_update(st_new, again)
        for a, b in zip(st_same, st_new):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_dirty_all_raises_before_the_cache_answers(self, where):
        t = HostTable(nbuckets=64, key_words=1, val_words=2,
                      stash=4 if where == "bucket" else 8, name=where)
        t.device_state()
        assert t.make_update(8) is t.empty_update(8)  # the cache is warm
        n = t.stash + 1  # a bulk build beyond the stash abandons the deltas
        t.bulk_insert(np.arange(1, 1 + n, dtype=np.uint32)[:, None],
                      np.ones((n, 2), np.uint32))
        assert t._dirty_all and not t._dirty
        with pytest.raises(RuntimeError, match="full upload"):
            t.make_update(8)
        t.device_state()
        assert t.make_update(8) is t.empty_update(8)
