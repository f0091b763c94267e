"""HBM-resident cuckoo hash tables — the TPU replacement for eBPF maps.

Design rationale (vs. the reference's kernel hash maps, bpf/maps.h:99-234):

- eBPF maps are pointer-chasing hash tables updated from both kernel and
  userspace. TPUs have no pointers and no atomics visible to XLA, but they
  have enormous gather bandwidth. So tables are structure-of-arrays uint32
  buffers in HBM, and lookup is **bucketized cuckoo hashing**: exactly two
  vectorized gathers of 4-way buckets per probe batch — branch-free, fixed
  cost, ideal for the VPU. (The reference already bounds probe loops to 64
  for the BPF verifier, bpf/nat44.c:423 — we go further: bound of 2.)
- The **host is the single writer** (insert/delete/relocate run on a numpy
  mirror; the device only gathers). This mirrors the reference's design
  where the Go slow path populates the fast-path cache
  (pkg/dhcp/server.go:1057-1097) and means no device-side synchronization
  is ever needed. Dirty slots are applied to the device copy as a bounded
  scatter inside the jitted step (see `TableUpdate` / `apply_update`).
- Cuckoo relocations on insert happen host-side; an insert that fails after
  MAX_KICKS goes to a small linear **stash** which the device compares
  against with one broadcast — the overflow path the reference gets from
  htab chaining.

Capacity sizing: ways=4 buckets sustain >90% load factor, so a 1M-entry
subscriber table (bpf/maps.h:10 MAX_SUBSCRIBERS) fits in 2^18 buckets x 4.

Writing a table inside the step: scatter whole rows (`vals.at[idx].set /
.add / .max(rows)`), which a TPU does natively, duplicates or not. A
part-row window (`vals.at[idx, a:b]`) compiles to a serial `while` of one
`dynamic-update-slice` a lane, and a single column (`vals.at[idx, c]`) to
two relayouts of the whole table around a flat scatter (PERF.md section 6,
PR 29: 34 ms of a 94 ms step for 8,192 lanes into `u32[2097216, 16]`).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from bng_tpu.ops.hashing import (SEED1, SEED2, hash_words, hash_words_int,
                                 mix32)
from bng_tpu.telemetry import spans as tele

WAYS = 4  # slots per bucket; one bucket = one contiguous gather
MAX_KICKS = 128  # bounded cuckoo eviction walk (host side)
# both hash functions in one pass over a batch of keys (HostTable.probe):
# a [2, 1] seed column broadcasts every word array to [2, n]
_SEEDS = np.array([[SEED1], [SEED2]], dtype=np.uint32)
_WAY = np.arange(WAYS)


def nbuckets_for(entries: int) -> int:
    """Bucket count that holds `entries` keys at about 50% load of the
    4-way buckets, a power of two and at least 2^10 — the one sizing
    rule behind every table built from a subscriber or flow count
    (`bng run` capacities, `bng loadtest`)."""
    return 1 << max(10, (entries // 2).bit_length())


def way_stride(key_words: int) -> int:
    """Words per way in the packed probe rows: key words + used flag,
    rounded up to a multiple of 8 — narrow (<8-word) gathers serialize at
    ~7ns/element on v5e while >=8-word row gathers run at full speed
    (PERF_NOTES §2; same finding drove ops/qtable.py)."""
    return ((key_words + 1 + 7) // 8) * 8


class TableState(NamedTuple):
    """Device-side table arrays (a pytree; all uint32).

    The probe data (keys + used) is bucket-packed: one [WAYS*KW]-word row
    per bucket, KW = way_stride(K), each way carrying its K key words then
    the used flag at word K. A probe is two wide row gathers — the
    narrow per-way key/used gathers of rounds 1-2 never appear. The host
    is the single writer of krows/stash_rows, so updates scatter whole
    bucket rows with no clobber hazard; vals keeps per-slot granularity
    because device kernels write it (NAT session accounting).

    krows:      [NB, WAYS*KW]  packed bucket probe rows
    stash_rows: [stash, KW]    packed stash probe rows
    vals:       [S, V]         value words; S = NB*WAYS + stash
    """

    krows: jax.Array
    stash_rows: jax.Array
    vals: jax.Array


class TableUpdate(NamedTuple):
    """A bounded batch of dirty rows/slots to scatter into a TableState.

    Index rows >= the target's length are dropped by the scatter (padding).
    A dirty slot's whole bucket row rides along (the host mirror knows all
    four ways), value updates stay slot-granular.
    """

    bidx: jax.Array  # [U] int32 bucket indices
    brows: jax.Array  # [U, WAYS*KW] uint32 replacement bucket rows
    sidx: jax.Array  # [U] int32 stash-local indices
    srows: jax.Array  # [U, KW] uint32 replacement stash rows
    idx: jax.Array  # [U] int32 global slots (val updates)
    vals: jax.Array  # [U, V] uint32


class LookupResult(NamedTuple):
    found: jax.Array  # [B] bool
    slot: jax.Array  # [B] int32 (valid only where found; owner-local if sharded)
    vals: jax.Array  # [B, V] uint32 (zeros where not found)
    # sharded lookups only: lane exceeded the per-destination exchange
    # capacity and was NOT probed (found=False there too) — the caller's
    # slow path must treat it as a miss-with-retry, not a definitive miss
    punted: jax.Array | None = None


class TableGeom(NamedTuple):
    """Static geometry of one table, plus optional ICI sharding.

    axis=None: the table is chip-local (or replicated) — plain 2-gather
    lookup. axis="x": the table is hash-sharded across `n_shards` devices
    on mesh axis "x"; lookups ride an all-to-all key/result exchange
    (see sharded_lookup). This is the TPU re-expression of the reference's
    hash-partitioned tables across nodes (SURVEY.md §2.3: Nexus hashring /
    rendezvous placement).
    """

    nbuckets: int
    stash: int
    axis: str | None = None
    n_shards: int = 1
    # sharded exchange sizing: per-destination capacity = ceil(b/N) *
    # capacity_factor (rounded up to 8 lanes). At factor f the exchange
    # moves f/N of the worst-case traffic; lanes beyond a destination's
    # capacity punt to the slow path (see sharded_lookup). factor >= N
    # reproduces the exact worst-case (never-punt) exchange.
    capacity_factor: float = 2.0


# shard-owner hash seed — distinct from the cuckoo bucket seeds so shard
# routing and in-table placement are independent
SEED_SHARD = np.uint32(0xC2B2AE35)


def shard_owner(query_words, n_shards: int):
    """Owner shard of each key: mix(key) % n_shards. Host (numpy) and
    device (jnp) both call this — routing must agree bit-for-bit."""
    h = hash_words(query_words, SEED_SHARD)
    return h % np.uint32(n_shards)


def apply_update(state: TableState, upd: TableUpdate) -> TableState:
    """Scatter dirty rows into the device table (inside jit, donated) —
    three wide row scatters (bucket rows, stash rows, value rows)."""
    return TableState(
        krows=state.krows.at[upd.bidx].set(upd.brows, mode="drop"),
        stash_rows=state.stash_rows.at[upd.sidx].set(upd.srows, mode="drop"),
        vals=state.vals.at[upd.idx].set(upd.vals, mode="drop"),
    )


def exchange_capacity(b: int, g: TableGeom) -> int:
    """Per-destination lane capacity of the sharded exchange for a local
    batch of b lanes: factor x the balanced share, 8-aligned, capped at b.
    Single source of truth — tests assert punt boundaries against this."""
    return min(b, max(8, int(-(-b // g.n_shards) * g.capacity_factor + 7) & ~7))


def lookup(state: TableState, query: jax.Array, g: TableGeom) -> LookupResult:
    """Geometry-dispatched lookup: local 2-gather probe, or sharded
    all-to-all exchange when g.axis names a mesh axis."""
    if g.axis is None or g.n_shards == 1:
        return device_lookup(state, query, g.nbuckets, g.stash)
    return sharded_lookup(state, query, g)


def sharded_lookup(state: TableState, query: jax.Array, g: TableGeom) -> LookupResult:
    """Cross-chip lookup via MoE-style dispatch over ICI.

    Must run inside shard_map over mesh axis g.axis. Each chip holds one
    hash-shard of the table (an independent cuckoo table) and a local
    [b, K] query batch. Only keys and result rows ride the interconnect —
    packets never move:

      1. owner = shard_owner(key) for each lane
      2. keys are packed into a [N, C, K] per-destination buffer with
         C = ceil(b/N) * capacity_factor (round-1 ask #7: the worst-case
         C = b exchange moved N*b rows per collective, N x the useful
         traffic on an N-chip mesh). Lanes past a destination's capacity
         PUNT: returned found=False + punted=True so the slow path
         retries them (a bounded-skew batch never punts; a pathological
         all-one-shard batch degrades to slow path instead of reserving
         worst-case ICI bandwidth on every batch)
      3. lax.all_to_all exchanges request buffers (one ICI shuffle)
      4. each chip probes its local shard for all received keys
      5. a second all_to_all returns results; lane i reads its
         (owner, position) cell

    The reference does this routing with HTTP forwards to the hashring
    owner (pkg/nexus/client.go:487-577, pkg/pool/peer.go:230-368); here
    it is two ICI collectives per batch.
    """
    b, K = query.shape
    N = g.n_shards
    C = exchange_capacity(b, g)
    words = [query[:, k] for k in range(K)]
    owner = shard_owner(words, N).astype(jnp.int32)  # [b]

    onehot = (owner[:, None] == jnp.arange(N, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1, owner[:, None], axis=1)[:, 0]
    fits = pos < C
    flat = jnp.where(fits, owner * C + pos, N * C)  # overflow -> dropped

    req = jnp.zeros((N * C, K), dtype=jnp.uint32).at[flat].set(query, mode="drop")
    req = req.reshape(N, C, K)
    req_recv = jax.lax.all_to_all(req, g.axis, split_axis=0, concat_axis=0, tiled=True)

    local = device_lookup(state, req_recv.reshape(N * C, K), g.nbuckets, g.stash)
    # pack found/slot/vals into ONE response buffer -> one return collective
    # (three separate all_to_alls would triple the response latency)
    V = local.vals.shape[1]
    packed = jnp.concatenate(
        [local.vals,
         local.found.astype(jnp.uint32)[:, None],
         local.slot.astype(jnp.uint32)[:, None]],
        axis=1,
    ).reshape(N, C, V + 2)
    resp = jax.lax.all_to_all(packed, g.axis, split_axis=0, concat_axis=0, tiled=True)

    cell = resp[owner, jnp.minimum(pos, C - 1)]  # [b, V+2]
    return LookupResult(
        found=(cell[:, V] != 0) & fits,
        slot=cell[:, V + 1].astype(jnp.int32),
        vals=jnp.where(fits[:, None], cell[:, :V], 0),
        punted=~fits,
    )


def device_lookup(state: TableState, query: jax.Array, nbuckets: int, stash: int) -> LookupResult:
    """Branch-free batched lookup (every hot-path kernel funnels here:
    DHCP 3-tier chain, NAT44 forward/reverse, antispoof, garden, PPPoE,
    and the sharded step's local probe): 2 wide bucket-row gathers +
    stash broadcast + 1 value-row gather — no narrow gathers anywhere.

    query: [B, K] uint32 key words.
    """
    B, K = query.shape
    KW = state.stash_rows.shape[1]
    words = [query[:, k] for k in range(K)]
    mask = np.uint32(nbuckets - 1)
    b1 = (hash_words(words, SEED1) & mask).astype(jnp.int32)
    b2 = (hash_words(words, SEED2) & mask).astype(jnp.int32)

    r1 = state.krows[b1]  # [B, WAYS*KW] — the fast gather shape
    r2 = state.krows[b2]
    cand = jnp.concatenate(
        [r1.reshape(B, WAYS, KW), r2.reshape(B, WAYS, KW)], axis=1
    )  # [B, 2W, KW]
    cand_match = jnp.all(cand[:, :, :K] == query[:, None, :], axis=-1) & (
        cand[:, :, K] != 0
    )  # [B, 2W]
    ways = jnp.arange(WAYS, dtype=jnp.int32)[None, :]
    cand_slots = jnp.concatenate(
        [b1[:, None] * WAYS + ways, b2[:, None] * WAYS + ways], axis=1
    )  # [B, 2W]

    if stash > 0:
        base = nbuckets * WAYS
        sm = jnp.all(state.stash_rows[None, :, :K] == query[:, None, :], axis=-1) & (
            state.stash_rows[None, :, K] != 0
        )  # [B, stash]
        s_slots = jnp.broadcast_to(
            base + jnp.arange(stash, dtype=jnp.int32)[None, :], sm.shape
        )
        cand_slots = jnp.concatenate([cand_slots, s_slots], axis=1)
        cand_match = jnp.concatenate([cand_match, sm], axis=1)

    found = jnp.any(cand_match, axis=1)
    first = jnp.argmax(cand_match, axis=1)
    # slot select as a one-hot masked sum (VPU) — take_along_axis lowers
    # to an in-context gather (65µs at B=8192, PERF_NOTES §2)
    onehot = jnp.arange(cand_slots.shape[1], dtype=jnp.int32)[None, :] == first[:, None]
    slot = jnp.sum(jnp.where(onehot, cand_slots, 0), axis=1)
    vals = jnp.where(found[:, None], state.vals[slot], 0)
    return LookupResult(found=found, slot=slot, vals=vals)


def placed(owner, name: str, host: np.ndarray) -> jax.Array:
    """The device copy of `owner`'s small dense array `name` (a pools /
    server / hairpin / ranges / allowlist block that rides every update
    batch and is applied wholesale): placed once, and placed again only
    when the host array's bytes differ from what was last placed. The
    compare is on bytes (2 KB at the most), so a write in place is seen,
    and a write made before a drain is in that drain's batch. Like the
    empty_update cache this leans on no program donating its updates;
    the init upload (device_tables) must NOT come from here, the step
    donates the tables it is given."""
    cache = owner.__dict__.setdefault("_placed", {})
    now = host.tobytes()
    hit = cache.get(name)
    if hit is None or hit[0] != now:
        # from a copy: on the CPU backend asarray may alias host memory,
        # and the owner writes its array in place
        t0 = tele.t()
        hit = cache[name] = (now, jnp.asarray(host.copy()))
        tele.xfer(tele.UPLOAD, t0, host.nbytes)
    return hit[1]


class HostTable:
    """Host-authoritative mirror of one device table (numpy, single writer).

    insert/delete mutate the numpy arrays and record dirty slots; drain the
    dirty set with `make_update()` to get a fixed-size TableUpdate for the
    jitted step. This is the pkg/ebpf/loader.go map-CRUD role
    (loader.go:352-442) re-hosted: map writes become HBM scatters.
    """

    def __init__(self, nbuckets: int, key_words: int, val_words: int,
                 stash: int = 64, name: str = "",
                 compat_val_pad_from: tuple[int, ...] = ()):
        if nbuckets & (nbuckets - 1):
            raise ValueError("nbuckets must be a power of two")
        self.nbuckets = nbuckets
        self.K = key_words
        self.KW = way_stride(key_words)
        self.V = val_words
        self.stash = stash
        self.name = name
        # historical val_words this table's live layout is a PURE
        # zero-pad of (checkpoint restore migration — see restore_arrays)
        self.compat_val_pad_from = tuple(compat_val_pad_from)
        S = nbuckets * WAYS + stash
        self.S = S
        self.keys = np.zeros((S, key_words), dtype=np.uint32)
        self.vals = np.zeros((S, val_words), dtype=np.uint32)
        self.used = np.zeros((S,), dtype=np.uint32)
        self.count = 0
        self._dirty: set[int] = set()
        self._dirty_all = False  # set by large bulk_insert: full resync needed
        self._rng = np.random.default_rng(0xB46)

    # -- hashing (must match device_lookup exactly) --
    def _buckets(self, key: np.ndarray) -> tuple[int, int]:
        # one key: plain ints, masked by hand (hash_words_int is
        # hash_words bit for bit, and must match device semantics)
        words = [int(w) for w in key]
        m = self.nbuckets - 1
        return (hash_words_int(words, SEED1) & m,
                hash_words_int(words, SEED2) & m)

    def _find_slot(self, key: np.ndarray) -> int | None:
        b1, b2 = self._buckets(key)
        for b in (b1, b2):
            for w in range(WAYS):
                s = b * WAYS + w
                if self.used[s] and np.array_equal(self.keys[s], key):
                    return s
        base = self.nbuckets * WAYS
        for s in range(base, base + self.stash):
            if self.used[s] and np.array_equal(self.keys[s], key):
                return s
        return None

    def _place(self, s: int, key: np.ndarray, val: np.ndarray) -> None:
        self.keys[s] = key
        self.vals[s] = val
        self.used[s] = 1
        self._dirty.add(s)

    def insert(self, key, val) -> int:
        """Insert or update. Returns the slot index."""
        key = np.asarray(key, dtype=np.uint32).reshape(self.K)
        val = np.asarray(val, dtype=np.uint32).reshape(self.V)
        s = self._find_slot(key)
        if s is not None:  # update in place
            self.vals[s] = val
            self._dirty.add(s)
            return s

        cur_key, cur_val = key, val
        moves: list[tuple[int, np.ndarray, np.ndarray]] = []  # for rollback
        for _kick in range(MAX_KICKS):
            b1, b2 = self._buckets(cur_key)
            for b in (b1, b2):
                for w in range(WAYS):
                    slot = b * WAYS + w
                    if not self.used[slot]:
                        self._place(slot, cur_key, cur_val)
                        self.count += 1
                        return self._find_slot(key)  # original key's slot
                # both buckets full -> evict a random way from a random bucket
            b = b1 if self._rng.integers(2) == 0 else b2
            w = int(self._rng.integers(WAYS))
            slot = b * WAYS + w
            evict_key = self.keys[slot].copy()
            evict_val = self.vals[slot].copy()
            self._place(slot, cur_key, cur_val)
            moves.append((slot, evict_key, evict_val))
            cur_key, cur_val = evict_key, evict_val

        # eviction walk exhausted -> stash the displaced key
        base = self.nbuckets * WAYS
        for s in range(base, base + self.stash):
            if not self.used[s]:
                self._place(s, cur_key, cur_val)
                self.count += 1
                return self._find_slot(key)

        # Table genuinely full: roll the eviction walk back (otherwise the
        # last displaced key — possibly a long-standing entry — is lost).
        for slot, old_key, old_val in reversed(moves):
            self._place(slot, old_key, old_val)
        raise RuntimeError(f"table {self.name!r} full (count={self.count})")

    def _place_free(self, keys: np.ndarray, vals: np.ndarray, b1: np.ndarray,
                    b2: np.ndarray) -> tuple[list[np.ndarray], np.ndarray]:
        """Place absent, distinct keys into free ways of their buckets `b1`
        / `b2`: 8 vectorized passes (2 buckets x 4 ways), first-wins per
        slot within a pass (np.unique). Returns the slots filled, a pass
        each, and the mask of the keys that found every way taken (the
        cuckoo-kick walk's)."""
        unplaced = np.ones((len(keys),), dtype=bool)
        placed_slots: list[np.ndarray] = []
        for side in (b1, b2):
            for w in range(WAYS):
                idxs = np.nonzero(unplaced)[0]
                if len(idxs) == 0:
                    break
                slot = side[idxs] * WAYS + w
                free = self.used[slot] == 0
                idxs, slot = idxs[free], slot[free]
                if len(idxs) == 0:
                    continue
                # first-wins per slot within this pass
                uq_slot, first = np.unique(slot, return_index=True)
                take = idxs[first]
                self.keys[uq_slot] = keys[take]
                self.vals[uq_slot] = vals[take]
                self.used[uq_slot] = 1
                unplaced[take] = False
                placed_slots.append(uq_slot)
        self.count += sum(len(s) for s in placed_slots)
        return placed_slots, unplaced

    def bulk_insert(self, keys: np.ndarray, vals: np.ndarray) -> None:
        """Vectorized batch insert for initial table builds (1M-entry scale).

        The per-key `insert` path is a Python loop — fine for slow-path
        churn (hundreds/sec), infeasible for building the reference-scale
        1M-subscriber table (bpf/maps.h:10). This places a whole batch with
        8 vectorized passes (2 buckets x 4 ways, first-wins conflict
        resolution via np.unique) and falls back to the cuckoo-kick path
        only for the residue whose candidate slots were all taken (<1% at
        the sizing rule of ~50% load).

        Keys must be unique within the batch and not already present
        (bulk = initial build / bulk restore, not upsert). After a large
        bulk insert the dirty set is abandoned: call device_state() for a
        full upload, as startup does anyway.
        """
        keys = np.ascontiguousarray(np.asarray(keys, dtype=np.uint32).reshape(-1, self.K))
        vals = np.ascontiguousarray(np.asarray(vals, dtype=np.uint32).reshape(-1, self.V))
        n = len(keys)
        if n == 0:
            return
        words = [keys[:, k] for k in range(self.K)]
        m = np.uint32(self.nbuckets - 1)
        b1 = (hash_words(words, SEED1) & m).astype(np.int64)
        b2 = (hash_words(words, SEED2) & m).astype(np.int64)

        placed_slots, unplaced = self._place_free(keys, vals, b1, b2)

        residue = np.nonzero(unplaced)[0]
        for i in residue:  # cuckoo-kick / stash path for the stragglers
            self.insert(keys[i], vals[i])

        # dirty tracking: a large bulk build invalidates bounded-delta sync
        if n > self.stash:
            self._dirty.clear()
            self._dirty_all = True
        else:
            for s in placed_slots:
                self._dirty.update(int(x) for x in s)

    # -- batches on a live table (a retire's new flows: control/nat.py) --
    def probe(self, keys: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """`_find_slot` of every row of `keys` ([n, K] uint32) in one
        vector pass: both hashes at once, the eight candidate ways
        gathered and compared, the stash by one broadcast where it holds
        anything. Returns (slot, b1, b2): the slot of each key, -1 where
        the table does not hold it, and its two buckets, which
        `insert_many` takes back instead of hashing again."""
        n = len(keys)
        b = (hash_words([keys[:, k] for k in range(self.K)], _SEEDS)
             & np.uint32(self.nbuckets - 1)).astype(np.int64)  # [2, n]
        cand = (b.T[:, :, None] * WAYS + _WAY).reshape(n, 2 * WAYS)
        match = ((self.keys[cand] == keys[:, None, :]).all(-1)
                 & (self.used[cand] != 0))
        base = self.nbuckets * WAYS
        if self.used[base:].any():
            match = np.concatenate(
                [match, (self.keys[base:] == keys[:, None, :]).all(-1)
                 & (self.used[base:] != 0)], axis=1)
            cand = np.concatenate(
                [cand, np.broadcast_to(np.arange(base, self.S), (n, self.stash))],
                axis=1)
        row, first = np.arange(n), match.argmax(1)
        slot = np.where(match[row, first], cand[row, first], -1)
        return slot, b[0], b[1]

    def lookup_many(self, keys) -> tuple[np.ndarray, np.ndarray]:
        """`lookup` of every row of `keys` by one `probe`: (found [n] bool,
        vals [n, V] uint32, zeros where not found)."""
        keys = np.asarray(keys, dtype=np.uint32).reshape(-1, self.K)
        slot = self.probe(keys)[0]
        found = slot >= 0
        return found, np.where(found[:, None], self.vals[slot], 0).astype(np.uint32)

    def insert_many(self, keys, vals, probed=None) -> tuple[np.ndarray, dict]:
        """`insert` of every row in order, as one batch on a live table:
        hash once, probe once, a key the table holds updated in place, the
        rest placed into free ways by `bulk_insert`'s first-wins passes,
        and only the residue whose eight ways are all taken through
        `insert`'s kick walk one by one. EVERY slot written goes into the
        dirty set whatever the batch's length (`bulk_insert` above `stash`
        rows abandons it for a full upload: right at start-up, 134 MB in a
        serving window). A key that repeats takes its last value.

        `probed`: what `probe(keys)` returned, where the caller has it
        (its keys then are distinct). Returns (walked, failed): the rows
        that took the kick walk, and {row: RuntimeError} for those the
        walk found no room for (the table is full for that key alone; the
        rest of the batch is in)."""
        keys = np.ascontiguousarray(np.asarray(keys, dtype=np.uint32).reshape(-1, self.K))
        vals = np.ascontiguousarray(np.asarray(vals, dtype=np.uint32).reshape(-1, self.V))
        rows = np.arange(len(keys))  # the batch's row of each working row
        if probed is None:
            last = {k: i for i, k in enumerate(map(tuple, keys.tolist()))}
            if len(last) < len(keys):
                rows = np.fromiter(last.values(), dtype=np.int64, count=len(last))
                keys, vals = keys[rows], vals[rows]
            probed = self.probe(keys)
        slot, b1, b2 = probed
        held = slot >= 0
        if held.any():
            self.vals[slot[held]] = vals[held]
            self._dirty.update(slot[held].tolist())
            new = np.nonzero(~held)[0]
            keys, vals, b1, b2, rows = (a[new] for a in (keys, vals, b1, b2, rows))
        placed_slots, unplaced = self._place_free(keys, vals, b1, b2)
        for s in placed_slots:
            self._dirty.update(s.tolist())
        walked = np.nonzero(unplaced)[0]
        failed = {}
        for i in walked.tolist():
            try:
                self.insert(keys[i], vals[i])
            except RuntimeError as e:
                failed[int(rows[i])] = e
        return rows[walked], failed

    def delete(self, key) -> bool:
        key = np.asarray(key, dtype=np.uint32).reshape(self.K)
        s = self._find_slot(key)
        if s is None:
            return False
        self.used[s] = 0
        self.keys[s] = 0
        self.vals[s] = 0
        self.count -= 1
        self._dirty.add(s)
        return True

    def lookup(self, key) -> np.ndarray | None:
        key = np.asarray(key, dtype=np.uint32).reshape(self.K)
        s = self._find_slot(key)
        return self.vals[s].copy() if s is not None else None

    def update_val_words(self, key, word_idx: int, words) -> bool:
        """Patch specific value words of an existing entry (e.g. lease expiry)."""
        key = np.asarray(key, dtype=np.uint32).reshape(self.K)
        s = self._find_slot(key)
        if s is None:
            return False
        words = np.atleast_1d(np.asarray(words, dtype=np.uint32))
        self.vals[s, word_idx : word_idx + len(words)] = words
        self._dirty.add(s)
        return True

    # -- device synchronization --
    def _pack_bucket_rows(self, buckets: np.ndarray,
                          mask_dirty: bool = False) -> np.ndarray:
        """Packed [len(buckets), WAYS*KW] probe rows from the host mirror.

        mask_dirty=True (partial drains): ways whose slot is STILL dirty
        get used=0 in the row — a half-drained bucket must not expose a
        sibling whose value row has not shipped yet (it would read as a
        hit with stale/zero vals; a temporary miss just takes the slow
        path, which is the correct conservative behavior)."""
        nb = len(buckets)
        rows = np.zeros((nb, WAYS * self.KW), dtype=np.uint32)
        r3 = rows.reshape(nb, WAYS, self.KW)
        slots = buckets[:, None] * WAYS + np.arange(WAYS)[None, :]  # [nb, WAYS]
        r3[:, :, : self.K] = self.keys[slots]
        used = self.used[slots]
        if mask_dirty and self._dirty:
            still_dirty = np.isin(slots, np.fromiter(self._dirty, dtype=np.int64,
                                                     count=len(self._dirty)))
            used = np.where(still_dirty, 0, used)
        r3[:, :, self.K] = used
        return rows

    def _pack_stash_rows(self, sidx: np.ndarray) -> np.ndarray:
        """Packed [len(sidx), KW] stash probe rows (sidx is stash-local)."""
        rows = np.zeros((len(sidx), self.KW), dtype=np.uint32)
        g = self.nbuckets * WAYS + sidx
        rows[:, : self.K] = self.keys[g]
        rows[:, self.K] = self.used[g]
        return rows

    def device_state(self) -> TableState:
        """Full upload (startup / resync)."""
        self._dirty.clear()
        self._dirty_all = False
        return TableState(
            krows=jnp.asarray(self._pack_bucket_rows(np.arange(self.nbuckets))),
            stash_rows=jnp.asarray(self._pack_stash_rows(np.arange(self.stash))),
            vals=jnp.asarray(self.vals),
        )

    def dirty_count(self) -> int:
        return self.S if self._dirty_all else len(self._dirty)

    def mark_dirty(self, slots) -> int:
        """Queue slots for the next bounded update drain without touching
        their host rows — the delta-replay primitive (blue/green standby
        hydration diffs host arrays against a snapshot and re-ships only
        the changed slots). Returns the number of NEWLY queued slots
        (already-dirty slots don't add drain traffic and must not inflate
        the delta_rows report)."""
        before = len(self._dirty)
        self._dirty.update(int(s) for s in slots)
        return len(self._dirty) - before

    def make_update(self, max_slots: int) -> TableUpdate:
        """Drain up to max_slots dirty slots into a fixed-size TableUpdate
        on the device (`host_update`, uploaded). With nothing dirty the
        batch is `empty_update`'s: the one that is already on the device,
        so a clean table uploads nothing."""
        if not self.dirty_count():  # the batch that is already placed
            return self.empty_update(max_slots)
        host = self.host_update(max_slots)
        t0 = tele.t()  # a drain that ships something shows as calls
        upd = TableUpdate(*(jnp.asarray(a) for a in host))
        tele.xfer(tele.UPLOAD, t0, sum(a.nbytes for a in host), len(host))
        return upd

    def host_update(self, max_slots: int) -> TableUpdate:
        """Drain up to max_slots dirty slots into a fixed-size TableUpdate
        of host arrays (all padding where nothing is dirty): `make_update`
        uploads it; a caller that places batches itself takes it as it is
        (the sharded drain stacks one a shard over the mesh).

        Remaining dirty slots stay queued for the next batch (bounded
        host->HBM traffic per step, like bounded map-update syscalls).
        A drained bucket slot carries its whole (current) bucket row with
        still-dirty siblings masked used=0 (their vals have not shipped —
        see _pack_bucket_rows); each sibling rewrites the row on its own
        drain."""
        if self._dirty_all:
            raise RuntimeError(
                f"table {self.name!r}: bulk_insert invalidated delta sync; "
                "call device_state() for a full upload first")
        take = sorted(self._dirty)[:max_slots]
        self._dirty.difference_update(take)
        base = self.nbuckets * WAYS
        b_take = sorted({s // WAYS for s in take if s < base})
        s_take = [s - base for s in take if s >= base]

        U = max_slots
        bidx = np.full((U,), self.nbuckets, dtype=np.int32)  # NB = dropped
        brows = np.zeros((U, WAYS * self.KW), dtype=np.uint32)
        sidx = np.full((U,), self.stash, dtype=np.int32)
        srows = np.zeros((U, self.KW), dtype=np.uint32)
        idx = np.full((U,), self.S, dtype=np.int32)
        vv = np.zeros((U, self.V), dtype=np.uint32)
        if b_take:
            bs = np.asarray(b_take, dtype=np.int32)
            bidx[: len(bs)] = bs
            brows[: len(bs)] = self._pack_bucket_rows(bs, mask_dirty=True)
        if s_take:
            ss = np.asarray(s_take, dtype=np.int32)
            sidx[: len(ss)] = ss
            srows[: len(ss)] = self._pack_stash_rows(ss)
        n = len(take)
        if n:
            ts = np.asarray(take, dtype=np.int32)
            idx[:n] = ts
            vv[:n] = self.vals[ts]
        return TableUpdate(bidx=bidx, brows=brows, sidx=sidx, srows=srows,
                           idx=idx, vals=vv)

    def empty_update(self, max_slots: int) -> TableUpdate:
        """An all-padding TableUpdate (applying it is a no-op scatter).

        Built WITHOUT touching dirty tracking — the latency scheduler's
        no-drain bulk steps pass this instead of make_update() so pending
        host deltas stay queued for the next drain-cadence step rather
        than being consumed by a step that won't ship them; a clean
        make_update() returns it too. The result is cached per size: no
        program donates its update argument (`donate_argnums` is the
        tables, and the packet batch of the express programs) and no
        caller writes into a batch, so one device-resident copy serves
        every step that ships nothing (zero host->HBM traffic). A
        program that donated its updates would delete this cache."""
        cache = getattr(self, "_empty_upd_cache", None)
        if cache is None:
            cache = self._empty_upd_cache = {}
        upd = cache.get(max_slots)
        if upd is None:
            U = max_slots
            upd = cache[max_slots] = TableUpdate(
                bidx=jnp.full((U,), self.nbuckets, dtype=jnp.int32),
                brows=jnp.zeros((U, WAYS * self.KW), dtype=jnp.uint32),
                sidx=jnp.full((U,), self.stash, dtype=jnp.int32),
                srows=jnp.zeros((U, self.KW), dtype=jnp.uint32),
                idx=jnp.full((U,), self.S, dtype=jnp.int32),
                vals=jnp.zeros((U, self.V), dtype=jnp.uint32),
            )
        return upd

    # -- checkpoint/warm-restart (runtime/checkpoint.py) ----------------
    def checkpoint_geom(self) -> dict:
        """Geometry signature a checkpoint must match to be restorable:
        slot indices/hashes are only meaningful at identical shape."""
        return {"nbuckets": self.nbuckets, "key_words": self.K,
                "val_words": self.V, "stash": self.stash}

    def checkpoint_arrays(self) -> dict[str, np.ndarray]:
        """The complete host-authoritative mirror state (slot-exact, so a
        restore needs no rehash and preserves cuckoo/stash placement)."""
        return {"keys": self.keys, "vals": self.vals, "used": self.used}

    def restore_arrays(self, arrays: dict[str, np.ndarray],
                       geom: dict) -> int:
        """Overwrite the mirror from checkpointed arrays. Raises
        ValueError on any geometry/shape mismatch (reject-on-mismatch —
        a silently reshaped table would corrupt every later probe).
        Abandons delta tracking like bulk_insert: the caller must follow
        with a full device upload (device_state / resync_tables).
        Returns the restored row count.

        One sanctioned mismatch: a checkpoint whose val_words appears in
        `compat_val_pad_from` (a construction-time declaration that the
        live width is a PURE zero-pad of that historical layout — the
        ISSUE 11 row widenings) restores with the value rows
        zero-padded, so warm restarts and HA failover survive the
        upgrade instead of cold-starting away all session state. Any
        other difference still rejects: only the declaring table knows
        its old words kept their meaning."""
        live = self.checkpoint_geom()
        pad_vals_from = None
        if geom != live:
            narrow = dict(geom)
            vw = narrow.pop("val_words", None)
            wide = dict(live)
            wide.pop("val_words")
            if (narrow == wide and vw in self.compat_val_pad_from):
                pad_vals_from = int(vw)
            else:
                raise ValueError(
                    f"table {self.name!r}: checkpoint geometry {geom} != "
                    f"live geometry {live}")
        for name, target in (("keys", self.keys), ("vals", self.vals),
                             ("used", self.used)):
            src = arrays[name]
            expect = target.shape
            if name == "vals" and pad_vals_from is not None:
                expect = (target.shape[0], pad_vals_from)
            if src.shape != expect or src.dtype != target.dtype:
                raise ValueError(
                    f"table {self.name!r}: checkpoint array {name!r} is "
                    f"{src.dtype}{src.shape}, expected "
                    f"{target.dtype}{expect}")
            if name == "vals" and pad_vals_from is not None:
                target[:] = 0
                target[:, :pad_vals_from] = src
            else:
                target[:] = src
        self.count = int(np.count_nonzero(self.used))
        self._dirty.clear()
        self._dirty_all = True
        return self.count

    def lookup_batch_host(self, queries: np.ndarray) -> np.ndarray:
        """Reference host-side batched lookup (for tests)."""
        out = np.zeros((len(queries), self.V), dtype=np.uint32)
        for i, q in enumerate(queries):
            v = self.lookup(q)
            if v is not None:
                out[i] = v
        return out
