"""Bucket-packed QoS policy table — one lane-dense row gather per hash probe.

Why this exists (measured on a real v5e through the round-3 profiling
sessions): the generic cuckoo table (ops/table.py) stores keys as [S, K]
and occupancy as [S]. For the QoS table K=1, so a probe compiles to many
*narrow* gathers (1 uint32 per index). On TPU those lower at ~7ns/element
(58µs per 8192-lane gather, 16 gathers per lookup ≈ 1ms/batch) while
*row* gathers of >=8-word rows run at full speed (~13µs for [8192, 8]).
That one layout artifact made the QoS kernel the bottleneck of the whole
dataplane (VERDICT r2: 0.114 Mpps standalone, 65ms fixed cost).

So the QoS table is **way-granular**: a way is one 8-word row, and ALL
of a subscriber's state — policy AND mutable token state — lives in it:

        +0 key (subscriber ip)   +1 flags (bit0 = used)
        +2 rate_lo  +3 rate_hi   +4 burst  +5 priority
        +6 tokens (f32 bitcast)  +7 last_us

On the device the table is held **lane-dense**, in the one shape every op
of the step reads and writes it in: ``rows[nbuckets/4, 128]``, four
4-way buckets (sixteen ways) a stored row. Way ``s`` is words
``(s % 16) * 8 ..+8`` of stored row ``s // 16``; bucket ``b`` is words
``(b % 4) * 32 ..+32`` of stored row ``b // 4``. The host mirror, the
hashing, the slot numbering and the checkpoint stay way rows ``[S, 8]``;
``device_state()`` reshapes on the host, where it is free, and
``way_rows()`` is the one way back.

Why 128 words (measured on a v5e at the deployment's 1M policies, PR 33,
PERF.md section 6): a u32 array's minor dimension is tiled to 128 lanes.
Held as ``[nbuckets*4, 8]`` the 67 MB table had three physical forms —
the transposed one the compiler kept it in, the 8-in-128 padded one the
row scatters wanted (1.07 GB), and the ``[nbuckets, 32]`` one the probe
gathered (268 MB) — and every step copied the whole table between them:
eight whole-table ops for the two tables, 8.7 ms of a 27.0 ms step, paid
whatever the batch held. The 58 us a narrow gather costs had been
measured on a table small enough for those copies to be free. With a
minor dimension of 128 the tiling pads nothing and there is no cheaper
form to move to: no op has the whole array as operand but the in-place
scatters and the gathers themselves. Policy sync + QoS kernel alone,
B = 8192, 524,288 buckets, ms a call: ``[nbuckets*4, 8]`` 4.83,
``[nbuckets, 32]`` 1.27 (the compiler holds it transposed), this 0.98;
the fused step 27.0 -> 18.4 ms.

- A lookup is two [B, 128] stored-row gathers, the bucket's 32 words by
  a select among the row's four static slices, then branch-free lane
  compares — tokens included, no separate narrow token gather.
- The token writeback is ONE whole-row scatter at unique indices: the
  lanes come sorted by slot (ops/qos.py), the ways a batch rewrites in
  one stored row are merged as u32 deltas over the run, and the run's
  last lane sets the row (the form of ops/nat44.py's accounting). Never
  a part-row window (``rows.at[r, c:c+8]``): that compiles to a serial
  loop of one dynamic-update-slice a lane (PR 29: 34 ms of 94).
- Host policy sync is a read-modify-write of whole stored rows: the host
  ships each dirty stored row once with a mask of the ways it replaces,
  so sibling ways keep their device-authoritative tokens.

Parity: the row carries the same fields as the reference's
``struct token_bucket`` (bpf/qos_ratelimit.c:24-31); the host mirror
plays pkg/qos/manager.go's role (install/remove policies, single writer).
Cuckoo relocation happens host-side exactly like ops/table.py; a
relocated entry's bucket refills to full burst (documented divergence —
the host cannot read device tokens mid-flight, and a one-off burst grant
on policy churn is bounded and harmless).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from bng_tpu.ops.hashing import SEED1, SEED2, hash_words
from bng_tpu.telemetry import spans as tele

WAYS = 4
SLOT_W = 8  # words per way row
ROW_W = WAYS * SLOT_W  # 32 — one bucket
ROW_BUCKETS = 4  # buckets per stored row
ROW_SLOTS = ROW_BUCKETS * WAYS  # 16 ways per stored row
STORE_W = ROW_SLOTS * SLOT_W  # 128 — the device array's minor dimension
MAX_KICKS = 128

# word offsets within a way row
(QW_KEY, QW_FLAGS, QW_RATE_LO, QW_RATE_HI, QW_BURST, QW_PRIORITY,
 QW_TOKENS, QW_LAST_US) = range(8)
FLAG_USED = np.uint32(1)


def _f2u(v: float) -> int:
    return int(np.array(v, dtype=np.float32).view(np.uint32))


def _u2f(u: int) -> float:
    return float(np.array(u, dtype=np.uint32).view(np.float32))


def stored_rows(nbuckets: int) -> int:
    """Stored rows of a table's device array. A table of fewer than
    ROW_BUCKETS buckets is padded to one whole row; the tail is never
    hashed to."""
    return -(-nbuckets // ROW_BUCKETS)


def to_stored(way_rows: np.ndarray) -> np.ndarray:
    """Host way rows [S, 8] in the device's shape [R, 128] (a view from
    ROW_BUCKETS buckets up)."""
    S = way_rows.shape[0]
    R = stored_rows(S // WAYS)
    if R * ROW_SLOTS != S:
        way_rows = np.concatenate(
            [way_rows, np.zeros((R * ROW_SLOTS - S, SLOT_W), way_rows.dtype)])
    return way_rows.reshape(R, STORE_W)


def way_rows(rows, nbuckets: int) -> np.ndarray:
    """The device array (or a mesh-stacked one, [n, R, 128]) as way rows
    [..., S, 8] on the host — the one way out for whatever reads device
    state by slot (checkpoint fold, mirror audit)."""
    a = np.asarray(rows)
    return a.reshape(*a.shape[:-2], -1, SLOT_W)[..., : nbuckets * WAYS, :]


class QTableState(NamedTuple):
    """Device array (a pytree of one leaf; host writes policy words, the
    QoS kernel writes token state — both as whole stored-row scatters)."""

    rows: jax.Array  # [stored_rows(NB), 128] uint32, sixteen ways a row


class QTableUpdate(NamedTuple):
    """Bounded dirty-row batch (host -> device policy sync).

    Each dirty stored row ships once, with a bit per way the host
    replaces; the other ways keep their device-side token state. row >=
    stored_rows(NB) is dropped padding."""

    row: jax.Array  # [U] int32 stored-row indices, unique below the padding
    ways: jax.Array  # [U] uint32, bit k set: way k of the row is replaced
    rows: jax.Array  # [U, 128] uint32 the host's stored rows


class QTableGeom(NamedTuple):
    """Static geometry. axis/n_shards mirror TableGeom so the pipeline's
    chip-local guard logic reads the same fields (QoS tables are placed by
    subscriber affinity, never hash-sharded — see ops/qos.py)."""

    nbuckets: int
    axis: str | None = None
    n_shards: int = 1


class QLookup(NamedTuple):
    found: jax.Array  # [B] bool
    slot: jax.Array  # [B] int32 global slot (valid where found)
    row: jax.Array  # [B, 8] uint32 the selected way row (stale where not found)
    rate_lo: jax.Array  # [B] uint32
    rate_hi: jax.Array  # [B] uint32
    burst: jax.Array  # [B] uint32
    priority: jax.Array  # [B] uint32
    tokens: jax.Array  # [B] float32 (stale where not found)
    last_us: jax.Array  # [B] uint32


def apply_qupdate(state: QTableState, upd: QTableUpdate) -> QTableState:
    """Policy sync (inside jit): gather the batch's stored rows, take the
    host's words in the ways it replaces, set the rows back."""
    cur = state.rows.at[upd.row].get(mode="clip")
    bit = (upd.ways[:, None] >> jnp.arange(ROW_SLOTS, dtype=jnp.uint32)) & 1
    new = jnp.where(jnp.repeat(bit, SLOT_W, axis=1) != 0, upd.rows, cur)
    return QTableState(rows=state.rows.at[upd.row].set(new, mode="drop"))


def qlookup(state: QTableState, ip: jax.Array, g: QTableGeom) -> QLookup:
    """Branch-free probe: 2 stored-row gathers + lane compares.

    ip: [B] uint32 keys.
    """
    Bsz = ip.shape[0]
    mask = np.uint32(g.nbuckets - 1)
    b1 = (hash_words([ip], SEED1) & mask).astype(jnp.int32)
    b2 = (hash_words([ip], SEED2) & mask).astype(jnp.int32)

    def bucket_words(b):
        stored = state.rows[b // ROW_BUCKETS]  # [B, 128] from the array as it stands
        at = b % ROW_BUCKETS
        words = stored[:, :ROW_W]
        for i in range(1, ROW_BUCKETS):
            words = jnp.where((at == i)[:, None],
                              stored[:, i * ROW_W:(i + 1) * ROW_W], words)
        return words.reshape(Bsz, WAYS, SLOT_W)

    cand = jnp.concatenate([bucket_words(b1), bucket_words(b2)], axis=1)  # [B, 2W, 8]

    match = (cand[:, :, QW_KEY] == ip[:, None]) & (
        (cand[:, :, QW_FLAGS] & FLAG_USED) != 0
    )  # [B, 2W]
    found = jnp.any(match, axis=1)
    first = jnp.argmax(match, axis=1)  # [B] in [0, 2W)
    # way select as a one-hot masked sum (pure VPU) — the take_along_axis
    # form lowered to a 65µs in-context gather on v5e (PERF_NOTES §2)
    onehot = jnp.arange(2 * WAYS, dtype=jnp.int32)[None, :] == first[:, None]
    sel = jnp.sum(jnp.where(onehot[:, :, None], cand, 0), axis=1,
                  dtype=jnp.uint32)  # [B, 8]

    bucket = jnp.where(first < WAYS, b1, b2)
    slot = bucket * WAYS + (first % WAYS)

    return QLookup(
        found=found,
        slot=slot,
        row=sel,
        rate_lo=sel[:, QW_RATE_LO],
        rate_hi=sel[:, QW_RATE_HI],
        burst=sel[:, QW_BURST],
        priority=sel[:, QW_PRIORITY],
        tokens=jax.lax.bitcast_convert_type(sel[:, QW_TOKENS], jnp.float32),
        last_us=sel[:, QW_LAST_US],
    )


def write_token_rows(state: QTableState, slot: jax.Array, head: jax.Array,
                     old: jax.Array, tokens: jax.Array,
                     now_us: jax.Array) -> QTableState:
    """Device-side token writeback: every head lane's way gets its new
    +6/+7 — one whole stored-row scatter at unique indices.

    The lanes come SORTED by slot (ops/qos.py _prefix_consumed), so the
    lanes of one stored row are one run. slot: [B] int32, negative where
    the lane has no bucket; head: [B] bool, the lane that writes its way;
    old: [B, 2] uint32, the +6/+7 the lane read this same step; tokens:
    [B] float32. A head lane's change is the u32 difference new - old on
    its own two words: the ways are disjoint words, so the run's sum is
    exact, and the run's last lane sets row + sum (ops/nat44.py's form).
    """
    Bsz = slot.shape[0]
    R = state.rows.shape[0]
    tok_u = jax.lax.bitcast_convert_type(tokens.astype(jnp.float32), jnp.uint32)
    now_b = jnp.broadcast_to(now_us, (Bsz,)).astype(jnp.uint32)
    delta = jnp.where(head[:, None], jnp.stack([tok_u, now_b], axis=1) - old, 0)
    at = jnp.arange(ROW_SLOTS, dtype=jnp.int32)[None, :] == (slot % ROW_SLOTS)[:, None]
    lane = jnp.where(at[:, :, None], delta[:, None, :], 0).reshape(Bsz, -1)

    row = slot // ROW_SLOTS
    edge = row[1:] != row[:-1]
    run_head = jnp.concatenate([jnp.ones((1,), dtype=bool), edge])
    run_last = jnp.concatenate([edge, jnp.ones((1,), dtype=bool)])
    csum = jnp.cumsum(lane, axis=0)
    head_at = jax.lax.cummax(jnp.where(run_head, jnp.arange(Bsz), 0))
    run_sums = csum - (csum - lane)[head_at]

    # a run of lanes with a bucket holds a head; the others point past
    # the table and the scatter drops them
    idx = jnp.where(run_last & (slot >= 0), row, R)
    cur = state.rows.at[idx].get(mode="clip")
    sums = jnp.pad(run_sums.reshape(Bsz, ROW_SLOTS, 2),
                   ((0, 0), (0, 0), (QW_TOKENS, 0))).reshape(Bsz, STORE_W)
    return QTableState(rows=state.rows.at[idx].set(cur + sums, mode="drop"))


class HostQTable:
    """Host-authoritative mirror (numpy, single writer) of one QoS table.

    Same role as ops/table.py:HostTable (pkg/ebpf loader map-CRUD), with
    slot-granular dirty tracking: a policy change marks its way row dirty
    and the whole 8-word way (config + re-seeded tokens) is replaced.
    """

    def __init__(self, nbuckets: int, name: str = ""):
        if nbuckets & (nbuckets - 1):
            raise ValueError("nbuckets must be a power of two")
        self.nbuckets = nbuckets
        self.S = nbuckets * WAYS
        self.name = name
        self.rows = np.zeros((self.S, SLOT_W), dtype=np.uint32)
        self.count = 0
        self._dirty: set[int] = set()
        self._dirty_all = False
        self._rng = np.random.default_rng(0xB46)

    # -- hashing (must match qlookup bit-for-bit) --
    def _buckets(self, ip: int) -> tuple[int, int]:
        k = np.asarray([ip], dtype=np.uint32)
        m = np.uint32(self.nbuckets - 1)
        return int((hash_words([k], SEED1) & m)[0]), int((hash_words([k], SEED2) & m)[0])

    def _find(self, ip: int) -> int | None:
        b1, b2 = self._buckets(ip)
        for b in (b1, b2):
            for w in range(WAYS):
                s = self.rows[b * WAYS + w]
                if (s[QW_FLAGS] & 1) and int(s[QW_KEY]) == (ip & 0xFFFFFFFF):
                    return b * WAYS + w
        return None

    def _place(self, slot: int, ip: int, rate_bps: int, burst: int,
               priority: int, start_full: bool) -> int:
        s = self.rows[slot]
        s[QW_KEY] = ip & 0xFFFFFFFF
        s[QW_FLAGS] = 1
        s[QW_RATE_LO] = rate_bps & 0xFFFFFFFF
        s[QW_RATE_HI] = (rate_bps >> 32) & 0xFFFFFFFF
        s[QW_BURST] = burst
        s[QW_PRIORITY] = priority
        s[QW_TOKENS] = _f2u(float(burst if start_full else 0))
        s[QW_LAST_US] = 0
        self._dirty.add(slot)
        return slot

    def insert(self, ip: int, rate_bps: int, burst: int, priority: int = 0,
               start_full: bool = True) -> int:
        """Install or update a policy. Returns the global slot index."""
        hit = self._find(ip)
        if hit is not None:  # update config in place; re-seed tokens
            return self._place(hit, ip, rate_bps, burst, priority, start_full)

        cur = (ip, rate_bps, burst, priority, start_full)
        moves: list[tuple[int, np.ndarray]] = []
        for _ in range(MAX_KICKS):
            b1, b2 = self._buckets(cur[0])
            for b in (b1, b2):
                for w in range(WAYS):
                    if not (self.rows[b * WAYS + w][QW_FLAGS] & 1):
                        self._place(b * WAYS + w, *cur)
                        self.count += 1
                        hit = self._find(ip)
                        assert hit is not None
                        return hit
            # both buckets full -> evict a random way; relocated entries
            # refill to full burst (host can't read device tokens)
            b = b1 if self._rng.integers(2) == 0 else b2
            w = int(self._rng.integers(WAYS))
            slot = b * WAYS + w
            s = self.rows[slot].copy()
            moves.append((slot, s))
            ev_rate = int(s[QW_RATE_LO]) | (int(s[QW_RATE_HI]) << 32)
            self._place(slot, *cur)
            cur = (int(s[QW_KEY]), ev_rate, int(s[QW_BURST]), int(s[QW_PRIORITY]), True)

        for slot, s in reversed(moves):  # roll back, keep old entries
            self.rows[slot] = s
            self._dirty.add(slot)
        raise RuntimeError(
            f"qos table {self.name!r} full (count={self.count}, "
            f"nbuckets={self.nbuckets}); size buckets >= subscribers/2")

    def delete(self, ip: int) -> bool:
        slot = self._find(ip)
        if slot is None:
            return False
        self.rows[slot] = 0
        self.count -= 1
        self._dirty.add(slot)
        return True

    def lookup(self, ip: int) -> dict | None:
        slot = self._find(ip)
        if slot is None:
            return None
        s = self.rows[slot]
        return {
            "slot": slot,
            "rate_bps": int(s[QW_RATE_LO]) | (int(s[QW_RATE_HI]) << 32),
            "burst": int(s[QW_BURST]),
            "priority": int(s[QW_PRIORITY]),
            "tokens": _u2f(int(s[QW_TOKENS])),
        }

    def bulk_insert(self, ips: np.ndarray, rates_bps: np.ndarray,
                    bursts: np.ndarray, priorities: np.ndarray | None = None,
                    start_full: bool = True) -> None:
        """Vectorized initial build (1M-subscriber scale; see
        HostTable.bulk_insert for the pass structure). Keys must be new."""
        ips = np.asarray(ips, dtype=np.uint32).reshape(-1)
        rates = np.asarray(rates_bps, dtype=np.uint64).reshape(-1)
        bursts = np.asarray(bursts, dtype=np.uint32).reshape(-1)
        prios = (np.zeros_like(ips) if priorities is None
                 else np.asarray(priorities, dtype=np.uint32).reshape(-1))
        n = len(ips)
        if n == 0:
            return
        m = np.uint32(self.nbuckets - 1)
        b1 = (hash_words([ips], SEED1) & m).astype(np.int64)
        b2 = (hash_words([ips], SEED2) & m).astype(np.int64)

        flags = self.rows[:, QW_FLAGS].reshape(self.nbuckets, WAYS)
        unplaced = np.ones((n,), dtype=bool)
        for side in (b1, b2):
            for w in range(WAYS):
                idxs = np.nonzero(unplaced)[0]
                if len(idxs) == 0:
                    break
                bb = side[idxs]
                free = flags[bb, w] == 0
                idxs, bb = idxs[free], bb[free]
                if len(idxs) == 0:
                    continue
                uq_b, firsti = np.unique(bb, return_index=True)
                take = idxs[firsti]
                slots = uq_b * WAYS + w
                self.rows[slots, QW_KEY] = ips[take]
                self.rows[slots, QW_FLAGS] = 1
                self.rows[slots, QW_RATE_LO] = (rates[take] & 0xFFFFFFFF).astype(np.uint32)
                self.rows[slots, QW_RATE_HI] = (rates[take] >> 32).astype(np.uint32)
                self.rows[slots, QW_BURST] = bursts[take]
                self.rows[slots, QW_PRIORITY] = prios[take]
                self.rows[slots, QW_TOKENS] = (
                    bursts[take].astype(np.float32).view(np.uint32)
                    if start_full else _f2u(0.0))
                self.rows[slots, QW_LAST_US] = 0
                unplaced[take] = False
                self.count += len(take)
                if n <= 256:  # small batches stay on the bounded-delta path
                    self._dirty.update(int(s) for s in slots)

        for i in np.nonzero(unplaced)[0]:  # cuckoo-kick residue
            self.insert(int(ips[i]), int(rates[i]), int(bursts[i]), int(prios[i]),
                        start_full)

        if n > 256:
            self._dirty.clear()
            self._dirty_all = True

    # -- checkpoint/warm-restart (runtime/checkpoint.py) ----------------
    def checkpoint_geom(self) -> dict:
        return {"nbuckets": self.nbuckets}

    def checkpoint_arrays(self) -> dict[str, np.ndarray]:
        """The packed way rows carry policy AND token state — one array
        is the whole mirror."""
        return {"rows": self.rows}

    def restore_arrays(self, arrays: dict[str, np.ndarray],
                       geom: dict) -> int:
        """Overwrite the mirror from a checkpoint (reject-on-mismatch;
        abandons delta tracking like bulk_insert — caller must follow
        with a full device upload). Returns the restored policy count."""
        if geom != self.checkpoint_geom():
            raise ValueError(
                f"qos table {self.name!r}: checkpoint geometry {geom} != "
                f"live geometry {self.checkpoint_geom()}")
        src = arrays["rows"]
        if src.shape != self.rows.shape or src.dtype != self.rows.dtype:
            raise ValueError(
                f"qos table {self.name!r}: checkpoint rows are "
                f"{src.dtype}{src.shape}, expected "
                f"{self.rows.dtype}{self.rows.shape}")
        self.rows[:] = src
        self.count = int(np.count_nonzero(self.rows[:, QW_FLAGS] & 1))
        self._dirty.clear()
        self._dirty_all = True
        return self.count

    # -- device synchronization --
    def device_state(self) -> QTableState:
        self._dirty.clear()
        self._dirty_all = False
        return QTableState(rows=jnp.asarray(to_stored(self.rows)))

    def dirty_count(self) -> int:
        return self.S if self._dirty_all else len(self._dirty)

    def mark_dirty(self, slots) -> int:
        """Queue way rows for the next bounded drain without touching the
        host rows — the delta-replay primitive (see HostTable.mark_dirty).
        Returns the number of NEWLY queued slots (already-dirty ones add
        no drain traffic)."""
        before = len(self._dirty)
        self._dirty.update(int(s) for s in slots)
        return len(self._dirty) - before

    def make_update(self, max_slots: int) -> QTableUpdate:
        """Drain the dirty ways of up to max_slots stored rows into a
        QTableUpdate on the device (`host_update`, uploaded); a clean
        table's is `empty_update`'s, already placed."""
        if not self.dirty_count():  # the batch that is already placed
            return self.empty_update(max_slots)
        host = self.host_update(max_slots)
        t0 = tele.t()  # a drain that ships something shows as calls
        upd = QTableUpdate(*(jnp.asarray(a) for a in host))
        tele.xfer(tele.UPLOAD, t0, sum(a.nbytes for a in host), len(host))
        return upd

    def host_update(self, max_slots: int) -> QTableUpdate:
        """Drain the dirty ways of up to max_slots stored rows (bounded
        host->HBM traffic) into host arrays, all padding where nothing is
        dirty: each row ships once, whole, with a bit per dirty way (see
        HostTable.host_update for who takes it as it is)."""
        if self._dirty_all:
            raise RuntimeError(
                f"qos table {self.name!r}: bulk_insert invalidated delta sync; "
                "call device_state() for a full upload first")
        ss = np.asarray(sorted(self._dirty), dtype=np.int64)
        rr, first = np.unique(ss // ROW_SLOTS, return_index=True)
        n = min(len(rr), max_slots)
        if n < len(rr):  # the ways of the rows beyond the batch wait
            ss = ss[: first[n]]
        self._dirty.difference_update(ss.tolist())
        row = np.full((max_slots,), stored_rows(self.nbuckets), dtype=np.int32)
        ways = np.zeros((max_slots,), dtype=np.uint32)
        rows = np.zeros((max_slots, STORE_W), dtype=np.uint32)
        row[:n] = rr[:n]
        np.bitwise_or.at(ways, np.searchsorted(rr, ss // ROW_SLOTS),
                         np.uint32(1) << (ss % ROW_SLOTS).astype(np.uint32))
        rows[:n] = to_stored(self.rows)[rr[:n]]
        return QTableUpdate(row=row, ways=ways, rows=rows)

    def empty_update(self, max_slots: int) -> QTableUpdate:
        """All-padding QTableUpdate (no-op scatter), built without touching
        dirty tracking and cached per size — see HostTable.empty_update
        for the scheduler no-drain-step rationale."""
        cache = getattr(self, "_empty_upd_cache", None)
        if cache is None:
            cache = self._empty_upd_cache = {}
        upd = cache.get(max_slots)
        if upd is None:
            upd = cache[max_slots] = QTableUpdate(
                row=jnp.full((max_slots,), stored_rows(self.nbuckets),
                             dtype=jnp.int32),
                ways=jnp.zeros((max_slots,), dtype=jnp.uint32),
                rows=jnp.zeros((max_slots, STORE_W), dtype=jnp.uint32))
        return upd
