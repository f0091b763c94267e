"""Per-subscriber token-bucket rate limiting, batched.

TPU re-expression of bpf/qos_ratelimit.c. The eBPF program does a
read-modify-write of one token bucket per packet (qos_ratelimit.c:70-104);
on TPU a batch may contain many packets for the same subscriber, so the
sequential "consume if tokens suffice" semantics are recovered with a
**stable-sort segment prefix sum**: lanes sorted by bucket slot (stable,
preserving arrival order), per-segment cumulative byte counts via cumsum +
cummax head-carry, admission decided against the bucket's available
tokens, then results unsorted. O(B log B) time, O(B) memory — scales to
the 8k+ lane batches the throughput target needs.

Admission rule: lane i passes iff (sum of lengths of same-bucket lanes
j<=i) <= available tokens at batch start. This is the reference's TBF with
one conservative difference: a dropped packet's bytes still occupy the
in-batch prefix (batch windows are ~µs, so the divergence is bounded by
one batch of one subscriber's traffic).

Token state is device-authoritative (tokens, last_update); the host only
writes rows when installing/changing a policy (pkg/qos/manager.go:167-246
role). Timestamps are µs with wrap-safe uint32 arithmetic.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bng_tpu.ops.qtable import (QW_TOKENS, QTableGeom, QTableState, qlookup,
                                write_token_rows)

# token_bucket fields (parity: qos_ratelimit.c:24-31) live in the packed
# 8-word ways of ops/qtable.py (policy + token state in one way row)
QOS_WORDS = 8

# stats (parity: struct qos_stats, qos_ratelimit.c:53-58)
(QST_PKTS_PASSED, QST_PKTS_DROPPED, QST_BYTES_PASSED, QST_BYTES_DROPPED) = range(4)
QOS_NSTATS = 4


# QoS table geometry is the packed-bucket table's
QoSGeom = QTableGeom

# same-bucket aggregation strategy; read by benchmark/lib/app.py selectors()
PREFIX_IMPL = "sort"


class SortedLanes(NamedTuple):
    """The lanes in slot order (stable: arrival order within a bucket),
    as the token writeback wants them: a stored row's lanes are one run."""

    slot: jax.Array  # [B] int32, negative on a lane without a limit
    head: jax.Array  # [B] bool, first limited lane of its bucket
    avail: jax.Array  # [B] float32
    consumed: jax.Array  # [B] float32 admitted bytes of the lane's bucket
    ride: jax.Array  # [B, 3] uint32, the caller's columns


def _prefix_consumed(limited, slot, lens_u, avail, ride):
    """Returns (allowed, consumed_f32, is_head, sorted lanes): stable
    argsort + segment cumsum, u32-exact to 4 GB a batch.

    allowed: sequential-TBF admission per lane (arrival = lane order);
    consumed: admitted bytes of the lane's bucket (valid on limited lanes);
    is_head: first limited lane of each bucket in the batch;
    ride: [B, 3] uint32 carried through the sort in the packed row's
    spare words.
    """
    Bsz = slot.shape[0]
    # lanes without a limit get unique negative ids -> group with nobody
    slot_eff = jnp.where(limited, slot, -1 - jnp.arange(Bsz, dtype=jnp.int32))

    # Narrow (1-word-per-index) gathers are the measured TPU pathology
    # (PERF_NOTES.md §2; >=8-word rows gather at full speed), so the
    # permutation moves ONE packed [B,8] row per lane instead of four
    # scalar gathers, and the unsort is ONE packed row scatter instead of
    # an inverse-permutation + three gathers. tests/test_hlo_structure.py
    # pins these counts.
    order = jnp.argsort(slot_eff, stable=True)
    avail = avail.astype(jnp.float32)
    packed = jnp.concatenate(
        [jnp.stack([slot_eff.astype(jnp.uint32), lens_u,
                    jax.lax.bitcast_convert_type(avail, jnp.uint32),
                    limited.astype(jnp.uint32)], axis=1),
         ride, jnp.zeros((Bsz, 1), dtype=jnp.uint32)], axis=1)  # [B, 8]
    ps = packed[order]
    s_sorted = ps[:, 0].astype(jnp.int32)
    lens_sorted = ps[:, 1]
    avail_f_sorted = jax.lax.bitcast_convert_type(ps[:, 2], jnp.float32)
    avail_sorted = jnp.clip(avail_f_sorted, 0.0, 4.0e9).astype(jnp.uint32)
    limited_sorted = ps[:, 3] != 0

    csum = jnp.cumsum(lens_sorted)
    is_head_sorted = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), s_sorted[1:] != s_sorted[:-1]])
    is_last_sorted = jnp.concatenate(
        [s_sorted[1:] != s_sorted[:-1], jnp.ones((1,), dtype=bool)])
    seg_base = jax.lax.cummax(jnp.where(is_head_sorted, csum - lens_sorted, 0))
    cum_incl_sorted = csum - seg_base
    allowed_sorted = ~limited_sorted | (cum_incl_sorted <= avail_sorted)

    # consumed = admitted bytes of the lane's whole segment, computed
    # without segment_sum's scatter/gather pair: admitted cumsum is
    # non-decreasing, so a reverse cummin over (segment-last -> its
    # cumsum, else +inf) fills every lane with ITS segment end's value
    admitted_sorted = jnp.where(allowed_sorted & limited_sorted, lens_sorted, 0)
    adm_csum = jnp.cumsum(admitted_sorted)
    seg_end = jax.lax.cummin(
        jnp.where(is_last_sorted, adm_csum, jnp.uint32(0xFFFFFFFF)),
        reverse=True)
    adm_base = jax.lax.cummax(
        jnp.where(is_head_sorted, adm_csum - admitted_sorted, 0))
    consumed_sorted = seg_end - adm_base

    head_sorted = is_head_sorted & limited_sorted
    zs = jnp.zeros_like(consumed_sorted)
    res_sorted = jnp.stack(
        [allowed_sorted.astype(jnp.uint32), consumed_sorted,
         head_sorted.astype(jnp.uint32),
         zs, zs, zs, zs, zs], axis=1)  # [B, 8] — wide unsort scatter
    res = jnp.zeros((Bsz, 8), dtype=jnp.uint32).at[order].set(res_sorted)
    return (res[:, 0] != 0,
            res[:, 1].astype(jnp.float32),
            (res[:, 2] != 0) & limited,
            SortedLanes(slot=s_sorted, head=head_sorted, avail=avail_f_sorted,
                        consumed=consumed_sorted.astype(jnp.float32),
                        ride=ps[:, 4:7]))


class QoSResult(NamedTuple):
    allowed: jax.Array  # [B] bool (True also for no-policy lanes)
    dropped: jax.Array  # [B] bool (policy present and bucket empty)
    priority: jax.Array  # [B] uint32 (skb->priority parity, :166)
    table: QTableState  # updated token state
    stats: jax.Array  # [QOS_NSTATS] uint32


def qos_kernel(
    ip_key: jax.Array,  # [B] uint32 — dst_ip for download, src_ip for upload
    pkt_len: jax.Array,  # [B] uint32
    active: jax.Array,  # [B] bool — lanes subject to this QoS direction
    table: QTableState,
    geom: QTableGeom,
    now_us: jax.Array,  # uint32 scalar, wraps
) -> QoSResult:
    # qos is the only device-side *writer* of its table: the token/timestamp
    # writeback below scatters into the LOCAL arrays at res.slot, which under
    # a sharded geometry would be an owner-local slot — silent corruption.
    # QoS tables are chip-local by design (subscriber traffic affinity).
    if geom.axis is not None and geom.n_shards > 1:
        raise ValueError("qos_kernel requires a chip-local table (geom.axis=None); "
                         "QoS state is placed by subscriber affinity, not hash-sharding")
    Bsz = ip_key.shape[0]
    res = qlookup(table, ip_key, geom)
    has_policy = res.found & active
    # rate==0 means unlimited (qos_ratelimit.c:79-80)
    limited = has_policy & ((res.rate_lo | res.rate_hi) != 0)

    burst_f = res.burst.astype(jnp.float32)

    # refill (f32 math: |err| ~1e-7 relative, fine for shaping):
    # bytes/sec = rate_bps / 8; refill = elapsed_us * Bps / 1e6
    elapsed_us = (now_us - res.last_us).astype(jnp.float32)  # uint32 wrap-safe diff
    rate_bps = res.rate_lo.astype(jnp.float32) + res.rate_hi.astype(jnp.float32) * jnp.float32(2.0**32)
    refill = elapsed_us * (rate_bps / 8.0) * jnp.float32(1e-6)
    avail = jnp.minimum(res.tokens + refill, burst_f)

    # --- same-bucket aggregation (sequential TBF admission per lane) ---
    lens_u = pkt_len.astype(jnp.uint32)
    ride = jnp.stack([res.burst, res.row[:, QW_TOKENS], res.last_us], axis=1)
    allowed, _, _, run = _prefix_consumed(limited, res.slot, lens_u, avail, ride)
    dropped = limited & ~allowed
    # the head lane of each bucket writes its way's tokens and timestamp,
    # in the sort's order: the lanes of one stored row are one run there,
    # merged into one whole-row write (qtable.write_token_rows)
    new_tokens = jnp.clip(run.avail - run.consumed, 0.0,
                          run.ride[:, 0].astype(jnp.float32))
    new_table = write_token_rows(table, run.slot, run.head, run.ride[:, 1:3],
                                 new_tokens, now_us)

    priority = jnp.where(has_policy, res.priority, 0)

    stats = jnp.zeros((QOS_NSTATS,), dtype=jnp.uint32)
    counted = has_policy  # stats only update when a policy exists (:149-162)
    stats = stats.at[QST_PKTS_PASSED].add(jnp.sum(counted & allowed, dtype=jnp.uint32))
    stats = stats.at[QST_PKTS_DROPPED].add(jnp.sum(dropped, dtype=jnp.uint32))
    stats = stats.at[QST_BYTES_PASSED].add(jnp.sum(jnp.where(counted & allowed, pkt_len, 0), dtype=jnp.uint32))
    stats = stats.at[QST_BYTES_DROPPED].add(jnp.sum(jnp.where(dropped, pkt_len, 0), dtype=jnp.uint32))

    return QoSResult(
        allowed=allowed,
        dropped=dropped,
        priority=priority,
        table=new_table,
        stats=stats,
    )
