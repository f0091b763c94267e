"""NAT44/CGNAT kernel: batched SNAT (egress) + DNAT (ingress) on device.

TPU re-expression of bpf/nat44.c. The reference's "conntrack hybrid"
architecture (nat44.c:6-9: first packet of a new flow goes slow-path, later
packets fast-path) maps perfectly onto the host-single-writer table design:

- Established flows: device translates at line rate from the `sessions` /
  `reverse` cuckoo tables and updates per-session counters with one
  whole-row HBM write per session a batch (the per-CPU-atomic role of
  nat44.c:286-292).
- New flows (session miss) return verdict PASS; the host NAT manager
  (bng_tpu.control.nat) performs RFC 6431 port-block allocation + RFC 4787
  EIM host-side — the get_eim_mapping/allocate_port_from_block logic
  (nat44.c:408-528) — inserts session+reverse rows, and the flow is
  device-resident from packet 2 on. This removes the reference's
  "benign race" port allocation (nat44.c:411-418) entirely: one writer.

Device-visible state:
- sessions: key [src_ip, dst_ip, ports, proto] -> session row (V=16)
- reverse:  key [remote_ip, nat_ip, ports, proto] -> original session key
- sub_nat:  key [private_ip] -> port-block summary (presence gates NAT;
            parity: subscriber_nat map, nat44.c:246-252)
- hairpin_ips: dense [H] public IPs (nat44.c:262-268)
- alg_ports: dense [A] (port<<16|proto) trigger list (nat44.c:300-306)
- config: flags word (nat44.c:270-277)

Counter semantics: device owns session counters/last_seen/TCP state;
the host treats them as read-only telemetry (fetched for accounting and
expiry) and only writes rows at insert/delete time.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bng_tpu.ops import bytes as B_
from bng_tpu.ops.checksum import csum_update16, csum_update32
from bng_tpu.ops.parse import PROTO_ICMP, PROTO_TCP, PROTO_UDP, Parsed
from bng_tpu.ops.table import TableGeom, TableState, lookup

# session value-word layout (parity: struct nat_session, nat44.c:123-141)
(SV_NAT_IP, SV_NAT_PORT, SV_ORIG_IP, SV_ORIG_PORT, SV_DEST_IP, SV_DEST_PORT,
 SV_CREATED, SV_LAST_SEEN, SV_STATE, SV_PROTO, SV_FLAGS,
 SV_PKTS_OUT, SV_PKTS_IN, SV_BYTES_OUT, SV_BYTES_IN) = range(15)
SESSION_WORDS = 16

# reverse rows carry the 4 original-session key words, padded to the
# 8-word gather-fast row shape (BNG014: <8-word value rows are the
# PERF_NOTES §2 serialization class — the pad is free HBM, the narrow
# gather was not)
REVERSE_WORDS = 8

# subscriber_nat value layout (parity: struct port_block, nat44.c:144-155)
(BV_PUBLIC_IP, BV_PORT_START, BV_PORT_END, BV_NEXT_PORT, BV_IN_USE,
 BV_SUB_ID, BV_FLAGS) = range(7)
SUBNAT_WORDS = 8

# NAT states (nat44.c:64-71)
NAT_STATE_NEW, NAT_STATE_ESTABLISHED, NAT_STATE_FIN_WAIT, NAT_STATE_CLOSING, NAT_STATE_TIME_WAIT = range(5)

# config flags (nat44.c:55-62)
FLAG_EIM, FLAG_EIF, FLAG_HAIRPIN, FLAG_ALG_FTP, FLAG_ALG_SIP, FLAG_PORT_PARITY, FLAG_PORT_CONTIG = (
    0x01, 0x02, 0x04, 0x08, 0x10, 0x20, 0x40)

# stats indices (parity: struct nat_stats, nat44.c:176-190)
(NST_SNAT, NST_DNAT, NST_HAIRPIN, NST_DROPPED, NST_PASSED, NST_CREATED,
 NST_EXPIRED, NST_PORT_EXH, NST_EIM_HIT, NST_EIM_MISS, NST_ALG) = range(11)
NAT_NSTATS = 11


class NATTables(NamedTuple):
    sessions: TableState  # K=4, V=SESSION_WORDS
    reverse: TableState  # K=4, V=8 (original key words + gather pad)
    sub_nat: TableState  # K=1, V=SUBNAT_WORDS
    hairpin_ips: jax.Array  # [H] uint32 (0 = empty)
    alg_ports: jax.Array  # [A] uint32 (port<<16|proto; 0 = empty)
    config: jax.Array  # [4] uint32: [flags, port_start, port_end, ports_per_sub]


class NATGeom(NamedTuple):
    sessions: TableGeom
    reverse: TableGeom
    sub_nat: TableGeom


class NATResult(NamedTuple):
    translated: jax.Array  # [B] bool — SNAT/DNAT applied (fast path hit)
    punted: jax.Array  # [B] bool — new flow / ALG: needs slow path
    dropped: jax.Array  # [B] bool
    out_pkt: jax.Array  # [B, L] uint8 rewritten packets
    stats: jax.Array  # [NAT_NSTATS] uint32
    is_hairpin: jax.Array  # [B] bool
    # per-lane hit info for the deferred accounting pass
    # (update_sessions applies counters only for lanes the pipeline
    # actually forwards, so QoS/antispoof drops are never billed)
    egress_hit: jax.Array  # [B] bool
    ingress_hit: jax.Array  # [B] bool
    e_slot: jax.Array  # [B] int32 session row for egress hits
    i_slot: jax.Array  # [B] int32 session row for ingress hits
    i_state: jax.Array  # [B] uint32 current TCP state (ingress rows)


def is_private_ip(ip):
    """Branch-free RFC1918 + 100.64/10 check. Parity: nat44.c:340-363."""
    o1 = ip >> 24
    o2 = (ip >> 16) & 0xFF
    return (
        (o1 == 10)
        | ((o1 == 172) & (o2 >= 16) & (o2 <= 31))
        | ((o1 == 192) & (o2 == 168))
        | ((o1 == 100) & (o2 >= 64) & (o2 <= 127))
    )


def _in_set(values, dense_set):
    """[B] membership test against a small dense uint32 set (0 = empty)."""
    eq = values[:, None] == dense_set[None, :]
    return jnp.any(eq & (dense_set[None, :] != 0), axis=1)


def _session_key(a_ip, b_ip, a_port, b_port, proto):
    return jnp.stack(
        [a_ip, b_ip, ((a_port & 0xFFFF) << 16) | (b_port & 0xFFFF), proto], axis=1
    ).astype(jnp.uint32)


def _rewrite_l3_l4(pkt, parsed, mask, new_ip, new_port, is_src):
    """Apply SNAT (is_src) or DNAT rewrite + incremental checksums.

    Parity: nat44.c:752-801 (egress) / :897-944 (ingress).
    """
    ip_field_off = parsed.l3_off + jnp.where(is_src, 12, 16)
    old_ip = jnp.where(is_src, parsed.src_ip, parsed.dst_ip)
    old_port = jnp.where(is_src, parsed.src_port, parsed.dst_port)

    # IP header checksum (incremental)
    ip_csum = B_.be16_at(pkt, parsed.l3_off + 10)
    new_ip_csum = csum_update32(ip_csum, old_ip, new_ip)

    pkt = B_.scatter_be32_at_masked(pkt, ip_field_off, new_ip, mask)
    pkt = B_.scatter_be16_at_masked(pkt, parsed.l3_off + 10, new_ip_csum, mask)

    # L4 rewrite
    port_off = parsed.l4_off + jnp.where(is_src, 0, 2)
    tcp_mask = mask & parsed.is_tcp
    udp_mask = mask & parsed.is_udp
    icmp_mask = mask & parsed.is_icmp

    # TCP checksum at l4_off+16 (pseudo-header includes IP)
    tcp_csum = B_.be16_at(pkt, parsed.l4_off + 16)
    tcp_csum = csum_update32(tcp_csum, old_ip, new_ip)
    tcp_csum = csum_update16(tcp_csum, old_port, new_port)
    pkt = B_.scatter_be16_at_masked(pkt, parsed.l4_off + 16, tcp_csum, tcp_mask)
    pkt = B_.scatter_be16_at_masked(pkt, port_off, new_port, tcp_mask)

    # UDP checksum at l4_off+6 (0 = absent; 0 result -> 0xFFFF, nat44.c:784)
    udp_csum = B_.be16_at(pkt, parsed.l4_off + 6)
    has_csum = udp_csum != 0
    new_udp_csum = csum_update16(csum_update32(udp_csum, old_ip, new_ip), old_port, new_port)
    new_udp_csum = jnp.where(new_udp_csum == 0, 0xFFFF, new_udp_csum)
    pkt = B_.scatter_be16_at_masked(pkt, parsed.l4_off + 6, new_udp_csum, udp_mask & has_csum)
    pkt = B_.scatter_be16_at_masked(pkt, port_off, new_port, udp_mask)

    # ICMP: echo id at l4_off+4, checksum at l4_off+2 (no pseudo-header)
    icmp_csum = B_.be16_at(pkt, parsed.l4_off + 2)
    new_icmp_csum = csum_update16(icmp_csum, old_port, new_port)
    pkt = B_.scatter_be16_at_masked(pkt, parsed.l4_off + 2, new_icmp_csum, icmp_mask)
    pkt = B_.scatter_be16_at_masked(pkt, parsed.l4_off + 4, new_port, icmp_mask)

    return pkt


def nat44_kernel(
    pkt: jax.Array,
    length: jax.Array,
    parsed: Parsed,
    tables: NATTables,
    geom: NATGeom,
    now_s: jax.Array,
) -> NATResult:
    """Fused egress-SNAT + ingress-DNAT over one batch.

    Direction is per-lane: private source -> egress path (nat44_egress),
    otherwise -> ingress path (nat44_ingress). Lanes that are not IPv4 or
    not TCP/UDP/ICMP pass through untouched (TC_ACT_OK parity).
    """
    Bsz = pkt.shape[0]
    stats = jnp.zeros((NAT_NSTATS,), dtype=jnp.uint32)
    cfg_flags = tables.config[0]

    def count(m):
        return jnp.sum(m, dtype=jnp.uint32)

    l4ok = parsed.is_tcp | parsed.is_udp | parsed.is_icmp
    eligible = parsed.is_ipv4 & l4ok
    egress = eligible & is_private_ip(parsed.src_ip)
    ingress = eligible & ~is_private_ip(parsed.src_ip)

    # ---- egress: subscriber allocation gate (nat44.c:589-596) ----
    sub_res = lookup(tables.sub_nat, parsed.src_ip[:, None], geom.sub_nat)
    has_alloc = sub_res.found & egress
    no_alloc = egress & ~sub_res.found
    stats = stats.at[NST_PASSED].add(count(no_alloc))

    # ---- ALG triggers (nat44.c:616-641): punt to host ALG ----
    alg_enabled = (cfg_flags & (FLAG_ALG_FTP | FLAG_ALG_SIP)) != 0
    alg_key = ((parsed.dst_port & 0xFFFF) << 16) | (parsed.proto & 0xFF)
    alg_hit = has_alloc & alg_enabled & _in_set(alg_key, tables.alg_ports) & (parsed.is_tcp | parsed.is_udp)
    stats = stats.at[NST_ALG].add(count(alg_hit))

    # ---- hairpin detection (nat44.c:659-665) ----
    hairpin_on = (cfg_flags & FLAG_HAIRPIN) != 0
    is_hairpin = has_alloc & hairpin_on & _in_set(parsed.dst_ip, tables.hairpin_ips)
    stats = stats.at[NST_HAIRPIN].add(count(is_hairpin))

    # ---- egress session lookup (nat44.c:668-681) ----
    # ICMP key halves per the reference: egress tracks (echo_id, 0)
    # (nat44.c:643-649), ingress matches (0, echo_id) (nat44.c:846-851).
    e_dst_port = jnp.where(parsed.is_icmp, 0, parsed.dst_port)
    ekey = _session_key(parsed.src_ip, parsed.dst_ip, parsed.src_port, e_dst_port, parsed.proto)
    esess = lookup(tables.sessions, ekey, geom.sessions)
    egress_active = has_alloc & ~alg_hit
    egress_hit = egress_active & esess.found
    egress_miss = egress_active & ~esess.found  # new flow -> punt to host
    stats = stats.at[NST_SNAT].add(count(egress_hit))

    # ---- ingress reverse lookup (nat44.c:860-876) ----
    i_src_port = jnp.where(parsed.is_icmp, 0, parsed.src_port)
    rkey = _session_key(parsed.src_ip, parsed.dst_ip, i_src_port, parsed.dst_port, parsed.proto)
    rres = lookup(tables.reverse, rkey, geom.reverse)
    ingress_rhit = ingress & rres.found
    stats = stats.at[NST_PASSED].add(count(ingress & ~rres.found))
    isess = lookup(tables.sessions, rres.vals[:, :4], geom.sessions)
    ingress_hit = ingress_rhit & isess.found
    ingress_orphan = ingress_rhit & ~isess.found  # reverse without session
    stats = stats.at[NST_EXPIRED].add(count(ingress_orphan))
    stats = stats.at[NST_DNAT].add(count(ingress_hit))

    hit_any = egress_hit | ingress_hit

    # ---- packet rewrite ----
    nat_ip = esess.vals[:, SV_NAT_IP]
    nat_port = esess.vals[:, SV_NAT_PORT]
    pkt = _rewrite_l3_l4(pkt, parsed, egress_hit, nat_ip, nat_port, is_src=jnp.ones((Bsz,), dtype=bool))
    orig_ip = isess.vals[:, SV_ORIG_IP]
    orig_port = isess.vals[:, SV_ORIG_PORT]
    pkt = _rewrite_l3_l4(pkt, parsed, ingress_hit, orig_ip, orig_port, is_src=jnp.zeros((Bsz,), dtype=bool))

    punted = egress_miss | alg_hit
    return NATResult(
        translated=hit_any,
        punted=punted,
        dropped=jnp.zeros((Bsz,), dtype=bool),
        out_pkt=pkt,
        stats=stats,
        is_hairpin=is_hairpin,
        egress_hit=egress_hit,
        ingress_hit=ingress_hit,
        e_slot=esess.slot.astype(jnp.int32),
        i_slot=isess.slot.astype(jnp.int32),
        i_state=isess.vals[:, SV_STATE],
    )


def nat44_update_sessions(
    sessions: TableState,
    res: NATResult,
    parsed: Parsed,
    length: jax.Array,
    keep: jax.Array,
    now_s: jax.Array,
) -> TableState:
    """Apply session counters/last_seen/TCP-state for forwarded lanes only.

    `keep` is the pipeline's final forward decision: packets dropped by
    QoS/antispoof after translation must not be billed to the subscriber
    (the kernel hooks get this for free from hook ordering; here the
    accounting pass is explicitly gated). A lane is an egress hit or an
    ingress hit, never both (`nat44_kernel` splits lanes by source address).

    One whole-row write per distinct slot. What the TPU compiler and a v5e
    showed (PERF.md section 6, PR 29, at `u32[2097216, 16]` and 8,192
    lanes): whole rows scatter natively; a part-row window
    (`vals.at[slot, 11:15].add`) serialises into a `while` of one
    `dynamic-update-slice` a lane, whatever the lanes hold; a single column
    (`vals.at[slot, 7].set`) relayouts the whole table twice around a flat
    scatter. Those three loops took 41 ms alone, 34 ms of a 94 ms step.
    """
    Bsz = length.shape[0]
    egress_hit = res.egress_hit & keep
    ingress_hit = res.ingress_hit & keep
    # a lane that touches nothing points past the table: it sorts behind
    # every row, and the scatter drops it
    S = sessions.vals.shape[0]
    slot = jnp.where(egress_hit, res.e_slot,
                     jnp.where(ingress_hit, res.i_slot, S)).astype(jnp.int32)

    # TCP state machine on ingress (nat44.c:885-895). States are ordered
    # NEW < ESTABLISHED < FIN_WAIT < CLOSING and a slot takes the max over
    # its lanes, so a FIN/RST lane always wins over a same-batch ACK lane
    # whatever the lane order. 0 is max's identity on u32: the lanes that
    # write no state (egress, UDP, ICMP) carry it.
    fin_or_rst = (parsed.tcp_flags & 0x05) != 0  # FIN|RST
    ack = (parsed.tcp_flags & 0x10) != 0
    cur_state = res.i_state
    new_state = jnp.where(
        fin_or_rst, NAT_STATE_CLOSING,
        jnp.where((cur_state == NAT_STATE_NEW) & ack, NAT_STATE_ESTABLISHED, cur_state),
    ).astype(jnp.uint32)
    state = jnp.where(ingress_hit & parsed.is_tcp, new_state, 0)

    # Sort the lanes by (slot, state): a slot's lanes (the up and the down
    # packet of one flow, say) become one run, whose last lane carries the
    # run's largest state. The counters ride along as operands of the sort.
    up = egress_hit.astype(jnp.uint32)
    down = ingress_hit.astype(jnp.uint32)
    plen = length.astype(jnp.uint32)
    # SV_PKTS_OUT, SV_PKTS_IN, SV_BYTES_OUT, SV_BYTES_IN
    slot, state, *counts = jax.lax.sort(
        (slot, state, up, down, up * plen, down * plen), num_keys=2)
    counts = jnp.stack(counts, axis=1)
    edge = slot[1:] != slot[:-1]
    run_head = jnp.concatenate([jnp.ones((1,), dtype=bool), edge])
    run_last = jnp.concatenate([edge, jnp.ones((1,), dtype=bool)])
    # a run's sums: the running sum less what it was before the run's head.
    # u32 wraps, and the difference is exact mod 2**32 all the same.
    csum = jnp.cumsum(counts, axis=0)
    head_at = jax.lax.cummax(jnp.where(run_head, jnp.arange(Bsz), 0))
    run_sums = csum - (csum - counts)[head_at]

    # read-modify-write of the rows at the runs' ends, on the array written
    rows = sessions.vals.at[slot].get(mode="clip")
    word = jnp.arange(SESSION_WORDS)
    sums = jnp.pad(run_sums, ((0, 0), (SV_PKTS_OUT, SESSION_WORDS - 1 - SV_BYTES_IN)))
    # last_seen is set, not raised: a host clock that steps back shows
    new_rows = jnp.where(
        word == SV_LAST_SEEN, jnp.uint32(now_s),
        jnp.where(word == SV_STATE, jnp.maximum(rows, state[:, None]), rows + sums))
    vals = sessions.vals.at[jnp.where(run_last, slot, S)].set(new_rows, mode="drop")
    return sessions._replace(vals=vals)
