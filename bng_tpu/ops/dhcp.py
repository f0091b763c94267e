"""DHCP fast-path kernel: batched in-device OFFER/ACK generation.

TPU re-expression of the XDP program dhcp_fastpath_prog
(bpf/dhcp_fastpath.c:619-813). One XDP invocation = one lane of a [B, L]
batch; `return XDP_PASS/XDP_TX` becomes per-lane verdict masks; the three
eBPF map lookups become cuckoo-table gathers; the in-place packet rewrite +
bpf_xdp_adjust_tail becomes a canonical-reply compose with per-lane VLAN
reinsertion. A byte shift whose amount takes a few static values (the tags:
0/4/8; the options tail after 0, 1 or 2 DNS servers: 0/6/10) is a select
among statically shifted copies at the reply's own width, never a per-byte
gather over the slot: one over [8192, 1536] ran at 1.0 GB/s of a v5e's 819
(ops/bytes.py's header; PERF.md section 6, PR 26).

The request is read the same way. The BOOTP header starts at `dhcp_off` =
14 + tags (0/4/8) + IHL (20..60) + 8, one of 13 static columns
(`REQ_BASES`), and everything the kernel reads of it lies in the `REQ_WIN`
bytes from there: the header fields, the magic, and the 64-byte option
window of both scans. `B_.window_at` builds that window once a step and
every field, the nine circuit-ID candidates among them, is a static slice
of it. Alone on a v5e at [8192, 1536] (PERF.md section 6, PR 31): the
circuit-ID scan as nine [B, 32] gathers 26.4 ms, over the window 0.38 ms
(one [B, 64] gather and slices of it 5.7, one dynamic slice a lane 10.4);
parse_batch + this kernel 36.1 ms before, 4.8 ms since. A new request
field is one more slice of `req`; `B_.bytes_at` with a per-lane offset has
no place here.

Parity notes (cited against /root/reference):
- msg-type extraction at fixed offsets {0,1,3,4,5,6}: dhcp_fastpath.c:216-250
- circuit-ID extraction at fixed positions {3, 12..19}: dhcp_fastpath.c:267-323
- lookup cascade VLAN -> circuit-ID -> MAC: dhcp_fastpath.c:653-681
- lease expiry check: dhcp_fastpath.c:690-695
- relay (giaddr!=0) vs broadcast reply: dhcp_fastpath.c:721-756
- option build order 53,54,51,1,3,[6],58,59,255: dhcp_fastpath.c:519-602
- stats enum: dhcp_fastpath.c:117-128
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bng_tpu.ops import bytes as B_
from bng_tpu.ops.checksum import ipv4_header_checksum
from bng_tpu.ops.parse import Parsed
from bng_tpu.ops.table import TableGeom, TableState, lookup

# ---- DHCP constants ----
DHCP_SERVER_PORT = 67
DHCP_CLIENT_PORT = 68
DHCP_MAGIC = 0x63825363
BOOTREQUEST, BOOTREPLY = 1, 2
DISCOVER, OFFER, REQUEST, ACK = 1, 2, 3, 5
FLAG_BROADCAST = 0x8000

# pool_assignment value-word layout (parity: bpf/maps.h:89-97)
AV_POOL_ID, AV_IP, AV_VLAN, AV_CLASS, AV_LEASE_EXP, AV_FLAGS = range(6)
ASSIGN_WORDS = 8

# ip_pool row layout (parity: bpf/maps.h:135-144); dense array, pool_id index
PV_NETWORK, PV_PREFIX, PV_GATEWAY, PV_DNS1, PV_DNS2, PV_LEASE_T, PV_VALID = range(7)
POOL_WORDS = 8

# server_config layout (parity: bpf/maps.h:153-159)
SC_MAC_HI, SC_MAC_LO, SC_IP = range(3)
SERVER_WORDS = 4

# stats indices (parity: enum stat_counter, dhcp_fastpath.c:117-128)
(ST_TOTAL, ST_HIT, ST_MISS, ST_ERROR, ST_EXPIRED,
 ST_OPT82_PRESENT, ST_OPT82_ABSENT, ST_BCAST, ST_UCAST, ST_VLAN) = range(10)
NSTATS = 10

CID_KEY_LEN = 32  # bpf/maps.h:216
CID_WORDS = 8

# canonical (untagged) reply geometry
_ETH, _IP, _UDP, _BOOTP = 14, 20, 8, 240
_OPT_HEAD = 27  # 53(3) + 54(6) + 51(6) + 1(6) + 3(6)
_OPT_DNS_MAX = 10
_OPT_TAIL = 13  # 58(6) + 59(6) + 255(1)
_OPT_MAX = _OPT_HEAD + _OPT_DNS_MAX + _OPT_TAIL
CANON_LEN = _ETH + _IP + _UDP + _BOOTP + _OPT_MAX  # 332

# request geometry: the columns `dhcp_off` takes on a lane parse_batch calls
# IPv4/UDP (no tag, 802.1Q, QinQ; IHL 5..15), 42..90 by 4, and the bytes
# read from there: BOOTP header + the option scans' window (c:276)
_OPT_SCAN = 64
REQ_BASES = tuple(sorted({_ETH + tags + ihl + _UDP
                          for tags in (0, 4, 8) for ihl in range(20, 64, 4)}))
REQ_WIN = _BOOTP + _OPT_SCAN  # 304


class DHCPTables(NamedTuple):
    """Device-side state for the DHCP fast path (pytree)."""

    sub: TableState  # key [mac_hi, mac_lo] -> assignment (subscriber_pools)
    vlan: TableState  # key [s_tag<<16|c_tag] -> assignment (vlan_subscriber_pools)
    cid: TableState  # key 8 words (32B circuit-id) -> assignment (circuit_id_subscribers)
    pools: jax.Array  # [P, POOL_WORDS] dense (ip_pools; pool_id is a small int)
    server: jax.Array  # [SERVER_WORDS] (server_config)


class DHCPGeom(NamedTuple):
    """Static table geometry (part of the jit closure / static args)."""

    sub: TableGeom
    vlan: TableGeom
    cid: TableGeom


class DHCPResult(NamedTuple):
    is_reply: jax.Array  # [B] bool — lane answered on device (XDP_TX)
    is_dhcp: jax.Array  # [B] bool — lane is a DHCP request (reply or slow path)
    out_pkt: jax.Array  # [B, L] uint8 — reply bytes (valid where is_reply)
    out_len: jax.Array  # [B] uint32
    stats: jax.Array  # [NSTATS] uint32 batch deltas


def _extract_msg_type(opts, opts_in_bounds):
    """Fixed-offset option-53 scan over the option window `opts` [B, 64].
    Parity: get_dhcp_msg_type."""
    found = jnp.zeros_like(opts_in_bounds)
    mtype = jnp.zeros(opts.shape[0], dtype=jnp.uint32)
    for o in (0, 1, 3, 4, 5, 6):  # same offsets, same order as the reference
        ok = (B_.u8_at(opts, o) == 53) & (B_.u8_at(opts, o + 1) == 1)
        take = ok & ~found & opts_in_bounds
        mtype = jnp.where(take, B_.u8_at(opts, o + 2), mtype)
        found = found | take
    return jnp.where(opts_in_bounds, mtype, 0)


def _extract_circuit_id(opts, opts_off, length):
    """Fixed-position Option-82 circuit-ID extraction from the option
    window `opts` [B, 64], which starts at column `opts_off` of a frame of
    `length` bytes.

    Parity: extract_circuit_id_fixed (dhcp_fastpath.c:267-323).
    Returns (found [B] bool, cid [B, 32] uint8 zero-padded).
    """
    Bsz = opts.shape[0]
    opts_off = opts_off.astype(jnp.uint32)
    scan_ok = (opts_off + _OPT_SCAN) <= length

    found = jnp.zeros((Bsz,), dtype=bool)
    cid = jnp.zeros((Bsz, CID_KEY_LEN), dtype=jnp.uint8)

    def try_pos(found, cid, tag_off, len_off, sub_off, cidlen_off, cid_off, extra_ok):
        tag = B_.u8_at(opts, tag_off)
        o82len = B_.u8_at(opts, len_off)
        sub1 = B_.u8_at(opts, sub_off)
        cl = B_.u8_at(opts, cidlen_off)
        in_b = (opts_off + cid_off + cl) <= length
        ok = (
            scan_ok & extra_ok & (tag == 82) & (o82len >= 4) & (sub1 == 1)
            & (cl > 0) & (cl <= CID_KEY_LEN) & in_b & ~found
        )
        raw = B_.bytes_at(opts, cid_off, CID_KEY_LEN)  # [B, 32]
        mask = jnp.arange(CID_KEY_LEN)[None, :] < cl[:, None]
        cand = jnp.where(mask, raw, 0)
        cid = jnp.where(ok[:, None], cand, cid)
        return found | ok, cid

    # Position A: [53][1][x][82][len][sub=1][cl][cid...] (tag at opts+3)
    a_extra = (opts_off + 5 + B_.u8_at(opts, 4)) <= length
    found, cid = try_pos(found, cid, 3, 4, 5, 6, 7, a_extra)
    # Positions 12..19
    for p in range(12, 20):
        p_extra = (opts_off + p + 8) <= length
        found, cid = try_pos(found, cid, p, p + 1, p + 2, p + 3, p + 4, p_extra)
    return found, cid


def pack_cid_words(cid_bytes):
    """[B, 32] uint8 -> [B, 8] uint32 big-endian words (table key form)."""
    b = cid_bytes.astype(jnp.uint32).reshape(cid_bytes.shape[0], CID_WORDS, 4)
    return (b[:, :, 0] << 24) | (b[:, :, 1] << 16) | (b[:, :, 2] << 8) | b[:, :, 3]


def _prefix_to_mask(plen):
    """CIDR prefix -> netmask. Parity: prefix_to_mask (dhcp_fastpath.c:510).

    Shift in two halves to dodge the undefined shift-by-32 (plen=0).
    """
    full = jnp.full_like(plen.astype(jnp.uint32), 0xFFFFFFFF)
    sh = jnp.clip(32 - plen.astype(jnp.int32), 0, 32)
    sh1 = jnp.minimum(sh, 16)
    sh2 = sh - sh1
    return (full << sh1) << sh2


def _placed(seg, at: int, width: int):
    """[B, n] segment at static column `at` of a zeroed [B, width] row
    (cut at `width`)."""
    n = seg.shape[1]
    return jnp.pad(seg, ((0, 0), (at, max(width - at - n, 0))))[:, :width]


def _placed_at(seg, at, choices: tuple[int, ...], width: int):
    """`_placed` at a per-lane column `at` [B] that takes one of the static
    `choices`: a select among the placed copies, one pass each, where a
    gather would move one byte an index."""
    out = _placed(seg, choices[-1], width)
    for c in reversed(choices[:-1]):
        out = jnp.where((at == c)[:, None], _placed(seg, c, width), out)
    return out


def dhcp_fastpath(
    pkt: jax.Array,
    length: jax.Array,
    parsed: Parsed,
    tables: DHCPTables,
    geom: DHCPGeom,
    now_s: jax.Array,
) -> DHCPResult:
    Bsz, L = pkt.shape
    length = length.astype(jnp.uint32)
    stats = jnp.zeros((NSTATS,), dtype=jnp.uint32)

    def count(m):
        return jnp.sum(m, dtype=jnp.uint32)

    # --- eligibility (parity: parse + op + magic checks, :624-633) ---
    dhcp_off = parsed.l4_off + _UDP
    is_dhcp_port = parsed.is_udp & (parsed.dst_port == DHCP_SERVER_PORT)
    hdr_in_bounds = (dhcp_off.astype(jnp.uint32) + _BOOTP) <= length
    base = is_dhcp_port & hdr_in_bounds
    # every request byte read below is a static slice of this window; a
    # lane at none of REQ_BASES is not IPv4/UDP, so `base` masks it
    req = B_.window_at(pkt, dhcp_off, REQ_BASES, REQ_WIN)
    opts = req[:, _BOOTP:]
    op = B_.u8_at(req, 0)
    magic = B_.be32_at(req, 236)
    base = base & (op == BOOTREQUEST) & (magic == DHCP_MAGIC)

    # vlan_packets counts every tagged frame the hook sees, not just DHCP
    # (the reference increments it mid-parse, dhcp_fastpath.c:384, before
    # the IPv4/UDP/port-67 filters)
    stats = stats.at[ST_VLAN].add(count(parsed.is_vlan & (length > 0)))
    stats = stats.at[ST_TOTAL].add(count(base))

    # --- message type (parity :639-645) ---
    opts_off = dhcp_off + 240
    opts_in_bounds = (opts_off.astype(jnp.uint32) + 12) <= length
    mtype = _extract_msg_type(opts, opts_in_bounds & base)
    is_fast_type = (mtype == DISCOVER) | (mtype == REQUEST)
    wrong_type = base & ~is_fast_type
    stats = stats.at[ST_MISS].add(count(wrong_type))
    elig = base & is_fast_type

    # --- lookup cascade (parity :653-681) ---
    # 1) VLAN key
    vlan_key = ((parsed.s_tag << 16) | parsed.c_tag)[:, None].astype(jnp.uint32)
    vlan_res = lookup(tables.vlan, vlan_key, geom.vlan)
    vlan_hit = vlan_res.found & parsed.is_vlan & elig

    # 2) circuit-ID
    cid_found, cid_bytes = _extract_circuit_id(opts, opts_off, length)
    cid_res = lookup(tables.cid, pack_cid_words(cid_bytes), geom.cid)
    cid_hit = cid_res.found & cid_found & elig & ~vlan_hit

    # 3) MAC (chaddr at dhcp_off+28)
    mac_hi = B_.be16_at(req, 28)
    mac_lo = B_.be32_at(req, 30)
    mac_key = jnp.stack([mac_hi, mac_lo], axis=1)
    mac_res = lookup(tables.sub, mac_key, geom.sub)
    mac_hit = mac_res.found & elig & ~vlan_hit & ~cid_hit

    stats = stats.at[ST_OPT82_PRESENT].add(count(cid_hit))

    hit = vlan_hit | cid_hit | mac_hit
    assign = jnp.where(
        vlan_hit[:, None], vlan_res.vals,
        jnp.where(cid_hit[:, None], cid_res.vals, mac_res.vals),
    )
    stats = stats.at[ST_MISS].add(count(elig & ~hit))

    # --- lease expiry (parity :690-695) ---
    lease_exp = assign[:, AV_LEASE_EXP]
    expired = hit & (now_s > lease_exp)
    stats = stats.at[ST_EXPIRED].add(count(expired))
    live = hit & ~expired

    # --- pool + server config (parity :698-713) ---
    P = tables.pools.shape[0]
    pool_id = assign[:, AV_POOL_ID]
    pool_ok_idx = pool_id < P
    pool_row = tables.pools[jnp.minimum(pool_id, P - 1).astype(jnp.int32)]  # [B, POOL_WORDS]
    pool_valid = pool_ok_idx & (pool_row[:, PV_VALID] != 0)
    pool_err = live & ~pool_valid
    stats = stats.at[ST_ERROR].add(count(pool_err))
    reply = live & pool_valid
    stats = stats.at[ST_HIT].add(count(reply))

    # --- reply field computation ---
    server_mac_hi = tables.server[SC_MAC_HI]
    server_mac_lo = tables.server[SC_MAC_LO]
    cfg_server_ip = tables.server[SC_IP]
    gateway = pool_row[:, PV_GATEWAY]
    server_ip = jnp.where(cfg_server_ip != 0, cfg_server_ip, gateway)  # :724

    reply_type = jnp.where(mtype == DISCOVER, OFFER, ACK)

    xid_b = B_.bytes_at(req, 4, 4)
    secs_b = B_.bytes_at(req, 8, 2)
    flags = B_.be16_at(req, 10)
    ciaddr = B_.be32_at(req, 12)
    giaddr = B_.be32_at(req, 24)
    chaddr_b = B_.bytes_at(req, 28, 16)
    giaddr_b = B_.bytes_at(req, 24, 4)

    relayed = giaddr != 0
    # broadcast decision (parity: setup_reply_l2_headers :436-462 — every
    # non-relay case with ciaddr==0 broadcasts; ciaddr!=0 without the
    # broadcast flag unicasts to chaddr)
    use_bcast = (~relayed) & (((flags & FLAG_BROADCAST) != 0) | (ciaddr == 0))
    stats = stats.at[ST_BCAST].add(count(reply & use_bcast))
    stats = stats.at[ST_UCAST].add(count(reply & ~use_bcast))  # covers relay :743

    # L2 dest: relay -> requester's src MAC; bcast -> ff:..; else chaddr
    req_src = B_.bytes_at(pkt, 6, 6)
    bcast_mac = jnp.full((Bsz, 6), 0xFF, dtype=jnp.uint8)
    dst_mac = jnp.where(
        relayed[:, None], req_src, jnp.where(use_bcast[:, None], bcast_mac, chaddr_b[:, :6])
    )

    ip_dst = jnp.where(relayed, giaddr, jnp.uint32(0xFFFFFFFF))  # :734 / :749
    udp_dst = jnp.where(relayed, DHCP_SERVER_PORT, DHCP_CLIENT_PORT)  # :740 / :754

    # --- options geometry ---
    dns1 = pool_row[:, PV_DNS1]
    dns2 = pool_row[:, PV_DNS2]
    dns_sz = jnp.where(dns1 == 0, 0, jnp.where(dns2 == 0, 6, 10)).astype(jnp.int32)
    opt_len = _OPT_HEAD + dns_sz + _OPT_TAIL
    lease_t = pool_row[:, PV_LEASE_T]
    t1 = lease_t // 2  # :585
    t2 = (lease_t * 7) // 8  # :593
    mask32 = _prefix_to_mask(pool_row[:, PV_PREFIX])

    dhcp_len = (_BOOTP + opt_len).astype(jnp.uint32)
    udp_len = 8 + dhcp_len
    ip_len = 20 + udp_len
    canon_total = 14 + ip_len
    out_len = canon_total + parsed.vlan_offset.astype(jnp.uint32)

    # --- canonical reply compose ---
    # One concatenation of [B, n] segments instead of ~60 chained
    # .at[].set() updates: each set() is a dynamic-update-slice (a serial
    # read-modify-write of the whole buffer); concat is a single kernel.
    ip_csum = ipv4_header_checksum([
        jnp.full((Bsz,), 0x4500, dtype=jnp.uint32), ip_len,
        jnp.zeros((Bsz,), dtype=jnp.uint32), jnp.zeros((Bsz,), dtype=jnp.uint32),
        jnp.full((Bsz,), (64 << 8) | 17, dtype=jnp.uint32), jnp.zeros((Bsz,), dtype=jnp.uint32),
        server_ip >> 16, server_ip & 0xFFFF, ip_dst >> 16, ip_dst & 0xFFFF,
    ])
    ones = jnp.ones_like(flags)
    canon = jnp.concatenate([
        # Ethernet
        dst_mac,                                     # 0: dst MAC
        B_.be16_seg(server_mac_hi * ones),           # 6: src MAC (server)
        B_.be32_seg(server_mac_lo * ones),
        B_.const_seg(Bsz, 0x08, 0x00),               # 12: ethertype IPv4
        # IPv4 (TTL=64, proto=UDP; :735/:750)
        B_.const_seg(Bsz, 0x45, 0x00),               # 14: ver/ihl, tos
        B_.be16_seg(ip_len),                         # 16: total length
        B_.const_seg(Bsz, 0, 0, 0, 0, 64, 17),       # 18: id, frag, ttl, proto
        B_.be16_seg(ip_csum),                        # 24: header checksum
        B_.be32_seg(server_ip),                      # 26: src IP
        B_.be32_seg(ip_dst),                         # 30: dst IP
        # UDP (checksum 0: legal for IPv4, matches :741/:755)
        B_.const_seg(Bsz, 0, DHCP_SERVER_PORT),      # 34: src port 67
        B_.be16_seg(udp_dst),                        # 36: dst port
        B_.be16_seg(udp_len),                        # 38: length
        B_.const_seg(Bsz, 0, 0),                     # 40: checksum
        # BOOTP (:759-766)
        B_.const_seg(Bsz, BOOTREPLY, 1, 6, 0),       # 42: op, htype, hlen, hops
        xid_b,                                       # 46
        secs_b,                                      # 50
        B_.be16_seg(flags),                          # 52
        B_.be32_seg(ciaddr),                         # 54
        B_.be32_seg(assign[:, AV_IP]),               # 58: yiaddr :761
        B_.be32_seg(server_ip),                      # 62: siaddr :762
        giaddr_b,                                    # 66
        chaddr_b,                                    # 70: chaddr (16B)
        jnp.zeros((Bsz, 192), dtype=jnp.uint8),      # 86: sname/file
        B_.be32_seg(jnp.full((Bsz,), DHCP_MAGIC, dtype=jnp.uint32)),  # 278
    ], axis=1)

    # options: head segment [B, 27] (order 53,54,51,1,3 — :519-602)
    head = jnp.concatenate([
        B_.const_seg(Bsz, 53, 1), B_.u8_seg(reply_type),
        B_.const_seg(Bsz, 54, 4), B_.be32_seg(server_ip),
        B_.const_seg(Bsz, 51, 4), B_.be32_seg(lease_t),
        B_.const_seg(Bsz, 1, 4), B_.be32_seg(mask32),
        B_.const_seg(Bsz, 3, 4), B_.be32_seg(gateway),
    ], axis=1)
    # dns segment [B, 10]
    dns = jnp.concatenate([
        B_.const_seg(Bsz, 6), B_.u8_seg(jnp.where(dns2 == 0, 4, 8)),
        B_.be32_seg(dns1), B_.be32_seg(dns2),
    ], axis=1)
    # tail segment [B, 13]
    tail = jnp.concatenate([
        B_.const_seg(Bsz, 58, 4), B_.be32_seg(t1),
        B_.const_seg(Bsz, 59, 4), B_.be32_seg(t2),
        B_.const_seg(Bsz, 255),
    ], axis=1)

    # compose options area [B, _OPT_MAX]: head and dns sit at fixed offsets,
    # tail follows dns (dns_sz is 0, 6 or 10)
    oj = jnp.arange(_OPT_MAX, dtype=jnp.int32)[None, :]
    head_dns = _placed(jnp.concatenate([head, dns], axis=1), 0, _OPT_MAX)
    tail_p = _placed_at(tail, _OPT_HEAD + dns_sz,
                        (_OPT_HEAD, _OPT_HEAD + 6, _OPT_HEAD + _OPT_DNS_MAX), _OPT_MAX)
    opt_area = jnp.where(
        oj < _OPT_HEAD + dns_sz[:, None],
        head_dns,
        jnp.where(oj < opt_len[:, None], tail_p, 0),
    )
    canon = jnp.concatenate([canon, opt_area], axis=1)

    # --- final compose with VLAN reinsertion ---
    # Everything after the MACs moves down by the request's tag bytes
    # (vlan_offset is 0, 4 or 8). Composed at the reply's own width W, then
    # zero-padded to the slot: out_len <= CANON_LEN + 8, and a narrower slot
    # cuts the reply short.
    W = min(L, CANON_LEN + 8)
    jj = jnp.arange(W, dtype=jnp.int32)[None, :]
    vo = parsed.vlan_offset
    shifted = _placed_at(canon, vo, (0, 4, 8), W)
    # bytes 0..11 the reply's MACs, 12..14+vo the request's tags + ethertype
    out = jnp.where(
        jj < 12,
        _placed(canon, 0, W),
        jnp.where(jj < 14 + vo[:, None], pkt[:, :W], shifted),
    )
    out = jnp.where(jj < out_len[:, None].astype(jnp.int32), out, 0)
    out = jnp.pad(out, ((0, 0), (0, L - W)))

    return DHCPResult(
        is_reply=reply,
        is_dhcp=base,
        out_pkt=out,
        out_len=jnp.where(reply, out_len, 0),
        stats=stats,
    )
