"""Batched PPPoE session-stage encap/decap + QinQ push/pop on device.

The reference runs the whole PPPoE stack in userspace Go over AF_PACKET
(pkg/pppoe/server.go:263-301): discovery and LCP/IPCP negotiation are
control traffic, but every DATA packet of an established session also
crosses into userspace (server.go:854). On TPU the session-stage framing
is pure per-lane byte movement — exactly what the batch engine is for —
so established-session data rides the device fast path and only
discovery (0x8863) and LCP/auth/IPCP control frames (PPP proto !=
0x0021) punt to the host PPPoE server, the same cache/miss split as the
DHCP fast path (SURVEY.md §7, BASELINE config 4).

Frame layouts:
  decap: [eth][vlans 0/4/8][0x8864][PPPoE hdr 6B][PPP proto 2B][IPv4...]
     ->  [eth][vlans]][0x0800][IPv4...]            (8-byte contraction)
  encap: the reverse 8-byte expansion, session id from the subscriber
     session table (keyed by dst IP on the downstream direction).

Validation on decap mirrors pppoe_session dispatch (server.go:466-499):
ver/type 0x11, code 0, session id found in the session table and bound
to the same MAC. Byte movement is a select among statically shifted
copies of the slot and every header read a static slice (the framing
sits behind 0, 1 or 2 VLAN tags: three bases), never a per-lane gather
or scatter over the slot's width.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bng_tpu.ops import bytes as B_
from bng_tpu.ops.parse import ETH_P_8021AD, ETH_P_8021Q, ETH_P_IP, ETH_P_IPV6
from bng_tpu.ops.table import TableGeom, TableState, lookup

ETH_PPPOE_SESSION = 0x8864
ETH_PPPOE_DISC = 0x8863
PPP_IPV4 = 0x0021
PPP_IPV6 = 0x0057
PPPOE_HDR = 8  # 6B PPPoE header + 2B PPP protocol
HDR_BASES = (14, 18, 22)  # where it starts behind 0 / 1 / 2 VLAN tags

# session table value words (device mirror of control.pppoe.PPPoESession);
# padded to the 8-word gather-fast row shape (BNG014 / PERF_NOTES §2)
(PS_SESSION_ID, PS_MAC_HI, PS_MAC_LO, PS_IP, PS_FLAGS) = range(5)
PPPOE_WORDS = 8

# stats
(PST_DECAP, PST_ENCAP, PST_CTRL_PUNT, PST_BAD, PST_MISS) = range(5)
PPPOE_NSTATS = 5


class PPPoEResult(NamedTuple):
    out_pkt: jax.Array  # [B, L] uint8
    out_len: jax.Array  # [B] uint32
    done: jax.Array  # [B] bool — lane rewritten by this op
    punt: jax.Array  # [B] bool — PPPoE control traffic for the host stack
    src_ip_hint: jax.Array  # [B] uint32 session IP (antispoof cross-check)
    stats: jax.Array  # [PPPOE_NSTATS] uint32


def _shift_bytes(pkt, shift, amounts: tuple[int, ...], start):
    """Shift packet bytes at/after per-lane `start` by per-lane `shift`,
    which is 0 (the lane stays as it is) or one of the static `amounts`.

    Positive shift contracts (decap: byte j reads from j+shift), negative
    expands (encap). Bytes before `start` (L2 addresses and any VLAN
    tags) never move. A select among the statically shifted copies of the
    slot, one pass each: a per-lane index over the slot's width would be
    a gather, which moves one byte an index (ops/bytes.py). Columns
    shifted in from beyond the slot read its edge byte, as a clipped
    index would; they lie past every `out_len`.
    """
    L = pkt.shape[1]
    jj = jnp.arange(L, dtype=jnp.int32)[None, :]
    moves = jj >= jnp.asarray(start).reshape(-1, 1)
    out = pkt
    for s in amounts:
        pad = ((0, 0), (0, s)) if s > 0 else ((0, 0), (-s, 0))
        copy = jnp.pad(pkt, pad, mode="edge")[:, max(s, 0):max(s, 0) + L]
        out = jnp.where((shift == s)[:, None] & moves, copy, out)
    return out


def pppoe_decap(
    pkt: jax.Array,
    length: jax.Array,
    vlan_offset: jax.Array,  # [B] int32 from parse (0/4/8)
    ethertype: jax.Array,  # [B] inner ethertype after VLANs
    sessions: TableState,
    geom: TableGeom,
) -> PPPoEResult:
    """Strip PPPoE+PPP framing from established-session IPv4/IPv6 data."""
    length = length.astype(jnp.uint32)
    et_off = 12 + vlan_offset  # offset of the ethertype field itself
    ph = et_off + 2  # PPPoE header start

    is_sess = ethertype == ETH_PPPOE_SESSION
    is_disc = ethertype == ETH_PPPOE_DISC
    hdr_ok = (ph.astype(jnp.uint32) + PPPOE_HDR) <= length

    # the 8 bytes of PPPoE header + PPP protocol, behind 0 / 1 / 2 tags
    hdr = B_.window_at(pkt, ph, HDR_BASES, PPPOE_HDR)
    ver_type = B_.u8_at(hdr, 0)
    code = B_.u8_at(hdr, 1)
    session_id = B_.be16_at(hdr, 2)
    plen = B_.be16_at(hdr, 4)  # PPPoE payload length (PPP proto + data)
    ppp_proto = B_.be16_at(hdr, 6)

    # length-field validation parity with codec.PPPoEPacket.decode: the
    # declared payload must fit the frame (frames may carry Ethernet
    # padding beyond it) and must at least hold the PPP protocol word
    plen_ok = (plen >= 2) & ((ph + 6).astype(jnp.uint32) + plen <= length)
    well_formed = is_sess & hdr_ok & (ver_type == 0x11) & (code == 0) & plen_ok
    # Only IPv4 data decaps on device for now: the encap direction is
    # IPv4-keyed (by_ip), so v6 PPP data punts to the host v6 stack to
    # keep the two directions symmetric (and src_ip_hint meaningful).
    is_data = well_formed & (ppp_proto == PPP_IPV4)
    is_malformed = is_sess & ~well_formed
    # control inside the session (LCP 0xC021, PAP/CHAP, IPCP 0x8021, v6...)
    is_ctrl = is_disc | (well_formed & ~is_data) | is_malformed

    # session validation: id+MAC must match the table (server.go:478-487)
    src_mac_hi = B_.be16_at(pkt, 6)
    src_mac_lo = B_.be32_at(pkt, 8)
    res = lookup(sessions, session_id[:, None].astype(jnp.uint32), geom)
    bound = (
        res.found
        & (res.vals[:, PS_MAC_HI] == src_mac_hi)
        & (res.vals[:, PS_MAC_LO] == src_mac_lo)
    )
    ok = is_data & bound
    miss = is_data & ~bound  # unknown/foreign session -> punt (teardown path)

    # contract by 8: bytes after the ethertype slide left, ethertype
    # becomes the inner protocol
    out = _shift_bytes(pkt, jnp.where(ok, PPPOE_HDR, 0), (PPPOE_HDR,), et_off)
    inner_et = jnp.where(ppp_proto == PPP_IPV4, ETH_P_IP, ETH_P_IPV6)
    out = B_.scatter_be16_at_masked(out, et_off, inner_et, ok)
    # inner frame = L2 up to ethertype (et_off+2) + IP bytes (plen-2);
    # trailing Ethernet padding past the declared payload is dropped
    out_len = jnp.where(ok, et_off.astype(jnp.uint32) + plen, length)

    stats = jnp.zeros((PPPOE_NSTATS,), dtype=jnp.uint32)
    stats = stats.at[PST_DECAP].add(jnp.sum(ok, dtype=jnp.uint32))
    # disjoint buckets: a malformed frame counts only as BAD, never CTRL
    stats = stats.at[PST_CTRL_PUNT].add(
        jnp.sum(is_disc | (well_formed & ~is_data), dtype=jnp.uint32))
    stats = stats.at[PST_MISS].add(jnp.sum(miss, dtype=jnp.uint32))
    stats = stats.at[PST_BAD].add(jnp.sum(is_malformed, dtype=jnp.uint32))

    return PPPoEResult(
        out_pkt=out,
        out_len=out_len,
        done=ok,
        punt=is_ctrl | miss,
        src_ip_hint=jnp.where(ok, res.vals[:, PS_IP], 0),
        stats=stats,
    )


def pppoe_encap(
    pkt: jax.Array,
    length: jax.Array,
    vlan_offset: jax.Array,
    ethertype: jax.Array,
    dst_ip: jax.Array,  # [B] from parse — downstream subscriber IP
    by_ip: TableState,  # session table keyed by subscriber IP
    geom: TableGeom,
    server_mac: jax.Array | None,  # [2] uint32 (hi16, lo32) AC MAC — REQUIRED
) -> PPPoEResult:
    """Add PPPoE+PPP framing to downstream IPv4 data for PPPoE subscribers.

    server_mac: the access concentrator's own MAC, written as the L2
    source of every encapsulated frame (the reference builds downstream
    frames with src=serverMAC, pkg/pppoe/server.go BuildEthernetFrame;
    without it the frame would carry the upstream router's source MAC —
    round-1 ADVICE finding). Deliberately has NO default: an integrator
    must either thread the AC MAC or pass None explicitly to declare the
    frames are pre-stamped upstream.
    """
    Bsz, L = pkt.shape
    length = length.astype(jnp.uint32)
    et_off = 12 + vlan_offset

    res = lookup(by_ip, dst_ip[:, None].astype(jnp.uint32), geom)
    is_v4 = ethertype == ETH_P_IP
    ok = is_v4 & res.found & ((length + PPPOE_HDR) <= L)

    # expand by 8 after the ethertype
    out = _shift_bytes(pkt, jnp.where(ok, -PPPOE_HDR, 0), (-PPPOE_HDR,), et_off)
    out = B_.scatter_be16_at_masked(out, et_off, jnp.full((Bsz,), ETH_PPPOE_SESSION, dtype=jnp.uint32), ok)
    ph = et_off + 2
    payload_len = length - et_off.astype(jnp.uint32)  # PPP proto (2B) + IP bytes
    out = B_.scatter_be16_at_masked(out, ph, jnp.full((Bsz,), 0x1100, dtype=jnp.uint32), ok)
    out = B_.scatter_be16_at_masked(out, ph + 2, res.vals[:, PS_SESSION_ID], ok)
    out = B_.scatter_be16_at_masked(out, ph + 4, payload_len, ok)
    out = B_.scatter_be16_at_masked(out, ph + 6, jnp.full((Bsz,), PPP_IPV4, dtype=jnp.uint32), ok)
    # rewrite L2 dest to the subscriber MAC from the session row
    out = B_.scatter_be16_at_masked(out, jnp.zeros_like(et_off), res.vals[:, PS_MAC_HI], ok)
    out = B_.scatter_be32_at_masked(out, jnp.zeros_like(et_off) + 2, res.vals[:, PS_MAC_LO], ok)
    if server_mac is not None:
        # ...and L2 source to the AC's MAC (src of all downstream frames)
        src_hi = jnp.broadcast_to(server_mac[0], (Bsz,)).astype(jnp.uint32)
        src_lo = jnp.broadcast_to(server_mac[1], (Bsz,)).astype(jnp.uint32)
        out = B_.scatter_be16_at_masked(out, jnp.zeros_like(et_off) + 6, src_hi, ok)
        out = B_.scatter_be32_at_masked(out, jnp.zeros_like(et_off) + 8, src_lo, ok)
    out_len = jnp.where(ok, length + PPPOE_HDR, length)

    stats = jnp.zeros((PPPOE_NSTATS,), dtype=jnp.uint32)
    stats = stats.at[PST_ENCAP].add(jnp.sum(ok, dtype=jnp.uint32))

    return PPPoEResult(
        out_pkt=out,
        out_len=out_len,
        done=ok,
        punt=jnp.zeros((Bsz,), dtype=bool),
        src_ip_hint=jnp.zeros((Bsz,), dtype=jnp.uint32),
        stats=stats,
    )


# ---- QinQ push/pop (pkg/qinq role, device side) ----


def qinq_push(pkt, length, s_tag, c_tag, gate):
    """Insert 802.1ad S-tag + 802.1Q C-tag after the MAC addresses.

    Parity: the QinQ framing dhcp_fastpath.c parses (:373-398), built
    host-side by pkg/qinq/VLANPair; here applied to a whole batch.
    """
    Bsz, L = pkt.shape
    length = length.astype(jnp.uint32)
    ok = gate & ((length + 8) <= L)
    z = jnp.zeros((Bsz,), dtype=jnp.int32)
    out = _shift_bytes(pkt, jnp.where(ok, -8, 0), (-8,), z + 12)
    out = B_.scatter_be16_at_masked(out, z + 12, jnp.full((Bsz,), ETH_P_8021AD, dtype=jnp.uint32), ok)
    out = B_.scatter_be16_at_masked(out, z + 14, s_tag & 0x0FFF, ok)
    out = B_.scatter_be16_at_masked(out, z + 16, jnp.full((Bsz,), ETH_P_8021Q, dtype=jnp.uint32), ok)
    out = B_.scatter_be16_at_masked(out, z + 18, c_tag & 0x0FFF, ok)
    return out, jnp.where(ok, length + 8, length), ok


def qinq_pop(pkt, length, vlan_offset, gate):
    """Strip all VLAN tags (0/4/8 bytes) from gated lanes."""
    length = length.astype(jnp.uint32)
    vo = vlan_offset.astype(jnp.int32)
    ok = gate & (vo > 0)
    out = _shift_bytes(pkt, jnp.where(ok, vo, 0), (4, 8), jnp.full_like(vo, 12))
    return out, jnp.where(ok, length - vo.astype(jnp.uint32), length), ok
