"""Batched packet header parsing: Eth -> [802.1ad/802.1Q] -> IPv4 -> L4.

Behavioral parity with parse_packet_headers (bpf/dhcp_fastpath.c:352-428)
and the L2/L3 parses in nat44.c/qos_ratelimit.c/antispoof.c, vectorized over
a [B, L] uint8 batch. Instead of the reference's early-return control flow,
every lane is parsed unconditionally and validity is tracked in boolean
flags — the XDP verdict "return XDP_PASS" becomes a lane mask.

All IPs/ports are returned as host-order uint32 values (10.0.0.1 ->
0x0A000001) for arithmetic; byte order only matters at the
compose/rewrite boundary in bytes.py.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bng_tpu.ops.bytes import be16_at, be32_at, u8_at, window_at

ETH_P_IP = 0x0800
ETH_P_IPV6 = 0x86DD
ETH_P_8021Q = 0x8100
ETH_P_8021AD = 0x88A8

PROTO_ICMP = 1
PROTO_TCP = 6
PROTO_UDP = 17


class Parsed(NamedTuple):
    """Structure-of-arrays parse result; all fields [B]."""

    # L2
    dst_mac_hi: jax.Array  # uint32, bytes 0-1
    dst_mac_lo: jax.Array  # uint32, bytes 2-5
    src_mac_hi: jax.Array
    src_mac_lo: jax.Array
    ethertype: jax.Array  # inner ethertype after VLAN tags
    is_vlan: jax.Array  # bool: at least one tag
    is_qinq: jax.Array  # bool: two tags
    s_tag: jax.Array  # outer VID (0 if untagged)
    c_tag: jax.Array  # inner VID (0 unless QinQ)
    vlan_offset: jax.Array  # int32: 0 / 4 / 8
    # L3 (IPv4)
    is_ipv4: jax.Array  # bool: ethertype==0x0800 and header in bounds
    is_ipv6: jax.Array  # bool: ethertype 0x86DD, fixed header in bounds; no v6 L4 parse
    l3_off: jax.Array  # int32: 14 + vlan_offset
    ihl_bytes: jax.Array  # int32
    total_len: jax.Array  # uint32 (IP total length field)
    ttl: jax.Array
    proto: jax.Array
    src_ip: jax.Array  # uint32 host order
    dst_ip: jax.Array
    # L4
    l4_off: jax.Array  # int32
    is_udp: jax.Array
    is_tcp: jax.Array
    is_icmp: jax.Array
    src_port: jax.Array  # uint32 (ICMP: echo id for egress tracking)
    dst_port: jax.Array
    tcp_flags: jax.Array  # uint32 (byte 13 of TCP header; 0 otherwise)
    # IPv6 destination, [B, 4] uint32 big-endian words; None unless the
    # parse was asked for it (the `v6` stage of ops/pipeline.py)
    dst6: jax.Array | None = None


def mac_words_at(pkt, off):
    """6 bytes at per-lane offset -> (hi16, lo32) uint32 words.

    Matches utils.net.mac_to_u64's split: u64 key = hi<<32 | lo.
    """
    hi = be16_at(pkt, off)
    lo = be32_at(pkt, off + 2)
    return hi, lo


def eth_vlan(pkt: jax.Array) -> tuple[jax.Array, jax.Array]:
    """VLAN peel only: per-lane (vlan_offset, inner ethertype).

    The PPPoE decap pre-stage needs just these two fields BEFORE the full
    parse (which must see the decapped bytes): three halfwords at static
    columns, so three slices and no gather."""
    et0 = be16_at(pkt, 12)
    outer_tagged = (et0 == ETH_P_8021Q) | (et0 == ETH_P_8021AD)
    et1 = be16_at(pkt, 16)
    inner_tagged = outer_tagged & (et1 == ETH_P_8021Q)
    et2 = be16_at(pkt, 20)
    vlan_offset = jnp.where(inner_tagged, 8,
                            jnp.where(outer_tagged, 4, 0)).astype(jnp.int32)
    ethertype = jnp.where(inner_tagged, et2, jnp.where(outer_tagged, et1, et0))
    return vlan_offset, ethertype


# the values `l3_off` takes: no tag, one tag, QinQ
L3_BASES = (14, 18, 22)


def words_be(b: jax.Array) -> jax.Array:
    """[B, 4k] uint8 -> [B, k] uint32 big-endian words."""
    w = b.astype(jnp.uint32).reshape(b.shape[0], -1, 4)
    return (w[:, :, 0] << 24) | (w[:, :, 1] << 16) | (w[:, :, 2] << 8) | w[:, :, 3]


def ipv6_dst_words(pkt: jax.Array, l3_off: jax.Array) -> jax.Array:
    """The IPv6 destination (16 bytes at `l3_off + 24`) as [B, 4] words:
    a select among three static slices, since `l3_off` takes three values
    (no per-lane 16-byte gather, ops/bytes.py)."""
    return words_be(window_at(pkt, l3_off + 24,
                              tuple(b + 24 for b in L3_BASES), 16))


def parse_batch(pkt: jax.Array, length: jax.Array, v6: bool = False) -> Parsed:
    """Parse [B, L] uint8 packets with [B] uint32 actual lengths. `v6`
    (static) also reads the IPv6 destination, for a program that forwards
    IPv6 (`Parsed.dst6`); without it no v6 address leaves the parser."""
    B = pkt.shape[0]
    zero32 = jnp.zeros((B,), dtype=jnp.int32)
    length = length.astype(jnp.uint32)

    dst_mac_hi, dst_mac_lo = mac_words_at(pkt, zero32)
    src_mac_hi, src_mac_lo = mac_words_at(pkt, zero32 + 6)

    # --- VLAN peeling (parity: dhcp_fastpath.c:373-398) ---
    et0 = be16_at(pkt, zero32 + 12)
    outer_tagged = (et0 == ETH_P_8021Q) | (et0 == ETH_P_8021AD)
    outer_vid = be16_at(pkt, zero32 + 14) & 0x0FFF
    et1 = be16_at(pkt, zero32 + 16)  # ethertype after one tag
    # QinQ: inner tag is 802.1Q only (reference checks ETH_P_8021Q)
    inner_tagged = outer_tagged & (et1 == ETH_P_8021Q)
    inner_vid = be16_at(pkt, zero32 + 18) & 0x0FFF
    et2 = be16_at(pkt, zero32 + 20)

    is_qinq = inner_tagged
    is_vlan = outer_tagged
    vlan_offset = jnp.where(is_qinq, 8, jnp.where(is_vlan, 4, 0)).astype(jnp.int32)
    ethertype = jnp.where(is_qinq, et2, jnp.where(is_vlan, et1, et0))
    s_tag = jnp.where(is_vlan, outer_vid, 0)
    c_tag = jnp.where(is_qinq, inner_vid, 0)

    l3_off = 14 + vlan_offset

    # --- IPv4 ---
    ver_ihl = u8_at(pkt, l3_off)
    ihl = (ver_ihl & 0x0F).astype(jnp.int32) * 4
    version = ver_ihl >> 4
    total_len = be16_at(pkt, l3_off + 2)
    ttl = u8_at(pkt, l3_off + 8)
    proto = u8_at(pkt, l3_off + 9)
    src_ip = be32_at(pkt, l3_off + 12)
    dst_ip = be32_at(pkt, l3_off + 16)

    ip_in_bounds = (l3_off.astype(jnp.uint32) + 20) <= length
    is_ipv4 = (ethertype == ETH_P_IP) & (version == 4) & (ihl >= 20) & ip_in_bounds
    is_ipv6 = (ethertype == ETH_P_IPV6) & ((l3_off.astype(jnp.uint32) + 40) <= length)

    # --- L4 ---
    l4_off = l3_off + ihl
    l4_in_bounds = (l4_off.astype(jnp.uint32) + 8) <= length
    is_udp = is_ipv4 & (proto == PROTO_UDP) & l4_in_bounds
    is_tcp = is_ipv4 & (proto == PROTO_TCP) & ((l4_off.astype(jnp.uint32) + 20) <= length)
    is_icmp = is_ipv4 & (proto == PROTO_ICMP) & l4_in_bounds

    sp = be16_at(pkt, l4_off)
    dp = be16_at(pkt, l4_off + 2)
    icmp_id = be16_at(pkt, l4_off + 4)  # echo id
    # ICMP "ports" for session tracking (parity: nat44.c:643-649,846-851):
    # egress uses echo id as src_port; ingress matches echo id as dst_port.
    src_port = jnp.where(is_icmp, icmp_id, jnp.where(is_udp | is_tcp, sp, 0))
    dst_port = jnp.where(is_icmp, icmp_id, jnp.where(is_udp | is_tcp, dp, 0))
    tcp_flags = jnp.where(is_tcp, u8_at(pkt, l4_off + 13), 0)

    dst6 = None
    if v6:
        with jax.named_scope("v6"):
            dst6 = ipv6_dst_words(pkt, l3_off)

    return Parsed(
        dst_mac_hi=dst_mac_hi,
        dst_mac_lo=dst_mac_lo,
        src_mac_hi=src_mac_hi,
        src_mac_lo=src_mac_lo,
        ethertype=ethertype,
        is_vlan=is_vlan,
        is_qinq=is_qinq,
        s_tag=s_tag,
        c_tag=c_tag,
        vlan_offset=vlan_offset,
        is_ipv4=is_ipv4,
        is_ipv6=is_ipv6,
        l3_off=l3_off,
        ihl_bytes=ihl,
        total_len=total_len,
        ttl=ttl,
        proto=proto,
        src_ip=src_ip,
        dst_ip=dst_ip,
        l4_off=l4_off,
        is_udp=is_udp,
        is_tcp=is_tcp,
        is_icmp=is_icmp,
        src_port=src_port,
        dst_port=dst_port,
        tcp_flags=tcp_flags,
        dst6=dst6,
    )
