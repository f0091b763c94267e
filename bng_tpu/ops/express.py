"""Express OFFER fast path: the minimal device program the 50us budget
permits (ISSUE 13).

The full DHCP-only program (`ops/dhcp.py dhcp_fastpath`) parses the raw
[B, L] frame batch on device and composes the complete reply bytes —
~60 gather/concat kernels over 512-byte lanes, almost all of it spent
re-deriving facts the host admission path already touched (VLAN tags,
chaddr, xid) and assembling bytes the host could patch into a
preassembled template. This module splits the work at the only boundary
the 50us `device` budget cares about:

- **Admission (host, once per frame):** `parse_express` extracts the
  express descriptor — the lane columns the probe cascade needs (MAC
  key words, VLAN key, circuit-ID key words, eligibility flags) plus
  the host-only patch-in fields (xid, msg type, offsets). Its parse
  semantics mirror `ops/parse.py parse_batch` + the fixed-offset
  option scans of `dhcp_fastpath` bit-for-bit: a frame this parser
  deems ineligible is exactly a frame the device program would have
  PASSed.
- **Device (`express_verdicts`):** the three-tier cuckoo probe
  (VLAN -> circuit-ID -> MAC, ops/table.device_lookup), lease-expiry
  and pool-validity checks, and a [B, XD_WORDS] verdict block: verdict + yiaddr + pool/lease words.
  No packet bytes enter or leave the program.
- **Retire (host):** the verdict block selects a preassembled
  `ExpressWireTemplate` (control/dhcp_codec.py, built on the same
  ReplyTemplate machinery the slow-path server renders through) and
  patches the per-client words — byte-identical to the dhcp_fastpath
  compose, pinned by tests/test_express.py.

The descriptor is donated to the program and the verdict block is
written over its first columns (`desc.at[...].set`), so XLA aliases the
output onto the input buffer — no per-dispatch allocation on the fast
lane. Stats use the `ops/dhcp.py` counter indices; divergences from the
full program's counting (wrong-type frames are rejected at admission
and never reach the device, so they are absent from ST_MISS here) are
confined to frames the express lane never answers.
"""

from __future__ import annotations

import struct
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from bng_tpu.ops.dhcp import (
    AV_IP,
    AV_LEASE_EXP,
    AV_POOL_ID,
    DHCP_MAGIC,
    DHCPGeom,
    DHCPTables,
    DISCOVER,
    NSTATS,
    PV_LEASE_T,
    PV_VALID,
    REQUEST,
    ST_BCAST,
    ST_ERROR,
    ST_EXPIRED,
    ST_HIT,
    ST_MISS,
    ST_OPT82_PRESENT,
    ST_TOTAL,
    ST_UCAST,
    ST_VLAN,
    CID_KEY_LEN,
)
from bng_tpu.ops.table import lookup

# ---- descriptor layout: one [XD_WORDS] uint32 row per express frame ----
# Columns 0..3 double as the verdict block on the way back (the program
# donates the descriptor and writes the verdict over these columns, so
# the output aliases the input staging buffer).
XD_FLAGS = 0  # XF_* eligibility bits
XD_MAC_HI = 1  # chaddr hi16 (table key word 0)
XD_MAC_LO = 2  # chaddr lo32 (table key word 1)
XD_VLAN = 3  # s_tag<<16 | c_tag (vlan table key)
XD_XID = 4  # host-only: request xid (identity/debug)
XD_MSG = 5  # host-only: DHCP message type (reply-type selection)
XD_CID0 = 8  # 8 big-endian uint32 words of the 32-byte circuit-id key
XD_WORDS = 16

# verdict block columns (overlaid on XD_FLAGS..XD_VLAN)
VB_VERDICT = 0  # 1 = answered on device (host patches a template reply)
VB_YIADDR = 1
VB_POOL = 2  # pool id (template selection)
VB_LEASE_T = 3  # pool lease seconds (the device-serving lease words)

XF_VALID = 1  # eligible DISCOVER/REQUEST (probe it)
XF_VLAN = 2  # frame was VLAN-tagged (vlan-key tier eligible)
XF_CID = 4  # option-82 circuit-id extracted (cid tier eligible)
XF_BCAST = 8  # reply will broadcast (stats parity: ST_BCAST/ST_UCAST)
XF_RELAYED = 16  # giaddr != 0 (host-side reply addressing)

# traces of express_verdicts since process start — incremented at TRACE
# time only, so tests can assert an AOT geometry hit serves without
# retracing (tests/test_express.py::TestAotCache)
TRACE_COUNT = 0


class ExpressDesc(NamedTuple):
    """One admitted express frame: device columns + host patch-in meta."""

    words: np.ndarray  # [XD_WORDS] uint32 (the device descriptor row)
    vlan_off: int  # 0 / 4 / 8 — reply copies frame[12:14+vlan_off]
    dhcp_off: int  # BOOTP payload offset in the frame
    msg_type: int  # DISCOVER or REQUEST
    relayed: bool  # giaddr != 0 -> unicast to giaddr, udp dst 67
    use_bcast: bool  # L2/L3 broadcast reply (dhcp_fastpath.c:436-462)


class ExpressResult(NamedTuple):
    """Device outputs of one express dispatch (futures until retire)."""

    block: jax.Array  # [B, XD_WORDS] uint32; cols VB_* are the verdict
    stats: jax.Array  # [NSTATS] uint32 batch deltas (ops/dhcp indices)


def _u16(frame: bytes, off: int) -> int:
    return (frame[off] << 8) | frame[off + 1]


def parse_express(frame: bytes) -> ExpressDesc | None:
    """Host-side express admission parse: frame -> descriptor, or None
    when the device program would not have answered it anyway (the
    frame takes the slow path / fused pipeline unchanged).

    Semantics mirror the device parse exactly — parse_batch's VLAN peel
    (outer 0x8100/0x88A8, inner 0x8100 only), dhcp_fastpath's bounds
    checks, its fixed-offset option-53 scan ({0,1,3,4,5,6}, first
    match) and its fixed-position option-82 circuit-id scan (position A
    then 12..19). A drift here would mis-steer a frame the device
    cascade resolves differently, so tests pin byte-identity of the
    whole express path against the full program across geometries.
    """
    L = len(frame)
    if L < 34:
        return None
    # VLAN peel (parse_batch semantics)
    et = _u16(frame, 12)
    vlan_off, s_tag, c_tag = 0, 0, 0
    tagged = et in (0x8100, 0x88A8)
    if tagged:
        if L < 18:
            return None
        s_tag = _u16(frame, 14) & 0x0FFF
        et1 = _u16(frame, 16)
        if et1 == 0x8100:  # QinQ: inner must be 802.1Q
            if L < 22:
                return None
            c_tag = _u16(frame, 18) & 0x0FFF
            vlan_off, et = 8, _u16(frame, 20)
        else:
            vlan_off, et = 4, et1
    l3 = 14 + vlan_off
    if et != 0x0800 or L < l3 + 20 or (frame[l3] >> 4) != 4:
        return None
    ihl = (frame[l3] & 0x0F) * 4
    if ihl < 20 or frame[l3 + 9] != 17:
        return None
    l4 = l3 + ihl
    if L < l4 + 8 or _u16(frame, l4 + 2) != 67:
        return None
    dhcp_off = l4 + 8
    if (L < dhcp_off + 240 or frame[dhcp_off] != 1
            or int.from_bytes(frame[dhcp_off + 236: dhcp_off + 240],
                              "big") != DHCP_MAGIC):
        return None

    # fixed-offset option-53 scan (dhcp_fastpath.c:216-250 order)
    opts = dhcp_off + 240
    mtype = 0
    if opts + 12 <= L:
        for o in (0, 1, 3, 4, 5, 6):
            if frame[opts + o] == 53 and frame[opts + o + 1] == 1:
                mtype = frame[opts + o + 2]
                break
    if mtype not in (DISCOVER, REQUEST):
        return None

    # fixed-position option-82 circuit-id (dhcp_fastpath.c:267-323)
    cid = b""
    if opts + 64 <= L:
        o82len_a = frame[opts + 4]
        positions = [(3, 4, 5, 6, 7, opts + 5 + o82len_a <= L)]
        positions += [(p, p + 1, p + 2, p + 3, p + 4, opts + p + 8 <= L)
                      for p in range(12, 20)]
        for tag_o, len_o, sub_o, cl_o, cid_o, extra_ok in positions:
            cl = frame[opts + cl_o]
            if (extra_ok and frame[opts + tag_o] == 82
                    and frame[opts + len_o] >= 4
                    and frame[opts + sub_o] == 1
                    and 0 < cl <= CID_KEY_LEN
                    and opts + cid_o + cl <= L):
                cid = frame[opts + cid_o: opts + cid_o + cl]
                break

    xid, secs, flags16 = struct.unpack_from("!IHH", frame, dhcp_off + 4)
    del secs  # patched into the reply straight from the frame at retire
    ciaddr, = struct.unpack_from("!I", frame, dhcp_off + 12)
    giaddr, = struct.unpack_from("!I", frame, dhcp_off + 24)
    relayed = giaddr != 0
    use_bcast = (not relayed) and ((flags16 & 0x8000) != 0 or ciaddr == 0)

    w = np.zeros((XD_WORDS,), dtype=np.uint32)
    fl = XF_VALID
    if tagged:
        fl |= XF_VLAN
    if cid:
        fl |= XF_CID
    if use_bcast:
        fl |= XF_BCAST
    if relayed:
        fl |= XF_RELAYED
    w[XD_FLAGS] = fl
    w[XD_MAC_HI] = _u16(frame, dhcp_off + 28)
    w[XD_MAC_LO] = int.from_bytes(frame[dhcp_off + 30: dhcp_off + 34], "big")
    w[XD_VLAN] = (s_tag << 16) | c_tag
    w[XD_XID] = xid
    w[XD_MSG] = mtype
    if cid:
        buf = (cid + b"\x00" * CID_KEY_LEN)[:CID_KEY_LEN]
        w[XD_CID0: XD_CID0 + 8] = np.frombuffer(buf, dtype=">u4")
    return ExpressDesc(words=w, vlan_off=vlan_off, dhcp_off=dhcp_off,
                       msg_type=mtype, relayed=relayed, use_bcast=use_bcast)


@jax.named_scope("dhcp")  # metadata only: the device trace's stage name
def express_verdicts(
    tables: DHCPTables,
    desc: jax.Array,
    geom: DHCPGeom,
    now_s: jax.Array,
) -> ExpressResult:
    """The minimal express device program: probe cascade + verdict block.

    Identical resolution semantics to `dhcp_fastpath` (VLAN ->
    circuit-ID -> MAC, lease expiry against now_s, pool validity) over
    pre-extracted descriptor columns instead of raw frames. The reply
    bytes never touch the device: the host patches verdict/yiaddr into
    a preassembled wire template at retire.
    """
    global TRACE_COUNT
    TRACE_COUNT += 1  # trace-time only: AOT geometry hits never re-enter

    flags = desc[:, XD_FLAGS]
    valid = (flags & XF_VALID) != 0

    def count(m):
        return jnp.sum(m, dtype=jnp.uint32)

    # --- lookup cascade (dhcp_fastpath.c:653-681 order) ---
    vlan_res = lookup(tables.vlan, desc[:, XD_VLAN: XD_VLAN + 1], geom.vlan)
    vlan_hit = vlan_res.found & ((flags & XF_VLAN) != 0) & valid
    cid_res = lookup(tables.cid, desc[:, XD_CID0: XD_CID0 + 8], geom.cid)
    cid_hit = cid_res.found & ((flags & XF_CID) != 0) & valid & ~vlan_hit
    mac_res = lookup(tables.sub, desc[:, XD_MAC_HI: XD_MAC_HI + 2], geom.sub)
    mac_hit = mac_res.found & valid & ~vlan_hit & ~cid_hit
    hit = vlan_hit | cid_hit | mac_hit
    assign = jnp.where(
        vlan_hit[:, None], vlan_res.vals,
        jnp.where(cid_hit[:, None], cid_res.vals, mac_res.vals))

    # --- lease expiry + pool validity (dhcp_fastpath.c:690-713) ---
    expired = hit & (now_s > assign[:, AV_LEASE_EXP])
    live = hit & ~expired
    P = tables.pools.shape[0]
    pool_id = assign[:, AV_POOL_ID]
    pool_row = tables.pools[jnp.minimum(pool_id, P - 1).astype(jnp.int32)]
    pool_valid = (pool_id < P) & (pool_row[:, PV_VALID] != 0)
    reply = live & pool_valid

    stats = jnp.zeros((NSTATS,), dtype=jnp.uint32)
    stats = stats.at[ST_TOTAL].add(count(valid))
    stats = stats.at[ST_VLAN].add(count(valid & ((flags & XF_VLAN) != 0)))
    stats = stats.at[ST_OPT82_PRESENT].add(count(cid_hit))
    stats = stats.at[ST_MISS].add(count(valid & ~hit))
    stats = stats.at[ST_EXPIRED].add(count(expired))
    stats = stats.at[ST_ERROR].add(count(live & ~pool_valid))
    stats = stats.at[ST_HIT].add(count(reply))
    bcast = (flags & XF_BCAST) != 0
    stats = stats.at[ST_BCAST].add(count(reply & bcast))
    stats = stats.at[ST_UCAST].add(count(reply & ~bcast))

    # verdict block written over the donated descriptor's lead columns:
    # XLA aliases the output onto the input staging buffer
    block = (desc
             .at[:, VB_VERDICT].set(reply.astype(jnp.uint32))
             .at[:, VB_YIADDR].set(jnp.where(reply, assign[:, AV_IP], 0))
             .at[:, VB_POOL].set(jnp.where(reply, pool_id, 0))
             .at[:, VB_LEASE_T].set(
                 jnp.where(reply, pool_row[:, PV_LEASE_T], 0)))
    return ExpressResult(block=block, stats=stats)
