"""Vectorized byte extraction/composition over packet batches.

Packets live as `[B, L]` uint8 arrays (L static, default 512 — covers DHCP's
~350 bytes worst case, bpf/maps.h:22 caps option scans at 312). All helpers
are branch-free gathers/selects — the TPU equivalent of the reference's
verifier-safe fixed-offset parsing style (bpf/dhcp_fastpath.c:216-250).

Offsets may be per-lane (`[B]` int32) because VLAN tagging shifts L3 by
0/4/8 bytes per packet (bpf/dhcp_fastpath.c:352-428).

What the reads cost, and which form to use. A per-lane offset makes a
read a gather, and a gather moves ONE byte an index whatever it holds:
measured on a v5e (PERF.md section 6, PR 25, 26, 31), a
`take_along_axis` over a [8192, 1536] slot ran at 1.0 GB/s of 819 (over
130 ms) and one over [8192, 32] takes 2.8-2.9 ms, paid by every lane of the
batch. So:

- a field at a STATIC column is a slice: pass the readers a Python int
  (`u8_at(pkt, 6)`, `bytes_at(win, 28, 16)`), and nothing is gathered;
- a field at a base that takes a FEW static values (the DHCP header
  behind 0/1/2 tags and an IHL of 5..15: 13 bases) is a static slice of
  `window_at(pkt, base, bases, n)`, the select among the statically
  sliced copies of the slot, built once for all such fields;
- a shift by a few static amounts is the same select over pad/slice
  copies (ops/dhcp.py's reply compose, `_placed_at`);
- `u8_at` .. `bytes_at` with a per-lane offset are for a base that is
  truly free (antispoof's IPv6 source behind extension headers), and
  for fields, never for moving a packet.
"""

from __future__ import annotations

import jax.numpy as jnp

PKT_LEN = 512  # static packet slot size


def _off(offs):
    return jnp.asarray(offs).astype(jnp.int32)


def u8_at(pkt, offs):
    """One byte per lane -> [B] uint32: a gather at per-lane offsets, a
    slice at a static (Python int) column."""
    if isinstance(offs, int):
        return pkt[:, offs].astype(jnp.uint32)
    idx = jnp.clip(_off(offs), 0, pkt.shape[1] - 1)
    return jnp.take_along_axis(pkt, idx[:, None], axis=1)[:, 0].astype(jnp.uint32)


def be16_at(pkt, offs):
    return (u8_at(pkt, offs) << 8) | u8_at(pkt, offs + 1)


def be32_at(pkt, offs):
    return (be16_at(pkt, offs) << 16) | be16_at(pkt, offs + 2)


def bytes_at(pkt, offs, n: int):
    """n consecutive bytes per lane -> [B, n] uint8 (n static): a gather
    at per-lane offsets, a slice at a static (Python int) column."""
    if isinstance(offs, int):
        return pkt[:, offs:offs + n]
    idx = _off(offs)[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    idx = jnp.clip(idx, 0, pkt.shape[1] - 1)
    return jnp.take_along_axis(pkt, idx, axis=1)


def window_at(pkt, offs, bases: tuple[int, ...], n: int):
    """`bytes_at(pkt, offs, n)` for a per-lane `offs` [B] that takes one of
    the static `bases`: a select among the statically sliced copies of the
    slot, one pass each, where the gather moves one byte an index. Columns
    past the slot's end read its last byte, as `bytes_at`'s clip does. A
    lane at none of the bases reads at the last one: its caller masks it."""
    offs = _off(offs)
    short = max(bases) + n - pkt.shape[1]
    if short > 0:
        pkt = jnp.pad(pkt, ((0, 0), (0, short)), mode="edge")
    out = pkt[:, bases[-1]:bases[-1] + n]
    for b in reversed(bases[:-1]):
        out = jnp.where((offs == b)[:, None], pkt[:, b:b + n], out)
    return out


# Per-lane writes are SELECTS, not scatters: a scatter with per-lane
# column indices serializes on TPU (row-at-a-time dynamic-update-slice),
# while a broadcast compare + where is one fused VPU pass over [B, L].
# An n-byte field costs one pass; consecutive field writes fuse.


def _select_write(pkt, offs, val, nbytes: int, mask=None):
    """Write an nbytes big-endian field at per-lane offsets via select."""
    col = jnp.arange(pkt.shape[1], dtype=jnp.int32)[None, :]
    rel = col - _off(offs)[:, None]  # [B, L] position within the field
    inb = (rel >= 0) & (rel < nbytes)
    if mask is not None:
        inb = inb & mask[:, None]
    sh = jnp.clip((nbytes - 1 - rel) * 8, 0, 31).astype(jnp.uint32)
    byte = (val.astype(jnp.uint32)[:, None] >> sh) & 0xFF
    return jnp.where(inb, byte.astype(pkt.dtype), pkt)


def scatter_u8_at(pkt, offs, val):
    """Write one byte per lane at per-lane offsets (in-place rewrite path).

    Used by NAT44 where a few fields are rewritten at VLAN/IHL-dependent
    offsets (bpf/nat44.c:752-801).
    """
    return _select_write(pkt, offs, val, 1)


def scatter_be16_at(pkt, offs, val):
    return _select_write(pkt, offs, val, 2)


def scatter_be32_at(pkt, offs, val):
    return _select_write(pkt, offs, val, 4)


def scatter_u8_at_masked(pkt, offs, val, mask):
    """Masked per-lane byte write: lanes with mask=False keep old bytes."""
    return _select_write(pkt, offs, val, 1, mask)


def scatter_be16_at_masked(pkt, offs, val, mask):
    return _select_write(pkt, offs, val, 2, mask)


def scatter_be32_at_masked(pkt, offs, val, mask):
    return _select_write(pkt, offs, val, 4, mask)


# ---- segment builders (compose-by-concatenation path) ----
# Building a reply by chaining .at[:, col].set(...) creates one
# dynamic-update-slice per field — dozens of serial buffer copies. Instead
# build [B, n] byte segments and concatenate once.


def const_seg(Bsz: int, *vals: int):
    """[B, len(vals)] uint8 segment of per-batch constants."""
    row = jnp.asarray(vals, dtype=jnp.uint8)
    return jnp.broadcast_to(row[None, :], (Bsz, len(vals)))


def be16_seg(val):
    """[B] value -> [B, 2] big-endian bytes."""
    v = val.astype(jnp.uint32)
    return jnp.stack([(v >> 8) & 0xFF, v & 0xFF], axis=1).astype(jnp.uint8)


def be32_seg(val):
    v = val.astype(jnp.uint32)
    return jnp.stack(
        [(v >> 24) & 0xFF, (v >> 16) & 0xFF, (v >> 8) & 0xFF, v & 0xFF], axis=1
    ).astype(jnp.uint8)


def u8_seg(val):
    return val.astype(jnp.uint8)[:, None]
