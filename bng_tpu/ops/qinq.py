"""The access VLANs terminated on the chip: tags off upstream, on downstream.

In the 1:1 VLAN model an access network tells its subscribers apart by a
pair of tags: the S-tag (outer, 802.1ad) names the access node, the C-tag
(inner, 802.1Q) the line (pkg/qinq/qinq.go:18-44). The access node forwards
by the pair, so a downstream frame without it reaches no port; the core is
routed, so an upstream frame must not carry it there. The reference keeps
the pair <-> subscriber registry in userspace (qinq.go:100-210) and leaves
the tagging to VLAN sub-interfaces of the Linux kernel beside its eBPF; here
the chip is the forwarding plane, so both happen in the fused step, after
every other stage has had the frame:

- a forwarded lane leaves without the tags it came with (`qinq_pop`: NAT,
  QoS and the PPPoE decap have read their offsets behind them by then);
- a forwarded downstream lane whose subscriber holds a pair leaves with it
  behind the MAC addresses, in front of the PPPoE header where the
  subscriber has a session (`qinq_push`, after `pppoe_encap`). The pair is
  looked up in `by_ip` by the subscriber's address: the post-DNAT
  destination, which the downstream QoS probe and the PPPoE encap key by
  too, or for an IPv6 lane the IPv4 address stage `v6` resolved;
- a lane that is passed, dropped or answered (a DHCP reply carries its
  request's tags, ops/dhcp.py) keeps its bytes.

A subscriber without a pair is served untagged and counted (`QQ_MISS`); a
lane the pair would push past its slot is left as it is and counted
(`QQ_OVERSIZE`). Both byte moves are selects among statically shifted
copies of the slot (ops/pppoe.py `_shift_bytes`), never a gather over it.

Not checked: that an upstream frame's pair is the one registered for its
subscriber. The reference's antispoof binds MAC and address only
(bpf/antispoof.c:188-293); in a 1:1 model the line is part of the identity
(ROADMAP M1).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bng_tpu.ops.pppoe import qinq_pop, qinq_push
from bng_tpu.ops.table import TableGeom, TableState, lookup

# by-address value words (8: narrower rows gather a word at a time,
# ops/table.py way_stride)
(QV_S_TAG, QV_C_TAG) = range(2)
QINQ_WORDS = 8

(QQ_PUSH, QQ_POP, QQ_MISS, QQ_OVERSIZE) = range(4)
QINQ_NSTATS = 4


class QinQResult(NamedTuple):
    out_pkt: jax.Array  # [B, L] uint8
    out_len: jax.Array  # [B] uint32
    stats: jax.Array  # [QINQ_NSTATS] uint32


def qinq_stage(pkt: jax.Array, length: jax.Array, vlan_offset: jax.Array,
               from_access: jax.Array, fwd: jax.Array, sub_ip: jax.Array,
               by_ip: TableState, geom: TableGeom) -> QinQResult:
    """`pkt` / `length`: the frames as the step would hand them back;
    `vlan_offset` [B]: the tag bytes each came with (parse: 0 / 4 / 8);
    `fwd` [B] bool: the lanes that leave forwarded; `sub_ip` [B] uint32:
    the subscriber's IPv4 address on a downstream lane."""
    res = lookup(by_ip, sub_ip[:, None].astype(jnp.uint32), geom)
    pkt, length, popped = qinq_pop(pkt, length, vlan_offset, fwd)
    down = fwd & ~from_access
    has = down & res.found
    pkt, length, pushed = qinq_push(pkt, length, res.vals[:, QV_S_TAG],
                                    res.vals[:, QV_C_TAG], has)
    n = lambda m: jnp.sum(m, dtype=jnp.uint32)  # noqa: E731
    return QinQResult(
        out_pkt=pkt, out_len=length,
        stats=jnp.stack([n(pushed), n(popped), n(down & ~res.found),
                         n(has & ~pushed)]))
