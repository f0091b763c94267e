"""IPv6 beside the CGNAT'd IPv4: bind, police and forward, batched.

The reference's antispoof holds one exact IPv6 binding a subscriber MAC
(bpf/antispoof.c:256-288) and leaves the forwarding of an admitted frame
to the Linux kernel beside its eBPF. Here the chip is the forwarding
plane, so an IPv6 data frame gets its verdict in the fused step:

- upstream (access side): the antispoof kernel already gathered the MAC's
  binding row; a source equal to the row's /128 is admitted, and the
  row's IPv4 address is the subscriber's QoS key (ops/antispoof.py
  `v6_bound`, `bound_v4`). Control (fe80::/10 or :: as source, ff00::/8 or
  fe80::/10 as destination) is never a violation and passes to the host;
- downstream (core side): the destination is looked up in `by_addr`, a
  table keyed by the four words of a bound /128 whose value is the
  subscriber's IPv4 address: the proof that the destination is a bound
  subscriber, and its QoS key. A miss passes to the host.

A forwarded lane leaves byte for byte (no hop-limit decrement: the v4
path leaves TTL alone too, ROADMAP M5). No v6 L4 parse: nothing here reads
a port, so extension headers need no walk.

One departure from the reference, stated in the benchmark's configuration:
qos_ratelimit.c keys its buckets by IPv4 address and so does not police
IPv6; here a subscriber's v6 bytes draw on the buckets its v4 address
names, because a rate plan is the subscriber's.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bng_tpu.ops.antispoof import AntispoofResult
from bng_tpu.ops.parse import Parsed
from bng_tpu.ops.table import TableGeom, TableState, lookup

# by-address value words (8: narrower rows gather a word at a time,
# ops/table.py way_stride); the MAC is the host's way back to the binding
(VA_IPV4, VA_MAC_HI, VA_MAC_LO) = range(3)
V6_WORDS = 8

(V6ST_FWD_UP, V6ST_FWD_DOWN, V6ST_MISS, V6ST_CTRL) = range(4)
V6_NSTATS = 4


class V6Result(NamedTuple):
    up: jax.Array  # [B] bool: upstream data, source bound
    down: jax.Array  # [B] bool: downstream, destination bound
    qos_key: jax.Array  # [B] uint32: the subscriber's v4 address on up | down
    miss: jax.Array  # [B] bool: downstream, destination unknown
    ctrl: jax.Array  # [B] bool: upstream control, left to the host


def v6_lanes(parsed: Parsed, spoof: AntispoofResult, from_access: jax.Array,
             by_addr: TableState, geom: TableGeom) -> V6Result:
    up = from_access & spoof.v6_bound
    to_sub = ~from_access & parsed.is_ipv6
    res = lookup(by_addr, parsed.dst6, geom)
    down = to_sub & res.found
    return V6Result(
        up=up, down=down,
        qos_key=jnp.where(up, spoof.bound_v4, res.vals[:, VA_IPV4]),
        miss=to_sub & ~res.found,
        ctrl=from_access & spoof.v6_ctrl)


def v6_stats(v6: V6Result, fwd: jax.Array) -> jax.Array:
    """[V6_NSTATS] uint32; `fwd` [B] bool: the lanes that left forwarded
    (an admitted lane out of tokens is a QoS drop, counted there)."""
    n = lambda m: jnp.sum(m, dtype=jnp.uint32)  # noqa: E731
    return jnp.stack([n(v6.up & fwd), n(v6.down & fwd), n(v6.miss),
                      n(v6.ctrl)])
