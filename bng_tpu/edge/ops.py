"""Device kernels for edge protection: intercept tap-match + next-hop
route rewrite (ISSUE 17).

Both kernels follow the `ops/antispoof.py` mold: a bucketized-cuckoo
probe through `lookup()`, dense side
arrays for per-row config, and a packed uint32 stats vector the engine
folds host-side.

Tap-match
---------
Warrants from `control/intercept.py` compile (via `edge/compile.py`)
into device rows keyed by the *subscriber* IPv4 (src for upstream
lanes, post-DNAT dst for downstream lanes). A row carries the warrant
id (`wid`); optional port/proto/peer filters live in a dense
`tap_filters[F, 4]` array keyed back to the wid. A matching lane gets
`wid` in the per-lane MIRROR word of the pipeline result (0 = not
mirrored) — deliberately a side array, NOT a bit OR'd into the verdict
word, so verdict histograms and `== VERDICT_*` comparisons stay exact.
The host retire path extracts flagged frames and feeds
`RecordCC`/HI3 export.

The zero-warrant configuration must add no device work beyond one
predicate: the whole armed body sits under a `jax.lax.cond` on
`tap_config[TC_ARMED]`, so a disarmed table costs a single scalar
branch, not a probe.

Route rewrite
-------------
`control/routing.py`'s manager state (ISP table + ECMP next-hop
selection by subscriber class) compiles into device rows keyed by the
subscriber IPv4. Upstream lanes that hit get their L2 destination MAC
rewritten in place to the selected next-hop gateway (the same masked
scatter mold as `pppoe_encap`'s MAC stamp) and the rewrite lands in
the downstream verdict as a FWD. Route flap churn arrives as bounded
dirty-slot deltas through the existing drain — never a resync.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

import bng_tpu.ops.bytes as B_
from bng_tpu.ops.table import TableGeom, TableState, lookup

# --- tap row value words --------------------------------------------------
# TW_FLAG: 1 = armed row (0-valued rows are dead slots)
# TW_WID:  warrant id the row mirrors for (host maps wid -> warrant)
(TW_FLAG, TW_WID) = range(2)
TAP_WORDS = 8

# dense filter rows [F, 4]; a row belongs to a wid, lane passes if ANY
# of its wid's rows match (0 in a column = wildcard; wid 0 = free row)
(TF_WID, TF_PORT, TF_PROTO, TF_PEER) = range(4)
TAP_FILTER_COLS = 4

# dense tap config words; TC_ARMED = count of armed rows (the single
# disarmed-path predicate)
TC_ARMED = 0
TAP_CONFIG_WORDS = 2

# --- route row value words ------------------------------------------------
# RW_FLAG:   1 = live next-hop row
# RW_MAC_*:  next-hop gateway MAC (hi16 / lo32, same split as pppoe rows)
# RW_TABLE:  ISP routing table id (telemetry/audit only on device)
# RW_CLASS:  subscriber class code the selection was made under
(RW_FLAG, RW_MAC_HI, RW_MAC_LO, RW_TABLE, RW_CLASS) = range(5)
ROUTE_WORDS = 8

# --- packed stats ---------------------------------------------------------
(EST_MIRRORED, EST_TAP_FILTERED, EST_ROUTE_REWRITES,
 EST_ROUTE_MISSES) = range(4)
EDGE_NSTATS = 4


class TapResult(NamedTuple):
    mirror: jax.Array   # [B] uint32: warrant id where mirrored, 0 = no
    stats: jax.Array    # [2] uint32: (mirrored, filtered-out)


class RouteResult(NamedTuple):
    out_pkt: jax.Array  # [B, S] uint8, dst MAC rewritten on hit lanes
    hit: jax.Array      # [B] bool: next-hop rewrite applied
    stats: jax.Array    # [2] uint32: (rewrites, eligible misses)


def tap_match(sub_ip: jax.Array, src_port: jax.Array, dst_port: jax.Array,
              proto: jax.Array, peer_ip: jax.Array, eligible: jax.Array,
              taps: TableState, filters: jax.Array, config: jax.Array,
              geom: TableGeom) -> TapResult:
    """Per-lane intercept tap match. `sub_ip`/`peer_ip` are uint32
    host-order IPv4 (subscriber side / far side of the flow); `eligible`
    gates to parsed IPv4 data lanes. Disarmed (zero armed rows) costs
    one predicate — the probe and filter scan never execute."""
    bsz = sub_ip.shape[0]

    def _armed(_):
        res = lookup(taps, sub_ip[:, None].astype(jnp.uint32), geom)
        hit = res.found & (res.vals[:, TW_FLAG] != 0) & eligible
        wid = res.vals[:, TW_WID]
        fw = filters[:, TF_WID]
        # [B, F]: filter row belongs to this lane's warrant
        mine = (fw[None, :] != 0) & (fw[None, :] == wid[:, None])
        port = filters[:, TF_PORT]
        port_ok = ((port[None, :] == 0)
                   | (src_port.astype(jnp.uint32)[:, None] == port[None, :])
                   | (dst_port.astype(jnp.uint32)[:, None] == port[None, :]))
        prt = filters[:, TF_PROTO]
        proto_ok = ((prt[None, :] == 0)
                    | (proto.astype(jnp.uint32)[:, None] == prt[None, :]))
        per = filters[:, TF_PEER]
        peer_ok = ((per[None, :] == 0)
                   | (peer_ip.astype(jnp.uint32)[:, None] == per[None, :]))
        has_filter = mine.any(axis=1)
        passes = (mine & port_ok & proto_ok & peer_ok).any(axis=1)
        matched = hit & (~has_filter | passes)
        mirror = jnp.where(matched, wid, 0).astype(jnp.uint32)
        stats = jnp.stack([
            matched.sum().astype(jnp.uint32),
            (hit & ~matched).sum().astype(jnp.uint32),
        ])
        return mirror, stats

    def _disarmed(_):
        return (jnp.zeros((bsz,), jnp.uint32), jnp.zeros((2,), jnp.uint32))

    mirror, stats = jax.lax.cond(config[TC_ARMED] > 0, _armed, _disarmed, 0)
    return TapResult(mirror=mirror, stats=stats)


def route_rewrite(pkt: jax.Array, sub_ip: jax.Array, eligible: jax.Array,
                  routes: TableState, geom: TableGeom) -> RouteResult:
    """Per-lane next-hop rewrite for upstream (subscriber -> ISP)
    traffic: probe by subscriber IPv4, stamp the selected gateway MAC
    into the L2 destination (offset 0) on hit lanes. Same masked
    scatter mold as pppoe_encap's MAC stamp — one fused VPU pass, no
    gather/scatter of whole frames."""
    res = lookup(routes, sub_ip[:, None].astype(jnp.uint32), geom)
    hit = res.found & (res.vals[:, RW_FLAG] != 0) & eligible
    z = jnp.zeros(sub_ip.shape, dtype=jnp.int32)
    out = B_.scatter_be16_at_masked(pkt, z, res.vals[:, RW_MAC_HI], hit)
    out = B_.scatter_be32_at_masked(out, z + 2, res.vals[:, RW_MAC_LO], hit)
    stats = jnp.stack([
        hit.sum().astype(jnp.uint32),
        (eligible & ~hit).sum().astype(jnp.uint32),
    ])
    return RouteResult(out_pkt=out, hit=hit, stats=stats)
