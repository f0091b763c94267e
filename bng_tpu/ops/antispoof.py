"""Anti-spoofing / uRPF source validation, batched.

TPU re-expression of bpf/antispoof.c (antispoof_ingress, :188-293).
Per-lane mode resolution, strict/loose/log-only semantics, IPv4 + IPv6
exact binding, and LPM "allowed ranges" done as a dense broadcast compare
(<=256 ranges, antispoof.c:113-119 — a [B, R] compare beats a trie on TPU).

Deliberate parity quirk preserved: a subscriber with a valid IPv4 binding
in LOOSE mode is never matched against the range list (antispoof.c:227-235
only checks ranges in the else-branch), so loose-mode-with-binding drops
unless the mode is strict/log-only and the IP matches.

Violation reporting: instead of a perf-event buffer (antispoof.c:100-105)
the kernel returns per-lane violation flags; the engine extracts violating
lanes and hands them to the host audit logger.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bng_tpu.ops import bytes as B_
from bng_tpu.ops.parse import Parsed
from bng_tpu.ops.table import TableGeom, TableState, lookup

# modes (antispoof.c:30-33)
MODE_DISABLED, MODE_STRICT, MODE_LOOSE, MODE_LOG_ONLY = range(4)

# binding value words (parity: struct subscriber_binding, antispoof.c:36-43)
(AB_IPV4, AB_V6_0, AB_V6_1, AB_V6_2, AB_V6_3, AB_VALIDS, AB_MODE) = range(7)
ANTISPOOF_WORDS = 8
VALID_V4, VALID_V6 = 0x01, 0x02

# stats (parity: struct antispoof_stats, antispoof.c:58-65)
(AST_ALLOWED, AST_DROPPED, AST_LOGGED, AST_V4_VIOL, AST_V6_VIOL, AST_UNKNOWN_MAC) = range(6)
ANTISPOOF_NSTATS = 6


AntispoofGeom = TableGeom


class AntispoofResult(NamedTuple):
    dropped: jax.Array  # [B] bool
    violation: jax.Array  # [B] bool (includes log-only violations)
    stats: jax.Array  # [ANTISPOOF_NSTATS] uint32
    # what the `v6` stage of ops/pipeline.py takes from the binding row
    # this kernel gathered anyway; None unless the parse read `dst6`
    v6_bound: jax.Array | None = None  # [B] bool: IPv6 data, source == the row's /128
    v6_ctrl: jax.Array | None = None  # [B] bool: IPv6 control (is_v6_control)
    bound_v4: jax.Array | None = None  # [B] uint32: the row's AB_IPV4 (0: no row)


def is_v6_control(src6: jax.Array, dst6: jax.Array) -> jax.Array:
    """IPv6 frames that can never carry a bound global /128 as source and
    still have to reach the host: source in fe80::/10 or ::, destination in
    ff00::/8 or fe80::/10 (RS, NS, DHCPv6 SOLICIT to ff02::1:2). [B, 4]
    big-endian words each -> [B] bool."""
    link_local = lambda a: (a[:, 0] >> 22) == 0x3FA  # noqa: E731
    return (link_local(src6) | jnp.all(src6 == 0, axis=1)
            | ((dst6[:, 0] >> 24) == 0xFF) | link_local(dst6))


def antispoof_kernel(
    pkt: jax.Array,
    parsed: Parsed,
    bindings: TableState,
    geom: AntispoofGeom,
    allowed_ranges: jax.Array,  # [R, 2] uint32: (prefix_len, network); plen 0 = empty row
    config: jax.Array,  # [2] uint32: [default_mode, log_violations]
) -> AntispoofResult:
    Bsz = pkt.shape[0]
    default_mode = config[0]

    mac_key = jnp.stack([parsed.src_mac_hi, parsed.src_mac_lo], axis=1)
    res = lookup(bindings, mac_key, geom)
    has_binding = res.found
    mode = jnp.where(has_binding, res.vals[:, AB_MODE], default_mode)

    disabled = mode == MODE_DISABLED

    # --- IPv4 (antispoof.c:219-253) ---
    v4_valid = has_binding & ((res.vals[:, AB_VALIDS] & VALID_V4) != 0)
    strict_ok = (parsed.src_ip == res.vals[:, AB_IPV4])
    # loose: membership in any allowed range (dense prefix compare)
    plen = allowed_ranges[:, 0]
    net = allowed_ranges[:, 1]
    sh = jnp.clip(32 - plen.astype(jnp.int32), 0, 32)
    sh1 = jnp.minimum(sh, 16)
    sh2 = sh - sh1
    src_pfx = ((parsed.src_ip[:, None] >> sh1[None, :]) >> sh2[None, :])
    net_pfx = ((net >> sh1) >> sh2)[None, :]
    in_range = jnp.any((src_pfx == net_pfx) & (plen != 0)[None, :], axis=1)

    v4_allowed = jnp.where(
        v4_valid,
        ((mode == MODE_STRICT) | (mode == MODE_LOG_ONLY)) & strict_ok,
        (mode == MODE_LOOSE) & in_range,
    )
    v4_viol = parsed.is_ipv4 & ~disabled & ~v4_allowed
    v4_drop = v4_viol & (mode != MODE_LOG_ONLY)

    # --- IPv6 (antispoof.c:256-288) ---
    v6_valid = has_binding & ((res.vals[:, AB_VALIDS] & VALID_V6) != 0)
    src6 = B_.bytes_at(pkt, parsed.l3_off + 8, 16)  # IPv6 saddr
    w = src6.astype(jnp.uint32).reshape(Bsz, 4, 4)
    src6_words = (w[:, :, 0] << 24) | (w[:, :, 1] << 16) | (w[:, :, 2] << 8) | w[:, :, 3]
    bound6 = res.vals[:, AB_V6_0 : AB_V6_3 + 1]
    v6_match = jnp.all(src6_words == bound6, axis=1)
    # loose mode with no binding allows (antispoof.c:273-277)
    v6_allowed = jnp.where(v6_valid, v6_match, mode == MODE_LOOSE)
    v6_viol = parsed.is_ipv6 & ~disabled & ~v6_allowed
    v6 = {}
    if parsed.dst6 is not None:
        # a program that forwards IPv6: control is no violation (a
        # subscriber has to be able to ask for the lease that binds it),
        # and the pipeline gets the match and the row's QoS key
        with jax.named_scope("v6"):
            ctrl = parsed.is_ipv6 & is_v6_control(src6_words, parsed.dst6)
            v6_viol = v6_viol & ~ctrl
            v6 = dict(v6_bound=parsed.is_ipv6 & ~ctrl & v6_valid & v6_match,
                      v6_ctrl=ctrl, bound_v4=res.vals[:, AB_IPV4])
    v6_drop = v6_viol & (mode != MODE_LOG_ONLY)

    dropped = v4_drop | v6_drop
    violation = v4_viol | v6_viol
    log_on = config[1] != 0

    stats = jnp.zeros((ANTISPOOF_NSTATS,), dtype=jnp.uint32)
    stats = stats.at[AST_DROPPED].add(jnp.sum(dropped, dtype=jnp.uint32))
    stats = stats.at[AST_ALLOWED].add(jnp.sum(~dropped, dtype=jnp.uint32))
    stats = stats.at[AST_V4_VIOL].add(jnp.sum(v4_drop, dtype=jnp.uint32))
    stats = stats.at[AST_V6_VIOL].add(jnp.sum(v6_drop, dtype=jnp.uint32))
    stats = stats.at[AST_LOGGED].add(jnp.sum(violation & log_on, dtype=jnp.uint32))

    return AntispoofResult(dropped=dropped, violation=violation & log_on,
                           stats=stats, **v6)
