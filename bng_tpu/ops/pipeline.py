"""The fused BNG packet pipeline — one jitted program per batch.

The reference runs four separate eBPF programs on different hooks (XDP
DHCP, TC antispoof/qos/NAT, SURVEY.md §1). On TPU, dispatch overhead
dominates small kernels, so the whole chain is ONE fused XLA program over a
[B, L] batch:

    parse -> antispoof -> DHCP responder -> NAT44 (SNAT/DNAT) -> QoS

Hook-order parity: XDP runs before TC in the kernel, so a DHCP fast-path
reply (XDP_TX) never traverses antispoof/QoS — here TX lanes are exempt
from the drop masks the same way. Slow-path DHCP requests (is_dhcp &
~is_reply) are likewise exempt from antispoof (DISCOVER's 0.0.0.0 source
must reach the DHCP server; the reference achieves this by attaching
antispoof only to data VLANs).

Direction is per-lane via `from_access` (True = subscriber-side ingress,
the uplink; False = core-side, the downlink) — the role of the two
interfaces in pkg/nat/tc_linux.go.

Verdicts (the XDP_TX/XDP_PASS/TC_ACT_SHOT model, per lane):
    PASS=0 (slow path / untouched), DROP=1, TX=2 (device-generated reply),
    FWD=3 (rewritten, forward).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bng_tpu.ops.antispoof import (
    ANTISPOOF_NSTATS,
    AntispoofGeom,
    antispoof_kernel,
)
from bng_tpu.ops import bytes as B_
from bng_tpu.ops.dhcp import DHCPGeom, DHCPTables, NSTATS as DHCP_NSTATS, dhcp_fastpath
from bng_tpu.ops.nat44 import (
    NATGeom,
    NATTables,
    NAT_NSTATS,
    nat44_kernel,
    nat44_update_sessions,
)
from bng_tpu.ops.parse import parse_batch
from bng_tpu.ops.qos import QOS_NSTATS, QoSGeom, qos_kernel
from bng_tpu.ops.qtable import QTableState
from bng_tpu.ops.table import TableGeom, TableState

VERDICT_PASS, VERDICT_DROP, VERDICT_TX, VERDICT_FWD = 0, 1, 2, 3


class PipelineTables(NamedTuple):
    """All device-resident state for the fused pipeline (a pytree)."""

    dhcp: DHCPTables
    nat: NATTables
    qos_up: QTableState  # keyed by src ip (upload; qos_ingress map role)
    qos_down: QTableState  # keyed by dst ip (download; qos_egress map role)
    spoof: TableState
    spoof_ranges: jax.Array  # [R, 2]
    spoof_config: jax.Array  # [2]
    # device-side walled garden (beyond the reference, ops/garden.py);
    # None = gate disabled (nil-safe, the reference's optional-maps
    # discipline, walledgarden/manager.go:113-116)
    garden: TableState | None = None
    garden_allowed: jax.Array | None = None  # [D, 3]
    # PPPoE session tables (ops/pppoe.py; control plane =
    # control/pppoe/server.py). None = no PPPoE stage compiled in — an
    # IPoE-only deployment pays nothing per batch. by_sid keys upstream
    # decap (session id -> MAC/IP row), by_ip keys downstream encap
    # (post-DNAT subscriber IP -> session row).
    pppoe_by_sid: TableState | None = None
    pppoe_by_ip: TableState | None = None
    pppoe_server_mac: jax.Array | None = None  # [2] uint32 (hi16, lo32)
    # edge protection (bng_tpu/edge): intercept tap-match rows + dense
    # filter/armed arrays, and the next-hop route table. None = no edge
    # stage compiled in; an armed-but-warrantless tap table costs one
    # predicate (the lax.cond in edge.ops.tap_match).
    tap: TableState | None = None
    tap_filters: jax.Array | None = None  # [F, 4] uint32
    tap_config: jax.Array | None = None  # [2] uint32
    route: TableState | None = None
    # IPv6 forwarding (ops/v6.py): bound /128 -> the subscriber's IPv4
    # address. None = no `v6` stage compiled in: an IPv6 frame is judged
    # by antispoof and otherwise left to the host, as before the stage.
    v6_by_addr: TableState | None = None
    # the access VLANs (ops/qinq.py): a subscriber's IPv4 address -> its
    # S- and C-tag. None = no `qinq` stage compiled in: a forwarded frame
    # leaves with the tags it came with, and a downstream one with none.
    qinq_by_ip: TableState | None = None


class PipelineGeom(NamedTuple):
    dhcp: DHCPGeom
    nat: NATGeom
    qos: QoSGeom
    spoof: AntispoofGeom
    garden: TableGeom | None = None
    pppoe: TableGeom | None = None
    tap: TableGeom | None = None
    route: TableGeom | None = None
    v6: TableGeom | None = None
    qinq: TableGeom | None = None


class PipelineResult(NamedTuple):
    verdict: jax.Array  # [B] int32
    out_pkt: jax.Array  # [B, L] uint8
    out_len: jax.Array  # [B] uint32
    tables: PipelineTables  # updated device state (counters/tokens)
    dhcp_stats: jax.Array  # [DHCP_NSTATS]
    nat_stats: jax.Array  # [NAT_NSTATS]
    qos_stats: jax.Array  # [QOS_NSTATS] (up + down combined)
    spoof_stats: jax.Array  # [ANTISPOOF_NSTATS]
    priority: jax.Array  # [B] uint32 (QoS class)
    nat_punt: jax.Array  # [B] bool — new flow, host must create session
    spoof_violation: jax.Array  # [B] bool — host audit log
    garden_stats: jax.Array | None = None  # [GARDEN_NSTATS] when gated
    pppoe_stats: jax.Array | None = None  # [PPPOE_NSTATS] when PPPoE on
    # [B] uint32: warrant id the lane mirrors for (0 = not mirrored).
    # Deliberately a side array, NOT a verdict bit: verdict histograms
    # and == VERDICT_* comparisons stay exact. The host retire path
    # (engine mirror_sink) extracts wid != 0 lanes for RecordCC/HI3.
    mirror: jax.Array | None = None
    edge_stats: jax.Array | None = None  # [EDGE_NSTATS] when edge on
    v6_stats: jax.Array | None = None  # [V6_NSTATS] when the v6 stage is on
    qinq_stats: jax.Array | None = None  # [QINQ_NSTATS] when qinq is on


# Each stage below runs under a `jax.named_scope` (parse, antispoof, dhcp,
# garden, nat44, qos, edge, pppoe, v6, rewrite, qinq). Metadata only: the HLO
# and its op names are the same, and a recorded device trace can be summed by
# stage
# (`python -m bng_tpu.utils.profiling <trace dir>`).
def pipeline_step(
    tables: PipelineTables,
    pkt: jax.Array,
    length: jax.Array,
    from_access: jax.Array,
    geom: PipelineGeom,
    now_s: jax.Array,
    now_us: jax.Array,
) -> PipelineResult:
    # --- PPPoE decap pre-stage (session-stage upstream data; the
    # AC-termination role of pkg/pppoe/server.go:466-529, moved on-device
    # for DATA frames — control negotiation stays host-side and reaches it
    # via PASS lanes). Runs BEFORE the main parse so NAT/QoS/antispoof see
    # the inner IPv4 packet; PPPoE control/discovery and unknown-session
    # frames keep their original bytes, parse as non-IP, and fall through
    # every later stage to VERDICT_PASS (the slow-path punt).
    pppoe_dec = None
    if tables.pppoe_by_sid is not None:
        from bng_tpu.ops.parse import eth_vlan
        from bng_tpu.ops.pppoe import pppoe_decap

        with jax.named_scope("pppoe"):
            vo, et = eth_vlan(pkt)
            # access-side only: a session ethertype arriving from the core
            # is foreign traffic — leave it untouched (PASS, host decides)
            et_gated = jnp.where(from_access, et, 0)
            pppoe_dec = pppoe_decap(pkt, length, vo, et_gated,
                                    tables.pppoe_by_sid, geom.pppoe)
            pkt = jnp.where(pppoe_dec.done[:, None], pppoe_dec.out_pkt, pkt)
            length = jnp.where(pppoe_dec.done, pppoe_dec.out_len, length)

    v6_on = tables.v6_by_addr is not None
    with jax.named_scope("parse"):
        parsed = parse_batch(pkt, length, v6=v6_on)

    # --- antispoof (TC ingress on access side; antispoof.c:188-293) ---
    with jax.named_scope("antispoof"):
        spoof = antispoof_kernel(pkt, parsed, tables.spoof, geom.spoof,
                                 tables.spoof_ranges, tables.spoof_config)
    spoof_drop = spoof.dropped & from_access

    # --- DHCP fast path (XDP; dhcp_fastpath.c:619-813) ---
    with jax.named_scope("dhcp"):
        dhcp = dhcp_fastpath(pkt, length, parsed, tables.dhcp, geom.dhcp, now_s)
    dhcp_tx = dhcp.is_reply & from_access
    dhcp_slow = dhcp.is_dhcp & from_access & ~dhcp_tx
    # DHCP traffic bypasses antispoof (XDP-before-TC for TX; DISCOVER src
    # 0.0.0.0 must reach the slow path)
    spoof_drop = spoof_drop & ~dhcp.is_dhcp

    # --- IPv6 beside the NAT'd IPv4 (ops/v6.py): which lanes are bound
    # IPv6 data, and the v4 address that names each one's QoS buckets ---
    v6 = None
    if v6_on:
        from bng_tpu.ops.v6 import v6_lanes, v6_stats

        with jax.named_scope("v6"):
            v6 = v6_lanes(parsed, spoof, from_access, tables.v6_by_addr,
                          geom.v6)

    # --- walled-garden gate (device-side; BEYOND the reference, whose
    # garden maps have no consuming bpf program — ops/garden.py) ---
    garden_drop = jnp.zeros_like(from_access)
    garden_stats = None
    if tables.garden is not None:
        from bng_tpu.ops.garden import garden_kernel

        with jax.named_scope("garden"):
            garden = garden_kernel(
                parsed,
                from_access & parsed.is_ipv4 & ~dhcp.is_dhcp,
                tables.garden, geom.garden, tables.garden_allowed)
        garden_drop = garden.gate_drop
        garden_stats = garden.stats

    # --- NAT44 (TC; nat44.c:565-948) — not for DHCP or gated lanes ---
    with jax.named_scope("nat44"):
        nat = nat44_kernel(pkt, length, parsed, tables.nat, geom.nat, now_s)
    natable = ~dhcp.is_dhcp & ~spoof_drop & ~garden_drop
    nat_fwd = nat.translated & natable
    nat_punt = nat.punted & natable

    # --- QoS (TC; qos_ratelimit.c:126-222) ---
    # upload: access-side lanes keyed by src ip (qos_ingress_prog :178)
    # a subscriber's v6 bytes draw on the buckets its v4 address names,
    # in the same call and the same sort as its v4 bytes
    up_key, up_on = parsed.src_ip, from_access & parsed.is_ipv4 & ~dhcp.is_dhcp
    if v6 is not None:
        up_key, up_on = jnp.where(v6.up, v6.qos_key, up_key), up_on | v6.up
    with jax.named_scope("qos"):
        up = qos_kernel(up_key, length, up_on, tables.qos_up, geom.qos, now_us)
    # download: core-side lanes keyed by POST-DNAT dst ip (the subscriber
    # address — after DNAT the dst is the private ip, qos_egress_prog :126).
    # Read it from the rewritten bytes: covers translated and untouched lanes.
    with jax.named_scope("qos"):
        dnat_dst = B_.be32_at(nat.out_pkt, parsed.l3_off + 16)
        down_key, down_on = dnat_dst, ~from_access & parsed.is_ipv4
        if v6 is not None:
            down_key = jnp.where(v6.down, v6.qos_key, down_key)
            down_on = down_on | v6.down
        down = qos_kernel(down_key, length, down_on,
                          tables.qos_down, geom.qos, now_us)
    qos_drop = (up.dropped & from_access) | (down.dropped & ~from_access)

    # --- edge protection (bng_tpu/edge): intercept tap-match + next-hop
    # route rewrite. The tap keys on the SUBSCRIBER address of the lane
    # (src upstream, post-DNAT dst downstream) so one row taps both
    # directions of a session; the route table steers upstream lanes to
    # their ISP next-hop (per-class ECMP compiled host-side). Mirror is
    # a side array (see PipelineResult); the route rewrite patches the
    # L2 dst MAC in place on nat.out_pkt — upstream-only, disjoint from
    # pppoe_encap's downstream MAC stamp.
    mirror = None
    edge_stats = None
    data_pkt = nat.out_pkt
    route_fwd = jnp.zeros_like(from_access)
    if tables.tap is not None:
        from bng_tpu.edge.ops import route_rewrite, tap_match

        with jax.named_scope("edge"):
            sub_ip = jnp.where(from_access, parsed.src_ip, dnat_dst)
            peer_ip = jnp.where(from_access, parsed.dst_ip, parsed.src_ip)
            data_lane = parsed.is_ipv4 & ~dhcp.is_dhcp
            tap = tap_match(sub_ip, parsed.src_port, parsed.dst_port,
                            parsed.proto, peer_ip, data_lane, tables.tap,
                            tables.tap_filters, tables.tap_config, geom.tap)
            mirror = tap.mirror
            rt = route_rewrite(data_pkt, sub_ip, data_lane & from_access,
                               tables.route, geom.route)
            data_pkt = rt.out_pkt
            route_fwd = rt.hit
            edge_stats = jnp.concatenate([tap.stats, rt.stats])

    # --- PPPoE encap post-stage: downstream data whose post-DNAT dst is
    # an OPEN PPPoE session gets its AC framing here (the reference builds
    # these frames host-side per packet, pkg/pppoe/server.go; batched
    # on-device they ride the same program). Applies to nat.out_pkt —
    # dhcp_tx lanes are access-side and disjoint.
    pppoe_enc = None
    if tables.pppoe_by_ip is not None:
        from bng_tpu.ops.pppoe import pppoe_encap

        with jax.named_scope("pppoe"):
            enc_et = jnp.where(~from_access, parsed.ethertype, 0)
            pppoe_enc = pppoe_encap(nat.out_pkt, length, parsed.vlan_offset,
                                    enc_et, dnat_dst, tables.pppoe_by_ip,
                                    geom.pppoe, tables.pppoe_server_mac)

    # --- verdict combination (precedence: TX > DROP > FWD > PASS) ---
    with jax.named_scope("rewrite"):
        drop = (spoof_drop | qos_drop | garden_drop) & ~dhcp_tx
        # a routed (next-hop-rewritten) lane forwards even when NAT left it
        # untouched — the non-CGNAT routed-subscriber case
        fwd = nat_fwd | (route_fwd & ~drop & ~dhcp_tx)
        out_pkt = jnp.where(dhcp_tx[:, None], dhcp.out_pkt, data_pkt)
        out_len = jnp.where(dhcp_tx, dhcp.out_len, length)
        if pppoe_enc is not None:
            enc_done = pppoe_enc.done & ~drop & ~dhcp_tx
            out_pkt = jnp.where(enc_done[:, None], pppoe_enc.out_pkt, out_pkt)
            out_len = jnp.where(enc_done, pppoe_enc.out_len, out_len)
            # an encapsulated frame forwards even when NAT left it untouched
            # (routed/IPoE-free deployments still need the PPP framing)
            fwd = fwd | enc_done
        if v6 is not None:
            # a bound IPv6 lane forwards with its bytes untouched (NAT
            # never translates one: `data_pkt` holds the frame as it came)
            fwd = fwd | (v6.up | v6.down)
        verdict = jnp.where(
            dhcp_tx, VERDICT_TX,
            jnp.where(drop, VERDICT_DROP,
                      jnp.where(fwd, VERDICT_FWD, VERDICT_PASS)),
        ).astype(jnp.int32)

    # --- the access VLANs (ops/qinq.py), last: every stage above has read
    # its offsets behind the tags a frame came with. `down_key` is the
    # subscriber's address on a downstream lane of either family.
    qinq = None
    if tables.qinq_by_ip is not None:
        from bng_tpu.ops.qinq import qinq_stage

        with jax.named_scope("qinq"):
            qinq = qinq_stage(out_pkt, out_len, parsed.vlan_offset,
                              from_access, verdict == VERDICT_FWD, down_key,
                              tables.qinq_by_ip, geom.qinq)
            out_pkt, out_len = qinq.out_pkt, qinq.out_len

    # NAT accounting only for lanes that actually forward: a packet the
    # pipeline drops (QoS/antispoof) must not advance session counters
    with jax.named_scope("nat44"):
        new_sessions = nat44_update_sessions(
            tables.nat.sessions, nat, parsed, length,
            keep=nat_fwd & ~drop, now_s=now_s)
    new_tables = tables._replace(
        nat=tables.nat._replace(sessions=new_sessions),
        qos_up=up.table,
        qos_down=down.table,
    )
    return PipelineResult(
        verdict=verdict,
        out_pkt=out_pkt,
        out_len=out_len,
        tables=new_tables,
        dhcp_stats=dhcp.stats,
        nat_stats=nat.stats,
        qos_stats=up.stats + down.stats,
        spoof_stats=spoof.stats,
        priority=jnp.maximum(up.priority, down.priority),
        nat_punt=nat_punt,
        spoof_violation=spoof.violation,
        garden_stats=garden_stats,
        pppoe_stats=(None if pppoe_dec is None else
                     pppoe_dec.stats + (0 if pppoe_enc is None
                                        else pppoe_enc.stats)),
        mirror=mirror,
        edge_stats=edge_stats,
        v6_stats=(None if v6 is None
                  else v6_stats(v6, verdict == VERDICT_FWD)),
        qinq_stats=None if qinq is None else qinq.stats,
    )
