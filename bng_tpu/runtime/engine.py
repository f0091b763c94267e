"""Host runtime engine: the packet ring <-> device pipeline glue.

This is the role pkg/ebpf plays in the reference (SURVEY.md §1 L1), turned
inside out for TPU: instead of loading programs into the kernel and writing
maps via syscalls, the engine

1. assembles frames into fixed [B, L] uint8 batches (the AF_XDP RX ring
   consumer; a C++ ring feeds this in production, synthetic sources in
   tests/bench),
2. drains bounded table-update batches from the host managers (the
   bpf_map_update_elem replacement): a table set with nothing dirty costs
   nothing, a dirty one goes through a packet-free apply program ahead of
   the step, and a dense config array that changed is put in the tables on
   the host,
3. invokes ONE donated jitted step over the tables, the window and the
   clock: fused pipeline -> verdicts,
4. applies verdicts: TX/FWD frames out, DROP counted, PASS lanes handed to
   the slow-path handlers (DHCP server, NAT new-flow manager) exactly like
   XDP_PASS delivers to the Go servers,
5. accumulates device stats into host counters (u64 in Python ints,
   mirroring pkg/metrics' 5s scrapes of the stats maps).

Single-chip engine; the sharded multi-chip variant lives in
bng_tpu.parallel.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Callable, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from bng_tpu.analysis.sanitize import owned_by
from bng_tpu.chaos.faults import FaultInjectedError, fault_point
from bng_tpu.telemetry import spans as tele
from bng_tpu.control.nat import NATManager, apply_nat_updates
from bng_tpu.edge.ops import (EST_MIRRORED, EST_ROUTE_MISSES,
                              EST_ROUTE_REWRITES, EST_TAP_FILTERED)
from bng_tpu.ops.antispoof import ANTISPOOF_NSTATS, AntispoofGeom
from bng_tpu.ops.dhcp import NSTATS as DHCP_NSTATS
from bng_tpu.ops.nat44 import NAT_NSTATS
from bng_tpu.ops.pppoe import PPPOE_NSTATS, PST_DECAP, PST_ENCAP, PST_MISS
from bng_tpu.ops.pipeline import (
    PipelineGeom,
    PipelineResult,
    PipelineTables,
    VERDICT_DROP,
    VERDICT_FWD,
    VERDICT_PASS,
    VERDICT_TX,
    pipeline_step,
)
from bng_tpu.ops.qinq import QINQ_NSTATS, QQ_MISS, QQ_POP, QQ_PUSH
from bng_tpu.ops.qos import QOS_NSTATS
from bng_tpu.ops.v6 import (V6_NSTATS, V6ST_CTRL, V6ST_FWD_DOWN, V6ST_FWD_UP,
                            V6ST_MISS)
from bng_tpu.ops.antispoof import ANTISPOOF_WORDS
from bng_tpu.ops.qtable import HostQTable, QTableGeom, apply_qupdate
from bng_tpu.ops.table import HostTable, TableGeom, apply_update, placed
from bng_tpu.runtime import hostpath
from bng_tpu.runtime.newflow import NewFlows
from bng_tpu.runtime.ring import FLAG_DHCP_CTRL
from bng_tpu.runtime.tables import (FastPathTables, PPPoEFastPathTables,
                                    QinQFastPathTables, V6FastPathTables,
                                    apply_fastpath_updates, mac_key_rows)
from bng_tpu.utils.structlog import ErrorLog, SlowPathErrorLog

# default per-lane packet slot: a full MTU frame (1500) + headroom for
# QinQ/PPPoE encap, like the reference's XDP frame slot. Engines that only
# ever see control traffic may shrink it (bench uses 512-byte slots).
PKT_SLOT = 1536


@jax.named_scope("updates")  # metadata only: the device trace's stage name
def _apply_all_updates(tables: PipelineTables, upd) -> PipelineTables:
    """upd layout: 7 mandatory entries + optional named tails — garden
    (garden_upd, allowed_rows), then pppoe (sid_upd, ip_upd), then edge
    (tap_upd, tap_filters, tap_config, route_upd), then v6 (by_addr_upd),
    then qinq (by_ip_upd) — each present exactly when the corresponding
    device stage is compiled in. Called from no one-chip step: the mesh
    loop's step applies its batch with it (parallel/sharded.py), and the
    engine's packet-free program is this function under one `jax.jit`
    (`_apply_updates_jit`), over tables whose dhcp chain is None and stays
    so (the chain has a program of its own, `_apply_fastpath_jit`)."""
    fp_upd, nat_upd, qup, qdown, sp_upd, sp_ranges, sp_config, *tails = upd
    tails = list(tails)
    g_state, g_allowed = tables.garden, tables.garden_allowed
    if tables.garden is not None:
        g_state = apply_update(tables.garden, tails.pop(0))
        g_allowed = tails.pop(0)
    p_sid, p_ip = tables.pppoe_by_sid, tables.pppoe_by_ip
    if p_sid is not None:
        p_sid = apply_update(p_sid, tails.pop(0))
        p_ip = apply_update(p_ip, tails.pop(0))
    e_tap, e_filters, e_config, e_route = (tables.tap, tables.tap_filters,
                                           tables.tap_config, tables.route)
    if e_tap is not None:
        e_tap = apply_update(e_tap, tails.pop(0))
        e_filters = tails.pop(0)
        e_config = tails.pop(0)
        e_route = apply_update(e_route, tails.pop(0))
    v6_by_addr = tables.v6_by_addr
    if v6_by_addr is not None:
        v6_by_addr = apply_update(v6_by_addr, tails.pop(0))
    qinq_by_ip = tables.qinq_by_ip
    if qinq_by_ip is not None:
        qinq_by_ip = apply_update(qinq_by_ip, tails.pop(0))
    return PipelineTables(
        dhcp=(apply_fastpath_updates(tables.dhcp, fp_upd)
              if tables.dhcp is not None else None),
        nat=apply_nat_updates(tables.nat, nat_upd),
        qos_up=apply_qupdate(tables.qos_up, qup),
        qos_down=apply_qupdate(tables.qos_down, qdown),
        spoof=apply_update(tables.spoof, sp_upd),
        spoof_ranges=sp_ranges,
        spoof_config=sp_config,
        garden=g_state,
        garden_allowed=g_allowed,
        pppoe_by_sid=p_sid,
        pppoe_by_ip=p_ip,
        pppoe_server_mac=tables.pppoe_server_mac,
        tap=e_tap,
        tap_filters=e_filters,
        tap_config=e_config,
        route=e_route,
        v6_by_addr=v6_by_addr,
        qinq_by_ip=qinq_by_ip,
    )


# the fused step's compile-shape ladder: a dispatched step runs at the
# narrowest rung that holds its window, not at `--batch-size` (a lane
# beyond the window is inert and costs the device what a live one costs).
# Geometric, down from the configured batch; coarser than the DHCP
# buckets below because a rung of the fused step is a 45 s cold compile
# and, warm, 2.5 s of start-up to trace, lower and load (PERF.md section
# 6: the 128 rung takes the renew cell's OFFER median down by 18% more
# than 8,192 / 1,024 alone, which is what keeps it).
STEP_RUNG_RATIO = 8
STEP_RUNG_FLOOR = 128
STEP_RUNGS_MAX = 3


@functools.lru_cache(maxsize=64)
def step_rungs(B: int) -> tuple[int, ...]:
    """The lane counts a fused step of configured batch `B` may be
    dispatched at, ascending; `B` itself is the last. A `B` at or under
    the floor has one rung."""
    rungs = [B]
    while len(rungs) < STEP_RUNGS_MAX and rungs[-1] > STEP_RUNG_FLOOR:
        rungs.append(max(STEP_RUNG_FLOOR, rungs[-1] // STEP_RUNG_RATIO))
    return tuple(reversed(rungs))


def step_rung(n: int, B: int) -> int:
    """The narrowest rung of `B`'s ladder that holds a window of `n`
    frames (`B` for a window longer than `B`: staging refuses that)."""
    for b in step_rungs(B):
        if n <= b:
            return b
    return B


def start_host_copies(outs) -> None:
    """Start the device-to-host copy of each of `outs` that lives on a
    device (None and host arrays among them are skipped), and tell the
    Tracer (`prefetch_calls`): a later `np.asarray` of one finds the bytes
    on the host, or blocks until they have landed, and starts no crossing
    of its own. The runtime orders each copy behind the step that writes
    the output."""
    outs = [a for a in outs if isinstance(a, jax.Array)]
    for a in outs:
        a.copy_to_host_async()
    tele.prefetched(outs)


def split_window(window):
    """A staged window's block (hostpath.seal_window: one host-to-device
    call a window) taken apart on the device: `pkt [b, L] u8`, `length [b]
    u32`, `from_access [b] bool`. `b` is static, read off the block's
    shape."""
    rows, width = window.shape
    b = hostpath.window_lanes(rows, width)
    m = hostpath.WINDOW_META_BYTES
    planes = window[b:].reshape(-1)[: m * b].reshape(m, b).astype(jnp.uint32)
    length = (planes[0] | (planes[1] << 8) | (planes[2] << 16)
              | (planes[3] << 24))
    return window[:b], length, planes[4] != 0


@functools.lru_cache(maxsize=8)
def _pipeline_jit(geom: PipelineGeom):
    def step(tables, window, now_s, now_us):
        pkt, length, from_access = split_window(window)
        return pipeline_step(tables, pkt, length, from_access, geom,
                             now_s, now_us)

    # donate the device tables: counter and token writes are in-place
    return jax.jit(step, donate_argnums=(0,))


# The two packet-free programs a DIRTY drain goes through, ahead of the
# step that reads its tables (a clean drain calls neither: no step takes an
# update batch, so a table set with nothing to ship passes nothing and
# scatters nothing). One for the dhcp chain, which may live on the express
# lane's own device and is threaded by three programs; one for every other
# table, over tables whose dhcp chain is None and threads through untouched
# (a bulk drain never ships the chain's tables, and including it would
# force a program across devices). Both donate the tables like a step and
# are built where the steps are (build_step_rungs, compile_express_aot): a
# first dirty drain builds nothing.
_apply_fastpath_jit = jax.jit(apply_fastpath_updates, donate_argnums=(0,))
_apply_updates_jit = jax.jit(_apply_all_updates, donate_argnums=(0,))


@functools.lru_cache(maxsize=8)
def _dhcp_jit(geom):
    """DHCP-only device program — the latency fast lane.

    In the reference the DHCP fast path is its OWN XDP program
    (bpf/dhcp_fastpath.c): an XDP_TX reply never traverses the TC
    NAT/QoS/antispoof hooks. A pre-classified control batch (UDP:67)
    therefore only needs parse + 3-tier lookup + OFFER compose — a
    several-fold smaller program than the fused step, which is what the
    p99-OFFER target is measured against. Shares (and donates) the same
    dhcp table leaves as the fused step, so the two programs can never
    fork state.

    The packet batch is donated too (argnum 1): out_pkt is shaped
    exactly like pkt, so XLA aliases the reply buffer onto the input
    staging upload instead of allocating per dispatch — the VERDICT r5
    input-output-aliasing item on the express-lane OFFER program.
    Every caller stages from numpy (_pack_frames / ring buffers), so
    the donated device buffer is always a fresh upload, never a live
    caller array."""
    from bng_tpu.ops.dhcp import dhcp_fastpath
    from bng_tpu.ops.parse import parse_batch

    def step(dhcp_tables, pkt, length, now_s):
        par = parse_batch(pkt, length)
        res = dhcp_fastpath(pkt, length, par, dhcp_tables, geom, now_s)
        return dhcp_tables, res.is_reply, res.out_pkt, res.out_len, res.stats

    return jax.jit(step, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=8)
def _express_jit(geom):
    """AOT express-lane OFFER program — the minimal program the 50us
    device budget permits (ISSUE 13).

    Consumes pre-parsed express descriptors (ops/express.py: MAC/xid/
    vlan/cid lane columns extracted once at admission) and emits only
    the verdict block (verdict + yiaddr + pool/lease words); the host
    patches replies into preassembled wire templates at retire. Donates
    the dhcp chain (argnum 0 — one authoritative chain shared with the
    full programs and the apply program, threaded through unchanged) AND
    the descriptor batch (argnum 1 — the verdict block is shaped exactly
    like it, so XLA aliases the output onto the input staging upload;
    every caller stages descriptors from numpy, never a live device
    array).

    The jit wrapper exists for tracing; the serving path compiles it
    ahead of time (`Engine.compile_express_aot`) for the express lane's
    fixed batch geometry and calls the compiled executable directly, so
    a dispatch pays neither trace nor jit-cache lookup."""
    from bng_tpu.ops.express import express_verdicts

    def step(dhcp_tables, desc, now_s):
        res = express_verdicts(dhcp_tables, desc, geom, now_s)
        return dhcp_tables, res.block, res.stats

    return jax.jit(step, donate_argnums=(0, 1))


# AOT-compiled express executables, shared across engines of one
# geometry (the _dhcp_jit sharing discipline, extended to compiled
# executables): (dhcp geom, batch, device) -> Compiled.
_EXPRESS_AOT: dict = {}


class _ExpressAotResult(NamedTuple):
    """AOT express dispatch result (futures until the ring retire).

    Shaped for Engine._fold_stats like _DhcpBatchResult; the verdict
    block replaces per-lane packet outputs — the scheduler's retire
    patches replies host-side from wire templates."""

    block: "jax.Array"  # [B, XD_WORDS] uint32 (ops/express VB_* cols)
    dhcp_stats: "jax.Array"  # [DHCP_NSTATS]
    nat_stats: np.ndarray  # zeros (no NAT on this program)
    qos_stats: np.ndarray  # zeros
    spoof_stats: np.ndarray  # zeros


class _DhcpBatchResult(NamedTuple):
    """DHCP-only step result, shaped for the ring verdict demux AND the
    stats fold — async like PipelineResult (device outputs stay futures
    until the ring retire forces them, so the fast lane pipelines too)."""

    verdict: "jax.Array"  # [B] uint8 (TX / PASS only)
    out_pkt: "jax.Array"
    out_len: "jax.Array"
    nat_punt: np.ndarray  # [B] all-False (no NAT on this program)
    spoof_violation: np.ndarray  # [B] all-False
    dhcp_stats: "jax.Array"  # [DHCP_NSTATS]
    nat_stats: np.ndarray  # zeros
    qos_stats: np.ndarray  # zeros
    spoof_stats: np.ndarray  # zeros


@dataclass
class EngineStats:
    dhcp: np.ndarray = field(default_factory=lambda: np.zeros(DHCP_NSTATS, dtype=np.uint64))
    nat: np.ndarray = field(default_factory=lambda: np.zeros(NAT_NSTATS, dtype=np.uint64))
    qos: np.ndarray = field(default_factory=lambda: np.zeros(QOS_NSTATS, dtype=np.uint64))
    spoof: np.ndarray = field(default_factory=lambda: np.zeros(ANTISPOOF_NSTATS, dtype=np.uint64))
    # device walled-garden gate: [gated_drops, allowed_hits] (ops/garden.py)
    garden: np.ndarray = field(default_factory=lambda: np.zeros(2, dtype=np.uint64))
    # device PPPoE decap/encap (ops/pppoe.py)
    pppoe: np.ndarray = field(default_factory=lambda: np.zeros(PPPOE_NSTATS, dtype=np.uint64))
    # device edge protection: tap mirror + route rewrite (edge/ops.py EST_*)
    edge: np.ndarray = field(default_factory=lambda: np.zeros(4, dtype=np.uint64))
    # device IPv6 stage: forwarded up, forwarded down, downstream miss,
    # control passed to the host (ops/v6.py V6ST_*)
    v6: np.ndarray = field(default_factory=lambda: np.zeros(V6_NSTATS, dtype=np.uint64))
    # device qinq stage: pairs pushed, tags popped, downstream lanes of a
    # subscriber without a pair, pushes the slot had no room for
    # (ops/qinq.py QQ_*)
    qinq: np.ndarray = field(default_factory=lambda: np.zeros(QINQ_NSTATS, dtype=np.uint64))
    batches: int = 0
    tx: int = 0
    fwd: int = 0
    dropped: int = 0
    passed: int = 0
    slow_errors: int = 0


class QoSTables:
    """Host side of the two QoS maps (pkg/qos/manager.go:167-246 role)."""

    def __init__(self, nbuckets: int = 1 << 12, stash: int = 64, update_slots: int = 128):
        # stash accepted for signature compat; the packed table has none
        # (capacity policy: size nbuckets >= subscribers/2, resize on full)
        self.up = HostQTable(nbuckets, name="qos_ingress")
        self.down = HostQTable(nbuckets, name="qos_egress")
        self.geom = QTableGeom(nbuckets)
        self.update_slots = update_slots

    def set_subscriber(self, ip: int, down_bps: int, up_bps: int,
                       down_burst: int | None = None, up_burst: int | None = None,
                       priority: int = 0) -> None:
        # burst default: 1.25s at rate /8 -> bytes (manager.go burst calc role)
        down_burst = down_burst if down_burst is not None else max(int(down_bps / 8 * 1.25), 1500)
        up_burst = up_burst if up_burst is not None else max(int(up_bps / 8 * 1.25), 1500)
        self.down.insert(ip, down_bps, down_burst, priority)
        self.up.insert(ip, up_bps, up_burst, priority)

    def bulk_set_subscribers(self, ips, down_bps: int, up_bps: int) -> None:
        """Vectorized install for table builds at the 1M-subscriber scale."""
        ips = np.asarray(ips, dtype=np.uint32)
        down_burst = max(int(down_bps / 8 * 1.25), 1500)
        up_burst = max(int(up_bps / 8 * 1.25), 1500)
        n = len(ips)
        self.down.bulk_insert(ips, np.full(n, down_bps, np.uint64),
                              np.full(n, down_burst, np.uint32))
        self.up.bulk_insert(ips, np.full(n, up_bps, np.uint64),
                            np.full(n, up_burst, np.uint32))

    def remove_subscriber(self, ip: int) -> None:
        self.down.delete(ip)
        self.up.delete(ip)


class AntispoofTables:
    """Host side of antispoof (pkg/antispoof/manager.go role)."""

    def __init__(self, nbuckets: int = 1 << 12, stash: int = 64, update_slots: int = 128):
        from bng_tpu.ops.antispoof import MODE_DISABLED

        self.bindings = HostTable(nbuckets, 2, ANTISPOOF_WORDS, stash=stash, name="subscriber_bindings")
        self.ranges = np.zeros((256, 2), dtype=np.uint32)
        self.config = np.array([MODE_DISABLED, 0], dtype=np.uint32)
        self.geom = TableGeom(nbuckets, stash)
        self.update_slots = update_slots

    def set_config(self, default_mode: int, log_violations: bool) -> None:
        self.config[0] = default_mode
        self.config[1] = 1 if log_violations else 0

    def add_binding(self, mac, ipv4: int, mode: int) -> None:
        from bng_tpu.ops.antispoof import AB_IPV4, AB_MODE, AB_VALIDS, VALID_V4
        from bng_tpu.utils.net import mac_to_u64, split_u64

        key = mac_to_u64(mac) if not isinstance(mac, int) else mac
        lo, hi = split_u64(key)
        row = np.zeros((ANTISPOOF_WORDS,), dtype=np.uint32)
        row[AB_IPV4] = ipv4
        row[AB_VALIDS] = VALID_V4
        row[AB_MODE] = mode
        self.bindings.insert([hi, lo], row)

    def bulk_add_bindings(self, macs_u64, ipv4s, mode: int,
                          ipv6_words=None) -> None:
        """Vectorized binding install for table builds at the
        1M-subscriber scale (MACs unique and not already bound): the v4
        address, and with `ipv6_words` [N, 4] each MAC's /128 beside it."""
        from bng_tpu.ops.antispoof import (AB_IPV4, AB_MODE, AB_V6_0,
                                           AB_VALIDS, VALID_V4, VALID_V6)

        keys = mac_key_rows(macs_u64)
        rows = np.zeros((len(keys), ANTISPOOF_WORDS), dtype=np.uint32)
        rows[:, AB_IPV4] = ipv4s
        rows[:, AB_VALIDS] = VALID_V4
        rows[:, AB_MODE] = mode
        if ipv6_words is not None:
            rows[:, AB_V6_0:AB_V6_0 + 4] = ipv6_words
            rows[:, AB_VALIDS] |= VALID_V6
        self.bindings.bulk_insert(keys, rows)

    def add_binding_v6(self, mac, ipv6_words: list[int], mode: int) -> None:
        from bng_tpu.ops.antispoof import AB_MODE, AB_V6_0, AB_VALIDS, VALID_V6
        from bng_tpu.utils.net import mac_to_u64, split_u64

        key = mac_to_u64(mac) if not isinstance(mac, int) else mac
        lo, hi = split_u64(key)
        existing = self.bindings.lookup([hi, lo])
        row = existing if existing is not None else np.zeros((ANTISPOOF_WORDS,), dtype=np.uint32)
        row[AB_V6_0 : AB_V6_0 + 4] = np.asarray(ipv6_words, dtype=np.uint32)
        row[AB_VALIDS] |= VALID_V6
        row[AB_MODE] = mode
        self.bindings.insert([hi, lo], row)

    def remove_binding(self, mac) -> bool:
        from bng_tpu.utils.net import mac_to_u64, split_u64

        key = mac_to_u64(mac) if not isinstance(mac, int) else mac
        lo, hi = split_u64(key)
        return self.bindings.delete([hi, lo])

    def add_allowed_range(self, network: int, prefix_len: int) -> None:
        free = np.nonzero(self.ranges[:, 0] == 0)[0]
        if len(free) == 0:
            raise RuntimeError("allowed-ranges table full")
        self.ranges[free[0]] = (prefix_len, network)


class GardenTables:
    """Host side of the device walled-garden gate (ops/garden.py).

    Beyond the reference: its walled garden never reaches a bpf program
    (walledgarden/manager.go:172-178 hooks are unconsumed), so pre-auth
    data traffic PASSes to the host. Here membership (subscriber private
    IP -> gardened flag) and the allowed destinations (portal, DNS —
    manager.go:95-103) live on-device and gate in the fused pipeline.
    Driven by WalledGardenManager state transitions through the normal
    bounded update drain."""

    def __init__(self, nbuckets: int = 1 << 12, stash: int = 64,
                 update_slots: int = 128, max_allowed: int = 64):
        from bng_tpu.ops.garden import GARDEN_WORDS

        self.subscribers = HostTable(nbuckets, 1, GARDEN_WORDS, stash=stash,
                                     name="garden_subscribers")
        self.allowed = np.zeros((max_allowed, 3), dtype=np.uint32)
        self.geom = TableGeom(nbuckets, stash)
        self.update_slots = update_slots

    def set_gardened(self, ip: int, gardened: bool) -> None:
        """Mark/unmark a subscriber IP as gardened (idempotent; insert is
        an upsert, so re-gardening costs one dirty slot, not two)."""
        from bng_tpu.ops.garden import GARDEN_WORDS, GV_FLAG

        if gardened:
            row = np.zeros((GARDEN_WORDS,), dtype=np.uint32)
            row[GV_FLAG] = 1
            self.subscribers.insert([ip], row)
        else:
            self.subscribers.delete([ip])

    def allow_destination(self, ip: int, port: int = 0, proto: int = 0) -> None:
        """port/proto 0 = wildcard (manager.go:237-242 key semantics)."""
        free = np.nonzero(self.allowed[:, 0] == 0)[0]
        if len(free) == 0:
            raise RuntimeError("allowed-destinations table full")
        self.allowed[free[0]] = (ip, port, proto)


@owned_by("loop", attrs=("tables",))
class Engine:
    def __init__(
        self,
        fastpath: FastPathTables,
        nat: NATManager,
        qos: QoSTables | None = None,
        antispoof: AntispoofTables | None = None,
        garden: "GardenTables | None" = None,
        pppoe: "PPPoEFastPathTables | None" = None,
        batch_size: int = 256,
        pkt_slot: int = PKT_SLOT,
        slow_path: Callable[[bytes], bytes | None] | None = None,
        violation_sink: Callable[[int, bytes], None] | None = None,
        clock: Callable[[], float] = time.time,
        device_tables: "PipelineTables | None" = None,
        edge: "EdgeTables | None" = None,
        mirror_sink: Callable[[int, bytes, int], None] | None = None,
        v6: "V6FastPathTables | None" = None,
        qinq: "QinQFastPathTables | None" = None,
    ):
        self.fastpath = fastpath
        self.nat = nat
        self.qos = qos or QoSTables()
        self.antispoof = antispoof or AntispoofTables()
        # None = device gate off: the pipeline compiles WITHOUT the garden
        # kernel (no per-batch lookup/compare for a disabled feature); the
        # composition root passes GardenTables only when the walled garden
        # is enabled (nil-safe optional maps, manager.go:113-116 role)
        self.garden = garden
        # None = no PPPoE stage in the compiled pipeline (IPoE-only
        # deployments pay nothing); the composition root passes
        # PPPoEFastPathTables when the PPPoE server is constructed
        self.pppoe = pppoe
        # None = no edge-protection stage (tap mirror + route rewrite) in
        # the compiled pipeline; the composition root passes EdgeTables
        # when intercept/routing programs are wired (edge/compile.py)
        self.edge = edge
        # None = no IPv6 stage in the compiled pipeline (an IPv6 frame is
        # judged by antispoof and left to the host); the composition root
        # passes V6FastPathTables under `bng run --ipv6-fastpath`
        self.v6 = v6
        # None = no qinq stage in the compiled pipeline (a forwarded frame
        # keeps the tags it came with); the composition root passes
        # QinQFastPathTables under `bng run --qinq-enabled`
        self.qinq = qinq
        # host retire hook for MIRROR-flagged lanes: (lane, frame, wid).
        # The MirrorPump (edge/compile.py) feeds RecordCC/HI3 export here.
        self.mirror_sink = mirror_sink
        self.B = batch_size
        self.L = pkt_slot
        self.slow_path = slow_path
        # batched slow-path handler (the slow-path fleet's fan-out hook):
        # [(lane, frame)] -> [(lane, reply|None)] in ascending lane
        # order. When set it takes precedence over the per-frame
        # slow_path for every PASS-lane drain (process / process_dhcp /
        # ring / scheduler retire).
        self.slow_path_batch = None
        self.violation_sink = violation_sink
        self.clock = clock
        self.stats = EngineStats()
        # frames NAT punted for a new flow: the create, and the frames
        # waiting to go through the chip a second time (runtime/newflow.py;
        # at most a quarter of a window waits, the rest is the ring's)
        self.newflows = NewFlows(
            lambda *flows: self.nat.handle_new_flows(*flows),
            bound=max(batch_size // 4, 1))
        self._inflight = None  # pipelined ring mode (process_ring_pipelined)
        self._stage_bufs = [None, None]  # ping-pong staging (lazy alloc)
        self._stage_high = [0, 0]  # lanes each buffer's last window filled
        self._stage_idx = 0
        # slow-path failures are counted AND logged (rate-limited): the
        # counter alone dropped the traceback (server.go:330 logs each)
        self._slow_err_log = SlowPathErrorLog("engine")
        # antispoof violation lanes are logged rate-limited (ISSUE 17
        # satellite): counters alone hid WHO is spoofing; an unbounded
        # log would melt under a DDoS burst storm
        self._viol_log = ErrorLog("antispoof", "antispoof violation",
                                  rate=5.0, burst=10)
        # bumped by resync_tables(); the scheduler watches it to know its
        # bulk-lane DHCP replica / express placement went stale
        self.resync_count = 0

        self.geom = PipelineGeom(
            dhcp=fastpath.geom, nat=nat.geom, qos=self.qos.geom,
            spoof=self.antispoof.geom,
            garden=self.garden.geom if self.garden else None,
            pppoe=self.pppoe.geom if self.pppoe else None,
            tap=self.edge.tap_geom if self.edge else None,
            route=self.edge.route_geom if self.edge else None,
            v6=self.v6.geom if self.v6 else None,
            qinq=self.qinq.geom if self.qinq else None,
        )
        # `device_tables` adopts a prebuilt geometry-identical device
        # pytree (the blue/green standby's snapshot-hydrated chain,
        # runtime/ops.py) in place of the init upload — without it the
        # standby would pay a full H2D upload of the live mirrors only
        # to discard it, doubling the swap's quiesce-held hydrate cost.
        # `_dense_held`, chain -> {field: bytes}: the dense config arrays
        # as the device tables hold them (_fresh_dense). Empty for an
        # adopted pytree: the first drain then places every one
        self._dense_held: dict = {}
        self._replica_out = None  # the bulk replica as the last step left it
        self.tables: PipelineTables = (
            device_tables if device_tables is not None
            else self._device_tables())
        # jit cache is keyed on geometry so Engine instances with identical
        # table shapes share one compile (tests build many engines).
        self.table_impl = "xla"  # read by benchmark/lib/app.py selectors()
        self._step = _pipeline_jit(self.geom)
        self._dhcp_step = _dhcp_jit(fastpath.geom)
        # host-path snapshot (ISSUE 14): vector = batch-native frame
        # staging through a cycling preallocated pool instead of a
        # fresh np.zeros + per-frame copy loop per dispatch. Resolved
        # once at construction.
        self.host_path = hostpath.resolved_host_path()
        self._stage_pool = (hostpath.StagingPool(self.L)
                            if self.host_path == "vector" else None)

    def _device_tables(self) -> PipelineTables:
        # what this upload holds of the dense arrays (the replica's comes
        # with its next copy of the chain)
        self._dense_held = {
            chain: {f: a.tobytes() for f, a in self._dense_arrays(chain)}
            for chain in ("dhcp", "nat", "rest")}
        return PipelineTables(
            dhcp=self.fastpath.device_tables(),
            nat=self.nat.device_tables(),
            qos_up=self.qos.up.device_state(),
            qos_down=self.qos.down.device_state(),
            spoof=self.antispoof.bindings.device_state(),
            spoof_ranges=jnp.asarray(self.antispoof.ranges),
            spoof_config=jnp.asarray(self.antispoof.config),
            garden=(self.garden.subscribers.device_state()
                    if self.garden else None),
            garden_allowed=(jnp.asarray(self.garden.allowed)
                            if self.garden else None),
            pppoe_by_sid=(self.pppoe.by_sid.device_state()
                          if self.pppoe else None),
            pppoe_by_ip=(self.pppoe.by_ip.device_state()
                         if self.pppoe else None),
            pppoe_server_mac=(jnp.asarray(self.pppoe.server_mac)
                              if self.pppoe else None),
            tap=(self.edge.tap.device_state() if self.edge else None),
            tap_filters=(jnp.asarray(self.edge.tap_filters)
                         if self.edge else None),
            tap_config=(jnp.asarray(self.edge.tap_config)
                        if self.edge else None),
            route=(self.edge.route.device_state() if self.edge else None),
            v6_by_addr=(self.v6.by_addr.device_state() if self.v6 else None),
            qinq_by_ip=(self.qinq.by_ip.device_state() if self.qinq
                        else None),
        )

    def resync_tables(self) -> None:
        """Full device re-upload after a bulk host-table build.

        A large bulk_insert abandons bounded-delta tracking (_dirty_all);
        this refreshes every device table from the host mirrors so the
        next step proceeds. Device-authoritative state written since the
        last upload (QoS tokens, NAT/session counters) resets to the host
        view — bulk installs are a provisioning-time operation."""
        self.tables = self._device_tables()
        self.resync_count += 1

    def _drain(self, fastpath: bool, rest: bool):
        """Drain the dirty sets of the fastpath tables, of every other
        table, or of both, by what the host mirrors show: (the dhcp
        chain's batch or None, the other tables' batch or None). A chain
        with NOTHING dirty yields None and costs nothing: no batch is
        built, no call is made, no program scatters (the steady state:
        lease and session writes arrive in bursts from the slow path). A
        chain with something to ship yields its bounded batch
        (make_update: the dirty tables' rows uploaded, the all-padding
        batch already on the chip for its clean ones), for the
        packet-free program ahead of the step (_apply_drained). On the
        bulk-build "full upload" signal (bulk_insert abandoned dirty
        tracking) the answer is one full device re-upload, after which
        nothing is left to ship — a bulk build on a live engine must not
        brick the step loop. The tracer hears how many of the drained
        tables hold something to ship (what the apply call carries) and
        how many are clean (and cost nothing)."""
        fp_dirty = rest_dirty = clean = 0
        for name, t in self.host_mirror_tables().items():
            of_chain = name.startswith("fastpath/")
            if not (fastpath if of_chain else rest):
                continue
            if not t.dirty_count():
                clean += 1
            elif of_chain:
                fp_dirty += 1
            else:
                rest_dirty += 1
        tele.drain_tables(fp_dirty + rest_dirty, clean)
        try:
            return (self.fastpath.make_updates() if fp_dirty else None,
                    self._updates(True) if rest_dirty else None)
        except RuntimeError as e:
            if "full upload" not in str(e):
                raise
            self.resync_tables()
            return None, None

    def _updates(self, drain: bool):
        """The update batch of every table but the dhcp chain's, in
        _apply_all_updates' order with None in the chain's place (the
        packet-free program runs over tables without the chain).
        `drain`: the dirty sets are drained (make_update), or stay queued
        behind the all-padding batch (empty_update: what start-up builds
        the program with). Only what changed is uploaded: a clean table's
        batch is the one already on the chip (HostTable.make_update), and
        the dense config arrays are placed again when they differ from
        what was last placed (ops/table.py placed)."""
        sp, g, p, e, v, q = (self.antispoof, self.garden, self.pppoe,
                             self.edge, self.v6, self.qinq)

        def one(t, slots):
            return t.make_update(slots) if drain else t.empty_update(slots)

        return (
            None,
            self.nat.make_updates() if drain else self.nat.empty_updates(),
            one(self.qos.up, self.qos.update_slots),
            one(self.qos.down, self.qos.update_slots),
            one(sp.bindings, sp.update_slots),
            placed(sp, "ranges", sp.ranges),
            placed(sp, "config", sp.config),
            *((one(g.subscribers, g.update_slots),
               placed(g, "allowed", g.allowed)) if g else ()),
            *((p.make_updates() if drain else p.empty_updates()) if p else ()),
            *((e.make_updates() if drain else e.empty_updates()) if e else ()),
            *((one(v.by_addr, v.update_slots),) if v else ()),
            *((one(q.by_ip, q.update_slots),) if q else ()),
        )

    def _empty_updates(self):
        """The all-padding batch of every table but the dhcp chain's: no
        dirty set is consumed. What start-up builds the packet-free
        program with (build_step_rungs)."""
        return self._updates(False)

    def _dense_arrays(self, chain: str):
        """(field, host array) of the small dense config arrays of one
        chain of the device tables: "dhcp" (DHCPTables: pools, server),
        "nat" (NATTables: hairpin, ALG ports, config) or "rest" (the
        PipelineTables' own: spoof ranges and config, the garden
        allowlist, the edge set's filters and config)."""
        if chain == "dhcp":
            return (("pools", self.fastpath.pools),
                    ("server", self.fastpath.server))
        if chain == "nat":
            return (("hairpin_ips", self.nat.hairpin),
                    ("alg_ports", self.nat.alg),
                    ("config", self.nat.config_array()))
        sp, g, e = self.antispoof, self.garden, self.edge
        return (("spoof_ranges", sp.ranges), ("spoof_config", sp.config),
                *((("garden_allowed", g.allowed),) if g else ()),
                *((("tap_filters", e.tap_filters),
                   ("tap_config", e.tap_config)) if e else ()))

    def _fresh_dense(self, chain: str, node, held: str | None = None):
        """`node` (the DHCPTables, NATTables or PipelineTables that holds
        `chain`'s dense arrays) with those whose host bytes are not what it
        holds replaced; `node` itself in the steady state. `held`: whose
        record of what is held, the chain's own unless `node` is the bulk
        replica. No program runs: the fields are `_replace`d on the host,
        and a write made before a dispatch is in the tables that
        dispatch's step reads. The compare is on bytes (16 KB at the
        most), so a write in place is seen. Each array is a fresh upload
        of a copy (the step donates the tables it is given, so no cached
        array may go into them, and on the CPU backend asarray may alias
        host memory the owner writes in place), placed as the array it
        replaces is: on its device where that one is committed to one
        (the express lane's own; every table behind a committed bulk
        replica), so the programs built for the tables still fit them."""
        rec = self._dense_held.setdefault(held or chain, {})
        new = {}
        for f, host in self._dense_arrays(chain):
            now = host.tobytes()
            if rec.get(f) != now:
                rec[f] = now
                old = getattr(node, f)
                t0 = tele.t()
                new[f] = (jax.device_put(host.copy(), next(iter(old.devices())))
                          if old.committed else jnp.asarray(host.copy()))
                tele.xfer(tele.UPLOAD, t0, host.nbytes)
        return node._replace(**new) if new else node

    def _place_dense(self) -> None:
        """The dense config arrays, as the host holds them now, into
        `self.tables`: every table's but the dhcp chain's."""
        t = self._fresh_dense("rest", self.tables)
        nat = self._fresh_dense("nat", t.nat)
        self.tables = t if nat is t.nat else t._replace(nat=nat)

    def _place_dense_dhcp(self) -> None:
        """Pools and server config, as the host holds them now, into the
        authoritative dhcp chain."""
        dhcp = self._fresh_dense("dhcp", self.tables.dhcp)
        if dhcp is not self.tables.dhcp:
            self.tables = self.tables._replace(dhcp=dhcp)

    def _apply_drained(self, fp_upd, rest_upd, device=None) -> None:
        """A drain's batches through the packet-free programs, on the same
        tables, donated, before `self.tables` is read for the step. None:
        that chain shipped nothing and no call is made."""
        if fp_upd is not None:
            if device is not None:
                fp_upd = jax.device_put(fp_upd, device)
            self.tables = self.tables._replace(
                dhcp=_apply_fastpath_jit(self.tables.dhcp, fp_upd))
        if rest_upd is not None:
            self.apply_updates_now(rest_upd)

    # -- latency-tiered scheduler support (runtime/scheduler.py) ----------
    #
    # The scheduler splits the steady-state loop into an express lane
    # (DHCP-only program, authoritative dhcp chain = self.tables.dhcp) and
    # a bulk lane (fused pipeline over a dhcp READ REPLICA, so a bulk
    # dispatch never rebinds — and an express dispatch never waits on —
    # the dhcp leaves). These helpers keep the donation bookkeeping here,
    # next to the invariants they must preserve.

    def prefetch_bulk_updates(self):
        """Build (and start uploading) the NEXT bulk drain's update batch
        while the current step still executes — the overlap-drain half of
        VERDICT r5 item 3. Consumes the host dirty sets of every table but
        the fastpath's (the express lane is the single consumer of the
        fastpath drain: one authoritative device DHCP chain, never
        forked) exactly like the in-dispatch drain: the delta is simply
        built one step early; writes landing after the prefetch ride the
        following drain, and the jnp.asarray transfers inside start their
        H2D copies immediately, so by the next dispatch the scatter
        operands are already device-resident. With nothing dirty the
        batch is `()`: drained, and nothing to apply. The caller
        (TieredScheduler) OWNS the returned batch: it must reach the
        device via the next dispatch_scheduled_bulk(upd=...) or
        apply_updates_now(), or host and HBM silently diverge."""
        return self._make_bulk_updates()

    def _make_bulk_updates(self):
        """Update drain for a scheduler bulk step: the batch of every
        bulk-owned table, `()` with nothing dirty among them; the
        fastpath tables' dirty sets stay queued for the express lane."""
        return self._drain(False, True)[1] or ()

    def apply_updates_now(self, upd) -> None:
        """Apply one already-built BULK update batch with no packet batch:
        the road of every dirty drain of the tables outside the dhcp
        chain, ahead of its step, and of a prefetched drain no later batch
        consumed (flush/quiesce). An empty batch (`()`: the drain found
        nothing dirty) makes no call. Donates and rebinds the non-dhcp
        device tables like a step; the authoritative dhcp chain (possibly
        express-lane device-resident) never enters the program."""
        if not upd:
            return
        rest = _apply_updates_jit(self.tables._replace(dhcp=None), upd)
        self.tables = rest._replace(dhcp=self.tables.dhcp)

    def dhcp_replica(self, copy):
        """A copy of the authoritative dhcp chain for the bulk lane, leaf
        by leaf through `copy`, with the chain's record of the dense
        arrays it holds (dispatch_scheduled_bulk keeps pools and server
        live on it between copies)."""
        rep = jax.tree_util.tree_map(copy, self.tables.dhcp)
        self._note_replica(rep, self._dense_held.get("dhcp", {}))
        return rep

    def _note_replica(self, replica, held=None) -> None:
        """`replica` is the bulk replica as the engine last saw it; with
        `held`, what it holds of the dense arrays."""
        self._replica_out = replica
        if held is not None:
            self._dense_held["replica"] = dict(held)

    def dispatch_scheduled_bulk(self, pkt, length, fa, now: float,
                                dhcp_replica, drain: bool = True,
                                upd=None):
        """Async bulk-lane dispatch for the tiered scheduler.

        Runs the fused step over `dhcp_replica` instead of the
        authoritative dhcp chain: self.tables.dhcp is NOT an input, so the
        express program's next dispatch has no data dependency on this
        step. The replica is donated and threaded bulk->bulk by the
        caller. The step takes no update batch: drain=True drains the
        bulk-owned tables here and a dirty drain goes through the
        packet-free program ahead of the step (apply_updates_now; a clean
        one makes no call); drain=False drains nothing — the scheduler
        owns the drain cadence; a prefetched batch from
        prefetch_bulk_updates() arrives via `upd` (overlap-drain mode),
        takes the drain's place and the same road. The dense config
        arrays are as live as the host's either way (_place_dense, and
        pools / server on the replica). The step runs at the lane count
        the caller packed to (the scheduler picks the rung, step_rung).
        Returns (res, new_replica); outputs are futures (retire at the
        completion ring, never here).
        """
        now_s = np.uint32(int(now))
        now_us = np.uint32(int(now * 1e6) & 0xFFFFFFFF)
        if upd is None and drain:
            upd = self._make_bulk_updates()
        # a prefetched drain was built (and uploading) since step N-1
        self.apply_updates_now(upd)
        self._place_dense()
        if dhcp_replica is not self._replica_out:
            # not the replica the last step left, nor a copy this engine
            # made (dhcp_replica): what it holds is not on record
            self._dense_held["replica"] = {}
        dhcp_replica = self._fresh_dense("dhcp", dhcp_replica, held="replica")
        # read self.tables AFTER the drain (a bulk-build resync rebinds it)
        tables_in = self.tables._replace(dhcp=dhcp_replica)
        res: PipelineResult = self._step(
            tables_in, self._upload_batch(pkt, length, fa), now_s, now_us)
        self._start_host_copies(res)
        # keep the authoritative dhcp chain out of the bulk rebind; the
        # replica-out threads back to the scheduler
        self.tables = res.tables._replace(dhcp=self.tables.dhcp)
        self._note_replica(res.tables.dhcp)
        self.stats.batches += 1
        return res, res.tables.dhcp

    def _pack_frames(self, frames: list[bytes], B: int):
        """Stage a frame list into device-shaped [B, L] + lengths.

        Vector host path: one ragged scatter into a pooled staging pair
        (hostpath.StagingPool — no per-dispatch allocation, no
        per-frame copy loop); scalar: the per-frame oracle."""
        if len(frames) > B:
            raise ValueError(f"batch of {len(frames)} exceeds batch size {B}")
        if self._stage_pool is not None:
            if not frames:
                return self._stage_pool.stage(frames, B)
            lens = hostpath.frame_lens(frames)
            if int(lens.max()) > self.L:
                # never truncate silently: a clipped frame would be
                # shaped and NAT-accounted at the wrong length and
                # TX'd corrupt
                raise ValueError(
                    f"frame of {int(lens.max())} bytes exceeds engine "
                    f"pkt_slot {self.L}")
            return self._stage_pool.stage(frames, B, lens=lens)
        pkt = hostpath.window_buffer(B, self.L)
        length = np.zeros((B,), dtype=np.uint32)
        for i, f in enumerate(frames):
            if len(f) > self.L:
                # never truncate silently: a clipped frame would be shaped
                # and NAT-accounted at the wrong length and TX'd corrupt
                raise ValueError(
                    f"frame of {len(f)} bytes exceeds engine pkt_slot {self.L}")
            pkt[i, : len(f)] = np.frombuffer(f, dtype=np.uint8)
            length[i] = len(f)
        return pkt, length

    def _handle_slow_lanes(self, items: list, path: str) -> list:
        """Drain a batch of PASS-lane frames through the slow path:
        the batched fleet handler when wired (fan-out to workers,
        replies re-merged in lane order), else the per-frame handler.
        items: [(lane, frame)] or [(lane, frame, enq_t)] (the scheduler
        threads per-frame enqueue times through for deadline shedding)
        -> [(lane, reply|None)] ascending-lane."""
        if not items:
            return []
        t0 = tele.t()
        if t0 is None:
            return self._handle_slow_lanes_inner(items, path)
        tele.stamp(tele.SLOW)
        out = self._handle_slow_lanes_inner(items, path)
        tele.lap(tele.SLOW, t0)
        return out

    def _handle_slow_lanes_inner(self, items: list, path: str) -> list:
        fp = fault_point("engine.slow_drain")
        if fp is not None and fp.kind == "fail":
            # chaos: the whole slow batch is lost BEFORE any handler
            # runs — no half-allocation is possible, clients retransmit
            self.stats.slow_errors += 1
            return [(item[0], None) for item in items]
        if self.slow_path_batch is not None:
            try:
                out = self.slow_path_batch(items)
            except Exception as e:  # noqa: BLE001 — fleet IPC can fail
                self.stats.slow_errors += 1
                self._slow_err_log.report(e, path=path, lane=-1)
                return [(item[0], None) for item in items]
            return sorted(out, key=lambda t: t[0])
        results = []
        for lane, frame in ((item[0], item[1]) for item in items):
            reply = None
            try:
                if self.slow_path is not None:
                    reply = self.slow_path(frame)
            except Exception as e:  # noqa: BLE001 — slow path is untrusted input
                self.stats.slow_errors += 1
                self._slow_err_log.report(e, path=path, lane=lane)
            results.append((lane, reply))
        return results

    def process(
        self,
        frames: list[bytes],
        from_access: list[bool] | bool = True,
        now: float | None = None,
    ) -> dict:
        """Run one batch through the device pipeline and apply verdicts.

        Returns {"tx": [(lane, frame)], "fwd": [...], "dropped": [lanes],
        "slow": [(lane, reply_frame|None)]}.
        """
        now = now if now is not None else self.clock()
        now_s = np.uint32(int(now))
        now_us = np.uint32(int(now * 1e6) & 0xFFFFFFFF)

        # staged at the rung the step will run at (_dispatch_step), as the
        # scheduler's bulk lane stages: the block's meta rows are then the
        # staging buffer's own tail
        b = step_rung(len(frames), self.B)
        pkt, length = self._pack_frames(frames, b)
        if isinstance(from_access, bool):
            fa = np.full((b,), from_access, dtype=bool)
        else:
            fa = np.zeros((b,), dtype=bool)
            fa[: len(from_access)] = from_access

        tok = tele.begin_batch(tele.LANE_ENGINE, len(frames))
        t0 = tele.t()
        try:
            res = self._run_step(pkt, length, fa, len(frames), now_s, now_us)
        except BaseException:
            tele.cancel_batch(tok)  # a failed dispatch must not leak a slot
            raise
        tele.lap(tele.DISPATCH, t0, tok)

        t0 = tele.t()
        verdict = np.asarray(res.verdict)[: len(frames)]
        out_len = np.asarray(res.out_len)
        tele.lap(tele.DEVICE_WAIT, t0, tok)
        out_pkt = res.out_pkt  # fetch rows lazily
        punt = np.asarray(res.nat_punt)[: len(frames)]
        viol = np.asarray(res.spoof_violation)[: len(frames)]
        mir = (np.asarray(res.mirror)[: len(frames)]
               if getattr(res, "mirror", None) is not None else None)

        out = {"tx": [], "fwd": [], "dropped": [], "slow": []}
        out_rows = None
        slow_items = []  # non-punt PASS lanes, drained in one batch below
        punt_lanes = []
        t0 = tele.t()
        for i, v in enumerate(verdict):
            if v == VERDICT_TX:
                if out_rows is None:
                    out_rows = np.asarray(out_pkt)
                out["tx"].append((i, bytes(out_rows[i, : int(out_len[i])])))
                self.stats.tx += 1
            elif v == VERDICT_FWD:
                if out_rows is None:
                    out_rows = np.asarray(out_pkt)
                out["fwd"].append((i, bytes(out_rows[i, : int(out_len[i])])))
                self.stats.fwd += 1
            elif v == VERDICT_DROP:
                out["dropped"].append(i)
                self.stats.dropped += 1
            else:
                self.stats.passed += 1
                if punt[i]:
                    # the caller holds the frame (no ring to send it
                    # round on): the session is created, the lane is
                    # reported among `slow` with no reply
                    try:
                        self.newflows.create(frames[i], int(now),
                                             self.pppoe is not None)
                    except Exception as e:  # noqa: BLE001 — untrusted input
                        self.stats.slow_errors += 1
                        self._slow_err_log.report(e, path="process", lane=i)
                    punt_lanes.append(i)
                else:
                    slow_items.append((i, frames[i]))
            if viol[i]:
                self._viol_log.report(ValueError("spoofed source address"),
                                      path="process", lane=i)
                if self.violation_sink is not None:
                    self.violation_sink(i, frames[i])
            if mir is not None and mir[i] and self.mirror_sink is not None:
                # interception observes the ORIGINAL frame even on lanes
                # the verdict later drops (garden/QoS/antispoof)
                self.mirror_sink(i, frames[i], int(mir[i]))
        tele.lap(tele.REPLY, t0, tok)
        out["slow"] = sorted(
            [(i, None) for i in punt_lanes]
            + self._handle_slow_lanes(slow_items, path="process"),
            key=lambda t: t[0])
        tele.end_batch(tok, punt=len(punt_lanes))
        return out

    # fast-lane compile-shape budget: every auto-sized control batch maps
    # onto one of these pow2 buckets, so a latency sweep over arbitrary
    # batch sizes can trigger at most len(DHCP_BATCH_BUCKETS) compiles of
    # the DHCP-only program (pinned by tests/test_hlo_structure.py)
    DHCP_BATCH_FLOOR = 64
    DHCP_BATCH_CAP = 8192

    @classmethod
    def dhcp_batch_bucket(cls, n: int) -> int:
        """Pow2 bucket (floor 64, cap 8192) for a fast-lane batch of n
        frames. The cap bounds the compile set; a caller with more than
        DHCP_BATCH_CAP control frames should split the batch (the engine's
        ring assembler never produces one that large)."""
        b = max(cls.DHCP_BATCH_FLOOR, 1 << max(0, n - 1).bit_length())
        return min(b, cls.DHCP_BATCH_CAP)

    def process_dhcp(self, frames: list[bytes], now: float | None = None,
                     batch: int | None = None) -> dict:
        """Latency fast lane: run a PRE-CLASSIFIED control batch (DHCP to
        UDP:67) through the DHCP-only device program.

        Reference hook-order parity: dhcp_fastpath.c is its own XDP
        program; an XDP_TX reply never traverses the TC NAT/QoS/antispoof
        chain, so a control batch must not pay the fused step's cost.
        Non-DHCP frames in the batch simply fall out as "slow" lanes
        (is_reply False), exactly like XDP_PASS.

        The dhcp table leaves of self.tables thread through this step
        (donated) just as the fused step threads them — one authoritative
        device copy, whichever program runs next. Returns
        {"tx": [(lane, frame)], "slow": [(lane, reply|None)]}.
        """
        if batch is None and len(frames) > self.DHCP_BATCH_CAP:
            # above the compile-shape cap: split into capped chunks and
            # merge (lane indices re-based), so callers keep the old
            # any-size behavior without growing the compile set
            out = {"tx": [], "slow": []}
            for base in range(0, len(frames), self.DHCP_BATCH_CAP):
                part = self.process_dhcp(frames[base : base + self.DHCP_BATCH_CAP],
                                         now=now)
                for k in ("tx", "slow"):
                    out[k].extend((base + i, v) for i, v in part[k])
            return out
        if batch is not None:
            B = batch
        else:
            B = self.dhcp_batch_bucket(len(frames))
        now = now if now is not None else self.clock()
        pkt, length = self._pack_frames(frames, B)
        tok = tele.begin_batch(tele.LANE_ENGINE, len(frames))
        t0 = tele.t()
        try:
            res = self._run_dhcp_batch(pkt, length, now)
        except BaseException:
            tele.cancel_batch(tok)  # a failed dispatch must not leak a slot
            raise
        tele.lap(tele.DISPATCH, t0, tok)
        t0 = tele.t()
        reply = np.asarray(res.verdict)[: len(frames)] == VERDICT_TX
        tele.lap(tele.DEVICE_WAIT, t0, tok)
        self._fold_stats(res)
        out_pkt, out_len = res.out_pkt, res.out_len
        out = {"tx": [], "slow": []}
        out_rows = None
        ol = np.asarray(out_len)
        slow_items = []
        t0 = tele.t()
        for i, r in enumerate(reply):
            if r:
                if out_rows is None:
                    out_rows = np.asarray(out_pkt)
                out["tx"].append((i, bytes(out_rows[i, : int(ol[i])])))
                self.stats.tx += 1
            else:
                self.stats.passed += 1
                slow_items.append((i, frames[i]))
        tele.lap(tele.REPLY, t0, tok)
        out["slow"] = self._handle_slow_lanes(slow_items, path="process_dhcp")
        tele.end_batch(tok)
        return out

    def _place_dhcp_chain(self, device) -> None:
        """Migrate the authoritative dhcp chain to `device` (the
        scheduler's express-lane isolation: its own execution stream, so
        an express dispatch cannot queue behind bulk work). Idempotent —
        and self-healing after a resync_tables() rebind put the fresh
        upload back on the default device."""
        leaf = jax.tree_util.tree_leaves(self.tables.dhcp)[0]
        if device in leaf.devices():
            return
        self.tables = self.tables._replace(
            dhcp=jax.device_put(self.tables.dhcp, device))

    def _run_dhcp_batch(self, pkt, length, now: float,
                        device=None) -> "_DhcpBatchResult":
        """Dispatch one staged batch to the DHCP-only device program,
        threading (and donating) the shared dhcp table leaves. Outputs are
        futures (async, like _dispatch_step) — the caller folds stats and
        forces verdicts when it needs them (TX for on-device replies,
        PASS otherwise; no NAT punts or spoof violations exist on this
        program). `device` pins the dispatch (tables + inputs) to a
        specific device — the scheduler's express lane."""
        self._dispatch_fault()
        B = pkt.shape[0]
        self._drain_fastpath_updates(device)
        # donation safety: the program donates the packet batch (out_pkt
        # aliases the staging upload). Every caller stages from numpy —
        # asarray then creates a fresh device buffer — but a jax-array
        # input would ALIAS the caller's live buffer into the donation,
        # so copy it defensively rather than consume it.
        tu = tele.t()
        pkt_d = (jnp.array(pkt, copy=True) if isinstance(pkt, jax.Array)
                 else jnp.asarray(pkt))
        len_d = jnp.asarray(length)
        if device is not None:
            pkt_d = jax.device_put(pkt_d, device)
            len_d = jax.device_put(len_d, device)
        if tu is not None:  # the two from the host; the placement is
            # device to device
            tele.xfer(tele.UPLOAD, tu, pkt.nbytes + length.nbytes, 2)
        dhcp_tables, is_reply, out_pkt, out_len, stats = self._dhcp_step(
            self.tables.dhcp, pkt_d, len_d, np.uint32(int(now)))
        self.tables = self.tables._replace(dhcp=dhcp_tables)
        self.stats.batches += 1
        verdict = jnp.where(is_reply, np.uint8(VERDICT_TX),
                            np.uint8(VERDICT_PASS))
        no = np.zeros((B,), dtype=bool)
        res = _DhcpBatchResult(
            verdict=verdict, out_pkt=out_pkt, out_len=out_len,
            nat_punt=no, spoof_violation=no, dhcp_stats=stats,
            nat_stats=np.zeros(NAT_NSTATS, dtype=np.uint32),
            qos_stats=np.zeros(QOS_NSTATS, dtype=np.uint32),
            spoof_stats=np.zeros(ANTISPOOF_NSTATS, dtype=np.uint32))
        self._start_host_copies(res)
        return res

    def _run_dhcp_batch_sync(self, pkt, length, now: float) -> "_DhcpBatchResult":
        """Dispatch + fold — the sync-path pairing (mirrors _run_step for
        the fused program; the pipelined path folds at retire instead)."""
        res = self._run_dhcp_batch(pkt, length, now)
        self._fold_stats(res)
        return res

    def _drain_fastpath_updates(self, device=None) -> None:
        """The dhcp chain, up to every host write made before this call:
        the express programs' drain. The steady-state fast lane has
        NOTHING dirty (lease writes arrive in bursts from the slow path)
        and then this makes no call at all; any dirty slot takes the real
        bounded drain through the chain's packet-free program
        (_apply_fastpath_jit), so an OFFER always sees the newest lease;
        pools and server config that changed on the host go into the
        chain on the host. `device`: where the chain lives when the
        express lane has a device of its own. That placement comes AFTER
        the drain: a bulk-build resync inside it rebinds self.tables onto
        the default device."""
        fp_upd, _ = self._drain(True, False)
        if device is not None:
            self._place_dhcp_chain(device)
        self._apply_drained(fp_upd, None, device)
        self._place_dense_dhcp()

    # -- AOT express OFFER path (runtime/scheduler.py fast lane) ----------

    def _express_aot_key(self, batch: int, device) -> tuple:
        # DHCPGeom covers only bucket/stash shapes; the compiled
        # executable's avals also bake the dense pools array
        # ([max_pools, POOL_WORDS]) — two engines differing only there
        # must not share an executable (a call-time shape mismatch would
        # crash the dispatch instead of falling back). update_slots is
        # the apply program's shape, and stays in the key: the hit that
        # skips the compile skips that program's build too
        return (self.fastpath.geom, len(self.fastpath.pools),
                self.fastpath.update_slots, batch,
                None if device is None else str(device))

    def express_aot(self, batch: int, device=None):
        """The compiled express executable for `batch`, or None — a None
        here is the GEOMETRY MISS the scheduler must fall back (loudly)
        from; it never compiles."""
        return _EXPRESS_AOT.get(self._express_aot_key(batch, device))

    def compile_express_aot(self, batch: int, device=None):
        """`jax.jit(...).lower(...).compile()` the express program for
        one fixed batch geometry — engine/scheduler init time, NEVER the
        dispatch path. Cached on (geometry, device) so engines of
        one shape share a single executable. Lowering uses the live
        chain's avals. The chain's packet-free program is built here too
        (one run over an EMPTY update batch, the pytree shapes of a real
        drain, placed as a real drain's: a real make_updates() here
        would consume dirty state the next dispatch needs), so the first
        dirty drain of a window builds nothing. A cache hit skips both:
        the engine that compiled the executable built that program for
        the same shapes."""
        from bng_tpu.ops.express import XD_WORDS

        key = self._express_aot_key(batch, device)
        exe = _EXPRESS_AOT.get(key)
        if exe is not None:
            return exe
        if device is not None:
            self._place_dhcp_chain(device)
        dev = device if device is not None else jax.devices()[0]
        desc = jax.device_put(jnp.zeros((batch, XD_WORDS), jnp.uint32), dev)
        now_d = jax.device_put(jnp.uint32(0), dev)
        exe = _express_jit(self.fastpath.geom).lower(
            self.tables.dhcp, desc, now_d).compile()
        # one inert batch (no descriptor: no lane answers, the tables are
        # read only), so that the apply program is built for the chain as
        # every later drain finds it: the one an executable returns is
        # committed to its device, and a fresh upload is not
        dhcp_tables, _block, _stats = exe(self.tables.dhcp, desc, now_d)
        self.tables = self.tables._replace(dhcp=dhcp_tables)
        self._apply_drained(self.fastpath.empty_updates(), None, device)
        _EXPRESS_AOT[key] = exe
        return exe

    def run_express_aot(self, express_exe, desc: np.ndarray, now: float,
                        device=None) -> "_ExpressAotResult":
        """Dispatch one staged descriptor batch to the AOT-compiled
        express program. Same discipline as _run_dhcp_batch: the
        fastpath delta drains first (an OFFER must see the newest
        lease), the authoritative dhcp chain threads (donated) through
        the program, outputs stay futures until the ring retire."""
        self._dispatch_fault()
        self._drain_fastpath_updates(device)
        # donation safety (the _run_dhcp_batch pkt guard): the program
        # donates the descriptor and writes the verdict block over its
        # lead columns. Callers stage from numpy (fresh device buffer);
        # a jax-array input would alias the caller's LIVE buffer into
        # the donation, so copy it defensively rather than consume it.
        # The descriptor crosses alone, in ONE call, straight to the lane's
        # device where it has one; the clock word is a numpy scalar and
        # crosses inside the call into the executable, as the fused step's
        # `now_s` / `now_us` do.
        tu = tele.t()
        if isinstance(desc, jax.Array):
            desc = jnp.array(desc, copy=True)
        desc_d = (jnp.asarray(desc) if device is None
                  else jax.device_put(desc, device))
        if tu is not None:
            tele.xfer(tele.UPLOAD, tu, desc.nbytes, 1)
        dhcp_tables, block, stats = express_exe(
            self.tables.dhcp, desc_d, np.uint32(int(now)))
        self.tables = self.tables._replace(dhcp=dhcp_tables)
        self.stats.batches += 1
        return _ExpressAotResult(
            block=block, dhcp_stats=stats,
            nat_stats=np.zeros(NAT_NSTATS, dtype=np.uint32),
            qos_stats=np.zeros(QOS_NSTATS, dtype=np.uint32),
            spoof_stats=np.zeros(ANTISPOOF_NSTATS, dtype=np.uint32))

    # the leaves of a step's result that a retire reads on the host, of
    # whichever program (a result lacks the blocks of stages that are not
    # compiled in; a DHCP-only batch's flags and zero blocks are host
    # arrays). A stage's new stats block is named HERE: its copy then
    # starts at dispatch and its read at the retire crosses nothing. Never
    # `tables`: they thread to the next step and are donated.
    _RETIRE_READS = ("verdict", "out_pkt", "out_len", "spoof_violation",
                     "nat_punt", "dhcp_stats", "nat_stats", "qos_stats",
                     "spoof_stats", "garden_stats", "pppoe_stats",
                     "edge_stats", "v6_stats", "qinq_stats")

    def _start_host_copies(self, res) -> None:
        """Start the device-to-host copy of every output of `res` that its
        retire will read, now, at dispatch: the runtime orders each copy
        behind the step that writes it, and by the retire (a beat later on
        the pipelined loops) `np.asarray` finds the bytes on the host
        instead of making one blocking round trip an output (0.39-0.45 ms
        each on the chip whatever it holds, PERF.md §6 PR 37, PR 43). The
        mirror column only where a sink reads it."""
        outs = [getattr(res, name, None) for name in self._RETIRE_READS]
        if self.mirror_sink is not None:
            outs.append(getattr(res, "mirror", None))
        start_host_copies(outs)

    def _drain_updates(self) -> None:
        """Every device table, up to every host write made before this
        call: the fused step's drain. Clean (the steady state): no call;
        a dirty chain goes through its packet-free program; a dense config
        array that changed is `_replace`d on the host."""
        self._apply_drained(*self._drain(True, True))
        self._place_dense()
        self._place_dense_dhcp()

    def _dispatch_step(self, pkt, length, fa, n: int,
                       now_s, now_us) -> PipelineResult:
        """Enqueue one jitted step (async — outputs are futures). The table
        state threads immediately; callers force outputs when they need
        them (sync path: right away; pipelined path: one batch later).

        The step runs at the rung of its window: the first `b` rows of the
        staged `[B, L]` buffer go to the device with the first `b` lengths
        and flags in the rows behind them, one block in one call
        (_upload_batch; rows n..b are inert by the staging invariant, the
        packet rows beyond `b` never reach the chip) and the outputs are
        `[b]` / `[b, L]`. A window over the next rung down runs the `B`
        program."""
        self._dispatch_fault()
        b = step_rung(n, self.B)
        tele.step_lanes(b)
        # drain FIRST: a bulk-build resync rebinds self.tables, and Python
        # evaluates arguments left-to-right — reading self.tables before
        # the drain would pass (and donate) the stale pre-resync reference
        t0 = tele.t()
        self._drain_updates()
        tele.lap(tele.DRAIN, t0)
        window = self._upload_batch(pkt[:b], length[:b], fa[:b])
        res: PipelineResult = self._step(self.tables, window, now_s, now_us)
        self._start_host_copies(res)
        self.tables = res.tables
        self.stats.batches += 1
        return res

    def build_step_rungs(self, max_n: int, batch: int | None = None,
                         dhcp=None):
        """Build every rung of the fused step's ladder that holds a window
        of up to `max_n` frames: start-up's, so that no program is built
        once a loop takes frames. One inert window (every length 0: no
        table changes) goes through each rung, which traces, lowers and
        compiles or loads it as a first window would, one after the other
        on this thread (a load from the compile cache handed to a worker
        thread took 4.9 s on the chip against 1.0 s here). The two
        packet-free apply programs are built with them. `batch`: the
        ladder's top where it is not `self.B` (the scheduler's bulk
        batch). `dhcp`: the scheduler's bulk replica, threaded in place of
        the authoritative chain as its dispatches thread it; the replica
        as the last window left it is returned."""
        batch = batch or self.B
        rungs = [b for b in step_rungs(batch) if b <= step_rung(max_n, batch)]
        now = self.clock()
        now_s = np.uint32(int(now))
        now_us = np.uint32(int(now * 1e6) & 0xFFFFFFFF)

        def tables_in():
            return (self.tables if dhcp is None
                    else self.tables._replace(dhcp=dhcp))

        held = [x for x in jax.tree_util.tree_leaves(tables_in())
                if x.committed]
        if held:
            # one committed input (the replica, copied over from an express
            # lane on a device of its own) commits every output of a step:
            # build for the tables as every later step finds them
            rest = jax.device_put(self.tables._replace(dhcp=None),
                                  next(iter(held[0].devices())))
            self.tables = rest._replace(dhcp=self.tables.dhcp)
        for b in rungs:
            inert = self._upload_batch(hostpath.window_buffer(b, self.L),
                                       np.zeros((b,), dtype=np.uint32),
                                       np.zeros((b,), dtype=bool))
            res = self._step(tables_in(), inert, now_s, now_us)
            if dhcp is None:
                self.tables = res.tables
            else:
                self.tables = res.tables._replace(dhcp=self.tables.dhcp)
                dhcp = res.tables.dhcp
        # and the packet-free programs a dirty drain takes ahead of a step,
        # over the tables as a step leaves them: the chain's own only where
        # this loop threads the authoritative chain (the scheduler's
        # express lane builds it on its device, compile_express_aot)
        self._apply_drained(
            self.fastpath.empty_updates() if dhcp is None else None,
            self._empty_updates())
        jax.block_until_ready((res.verdict, self.tables))
        if dhcp is not None:
            self._note_replica(dhcp)
        return dhcp

    @staticmethod
    def _upload_batch(pkt, length, fa):
        """The staged window to the device: ONE host-to-device call, of the
        block that holds the packet slots and, in the rows behind them,
        the lengths and the access flags (hostpath.seal_window; the step
        takes it apart, split_window), under one `upload` lap (the time to
        return; the copy lands while the call into the step is made).
        `now_s` / `now_us` are numpy scalars: they cross inside the call
        into the step, `dispatch`'s own."""
        block = hostpath.seal_window(pkt, length, fa)
        t0 = tele.t()
        window = jnp.asarray(block)
        if t0 is not None:
            tele.xfer(tele.UPLOAD, t0, block.nbytes, 1)
        return window

    @staticmethod
    def _dispatch_fault() -> None:
        """Chaos hook on every device dispatch: `delay` simulates a slow
        device (bounded sleep), `fail` a failing one — raised BEFORE the
        update drain is consumed, so no table delta is lost with the
        batch. Disarmed: one no-op call per batch."""
        fp = fault_point("engine.dispatch")
        if fp is not None:
            if fp.kind == "fail":
                raise FaultInjectedError(
                    "chaos: injected device dispatch failure")
            if fp.kind == "delay":
                time.sleep(min(max(fp.arg, 0.0), 0.05))

    def _fold_stats(self, res: PipelineResult) -> None:
        """Fold a retired step's stats blocks into the host's counters:
        one small read a block, under one `fetch` lap (no parent stage:
        both retires call this between their laps)."""
        t0 = tele.t()
        self.stats.dhcp += np.asarray(res.dhcp_stats, dtype=np.uint64)
        self.stats.nat += np.asarray(res.nat_stats, dtype=np.uint64)
        self.stats.qos += np.asarray(res.qos_stats, dtype=np.uint64)
        self.stats.spoof += np.asarray(res.spoof_stats, dtype=np.uint64)
        gs = getattr(res, "garden_stats", None)  # DHCP-only batches have none
        if gs is not None:
            self.stats.garden += np.asarray(gs, dtype=np.uint64)
        ps_d = getattr(res, "pppoe_stats", None)
        if ps_d is not None:
            ps = np.asarray(ps_d, dtype=np.uint64)
            self.stats.pppoe += ps
            tele.pppoe_lanes(int(ps[PST_DECAP]), int(ps[PST_ENCAP]),
                             int(ps[PST_MISS]))
        es_d = getattr(res, "edge_stats", None)
        if es_d is not None:
            es = np.asarray(es_d, dtype=np.uint64)
            self.stats.edge += es
            tele.edge_lanes(int(es[EST_MIRRORED]), int(es[EST_TAP_FILTERED]),
                            int(es[EST_ROUTE_REWRITES]),
                            int(es[EST_ROUTE_MISSES]))
        vs_d = getattr(res, "v6_stats", None)
        if vs_d is not None:
            vs = np.asarray(vs_d, dtype=np.uint64)
            self.stats.v6 += vs
            tele.v6_lanes(int(vs[V6ST_FWD_UP] + vs[V6ST_FWD_DOWN]),
                          int(vs[V6ST_MISS]), int(vs[V6ST_CTRL]))
        qs_d = getattr(res, "qinq_stats", None)
        if qs_d is not None:
            qs = np.asarray(qs_d, dtype=np.uint64)
            self.stats.qinq += qs
            tele.qinq_lanes(int(qs[QQ_PUSH]), int(qs[QQ_POP]),
                            int(qs[QQ_MISS]))
        tele.fetched(t0, res.dhcp_stats, res.nat_stats, res.qos_stats,
                     res.spoof_stats, gs, ps_d, es_d, vs_d, qs_d)

    def _run_step(self, pkt, length, fa, n: int,
                  now_s, now_us) -> PipelineResult:
        """Dispatch + fold (the synchronous step both process paths use)."""
        res = self._dispatch_step(pkt, length, fa, n, now_s, now_us)
        self._fold_stats(res)
        return res

    def process_ring(self, ring, now: float | None = None) -> int:
        """Drain one batch from a packet ring (NativeRing/PyRing) through
        the device pipeline and apply verdicts back to the ring.

        This is the production I/O loop: the ring's assembler writes frames
        straight into the [B, L] staging buffer that goes to the device,
        and complete() demuxes the verdicts (TX/FWD back to the wire, PASS
        to the slow ring — drained here into the slow-path handlers, the
        XDP_PASS delivery). Returns the number of frames processed.
        """
        if self._inflight is not None:
            # a pipelined batch holds one of its ring's assemble windows;
            # retire it (against the ring it came from — not necessarily
            # this one) or the sync path would starve (assemble -> 0)
            self.flush_pipeline()
        pkt = hostpath.window_buffer(self.B, self.L)
        length = np.zeros((self.B,), dtype=np.uint32)
        flags = np.zeros((self.B,), dtype=np.uint32)
        t0 = tele.t()
        n, held = self._fill_window(ring, pkt, length, flags)
        if n == 0:
            return 0
        tok = tele.begin_batch(tele.LANE_RING_L, n - len(held))
        tele.lap(tele.RING, t0, tok)
        now = now if now is not None else self.clock()
        now_s = np.uint32(int(now))
        now_us = np.uint32(int(now * 1e6) & 0xFFFFFFFF)
        fa = (flags & 0x1) != 0

        # all-control batches (ring-classified DHCP, flag bit1) take the
        # DHCP-only fast lane — reference hook-order parity, and a
        # several-fold smaller program for the latency-sensitive traffic.
        # Mixed batches run the fused step: one dispatch beats two.
        t0 = tele.t()
        try:
            if bool(((flags[:n] & FLAG_DHCP_CTRL) != 0).all()):
                res = self._run_dhcp_batch_sync(pkt, length, now)
            else:
                res = self._run_step(pkt, length, fa, n, now_s, now_us)
        except BaseException:
            tele.cancel_batch(tok)  # a failed dispatch must not leak a slot
            raise
        tele.lap(tele.DISPATCH, t0, tok)
        self._apply_ring_verdicts(ring, res, pkt, length, n, now, held=held)
        tele.end_batch(tok)
        return n

    def _fill_window(self, ring, pkt, length, flags) -> tuple[int, list]:
        """One window into a staging buffer: the frames waiting for their
        second pass (runtime/newflow.py) in its first lanes, the ring's
        behind them. Returns (lanes filled, the held frames among them):
        the ring's window, if it opened one, is lanes len(held)..n, and is
        completed as that (_apply_ring_verdicts). No held frame (every
        window of a deployment that opens no flow): one length check and
        the ring's own assemble."""
        held = self.newflows.take(self.B // 2) if len(self.newflows) else []
        h = len(held)
        for i, (frame, fl) in enumerate(held):
            self.newflows.stage(pkt, length, flags, i, frame, fl)
        if not h:
            return ring.assemble(pkt, length, flags), held
        return h + ring.assemble(pkt[h:], length[h:], flags[h:]), held

    def _apply_ring_verdicts(self, ring, res: PipelineResult, pkt, length,
                             n: int, now: float, tok=None, held=()) -> None:
        """Force the step's outputs and demux verdicts back to the ring.
        `held`: the frames on their second pass in the window's first
        lanes (_fill_window); the ring's own window is the lanes behind
        them, and every count of accepted frames is over those alone."""
        t0 = tele.t()
        # armed: the wait apart from the reads (the pipelined loop's
        # window, seen ready); disarmed the first read waits and copies
        tf = tele.ready(res.verdict, tok)
        h = len(held)
        vv = np.asarray(res.verdict)[h:n]
        out_pkt = np.asarray(res.out_pkt)
        out_len = np.asarray(res.out_len).astype(np.uint32)
        tele.fetched(tf, res.verdict, res.out_pkt, res.out_len)
        tele.lap(tele.DEVICE_WAIT, t0)
        t0 = tele.t()
        if not h:
            ring.complete(vv.astype(np.uint8), out_pkt, out_len, n)
        elif n > h:
            ring.complete(vv.astype(np.uint8), out_pkt[h:], out_len[h:], n - h)

        self.stats.tx += int((vv == VERDICT_TX).sum())
        self.stats.fwd += int((vv == VERDICT_FWD).sum())
        self.stats.dropped += int((vv == VERDICT_DROP).sum())
        self.stats.passed += int((vv == VERDICT_PASS).sum())
        if h:
            # behind the window's own: a second packet of the flow that
            # sits in this window's ring lanes left by `complete` above
            fwd, gone = self.newflows.retire_held(
                ring, held, range(h), np.asarray(res.verdict), out_pkt,
                out_len)
            self.stats.fwd += fwd
            self.stats.dropped += gone

        # the flags the demux below needs, read side by side: one `fetch`
        # lap inside `reply`
        tf = tele.t()
        viol = np.asarray(res.spoof_violation)[h:n]
        punt = np.asarray(res.nat_punt)[h:n]
        mir = getattr(res, "mirror", None)  # DHCP-only batches have none
        mirw = (np.asarray(mir)[h:n]
                if mir is not None and self.mirror_sink is not None else None)
        if h:  # the sinks below index the window's own lanes
            pkt, length = pkt[h:], length[h:]
        tele.fetched(tf, res.spoof_violation, res.nat_punt,
                     mir if mirw is not None else None)
        for lane in np.nonzero(viol)[0]:
            self._viol_log.report(ValueError("spoofed source address"),
                                  path="ring", lane=int(lane))
            if self.violation_sink is not None:
                self.violation_sink(int(lane), bytes(pkt[lane, : int(length[lane])]))
        if mirw is not None:
            tm = tele.t()
            for lane in np.nonzero(mirw)[0]:
                # original ring bytes: interception sees the frame as it
                # arrived, regardless of the verdict demux above
                self.mirror_sink(int(lane),
                                 bytes(pkt[lane, : int(length[lane])]),
                                 int(mirw[lane]))
            tele.lap(tele.MIRROR, tm)

        # Drain the slow ring: the slow ring preserves lane order (PASS
        # frames are queued in lane order by complete()), so align pops
        # with the PASS lanes to recover per-lane punt flags. NAT new-flow
        # punts are handled inline; everything else goes to the slow-path
        # handler, whose replies are injected on the TX ring (the Go
        # server's socket-write role). Per-frame handler errors must not
        # abort the drain: a partially drained slow ring would misalign
        # every later batch's lane/punt matching (and wedge PyRing).
        slow_items = []  # (lane, frame); from_access flags kept aside
        slow_fa = {}
        # the punted lanes' (frame, ring flags, lane), served in one batch
        # after the walk. Built at the first punt: a retire without one
        # allocates nothing for it and makes no call
        punted = None
        for lane in np.nonzero(vv == VERDICT_PASS)[0]:
            got = ring.slow_pop()
            if got is None:
                break  # slow ring overflowed during complete()
            frame, fl = got
            if punt[lane]:
                if punted is None:
                    punted = []
                punted.append((frame, fl, int(lane)))
            else:
                slow_items.append((int(lane), frame))
                slow_fa[int(lane)] = (fl & 0x1) != 0
        punts = 0
        if punted is not None:
            frames, fls, lanes = zip(*punted)
            punts = len(frames)
            kept = self.newflows.punt_many(
                frames, fls, int(now), self.pppoe is not None,
                on_error=self.punt_reporter("ring", lanes))
            # a frame that will not go round (refused flow, no room to
            # wait in, a create that raised) is a counted drop, not a
            # silent consumption
            self.stats.dropped += kept.count(False)
        tele.lap(tele.REPLY, t0)
        tele.add(punt=punts)
        # fan-out/fan-in: replies come back re-merged in lane order, so
        # TX injection keeps the slow ring's arrival order on the wire
        for lane, reply in self._handle_slow_lanes(slow_items, path="ring"):
            if reply is not None:
                ring.tx_inject(reply, from_access=slow_fa[lane])

    def punt_reporter(self, path: str, lanes):
        """`NewFlows.punt_many`'s `on_error` for a retire on loop `path`
        whose punted frames came from `lanes`: a frame whose create raised
        is counted and logged with its lane, as any slow-path failure."""
        def report(i: int, e: Exception) -> None:
            self.stats.slow_errors += 1
            self._slow_err_log.report(e, path=path, lane=lanes[i])
        return report

    def _staging(self, idx: int):
        """Ping-pong staging buffers (allocated once; the in-flight batch
        owns one while the next assembles into the other)."""
        if self._stage_bufs[idx] is None:
            self._stage_bufs[idx] = (
                hostpath.window_buffer(self.B, self.L),
                np.zeros((self.B,), dtype=np.uint32),
                np.zeros((self.B,), dtype=np.uint32),
            )
        return self._stage_bufs[idx]

    def _mask_stale_lanes(self, idx: int, n: int) -> None:
        """Keep the staging invariant: a lane beyond `n` is inert (length
        0, flags 0). `ring.assemble` fills rows 0..n and leaves the rest
        as this buffer's last window left them, and only the engine knows
        how long that window was: a high-water mark a buffer, so the
        clear is at most the previous window's lanes. The rows' bytes
        stay: every stage of both device programs gates on `length`
        (tests/test_inert_lanes.py holds the loop to process_ring's
        state, which starts from zeros)."""
        _pkt, length, flags = self._stage_bufs[idx]
        high = self._stage_high[idx]
        if high > n:
            tele.masked_lanes(int(np.count_nonzero(length[n:high])))
            length[n:high] = 0
            flags[n:high] = 0
        self._stage_high[idx] = n

    def process_ring_pipelined(self, ring, now: float | None = None) -> int:
        """Double-buffered ring loop: dispatch batch k+1, THEN retire k.

        The SURVEY §7 'hard parts' dispatch design. Per call: assemble the
        next batch into the idle ping-pong buffer and dispatch it (the
        device starts immediately), then force + demux the PREVIOUS
        batch's verdicts — so host demux work overlaps device execution.
        Requires ring backends that tolerate two outstanding
        assemble..complete windows (bngring MAX_INFLIGHT=2; complete()
        retires FIFO, matching this loop's order). Per-batch latency grows
        by one batch window; call flush_pipeline() before reading final
        state (shutdown/tests). Returns frames retired this call.

        Invariant, as process_ring and the sharded loop keep it: every
        lane beyond the assembled count that reaches a device program is
        inert (length 0, flags 0). The staging buffers are reused and
        `assemble` leaves rows n..B alone, so the loop clears what the
        buffer's last window left there (_mask_stale_lanes) before either
        program sees it; otherwise a short window after a long one has
        the stale frames counted, NAT-accounted and policed again.
        """
        now = now if now is not None else self.clock()
        prev = self._inflight
        self._inflight = None

        try:
            # 1. feed the device first: assemble into the buffer prev is
            # NOT using, so its frames stay intact until retirement
            idx = 1 - self._stage_idx
            pkt, length, flags = self._staging(idx)
            t0 = tele.t()
            n, held = self._fill_window(ring, pkt, length, flags)
            if n:
                self._mask_stale_lanes(idx, n)
                tok = tele.begin_batch(tele.LANE_RING_L, n - len(held))
                tele.lap(tele.RING, t0, tok)
                now_s = np.uint32(int(now))
                now_us = np.uint32(int(now * 1e6) & 0xFFFFFFFF)
                t0 = tele.t()
                try:
                    # all-control batches ride the DHCP-only fast lane here
                    # too — its outputs are equally async, so the overlap
                    # with the previous batch's retire is preserved
                    if bool(((flags[:n] & FLAG_DHCP_CTRL) != 0).all()):
                        res = self._run_dhcp_batch(pkt, length, now)
                    else:
                        res = self._dispatch_step(pkt, length,
                                                  (flags & 0x1) != 0,
                                                  n, now_s, now_us)
                except BaseException:
                    # fail closed: the assemble opened a ring window that
                    # must not wedge. complete() retires FIFO, so the
                    # previous batch's (older) window must retire FIRST —
                    # dropping into it would mis-complete prev's frames.
                    tele.cancel_batch(tok)
                    self._retire(prev)
                    prev = None
                    h = len(held)  # frames on their second pass go with it
                    self.stats.dropped += h
                    if n > h:
                        ring.complete(
                            np.full((n - h,), VERDICT_DROP, dtype=np.uint8),
                            pkt[h:], length[h:], n - h)
                    raise
                tele.lap(tele.DISPATCH, t0, tok)
                tele.device_up(tok)
                self._inflight = (ring, res, pkt, length, n, now, tok, held)
                self._stage_idx = idx
        finally:
            # 2. retire the previous batch (even if dispatch raised) while
            # the device runs the new one
            retired = self._retire(prev)
        return retired

    def _retire(self, entry) -> int:
        """Apply a pipelined batch's verdicts to the ring it came from."""
        if entry is None:
            return 0
        ring, res, pkt, length, n, now, tok, held = entry
        tele.focus(tok)
        self._apply_ring_verdicts(ring, res, pkt, length, n, now, tok, held)
        self._fold_stats(res)
        tele.end_batch(tok)
        return n

    def flush_pipeline(self, ring=None) -> int:
        """Retire any in-flight pipelined batch (shutdown/test barrier).

        The batch retires against the ring it was assembled from; the
        optional argument is accepted for call-site symmetry only."""
        entry = self._inflight
        self._inflight = None
        return self._retire(entry)

    def fetch_session_vals(self) -> np.ndarray:
        """Device-authoritative session counters for accounting/expiry."""
        return np.asarray(self.tables.nat.sessions.vals)

    # -- checkpoint/warm-restart support (runtime/checkpoint.py) ---------

    def quiesce(self) -> int:
        """Drain barrier for the engine-driven loops (no scheduler):
        retire any in-flight pipelined batch, then block until the
        threaded device table state has materialized — after this no
        scatter is in flight, so a checkpoint can fetch HBM arrays
        without interleaving with an update. Returns frames retired."""
        n = self.flush_pipeline()
        jax.block_until_ready(jax.tree_util.tree_leaves(self.tables))
        return n

    # -- blue/green engine swap support (runtime/ops.py) ------------------

    def adopt_device_tables(self, tables: PipelineTables) -> None:
        """Standby hydration: adopt a device pytree built from a
        checkpoint snapshot (via geometry-identical clone mirrors) in
        place of the init-time upload. Must be shape-identical to
        self.geom — callers hydrate through restore_checkpoint, whose
        verify gate already enforced that. This is the ONE sanctioned
        rebind of .tables outside the step/resync paths; the delta
        accumulated since the snapshot is replayed afterwards through
        the normal bounded update drain (ops.replay_delta_since); what
        the adopted pytree holds of the dense config arrays is not on
        record, so the next drain places every one."""
        self.tables = tables
        self._dense_held = {}

    def host_mirror_tables(self) -> dict:
        """{name: HostTable|HostQTable} of every sparse host mirror this
        engine drains — the delta-replay walk surface (runtime/ops.py).
        Dense config arrays (pools/server, spoof ranges, garden allowed,
        NAT hairpin/alg) ride every batch whole (ops/table.py placed) and
        need no diffing."""
        out = {
            "fastpath/sub": self.fastpath.sub,
            "fastpath/vlan": self.fastpath.vlan,
            "fastpath/cid": self.fastpath.cid,
            "nat/sessions": self.nat.sessions,
            "nat/reverse": self.nat.reverse,
            "nat/sub_nat": self.nat.sub_nat,
            "qos/up": self.qos.up,
            "qos/down": self.qos.down,
            "antispoof/bindings": self.antispoof.bindings,
        }
        if self.garden is not None:
            out["garden/subscribers"] = self.garden.subscribers
        if self.pppoe is not None:
            out["pppoe/by_sid"] = self.pppoe.by_sid
            out["pppoe/by_ip"] = self.pppoe.by_ip
        if self.edge is not None:
            out["edge/tap"] = self.edge.tap
            out["edge/route"] = self.edge.route
        if self.v6 is not None:
            out["v6/by_addr"] = self.v6.by_addr
        if self.qinq is not None:
            out["qinq/by_ip"] = self.qinq.by_ip
        return out

    def pending_dirty(self) -> int:
        """Dirty slots across every drained host mirror — 0 means the
        device chain is current (the delta-replay completion test)."""
        return sum(t.dirty_count() for t in self.host_mirror_tables().values())

    @staticmethod
    def _uploaded_mask(table, live: np.ndarray) -> np.ndarray:
        """Slots whose host row has actually SHIPPED to the device: live
        minus the pending dirty set (a host insert the bounded drain has
        not scattered yet reads back as zeros/stale from HBM — folding
        it would destroy the newer host row). A _dirty_all table has
        shipped nothing since its bulk build."""
        if table._dirty_all:
            return np.zeros_like(live)
        if not table._dirty:
            return live
        mask = live.copy()
        mask[np.fromiter(table._dirty, dtype=np.int64,
                         count=len(table._dirty))] = False
        return mask

    def fold_device_authoritative(self) -> None:
        """Pull the device-WRITTEN words back into the host mirrors — the
        pre-checkpoint fetch. Two tables carry device-authoritative
        state: NAT session rows (counters + last_seen, written by the
        NAT44 kernel) and the QoS token buckets (tokens + last_us words
        of the packed way rows). Everything else is host-authoritative
        already. Only slots whose host row has shipped are folded (see
        _uploaded_mask); not-yet-drained host writes stay authoritative.
        Call behind quiesce(): a fetch that overlaps an in-flight
        scatter could tear a row."""
        from bng_tpu.ops.qtable import (QW_FLAGS, QW_LAST_US, QW_TOKENS,
                                        way_rows)

        dev = self.fetch_session_vals()
        mask = self._uploaded_mask(self.nat.sessions,
                                   self.nat.sessions.used.astype(bool))
        self.nat.sessions.vals[mask] = dev[mask]
        for host, dev_rows in ((self.qos.up, self.tables.qos_up.rows),
                               (self.qos.down, self.tables.qos_down.rows)):
            rows = way_rows(dev_rows, host.nbuckets)
            live = self._uploaded_mask(host,
                                       (host.rows[:, QW_FLAGS] & 1) != 0)
            host.rows[live, QW_TOKENS] = rows[live, QW_TOKENS]
            host.rows[live, QW_LAST_US] = rows[live, QW_LAST_US]

    def expire(self, now: int | None = None) -> int:
        now = int(now if now is not None else self.clock())
        return self.nat.expire_sessions(now, device_vals=self.fetch_session_vals())
