"""Multi-chip BNG: the fused pipeline under shard_map over a device Mesh.

Scale-out design (replacing the reference's HTTP/SSE + hashring node mesh,
SURVEY.md §2.3, with ICI collectives):

- **Packets are data-parallel**: the host ring steers each subscriber's
  traffic to a consistent chip (runtime/ring.py shard_of + bngring.cpp
  bng_ring_shard_of — the pkg/pool/peer.go owner-routing role, re-hosted
  at the ring): upstream by FNV-1a32(private src IP), downstream by NAT
  public-IP ownership, so each chip's batch region (assemble_sharded) is
  its own subscribers' traffic. affinity_shard_ip() is the same function
  on the control-plane side.
- **Flow state is chip-local**: NAT sessions / QoS buckets / antispoof
  bindings live on the chip that owns the subscriber — no cross-chip
  traffic for the hot NAT path (mirrors the reference where each node owns
  its subscribers' conntrack outright).
- **DHCP subscriber tables are hash-sharded across chips** with all-to-all
  key/result exchange (ops.table.sharded_lookup): DISCOVER/REQUEST can
  arrive on any chip (broadcasts, relays), and the 1M-entry table sharded
  over 8 chips is the capacity headline. Only 8-byte keys and 32-byte
  results ride ICI, never packets.
- **Stats are psum-reduced** over the mesh (the per-CPU-map -> global
  counter role, bpf maps PERCPU_ARRAY).

Host side: ShardedCluster owns one host-table stack per shard, routes
control-plane writes to the owner shard (DHCP tables by key hash; NAT/QoS/
spoof by the subscriber-affinity shard), and stacks the per-shard device
arrays with a leading mesh dimension. Its serving loop
(`process_ring_pipelined`) is a beat of: assemble a window off the
steered ring, place it over the mesh, drain the host's table writes,
dispatch the step and start every output's copy to the host
(`_start_host_copies`), then retire the window dispatched a beat before
from host memory (`_retire`: the reads, `ring.complete`, the slow path).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bng_tpu.control.nat import NATManager
from bng_tpu.edge.tables import EdgeTables
from bng_tpu.ops.pipeline import PipelineGeom, PipelineTables, pipeline_step
from bng_tpu.ops.table import TableGeom, shard_owner
from bng_tpu.runtime.engine import (AntispoofTables, GardenTables, QoSTables,
                                    _apply_all_updates, start_host_copies)
from bng_tpu.runtime.tables import (FastPathTables, FastPathUpdates,
                                    PPPoEFastPathTables)
from bng_tpu.telemetry import spans as tele
from bng_tpu.utils.net import mac_to_u64, split_u64

AXIS = "shard"


def make_mesh(n_devices: int) -> Mesh:
    devs = jax.devices()[:n_devices]
    if len(devs) < n_devices:
        raise RuntimeError(f"need {n_devices} devices, have {len(jax.devices())}")
    return Mesh(np.array(devs), (AXIS,))


def _shard_map(f, mesh, in_specs, out_specs):
    """Replication checking is off: the stats psums are deliberately
    cross-chip."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _sharded_geom(geom: PipelineGeom, n: int) -> PipelineGeom:
    """Mark the DHCP lookup tables as hash-sharded over the mesh axis.

    PUNT-SAFETY INVARIANT: only tables whose device-miss path falls
    through to an authoritative slow path may be sharded. The bounded
    all-to-all exchange punts overflow lanes as found=False
    (ops/table.py sharded_lookup); for the DHCP tables that turns a
    skew-overflowed DISCOVER into a slow-path request the host server
    answers from its authoritative state — degraded latency, never
    wrong behavior. Do NOT shard tables where found=False changes the
    verdict (antispoof would drop, QoS would unshape): keep those
    chip-local by subscriber affinity (qos_kernel enforces this for
    itself)."""
    dhcp = geom.dhcp._replace(
        sub=geom.dhcp.sub._replace(axis=AXIS, n_shards=n),
        vlan=geom.dhcp.vlan._replace(axis=AXIS, n_shards=n),
        cid=geom.dhcp.cid._replace(axis=AXIS, n_shards=n),
    )
    return geom._replace(dhcp=dhcp)


def _step_out_names(geom: PipelineGeom) -> tuple[str, ...]:
    """The tuple `_sharded_step_jit`'s program returns, leaf by leaf in its
    order (`local_step`'s `out`). A stage's new output is named HERE and
    nowhere else for its host copy to start at dispatch
    (`ShardedCluster._start_host_copies`)."""
    names = ("verdict", "out_pkt", "out_len", "tables", "dhcp_stats",
             "nat_stats", "qos_stats", "spoof_stats", "nat_punt", "violation")
    if geom.garden is not None:
        names += ("garden_stats",)
    if geom.pppoe is not None:
        names += ("pppoe_stats",)
    if geom.tap is not None:
        names += ("mirror", "edge_stats")
    return names


# the same for `_sharded_dhcp_jit`'s program
_DHCP_OUT_NAMES = ("tables", "is_reply", "out_pkt", "out_len", "stats")


@functools.lru_cache(maxsize=4)
def _sharded_step_jit(mesh: Mesh, geom: PipelineGeom, n: int):
    geom_sh = _sharded_geom(geom, n)

    has_garden = geom.garden is not None
    has_pppoe = geom.pppoe is not None
    has_edge = geom.tap is not None

    def local_step(tables1, upd1, pkt, length, fa, now_s, now_us):
        # shard_map hands each chip a leading dim of 1: drop it
        tables = jax.tree.map(lambda x: x[0], tables1)
        upd = jax.tree.map(lambda x: x[0], upd1)
        # host table deltas land here, inside the donated step — the
        # bpf_map_update_elem replacement, same as the single-chip Engine
        tables = _apply_all_updates(tables, upd)
        res = pipeline_step(tables, pkt, length, fa, geom_sh,
                            now_s, now_us)
        new_tables1 = jax.tree.map(lambda x: x[None], res.tables)
        # global stats over ICI (per-CPU map -> one counter)
        with jax.named_scope("stats"):
            dhcp_stats = jax.lax.psum(res.dhcp_stats, AXIS)
            nat_stats = jax.lax.psum(res.nat_stats, AXIS)
            qos_stats = jax.lax.psum(res.qos_stats, AXIS)
            spoof_stats = jax.lax.psum(res.spoof_stats, AXIS)
        out = (res.verdict, res.out_pkt, res.out_len, new_tables1,
               dhcp_stats, nat_stats, qos_stats, spoof_stats,
               res.nat_punt, res.spoof_violation)
        if has_garden:
            out += (jax.lax.psum(res.garden_stats, AXIS),)
        if has_pppoe:
            out += (jax.lax.psum(res.pppoe_stats, AXIS),)
        if has_edge:
            # mirror wids stay per-lane (the host retire extracts flagged
            # frames from its own shard region); stats psum like the rest
            out += (res.mirror, jax.lax.psum(res.edge_stats, AXIS))
        return out

    out_specs = (P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(), P(), P(), P(),
                 P(AXIS), P(AXIS))
    if has_garden:
        out_specs += (P(),)
    if has_pppoe:
        out_specs += (P(),)
    if has_edge:
        out_specs += (P(AXIS), P())
    sharded = _shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(), P()),
        out_specs=out_specs,
    )
    return jax.jit(sharded, donate_argnums=(0,))


@functools.lru_cache(maxsize=4)
def _sharded_dhcp_jit(mesh: Mesh, geom: PipelineGeom, n: int):
    """Sharded DHCP-only program — the multichip OFFER latency fast lane.

    Mirrors Engine._dhcp_jit (reference hook-order parity: the DHCP fast
    path is its own XDP program) over the mesh: parse + hash-sharded
    3-tier lookup (all-to-all key/result exchange) + OFFER compose, with
    stats psum-reduced. Shares (and donates) the same dhcp table leaves
    as the fused sharded step, so the two programs can never fork state.
    """
    from bng_tpu.ops.dhcp import dhcp_fastpath
    from bng_tpu.ops.parse import parse_batch
    from bng_tpu.runtime.tables import apply_fastpath_updates

    dhcp_geom = _sharded_geom(geom, n).dhcp

    def local(dhcp1, upd1, pkt, length, now_s):
        dhcp = jax.tree.map(lambda x: x[0], dhcp1)
        upd = jax.tree.map(lambda x: x[0], upd1)
        dhcp = apply_fastpath_updates(dhcp, upd)
        par = parse_batch(pkt, length)
        res = dhcp_fastpath(pkt, length, par, dhcp, dhcp_geom, now_s)
        return (jax.tree.map(lambda x: x[None], dhcp), res.is_reply,
                res.out_pkt, res.out_len, jax.lax.psum(res.stats, AXIS))

    sharded = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P()),
    )
    return jax.jit(sharded, donate_argnums=(0,))


class ShardTelemetry:
    """Per-shard verdict/punt counters — the sharded path's counts.

    Counters only. The sharded step is ONE program over the mesh, so
    there is no per-shard latency to tell apart: the loop's times are
    the Tracer's (telemetry/spans.py, lane `sharded`: ring, pack, drain,
    dispatch, device, device_wait, reply, tx), stamped once a step by the
    loop itself (`dispatch` holds the start of every output's copy to the
    host, `device_wait` the retire's reads of what has landed: `fetch`
    times them and counts no crossing, `xfer.prefetch_calls` counts the
    starts), and the snapshot carries the Tracer's tiling and
    device-occupancy sums as its `trace` subtree. What DOES differ per
    shard is the work: verdict counts (tx/fwd/drop/pass), NAT egress-miss
    punts and antispoof violations are counted from each shard's lane
    region of the batch.

    PASS accounting (the serving-path split, ISSUE 12): now that the
    ring classifier owns the steering decision, wrong-shard punts are
    counted EXACTLY at retire — a PASS lane whose frame's affinity
    owner (FNV-1a32 of the subscriber key, the same function the ring
    steers with) is not the shard it executed on increments
    `missteers`; every other PASS lane (DHCP misses answered by the
    host server, NAT new-flow punts, unknown return traffic) is a
    legitimate slow-path punt and stays in `pass_total`. Callers that
    assemble their own batches without steering metadata (dryrun's raw
    step()) record no missteer verdicts, so for them `pass_total`
    remains the historical upper bound. DHCP hits are psum-reduced ON
    DEVICE (ops cross-shard answer) — the host folds the global
    counter.
    """

    VERDICT_NAMES = ("pass", "drop", "tx", "fwd")

    def __init__(self, n_shards: int, batch_per_shard: int, nat=None):
        self.n = n_shards
        self.b = batch_per_shard
        # the shards' NATManagers, for what each host mirror holds (a
        # cluster hands its own; None: a telemetry with no cluster)
        self.nat = nat
        self.frames = np.zeros((n_shards,), dtype=np.int64)
        self.verdicts = np.zeros((n_shards, 4), dtype=np.int64)
        self.nat_punts = np.zeros((n_shards,), dtype=np.int64)
        self.missteers = np.zeros((n_shards,), dtype=np.int64)
        self.violations = np.zeros((n_shards,), dtype=np.int64)
        self.dhcp_replies = np.zeros((n_shards,), dtype=np.int64)
        self.psum_dhcp_hits = 0
        self.steps = 0

    def _active(self, length) -> np.ndarray:
        real = (np.asarray(length) > 0).reshape(self.n, self.b)
        self.frames += real.sum(axis=1)
        return real

    def record_fused(self, length, verdict, nat_punt, viol,
                     dhcp_hits: int, missteer=None) -> None:
        real = self._active(length)
        v = np.asarray(verdict).reshape(self.n, self.b)
        for k in range(4):
            self.verdicts[:, k] += ((v == k) & real).sum(axis=1)
        if nat_punt is not None:
            self.nat_punts += (np.asarray(nat_punt).reshape(self.n, self.b)
                               & real).sum(axis=1)
        if missteer is not None:
            # exact wrong-shard punts, classified at retire by the
            # serving path (the steering-ring owner recomputation) —
            # a subset of the PASS verdicts counted above
            self.missteers += (np.asarray(missteer).reshape(self.n, self.b)
                               & real).sum(axis=1)
        if viol is not None:
            self.violations += (np.asarray(viol).reshape(self.n, self.b)
                                & real).sum(axis=1)
        self.psum_dhcp_hits += int(dhcp_hits)
        self.steps += 1

    def record_dhcp(self, length, is_reply, dhcp_hits: int) -> None:
        real = self._active(length)
        rep = np.asarray(is_reply).reshape(self.n, self.b) & real
        self.dhcp_replies += rep.sum(axis=1)
        self.verdicts[:, 2] += rep.sum(axis=1)  # replies TX
        self.verdicts[:, 0] += (real & ~rep).sum(axis=1)  # misses punt
        self.psum_dhcp_hits += int(dhcp_hits)
        self.steps += 1

    def snapshot(self) -> dict:
        """The MULTICHIP JSON / metrics payload: per-shard counters, the
        psum-reduced global DHCP hit counter, and the Tracer's sums for
        the loop (`trace`: armed, or as the last disarm left them)."""
        per_shard = []
        for i in range(self.n):
            verdicts = {name: int(self.verdicts[i, k])
                        for k, name in enumerate(self.VERDICT_NAMES)}
            # one consistent accounting everywhere: "pass" is LEGIT
            # slow-path punts only, missteers are their own counter
            # (sum(per-shard pass) == pass_total by construction)
            verdicts["pass"] -= int(self.missteers[i])
            per_shard.append({
                "frames": int(self.frames[i]),
                "verdicts": verdicts,
                "nat_punts": int(self.nat_punts[i]),
                "missteers": int(self.missteers[i]),
                "violations": int(self.violations[i]),
                "dhcp_replies": int(self.dhcp_replies[i]),
            })
            if self.nat is not None:
                nat = self.nat[i]
                per_shard[-1].update(nat_sessions=int(nat.sessions.count),
                                     nat_blocks=len(nat.blocks),
                                     nat_pool=nat.pool_stats())
        return {
            "shards": self.n,
            "steps": self.steps,
            "psum_dhcp_hits": self.psum_dhcp_hits,
            # legitimate slow-path punts: missteers (exact wrong-shard
            # punts, counted at retire by the serving path) are SPLIT
            # OUT of the PASS class. Raw-step callers that record no
            # missteer verdicts still read this as the historical
            # upper bound (see class docstring).
            "pass_total": int(self.verdicts[:, 0].sum()
                              - self.missteers.sum()),
            "missteer_total": int(self.missteers.sum()),
            "nat_punt_total": int(self.nat_punts.sum()),
            "per_shard": per_shard,
            "trace": tele.trace_sums(),
        }


class ShardedCluster:
    """N-shard BNG over a 1D mesh. Control-plane writes route to owners."""

    def __init__(
        self,
        n_shards: int,
        mesh: Mesh | None = None,
        batch_per_shard: int = 64,
        sub_nbuckets: int = 256,
        vlan_nbuckets: int = 64,
        cid_nbuckets: int = 64,
        max_pools: int = 16,
        nat_sessions_nbuckets: int = 256,
        nat_sub_nbuckets: int = 256,
        nat_ports_per_subscriber: int = 1024,
        qos_nbuckets: int = 256,
        spoof_nbuckets: int = 256,
        public_ips: list[int] | None = None,
        garden_enabled: bool = True,
        pppoe_enabled: bool = False,
        pppoe_nbuckets: int = 256,
        server_mac: bytes = b"\x02\xbb\x00\x00\x00\x01",
        edge_enabled: bool = False,
        edge_nbuckets: int = 256,
    ):
        self.n = n_shards
        self.mesh = mesh if mesh is not None else make_mesh(n_shards)
        self.b = batch_per_shard
        # geometry-identical clone recipe (the blue/green standby builder
        # and the checkpoint N==M fast path both need an empty twin);
        # mesh rides along so the standby's jit cache keys HIT the live
        # cluster's compiled programs instead of recompiling the mesh
        self._ctor_kwargs = dict(
            n_shards=n_shards, batch_per_shard=batch_per_shard,
            sub_nbuckets=sub_nbuckets, vlan_nbuckets=vlan_nbuckets,
            cid_nbuckets=cid_nbuckets, max_pools=max_pools,
            nat_sessions_nbuckets=nat_sessions_nbuckets,
            nat_sub_nbuckets=nat_sub_nbuckets,
            nat_ports_per_subscriber=nat_ports_per_subscriber,
            qos_nbuckets=qos_nbuckets, spoof_nbuckets=spoof_nbuckets,
            public_ips=list(public_ips) if public_ips else None,
            garden_enabled=garden_enabled, pppoe_enabled=pppoe_enabled,
            pppoe_nbuckets=pppoe_nbuckets, server_mac=server_mac,
            edge_enabled=edge_enabled, edge_nbuckets=edge_nbuckets)
        self.fastpath = [
            FastPathTables(sub_nbuckets=sub_nbuckets, vlan_nbuckets=vlan_nbuckets,
                           cid_nbuckets=cid_nbuckets, max_pools=max_pools)
            for _ in range(n_shards)
        ]
        base_pub = public_ips or [0xCB007100 + i for i in range(n_shards)]
        if len(base_pub) < n_shards:
            # downstream ring steering is by public-IP ownership: a public
            # IP shared across shards is not expressible (return traffic
            # could only reach one of them) — reject at construction, not
            # at make_ring time
            raise ValueError(
                f"need >= {n_shards} public IPs for {n_shards} shards "
                f"(got {len(base_pub)}): each shard's NAT pool must own "
                f"its public IPs exclusively")
        # every address is used, each owned by one shard: the list is
        # dealt in contiguous runs in the order given, so a pool that is
        # one address range costs the ring one range test a shard
        # (make_ring) however many addresses it holds
        k = len(base_pub)
        self.nat = [
            NATManager(public_ips=base_pub[i * k // n_shards:
                                           (i + 1) * k // n_shards],
                       sessions_nbuckets=nat_sessions_nbuckets,
                       ports_per_subscriber=nat_ports_per_subscriber,
                       sub_nat_nbuckets=nat_sub_nbuckets)
            for i in range(n_shards)
        ]
        self.qos = [QoSTables(nbuckets=qos_nbuckets) for _ in range(n_shards)]
        self.spoof = [AntispoofTables(nbuckets=spoof_nbuckets) for _ in range(n_shards)]
        # device walled-garden gate, chip-local like NAT/QoS (membership is
        # keyed by subscriber private IP = the affinity key). Optional: a
        # disabled feature must cost zero per batch (garden_enabled=False
        # compiles the kernel out, same as Engine's garden=None)
        self.garden = ([GardenTables(nbuckets=spoof_nbuckets)
                        for _ in range(n_shards)] if garden_enabled else None)
        # PPPoE session tables, chip-local like NAT/QoS: by_sid AND by_ip
        # rows live on the subscriber's affinity shard — the ring steers
        # session DATA by the inner src IP (bngring.h steering spec), so
        # the decap always happens where the session row is
        self.pppoe = ([PPPoEFastPathTables(nbuckets=pppoe_nbuckets,
                                           server_mac=server_mac)
                       for _ in range(n_shards)] if pppoe_enabled else None)
        # edge protection tables (tap mirror + route rewrite), chip-local
        # like NAT/QoS: both key on the subscriber private IP = the
        # affinity key, so the ring already steers the matching lanes to
        # the shard holding the row. Optional: a cluster without warrants
        # or route policy compiles the stage out entirely.
        self.edge = ([EdgeTables(tap_nbuckets=edge_nbuckets,
                                 route_nbuckets=edge_nbuckets)
                      for _ in range(n_shards)] if edge_enabled else None)
        # host retire hook for MIRROR-flagged lanes (lane, frame, wid) —
        # the Engine.mirror_sink analog; wire a MirrorPump here
        self.mirror_sink = None
        self.geom = PipelineGeom(
            dhcp=self.fastpath[0].geom,
            nat=self.nat[0].geom,
            qos=self.qos[0].geom,
            spoof=self.spoof[0].geom,
            garden=self.garden[0].geom if garden_enabled else None,
            pppoe=self.pppoe[0].geom if pppoe_enabled else None,
            tap=self.edge[0].tap_geom if edge_enabled else None,
            route=self.edge[0].route_geom if edge_enabled else None,
        )
        self.table_impl = "xla"  # read by benchmark/lib/app.py selectors()
        self._step = _sharded_step_jit(self.mesh, self.geom, self.n)
        self._step_out_names = _step_out_names(self.geom)
        self._dhcp_step = _sharded_dhcp_jit(self.mesh, self.geom, self.n)
        self.tables = None  # lazily built on first step / sync()
        # the drain's own: a placed all-padding batch a table kind, and
        # the dense arrays as last placed with their bytes (update leaves
        # over the mesh only; never a shard's tables)
        self._noop_upd: dict = {}
        self._dense_placed: dict = {}
        # ping-pong ring staging: the in-flight batch owns one buffer set
        # while the next assembles into the other (Engine._staging role)
        self._ring_bufs = [None, None]
        self._stage_idx = 0
        self._inflight = None  # process_ring_pipelined window
        # per-step psum deltas folded by process_ring (Engine.stats role)
        self.stats: dict = {"slow_errors": 0}
        # frames NAT punted for a new flow: the create on the owner shard,
        # and the frames waiting to go through the mesh a second time
        # (runtime/newflow.py; Engine.newflows role)
        from bng_tpu.runtime.newflow import NewFlows

        self.newflows = NewFlows(
            lambda *flows: self.handle_new_flows(*flows),
            bound=max(n_shards * batch_per_shard // 4, 1))
        # count AND log slow-path failures (rate-limited; Engine parity)
        from bng_tpu.utils.structlog import SlowPathErrorLog

        self._slow_err_log = SlowPathErrorLog("sharded")
        # per-shard verdict + psum-hit/punt counters (the loop's times
        # are the Tracer's, lane `sharded`). dryrun_multichip stamps
        # the snapshot into its MULTICHIP JSON; a composition root that
        # owns a cluster AND a BNGMetrics exports it via
        # BNGMetrics.collect_sharded (the serving-path promotion's
        # scrape source — `bng run` has no cluster yet)
        self.telemetry = ShardTelemetry(n_shards, batch_per_shard,
                                        nat=self.nat)
        # NAT public-IP -> owner shard, resolved lazily for the missteer
        # classifier (ownership is fixed at construction: each shard's
        # NATManager keeps its public_ips for its lifetime)
        self._pub_owner_cache: dict[int, int] | None = None

    # ---- owner routing (must match device shard_owner) ----
    def dhcp_sub_shard(self, mac) -> int:
        key = mac_to_u64(mac) if not isinstance(mac, int) else mac
        lo, hi = split_u64(key)
        words = [np.array([hi], dtype=np.uint32), np.array([lo], dtype=np.uint32)]
        return int(shard_owner(words, self.n)[0])

    def dhcp_vlan_shard(self, s_tag: int, c_tag: int) -> int:
        words = [np.array([(s_tag << 16) | c_tag], dtype=np.uint32)]
        return int(shard_owner(words, self.n)[0])

    def dhcp_cid_shard(self, circuit_id: bytes) -> int:
        from bng_tpu.runtime.tables import pack_cid_host

        w = pack_cid_host(circuit_id)
        words = [w[i : i + 1] for i in range(8)]
        return int(shard_owner(words, self.n)[0])

    def affinity_shard_ip(self, private_ip: int) -> int:
        """Traffic-placement shard for a subscriber's private IP.

        MUST match the host ring's per-frame steering decision bit-for-bit
        (ring.shard_of / bngring.cpp bng_ring_shard_of: FNV-1a32 over the
        4 wire-order IP bytes, mod n): the ring steers the subscriber's
        upstream traffic here, so this is the only shard where chip-local
        NAT/QoS/antispoof state for the subscriber is ever consulted.
        Place that state via allocate_nat/set_qos/add_spoof_binding below
        rather than indexing self.nat[...] directly."""
        from bng_tpu.utils.net import fnv1a32

        return fnv1a32(int(private_ip).to_bytes(4, "big")) % self.n

    def affinity_shards(self, private_ips) -> np.ndarray:
        """`affinity_shard_ip` of many addresses at once ([N] int64)."""
        from bng_tpu.runtime.hostpath import fnv1a32_cols

        ips = np.asarray(private_ips, dtype=np.uint32)
        return (fnv1a32_cols(ips.astype(">u4").view(np.uint8).reshape(-1, 4))
                % np.uint32(self.n)).astype(np.int64)

    # ---- subscriber-affinity service placement (owner-shard routing) ----
    def bulk_allocate_nat(self, private_ips, now: int = 0) -> np.ndarray:
        """Port blocks for many subscribers, each on its owner shard
        (`NATManager.bulk_allocate_nat` a shard). Returns the blocks made
        a shard ([n] int64): less than a shard's share of the addresses
        means its pool is exhausted."""
        ips = np.asarray(private_ips, dtype=np.uint32)
        owner = self.affinity_shards(ips)
        return np.array([self.nat[s].bulk_allocate_nat(ips[owner == s], now)
                         for s in range(self.n)], dtype=np.int64)

    def bulk_flows(self, src_ips, dst_ips, src_ports, dst_ports, protos,
                   pkt_len: int, now: int):
        """Sessions for many 5-tuples, each on its source's owner shard
        (`NATManager.bulk_flows` a shard: the shards' managers share
        nothing). Returns (nat_ips, nat_ports, ok) in the order given."""
        src_ips = np.atleast_1d(np.asarray(src_ips, dtype=np.uint32))
        nf = len(src_ips)
        cols = [np.broadcast_to(np.asarray(c, dtype=np.uint32), (nf,))
                for c in (dst_ips, src_ports, dst_ports, protos)]
        owner = self.affinity_shards(src_ips)
        nat_ip = np.zeros(nf, dtype=np.uint32)
        nat_port = np.zeros(nf, dtype=np.uint32)
        ok = np.zeros(nf, dtype=bool)
        for s in range(self.n):
            m = owner == s
            if m.any():
                nat_ip[m], nat_port[m], ok[m] = self.nat[s].bulk_flows(
                    src_ips[m], *(c[m] for c in cols), pkt_len, now)
        return nat_ip, nat_port, ok

    def allocate_nat(self, private_ip: int, now: int = 0):
        """Allocate a NAT port block on the subscriber's owner shard.

        Returns (owner_shard, allocation) — the pkg/pool/peer.go
        owner-or-forward role: the ring steers the subscriber's packets to
        owner_shard, so its NAT state lives there and nowhere else."""
        o = self.affinity_shard_ip(private_ip)
        return o, self.nat[o].allocate_nat(private_ip, now)

    def handle_new_flow(self, src_ip: int, *args, **kw):
        o = self.affinity_shard_ip(src_ip)
        return o, self.nat[o].handle_new_flow(src_ip, *args, **kw)

    def handle_new_flows(self, src_ips, dst_ips, src_ports, dst_ports, protos,
                         pkt_lens, now: int) -> list:
        """`NATManager.handle_new_flows` over the mesh: each flow is
        created on its owner shard's manager, a shard's flows in one batch
        and in their lane order (the shards' managers share no state, so
        the state is the one-by-one path's). Answers in lane order."""
        cols = (src_ips, dst_ips, src_ports, dst_ports, protos, pkt_lens)
        by_owner: dict[int, list[int]] = {}
        for i, ip in enumerate(src_ips):
            by_owner.setdefault(self.affinity_shard_ip(ip), []).append(i)
        answers: list = [None] * len(src_ips)
        for o, lanes in by_owner.items():
            got = self.nat[o].handle_new_flows(
                *([c[i] for i in lanes] for c in cols), now)
            for i, g in zip(lanes, got):
                answers[i] = g
        return answers

    def set_qos(self, private_ip: int, **kw) -> int:
        o = self.affinity_shard_ip(private_ip)
        self.qos[o].set_subscriber(private_ip, **kw)
        return o

    def add_spoof_binding(self, mac, ipv4: int, mode: int) -> int:
        o = self.affinity_shard_ip(ipv4)
        self.spoof[o].add_binding(mac, ipv4, mode)
        return o

    def set_gardened(self, private_ip: int, gardened: bool) -> int:
        if self.garden is None:
            raise RuntimeError("device garden gate disabled for this cluster")
        o = self.affinity_shard_ip(private_ip)
        self.garden[o].set_gardened(private_ip, gardened)
        return o

    def allow_garden_destination(self, ip: int, port: int = 0,
                                 proto: int = 0) -> None:
        if self.garden is None:
            raise RuntimeError("device garden gate disabled for this cluster")
        for g in self.garden:  # policy is global; membership is per-shard
            g.allow_destination(ip, port, proto)

    def pppoe_session_up(self, sess) -> int:
        """Publish an OPEN PPPoE session on its affinity shard (both
        directions: by_sid for upstream decap, by_ip for downstream
        encap — the ring steers both sides there)."""
        if self.pppoe is None:
            raise RuntimeError("PPPoE disabled for this cluster")
        o = self.affinity_shard_ip(sess.assigned_ip)
        self.pppoe[o].session_up(sess)
        return o

    def pppoe_session_down(self, event) -> int:
        if self.pppoe is None:
            raise RuntimeError("PPPoE disabled for this cluster")
        sess = getattr(event, "session", event)
        o = self.affinity_shard_ip(sess.assigned_ip)
        self.pppoe[o].session_down(event)
        return o

    # ---- edge protection (rows live on the subscriber's affinity shard) --
    # The same duck-typed surface EdgeTables exposes, with owner routing
    # in front, so InterceptTapProgram/RouteProgram target a cluster
    # exactly as they target a single engine's tables.
    def _edge_or_raise(self) -> list[EdgeTables]:
        if self.edge is None:
            raise RuntimeError("edge protection disabled for this cluster")
        return self.edge

    def arm_tap(self, private_ip: int, wid: int, filters=()) -> int:
        edge = self._edge_or_raise()
        o = self.affinity_shard_ip(private_ip)
        edge[o].arm_tap(private_ip, wid, filters)
        # filter rows are warrant-global: replicate to every shard so
        # any shard's dense copy (and shard 0's at checkpoint time) is
        # authoritative for the whole cluster
        for i, e in enumerate(edge):
            if i != o:
                e.set_tap_filters(wid, filters)
        return o

    def disarm_tap(self, private_ip: int) -> bool:
        edge = self._edge_or_raise()
        return edge[self.affinity_shard_ip(private_ip)].disarm_tap(private_ip)

    def get_tap(self, private_ip: int):
        edge = self._edge_or_raise()
        return edge[self.affinity_shard_ip(private_ip)].get_tap(private_ip)

    def set_tap_filters(self, wid: int, filters) -> int:
        """Filter rows replicate cluster-wide (one warrant may arm IPs on
        several shards); returns the smallest per-shard write count so a
        truncation anywhere reads as dropped."""
        edge = self._edge_or_raise()
        return min(e.set_tap_filters(wid, filters) for e in edge)

    def set_route(self, private_ip: int, nh_mac: bytes, table_id: int,
                  klass: int = 0) -> int:
        edge = self._edge_or_raise()
        o = self.affinity_shard_ip(private_ip)
        edge[o].set_route(private_ip, nh_mac, table_id, klass)
        return o

    def clear_route(self, private_ip: int) -> bool:
        edge = self._edge_or_raise()
        return edge[self.affinity_shard_ip(private_ip)].clear_route(private_ip)

    def get_route(self, private_ip: int):
        edge = self._edge_or_raise()
        return edge[self.affinity_shard_ip(private_ip)].get_route(private_ip)

    def tap_rows(self):
        """Cluster-wide tap rows, sorted by IP (the audit surface)."""
        edge = self._edge_or_raise()
        return sorted((kv for e in edge for kv in e.tap_rows()),
                      key=lambda kv: kv[0])

    def route_rows(self):
        edge = self._edge_or_raise()
        return sorted((kv for e in edge for kv in e.route_rows()),
                      key=lambda kv: kv[0])

    def pub_ip_map(self) -> dict[int, int]:
        """NAT public IP -> owner shard (downstream ring steering).

        Raises when one public IP is claimed by multiple shards: downstream
        steering is by-IP only, so shared ownership is not expressible — a
        silent last-shard-wins map would punt every return packet of the
        other shards' flows to the slow path."""
        owners: dict[int, int] = {}
        for s in range(self.n):
            for ip in self.nat[s].public_ips:
                if ip in owners and owners[ip] != s:
                    raise ValueError(
                        f"public IP {ip:#x} owned by shards {owners[ip]} and "
                        f"{s}: downstream steering needs exclusive ownership "
                        f"(give each shard distinct public_ips)")
                owners[ip] = s
        return owners

    def make_ring(self, nframes: int = 4096, frame_size: int = 2048,
                  depth: int = 1024, prefer_native: bool = True):
        """A host packet ring steering frames to this cluster's shards.

        The assemble_sharded layout (shard i's lanes at rows i*b..(i+1)*b)
        is exactly step()'s batch contract, so `ring -> assemble_sharded ->
        step -> complete` is the full multichip I/O loop."""
        from bng_tpu.runtime.ring import make_ring as _mk

        return self.steer_ring(_mk(nframes, frame_size, depth,
                                   prefer_native=prefer_native,
                                   n_shards=self.n))

    def steer_ring(self, ring):
        """Hand `ring` (one of `n` shards) this cluster's ownership of the
        public pool; returns it. Raises when the ring cannot hold it."""
        # ownership as the ring holds it: a run of consecutive addresses
        # with one owner is one range test, a lone address an entry of
        # the exact map. A pool dealt in contiguous runs is a range a
        # shard, so steering costs the same at 4 addresses and at 16,000.
        owners = sorted(self.pub_ip_map().items())
        at = 0
        while at < len(owners):
            lo, s = owners[at]
            end = at
            while (end + 1 < len(owners)
                   and owners[end + 1] == (owners[end][0] + 1, s)):
                end += 1
            hi = owners[end][0]
            took = (ring.steer_pub_ip(lo, s) if hi == lo
                    else ring.steer_pub_range(lo, hi, s))
            if not took:
                # an unregistered public IP would silently fall back to
                # dst-IP hashing — every return packet punts on a wrong
                # shard. A ring that cannot express the placement is a
                # configuration error, not a degraded mode.
                raise RuntimeError(
                    f"ring steering tables rejected public IPs {lo:#x}.."
                    f"{hi:#x} of shard {s} (capacity/probe bound): list "
                    f"each shard's addresses as fewer contiguous runs")
            at = end + 1
        return ring

    # ---- control-plane writes ----
    def add_pool_all(self, pool_id: int, network: int, prefix_len: int, gateway: int,
                     dns1: int = 0, dns2: int = 0, lease_time: int = 3600) -> None:
        for fp in self.fastpath:
            fp.add_pool(pool_id, network, prefix_len, gateway, dns1, dns2, lease_time)

    def set_server_config_all(self, mac, ip: int) -> None:
        for fp in self.fastpath:
            fp.set_server_config(mac, ip)

    def add_subscriber(self, mac, **kw) -> int:
        o = self.dhcp_sub_shard(mac)
        self.fastpath[o].add_subscriber(mac, **kw)
        return o

    def add_subscribers_bulk(self, macs_u64, pool_ids, ips, lease_expiries,
                             **kw) -> np.ndarray:
        """Reference-scale sharded build: split 1M+ subscribers by owner
        shard (vectorized shard_owner — the same mix the device lookup
        routes with) and bulk-insert each shard's slice. Returns the [N]
        owner-shard array. Follow with sync_tables() for a full upload
        (maps sized for 1M: /root/reference/bpf/maps.h:10)."""
        macs_u64 = np.asarray(macs_u64, dtype=np.uint64)
        hi = (macs_u64 >> np.uint64(32)).astype(np.uint32)
        lo = (macs_u64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        owners = np.asarray(shard_owner([hi, lo], self.n))
        pool_ids = np.broadcast_to(np.asarray(pool_ids, dtype=np.uint32),
                                   macs_u64.shape)
        ips = np.broadcast_to(np.asarray(ips, dtype=np.uint32), macs_u64.shape)
        lease_expiries = np.broadcast_to(
            np.asarray(lease_expiries, dtype=np.uint32), macs_u64.shape)
        for s in range(self.n):
            m = owners == s
            if m.any():
                self.fastpath[s].add_subscribers_bulk(
                    macs_u64[m], pool_ids=pool_ids[m], ips=ips[m],
                    lease_expiries=lease_expiries[m], **kw)
        return owners

    def add_vlan_subscriber(self, s_tag: int, c_tag: int, **kw) -> int:
        o = self.dhcp_vlan_shard(s_tag, c_tag)
        self.fastpath[o].add_vlan_subscriber(s_tag, c_tag, **kw)
        return o

    def add_circuit_id_subscriber(self, circuit_id: bytes, **kw) -> int:
        o = self.dhcp_cid_shard(circuit_id)
        self.fastpath[o].add_circuit_id_subscriber(circuit_id, **kw)
        return o

    def remove_subscriber(self, mac) -> bool:
        return self.fastpath[self.dhcp_sub_shard(mac)].remove_subscriber(mac)

    def remove_vlan_subscriber(self, s_tag: int, c_tag: int) -> bool:
        o = self.dhcp_vlan_shard(s_tag, c_tag)
        return self.fastpath[o].remove_vlan_subscriber(s_tag, c_tag)

    def remove_circuit_id_subscriber(self, circuit_id: bytes) -> bool:
        o = self.dhcp_cid_shard(circuit_id)
        return self.fastpath[o].remove_circuit_id_subscriber(circuit_id)

    def touch_lease(self, mac, lease_expiry: int) -> bool:
        o = self.dhcp_sub_shard(mac)
        return self.fastpath[o].touch_lease(mac, lease_expiry)

    def get_subscriber(self, mac):
        return self.fastpath[self.dhcp_sub_shard(mac)].get_subscriber(mac)

    # ---- device sync ----
    def _place(self, stacked: np.ndarray):
        """A host array with a leading mesh dimension, placed over the
        mesh: one `upload`."""
        t0 = tele.t()
        out = jax.device_put(stacked, NamedSharding(self.mesh, P(AXIS)))
        tele.xfer(tele.UPLOAD, t0, stacked.nbytes)
        return out

    def _stack(self, arrs):
        """One leaf of every shard, stacked on the host and placed over
        the mesh: a read back a shard (`fetch`, those that live on a
        device) and one placement."""
        t0 = tele.t()
        stacked = np.stack([np.asarray(a) for a in arrs])
        tele.fetched(t0, *arrs)
        return self._place(stacked)

    def _stack_per_shard(self, per_shard):
        """Stack a per-shard pytree list on the mesh axis (sync_tables':
        whole tables staged on chip 0; nothing here is kept)."""
        return jax.tree.map(lambda *xs: self._stack(xs), *per_shard)

    def _host_tables(self, fastpath_only: bool = False) -> list:
        """Every shard's host tables that a drain ships deltas of."""
        out = []
        for i in range(self.n):
            fp = self.fastpath[i]
            out += [fp.sub, fp.vlan, fp.cid]
            if fastpath_only:
                continue
            nat = self.nat[i]
            out += [nat.sessions, nat.reverse, nat.sub_nat, self.qos[i].up,
                    self.qos[i].down, self.spoof[i].bindings]
            if self.garden is not None:
                out.append(self.garden[i].subscribers)
            if self.pppoe is not None:
                out += [self.pppoe[i].by_sid, self.pppoe[i].by_ip]
            if self.edge is not None:
                out += [self.edge[i].tap, self.edge[i].route]
        return out

    def _drain_with_resync(self, drain, fastpath_only: bool = False):
        """Run a make-updates drain; on the bulk-build "full upload"
        signal answer with one full re-upload and drain again — the
        Engine._drain_with_resync contract, so a bulk build on a live
        cluster does not brick the step loop, and its count for the
        tracer: of the shards' tables, those with something to ship and
        the clean ones. (The re-upload resets device-authoritative
        counters/tokens, as documented there.)"""
        if tele.t() is not None:
            tabs = self._host_tables(fastpath_only)
            built = sum(1 for t in tabs if t.dirty_count())
            tele.drain_tables(built, len(tabs) - built)
        try:
            return drain()
        except RuntimeError as e:
            if "full upload" not in str(e):
                raise
            self.sync_tables()
            return drain()

    def _table_upd(self, owners: list, name: str):
        """The update batch of the shards' table `name` (one kind: an
        attribute of each of `owners`), stacked over the mesh. Clean on
        every shard (the steady state): the all-padding batch placed when
        the kind was first drained clean, the same arrays every step, and
        no crossing. Dirty on any: each shard's batch built on the host
        (padding for the clean ones), stacked and placed, one placement
        a leaf. The placed no-op is geometry's, not contents': it
        outlives a resync. Like `empty_update`'s cache it leans on no
        program donating its updates."""
        tables = [getattr(o, name) for o in owners]
        kind = (type(owners[0]).__name__, name)
        clean = not any(t.dirty_count() for t in tables)
        if clean and kind in self._noop_upd:
            return self._noop_upd[kind]
        slots = owners[0].update_slots
        upd = jax.tree.map(lambda *xs: self._place(np.stack(xs)),
                           *[t.host_update(slots) for t in tables])
        if clean:  # all padding
            self._noop_upd[kind] = upd
        return upd

    def _dense_upd(self, owners: list, name: str):
        """The shards' small dense array `name` (pools, server, hairpin,
        ranges, allowlist: applied wholesale by every step; an attribute
        of each of `owners`, or a method that builds it), stacked over
        the mesh: placed again only when the bytes differ from what was
        last placed, as `ops/table.py placed` does on one chip, so a
        write in place before a drain is in that drain's batch."""
        hosts = [getattr(o, name) for o in owners]
        stacked = np.stack([h() if callable(h) else h for h in hosts])
        kind = (type(owners[0]).__name__, name)
        now = stacked.tobytes()
        hit = self._dense_placed.get(kind)
        if hit is None or hit[0] != now:
            hit = self._dense_placed[kind] = (now, self._place(stacked))
        return hit[1]

    def _fastpath_upd(self) -> FastPathUpdates:
        tab, dense, fp = self._table_upd, self._dense_upd, self.fastpath
        return FastPathUpdates(
            sub=tab(fp, "sub"), vlan=tab(fp, "vlan"), cid=tab(fp, "cid"),
            pools=dense(fp, "pools"), server=dense(fp, "server"))

    def _updates(self) -> tuple:
        """The stacked update batch of a fused step, in
        _apply_all_updates' order (the engine's `_updates`, a kind a
        shard-stack: ROADMAP D1)."""
        tab, dense = self._table_upd, self._dense_upd
        nat, qos, sp, g, p, e = (self.nat, self.qos, self.spoof, self.garden,
                                 self.pppoe, self.edge)
        return (
            self._fastpath_upd(),
            (tab(nat, "sessions"), tab(nat, "reverse"), tab(nat, "sub_nat"),
             dense(nat, "hairpin"), dense(nat, "alg"),
             dense(nat, "config_array")),
            tab(qos, "up"), tab(qos, "down"),
            tab(sp, "bindings"), dense(sp, "ranges"), dense(sp, "config"),
            *((tab(g, "subscribers"), dense(g, "allowed"))
              if g is not None else ()),
            *((tab(p, "by_sid"), tab(p, "by_ip")) if p is not None else ()),
            *((tab(e, "tap"), dense(e, "tap_filters"),
               dense(e, "tap_config"), tab(e, "route"))
              if e is not None else ()),
        )

    def _drain_updates(self):
        """The shards' bounded update batches, stacked on the mesh axis.

        Same mechanism as Engine._drain_updates: host writes since the
        last step ride into the donated jitted step as fixed-size deltas,
        so device-authoritative state (NAT session counters, QoS tokens)
        is never clobbered by a full re-upload. What crosses follows what
        changed (`_table_upd`, `_dense_upd`): a clean mesh drains to the
        batch that is already on its chips.
        """
        return self._drain_with_resync(self._updates)

    def _drain_fastpath(self):
        """Fastpath-only drain (the DHCP fast lane's update path)."""
        return self._drain_with_resync(self._fastpath_upd, fastpath_only=True)

    def sync_tables(self) -> None:
        """Full upload of every shard's tables, stacked on the mesh axis.

        Initial upload only: after the first step(), incremental writes
        flow through _drain_updates — re-syncing would reset
        device-authoritative counters/tokens.
        """
        per_shard = []
        for i in range(self.n):
            t = PipelineTables(
                dhcp=self.fastpath[i].device_tables(),
                nat=self.nat[i].device_tables(),
                qos_up=self.qos[i].up.device_state(),
                qos_down=self.qos[i].down.device_state(),
                spoof=self.spoof[i].bindings.device_state(),
                spoof_ranges=jnp.asarray(self.spoof[i].ranges),
                spoof_config=jnp.asarray(self.spoof[i].config),
                garden=(self.garden[i].subscribers.device_state()
                        if self.garden is not None else None),
                garden_allowed=(jnp.asarray(self.garden[i].allowed)
                                if self.garden is not None else None),
                pppoe_by_sid=(self.pppoe[i].by_sid.device_state()
                              if self.pppoe is not None else None),
                pppoe_by_ip=(self.pppoe[i].by_ip.device_state()
                             if self.pppoe is not None else None),
                pppoe_server_mac=(jnp.asarray(self.pppoe[i].server_mac)
                                  if self.pppoe is not None else None),
                tap=(self.edge[i].tap.device_state()
                     if self.edge is not None else None),
                tap_filters=(jnp.asarray(self.edge[i].tap_filters)
                             if self.edge is not None else None),
                tap_config=(jnp.asarray(self.edge[i].tap_config)
                            if self.edge is not None else None),
                route=(self.edge[i].route.device_state()
                       if self.edge is not None else None),
            )
            per_shard.append(t)
        self.tables = self._stack_per_shard(per_shard)

    def _start_host_copies(self, names, outs) -> None:
        """Start, at dispatch, the device-to-host copy of every leaf of a
        mesh step's result `outs` (named by `names`) that its retire
        reads: by then (a beat later on the pipelined loop) each
        `np.asarray` finds the bytes on the host instead of making one
        blocking gather from the chips an output (0.87 ms each over four
        chips, ten a fused step: PERF.md §6 PR 44). A sharded leaf starts
        one copy an addressable shard, a replicated stats block one. Every
        leaf but the tables, which thread to the next step and are
        donated; the mirror column only where a sink reads it."""
        start_host_copies(
            a for name, a in zip(names, outs, strict=True)
            if name != "tables"
            and (name != "mirror" or self.mirror_sink is not None))

    def _dispatch_dhcp(self, pkt, length, now_s: int):
        """device_put + fastpath drain + donated sharded DHCP step, and
        the start of the outputs' copies to the host. Nothing is waited
        for: the outputs are futures on their way (async half)."""
        if self.tables is None:
            self.sync_tables()
        t0 = tele.t()
        sh = NamedSharding(self.mesh, P(AXIS))
        pkt_d = jax.device_put(pkt, sh)
        len_d = jax.device_put(length.astype(np.uint32), sh)
        if t0 is not None:  # `pack` here is two placements and no more
            tele.xfer(tele.UPLOAD, t0, pkt.nbytes + 4 * len(length), 2)
        tele.lap(tele.PACK, t0)
        t0 = tele.t()
        upd = self._drain_fastpath()
        tele.lap(tele.DRAIN, t0)
        t0 = tele.t()
        raw = self._dhcp_step(self.tables.dhcp, upd, pkt_d, len_d,
                              jnp.uint32(now_s))
        self.tables = self.tables._replace(dhcp=raw[0])
        self._start_host_copies(_DHCP_OUT_NAMES, raw)
        tele.lap(tele.DISPATCH, t0)
        return raw[1:]

    def _dispatch_fused(self, pkt, length, from_access, now_s: int,
                        now_us: int):
        """device_put + full drain + donated sharded step, and the start
        of the outputs' copies to the host. The ONE owner of the
        drain-before-tables-read donation invariant and of the copies'
        start (inside the `dispatch` lap: what the starts cost is there);
        nothing is waited for (async half)."""
        if self.tables is None:
            self.sync_tables()
        t0 = tele.t()
        sh = NamedSharding(self.mesh, P(AXIS))
        pkt_d = jax.device_put(pkt, sh)
        len_d = jax.device_put(length.astype(np.uint32), sh)
        fa_d = jax.device_put(from_access, sh)
        if t0 is not None:  # `pack` here is three placements and no more
            tele.xfer(tele.UPLOAD, t0, pkt.nbytes + 4 * len(length)
                      + from_access.nbytes, 3)
        tele.lap(tele.PACK, t0)
        # drain FIRST: a bulk-build resync rebinds self.tables, and Python
        # evaluates arguments left-to-right — reading self.tables before
        # the drain would pass (and donate) the stale pre-resync reference
        t0 = tele.t()
        upd = self._drain_updates()
        tele.lap(tele.DRAIN, t0)
        t0 = tele.t()
        raw = self._step(self.tables, upd, pkt_d, len_d, fa_d,
                         jnp.uint32(now_s), jnp.uint32(now_us))
        self.tables = raw[3]
        self._start_host_copies(self._step_out_names, raw)
        tele.lap(tele.DISPATCH, t0)
        return raw

    def dhcp_step(self, pkt: np.ndarray, length: np.ndarray, now_s: int):
        """One sharded DHCP-only step (the control-batch fast lane).

        Same layout contract as step(); only the fastpath update drain
        runs, and the shared dhcp table leaves thread through donated —
        NAT/QoS/antispoof deltas stay queued for the next fused step.
        Returns {"is_reply", "out_pkt", "out_len", "dhcp_stats"}.
        """
        from bng_tpu.ops.dhcp import ST_HIT

        tok = tele.begin_batch(tele.LANE_SHARDED, len(length))
        try:
            is_reply, out_pkt, out_len, stats = self._dispatch_dhcp(
                pkt, length, now_s)
        except BaseException:
            tele.cancel_batch(tok)
            raise
        tele.device_up(tok)
        t0 = tele.t()
        tf = tele.ready(is_reply, tok)  # armed: the wait, then the reads
        out = {"is_reply": np.asarray(is_reply)}
        out.update(out_pkt=out_pkt, out_len=np.asarray(out_len),
                   dhcp_stats=np.asarray(stats))
        tele.fetched(tf, is_reply, out_len, stats, tok=tok)
        tele.lap(tele.DEVICE_WAIT, t0, tok)
        self.telemetry.record_dhcp(
            length, out["is_reply"], int(out["dhcp_stats"][ST_HIT]))
        tele.end_batch(tok)
        return out

    def process_ring(self, ring, now_s: int, now_us: int,
                     pkt_slot: int = 2048, slow_path=None,
                     violation_sink=None) -> int:
        """One multichip production beat: drain a STEERING ring through
        the sharded step and demux verdicts back (the single-chip analog
        is Engine.process_ring; the batch layout contract is
        assemble_sharded's per-shard lane ranges = step()'s rows).

        Engine-parity semantics:
        - all-control batches (ring-classified DHCP, FLAG_DHCP_CTRL on
          every real lane) ride the sharded DHCP-only fast lane;
        - per-step stats deltas fold into self.stats;
        - the slow queue is drained lane-aligned: NAT new-flow punts
          create the session on the subscriber's OWNER shard inline,
          everything else goes to `slow_path(frame) -> reply|None` with
          replies injected on the TX ring; spoof violations reach
          `violation_sink(lane, frame)`.

        The ring must be one of this cluster's (make_ring) so shard i's
        region holds shard i's subscribers; pkt_slot must cover the
        ring's frame size or oversize frames would be staged truncated.
        Returns frames processed."""
        if pkt_slot < ring.frame_size:
            raise ValueError(
                f"pkt_slot {pkt_slot} < ring frame_size {ring.frame_size}: "
                f"oversize frames would be silently truncated")
        if self._inflight is not None:
            # a pipelined batch holds one of its ring's assemble windows;
            # retire it — WITH this call's handlers, or its PASS frames
            # would pop from the slow ring and vanish (Engine parity)
            self.flush_pipeline(slow_path, violation_sink)
        t0 = tele.t()
        pkt, length, flags = self._staging(self._stage_idx, pkt_slot)
        got, held = self._fill_window(ring, pkt, length, flags)
        if not got and not held:
            tele.lap(tele.RING, t0)
            return 0
        entry = self._dispatch_ring_batch(ring, pkt, length, flags, got,
                                          now_s, now_us, t0, held=held)
        self._retire(entry, slow_path, violation_sink)
        return got

    def process_ring_pipelined(self, ring, now_s: int, now_us: int,
                               pkt_slot: int = 2048, slow_path=None,
                               violation_sink=None) -> int:
        """Double-buffered multichip ring loop: dispatch batch k+1, THEN
        retire k — host demux overlaps device execution, the same
        two-window design as Engine.process_ring_pipelined (engine.py)
        which the single-chip path uses to hold latency at load. Batch
        k's outputs have been on their way to the host since its own
        dispatch, a beat ago: its retire reads host memory. Requires
        ring backends tolerating two outstanding assemble..complete
        windows (bngring MAX_INFLIGHT=2; complete() retires FIFO in this
        loop's order). Call flush_pipeline() before reading final state.
        Returns frames retired this call."""
        if pkt_slot < ring.frame_size:
            raise ValueError(
                f"pkt_slot {pkt_slot} < ring frame_size {ring.frame_size}: "
                f"oversize frames would be silently truncated")
        prev = self._inflight
        self._inflight = None
        try:
            # 1. feed the mesh first: assemble into the buffer prev is NOT
            # using, so its frames stay intact until retirement
            self._probe(prev)
            t0 = tele.t()
            idx = 1 - self._stage_idx
            pkt, length, flags = self._staging(idx, pkt_slot)
            got, held = self._fill_window(ring, pkt, length, flags)
            if not got and not held:
                tele.lap(tele.RING, t0)
            else:
                try:
                    entry = self._dispatch_ring_batch(
                        ring, pkt, length, flags, got, now_s, now_us, t0,
                        prev, held)
                except BaseException:
                    # fail closed: the assemble opened a ring window that
                    # must not wedge. complete() retires FIFO, so the
                    # previous (older) window must retire FIRST.
                    from bng_tpu.runtime.ring import VERDICT_DROP

                    self._retire(prev, slow_path, violation_sink)
                    prev = None
                    B = self.n * self.b
                    if got:
                        ring.complete(
                            np.full((B,), VERDICT_DROP, dtype=np.uint8),
                            pkt, length, B)
                    raise
                self._inflight = entry
                self._stage_idx = idx
        finally:
            # 2. retire the previous batch (even if dispatch raised) while
            # the mesh runs the new one
            retired = self._retire(prev, slow_path, violation_sink)
        return retired

    def flush_pipeline(self, slow_path=None, violation_sink=None) -> int:
        """Retire any in-flight pipelined batch (shutdown/test barrier)."""
        entry = self._inflight
        self._inflight = None
        return self._retire(entry, slow_path, violation_sink)

    def _staging(self, idx: int, pkt_slot: int):
        B = self.n * self.b
        if self._ring_bufs[idx] is None or \
                self._ring_bufs[idx][0].shape != (B, pkt_slot):
            self._ring_bufs[idx] = (np.zeros((B, pkt_slot), dtype=np.uint8),
                                    np.zeros((B,), dtype=np.uint32),
                                    np.zeros((B,), dtype=np.uint32))
        return self._ring_bufs[idx]

    def _fill_window(self, ring, pkt, length, flags) -> tuple[int, tuple]:
        """One window into a staging buffer: the ring's frames by shard
        (assemble_sharded), then the frames waiting for their second pass
        (runtime/newflow.py), each in the first padding lane of the region
        of the shard the ring steers it to; one whose region is full waits
        for the next window, and so does every held frame behind it (they
        leave in the order they came). Returns (frames the ring staged,
        (held frames, their lanes)): the ring opened a window only where
        it staged a frame, and `complete` skips the lanes it did not fill."""
        got = ring.assemble_sharded(pkt, length, flags)
        nf = self.newflows
        if not len(nf):
            return got, ()
        if not got:  # no window: the rows are as the last one left them
            length[:] = 0
            flags[:] = 0
        used = (length > 0).reshape(self.n, self.b).sum(axis=1)
        held, lanes, waiting = [], [], []
        for frame, fl in nf.take(len(nf)):
            s = ring.shard_of(frame, fl)
            if waiting or used[s] >= self.b:
                waiting.append((frame, fl))
                continue
            lane = int(s * self.b + used[s])
            used[s] += 1
            nf.stage(pkt, length, flags, lane, frame, fl)
            held.append((frame, fl))
            lanes.append(lane)
        nf.put_back(waiting)
        return got, ((held, lanes) if held else ())

    def _dispatch_ring_batch(self, ring, pkt, length, flags, got,
                             now_s: int, now_us: int, t_ring=None,
                             prev=None, held=()):
        """Dispatch one assembled window to the mesh WITHOUT waiting for
        its outputs (their copies to the host are started and nothing is
        read until _retire) — the async half of the beat, so a pipelined
        caller overlaps demux with compute and with the copies.
        `t_ring` is the Tracer origin of the assemble that filled the
        window; `prev` the window still in flight, probed for readiness
        between the stages. The entry's last field is the batch token."""
        from bng_tpu.runtime.ring import FLAG_DHCP_CTRL

        tok = tele.begin_batch(tele.LANE_SHARDED, got)
        tele.lap(tele.RING, t_ring, tok)
        self._probe(prev)
        try:
            t0 = tele.t()
            real = length > 0
            all_ctrl = bool(((flags[real] & FLAG_DHCP_CTRL) != 0).all())
            fa = None if all_ctrl else (flags & 0x1) != 0
            tele.lap(tele.PACK, t0, tok)
            if all_ctrl:  # the multichip OFFER-latency fast lane
                is_reply, out_pkt, out_len, stats = self._dispatch_dhcp(
                    pkt, length, now_s)
                out = ("dhcp", is_reply, out_pkt, out_len, stats)
            else:
                out = ("fused", self._dispatch_fused(pkt, length, fa,
                                                     now_s, now_us))
        except BaseException:
            tele.cancel_batch(tok)  # a failed dispatch must not leak a slot
            raise
        self._probe(prev)  # before this window goes up: was the mesh idle?
        tele.device_up(tok)
        return (ring, out, pkt, length, flags, got, now_s, held, tok)

    @staticmethod
    def _probe(entry) -> None:
        """Armed only: has the mesh finished this in-flight window? The
        loop looks between its stages, so device time by readiness is at
        most one host stage late (spans.py device_down). Disarmed: one
        global load and compare."""
        if entry is not None and tele.device_pending(entry[-1]):
            out = entry[1]
            first = out[1] if out[0] == "dhcp" else out[1][0]
            if first.is_ready():
                tele.device_down(entry[-1])

    def _retire(self, entry, slow_path, violation_sink) -> int:
        """Read a dispatched window's outputs on the host and demux
        verdicts back to its ring (the sync half of the beat). Every
        output's copy was started at its dispatch: a read finds the bytes
        landed, or blocks until they have (never before the step wrote
        them), and no frame is completed before its verdict is here."""
        if entry is None:
            return 0
        from bng_tpu.ops.dhcp import ST_HIT
        from bng_tpu.ops.nat44 import NST_DNAT, NST_SNAT
        from bng_tpu.runtime.ring import VERDICT_PASS, VERDICT_TX

        ring, out, pkt, length, flags, got, now_s, held, tok = entry
        B = self.n * self.b
        real = length > 0
        if held:  # lanes on their second pass: not the ring's window's
            real = real.copy()
            real[held[1]] = False
        tele.focus(tok)
        t0 = tele.t()
        if out[0] == "dhcp":
            _, is_reply, out_pkt, out_len, stats = out
            tf = tele.ready(is_reply, tok)  # armed: the wait, then the reads
            is_reply_h = np.asarray(is_reply)
            verdict = np.where(is_reply_h, np.uint8(VERDICT_TX),
                               np.uint8(VERDICT_PASS))
            punt = np.zeros((B,), dtype=bool)
            viol = np.zeros((B,), dtype=bool)
            mir = None
            stats_h = np.asarray(stats)
            self._fold_stats(dhcp=stats_h)
            out_pkt_h = np.asarray(out_pkt)
            out_len_h = np.asarray(out_len).astype(np.uint32)
            tele.fetched(tf, is_reply, stats, out_pkt, out_len, tok=tok)
            tele.lap(tele.DEVICE_WAIT, t0, tok)
            t0 = tele.t()
            self.telemetry.record_dhcp(length, is_reply_h,
                                       int(stats_h[ST_HIT]))
        else:
            (verdict_d, out_pkt, out_len, _tables, dhcp_stats, nat_stats,
             qos_stats, spoof_stats, nat_punt, viol_d, *tails) = out[1]
            tails = list(tails)
            g_stats = tails.pop(0) if self.garden is not None else None
            p_stats = tails.pop(0) if self.pppoe is not None else None
            mir = tails.pop(0) if self.edge is not None else None
            e_stats = tails.pop(0) if self.edge is not None else None
            tf = tele.ready(verdict_d, tok)  # armed: the wait, then the reads
            verdict = np.asarray(verdict_d).astype(np.uint8)
            punt = np.asarray(nat_punt)
            viol = np.asarray(viol_d)
            dhcp_h = np.asarray(dhcp_stats)
            nat_h = np.asarray(nat_stats)
            if tele.t() is not None:
                # lanes this step translated and lanes NAT punted, from
                # the blocks read here already
                tele.nat_lanes(int(nat_h[NST_SNAT]) + int(nat_h[NST_DNAT]),
                               int((punt & real).sum()))
            self._fold_stats(dhcp=dhcp_h,
                             nat=nat_h,
                             qos=np.asarray(qos_stats),
                             spoof=np.asarray(spoof_stats),
                             garden=(np.asarray(g_stats)
                                     if g_stats is not None else None),
                             pppoe=(np.asarray(p_stats)
                                    if p_stats is not None else None),
                             edge=(np.asarray(e_stats)
                                   if e_stats is not None else None))
            out_pkt_h = np.asarray(out_pkt)
            out_len_h = np.asarray(out_len).astype(np.uint32)
            tele.fetched(tf, verdict_d, nat_punt, viol_d, dhcp_stats,
                         nat_stats, qos_stats, spoof_stats, g_stats, p_stats,
                         e_stats, out_pkt, out_len, tok=tok)
            tele.lap(tele.DEVICE_WAIT, t0, tok)
            self._probe(self._inflight)
            t0 = tele.t()
            # exact missteer classification (ISSUE 12): a PASS lane that
            # is not a NAT new-flow punt and whose affinity owner is a
            # DIFFERENT shard punted because the steering put it in the
            # wrong region — count it apart from legit slow-path punts
            missteer = np.zeros((B,), dtype=bool)
            for lane in np.nonzero((verdict == VERDICT_PASS) & real
                                   & ~punt)[0]:
                owner = self._frame_affinity_owner(
                    bytes(pkt[lane, : int(length[lane])]),
                    int(flags[lane]))
                if owner is not None and owner != lane // self.b:
                    missteer[lane] = True
            self.telemetry.record_fused(length, verdict, punt, viol,
                                        int(dhcp_h[ST_HIT]),
                                        missteer=missteer)
        tele.lap(tele.REPLY, t0, tok)
        self._probe(self._inflight)
        t0 = tele.t()
        if got:
            ring.complete(verdict, out_pkt_h, out_len_h, B)
        if held:
            self.newflows.retire_held(ring, *held, verdict, out_pkt_h,
                                      out_len_h)
        tele.lap(tele.TX, t0, tok)
        self._probe(self._inflight)

        t0 = tele.t()
        if violation_sink is not None:
            for lane in np.nonzero(viol)[0]:
                violation_sink(int(lane),
                               bytes(pkt[lane, : int(length[lane])]))
        if mir is not None and self.mirror_sink is not None:
            t1 = tele.t()
            mirw = np.asarray(mir)
            tele.fetched(t1, mir, tok=tok)
            for lane in np.nonzero((mirw != 0) & real)[0]:
                # interception observes the ORIGINAL ring bytes even on
                # lanes the verdict demux above dropped (Engine parity)
                self.mirror_sink(int(lane),
                                 bytes(pkt[lane, : int(length[lane])]),
                                 int(mirw[lane]))
        # slow drain, lane-aligned with the PASS lanes complete() queued;
        # the punted lanes' (frame, ring flags, lane) are served in one
        # batch after the walk (built at the first punt)
        punted = None
        for lane in np.nonzero((verdict == VERDICT_PASS) & real)[0]:
            got_f = ring.slow_pop()
            if got_f is None:
                break  # slow ring overflowed during complete()
            frame, fl = got_f
            if punt[lane]:
                if punted is None:
                    punted = []
                punted.append((frame, fl, int(lane)))
                continue
            try:
                if slow_path is not None:
                    reply = slow_path(frame)
                    if reply is not None:
                        t1 = tele.t()
                        ring.tx_inject(reply, from_access=(fl & 0x1) != 0)
                        tele.lap(tele.TX, t1, tok)
            except Exception as e:  # noqa: BLE001 — slow path is untrusted input
                self._slow_error(int(lane), e)
        if punted is not None:
            # the creates on the owner shards; a refused flow's frame is a
            # counted drop (newflows.stats)
            frames, fls, lanes = zip(*punted)
            self.newflows.punt_many(
                frames, fls, int(now_s), self.pppoe is not None,
                on_error=lambda i, e: self._slow_error(lanes[i], e))
        tele.lap(tele.SLOW, t0, tok)
        self._probe(self._inflight)
        tele.end_batch(tok)
        return got

    def _slow_error(self, lane: int, e: Exception) -> None:
        self.stats["slow_errors"] += 1
        self._slow_err_log.report(e, path="ring", lane=lane)

    def _fold_stats(self, **deltas) -> None:
        for k, v in deltas.items():
            if v is None:
                continue
            acc = self.stats.get(k)
            if acc is None:
                self.stats[k] = np.asarray(v, dtype=np.uint64).copy()
            else:
                acc += np.asarray(v, dtype=np.uint64)

    def _frame_affinity_owner(self, frame: bytes, flags: int) -> int | None:
        """Affinity owner shard of a frame's chip-local state, or None
        when no shard owns it (DHCP control, PPPoE control, non-IPv4,
        return traffic for an unregistered public IP — all of which any
        shard's slow path answers authoritatively). Mirrors the ring
        steering spec (runtime/ring.py shard_of / bngring.h): upstream
        by FNV-1a32(src IP), PPPoE session DATA by the inner src IP,
        downstream by NAT public-IP ownership."""
        from bng_tpu.runtime.ring import FLAG_DHCP_CTRL, FLAG_FROM_ACCESS
        from bng_tpu.utils.net import fnv1a32

        if (flags & FLAG_DHCP_CTRL) or len(frame) < 14:
            return None
        off = 12
        et = (frame[off] << 8) | frame[off + 1]
        for _ in range(2):
            if et not in (0x8100, 0x88A8):
                break
            off += 4
            if len(frame) < off + 2:
                return None
            et = (frame[off] << 8) | frame[off + 1]
        off += 2  # L3 start
        if et == 0x0800 and len(frame) >= off + 20 and (frame[off] >> 4) == 4:
            if flags & FLAG_FROM_ACCESS:
                return fnv1a32(frame[off + 12 : off + 16]) % self.n
            dst = int.from_bytes(frame[off + 16 : off + 20], "big")
            if self._pub_owner_cache is None:
                self._pub_owner_cache = self.pub_ip_map()
            return self._pub_owner_cache.get(dst)
        if (et == 0x8864 and (flags & FLAG_FROM_ACCESS)
                and len(frame) >= off + 8 + 20
                and frame[off] == 0x11 and frame[off + 1] == 0
                and ((frame[off + 6] << 8) | frame[off + 7]) == 0x0021
                and (frame[off + 8] >> 4) == 4):
            return fnv1a32(frame[off + 8 + 12 : off + 8 + 16]) % self.n
        return None

    def step(self, pkt: np.ndarray, length: np.ndarray, from_access: np.ndarray,
             now_s: int, now_us: int):
        """One sharded pipeline step.

        pkt: [N*b, L] uint8 (shard i's lanes at rows i*b..(i+1)*b).
        Returns (verdict, out_pkt, out_len, stats tuple...) — batch-sharded
        outputs are fetched to host.
        """
        from bng_tpu.ops.dhcp import ST_HIT

        tok = tele.begin_batch(tele.LANE_SHARDED, len(length))
        try:
            out = self._dispatch_fused(pkt, length, from_access, now_s,
                                       now_us)
        except BaseException:
            tele.cancel_batch(tok)
            raise
        tele.device_up(tok)
        (verdict, out_pkt, out_len, _new_tables, dhcp_stats, nat_stats,
         qos_stats, spoof_stats, nat_punt, viol, *tails) = out
        tails = list(tails)
        garden_stats = [tails.pop(0)] if self.garden is not None else []
        pppoe_stats = [tails.pop(0)] if self.pppoe is not None else []
        edge_out = list(tails[:2]) if self.edge is not None else []
        t0 = tele.t()
        tf = tele.ready(verdict, tok)  # armed: the wait, then the reads
        verdict_h = np.asarray(verdict)
        res = {
            "verdict": verdict_h,
            "out_pkt": out_pkt,
            "out_len": np.asarray(out_len),
            "dhcp_stats": np.asarray(dhcp_stats),
            "nat_stats": np.asarray(nat_stats),
            "qos_stats": np.asarray(qos_stats),
            "spoof_stats": np.asarray(spoof_stats),
            "nat_punt": np.asarray(nat_punt),
            "violation": np.asarray(viol),
            **({"garden_stats": np.asarray(garden_stats[0])}
               if garden_stats else {}),
            **({"pppoe_stats": np.asarray(pppoe_stats[0])}
               if pppoe_stats else {}),
            **({"mirror": np.asarray(edge_out[0]),
                "edge_stats": np.asarray(edge_out[1])}
               if edge_out else {}),
        }
        tele.fetched(tf, verdict, out_len, dhcp_stats, nat_stats, qos_stats,
                     spoof_stats, nat_punt, viol, *garden_stats,
                     *pppoe_stats, *edge_out, tok=tok)
        tele.lap(tele.DEVICE_WAIT, t0, tok)
        self.telemetry.record_fused(
            length, res["verdict"], res["nat_punt"], res["violation"],
            int(res["dhcp_stats"][ST_HIT]))
        tele.end_batch(tok)
        return res

    # ---- serving-path operations (quiesce / checkpoint / swap / expiry) --

    def quiesce(self) -> int:
        """Drain barrier for the sharded serving loop: retire any
        in-flight pipelined window, then block until the mesh table
        state has materialized — after this no scatter is in flight, so
        a checkpoint or swap can read host/device state without
        interleaving with an update (Engine.quiesce parity). Returns
        frames retired. Callers that hold a ring's slow queue must
        flush through process_ring/flush_pipeline with handlers first."""
        n = self.flush_pipeline()
        if self.tables is not None:
            jax.block_until_ready(jax.tree_util.tree_leaves(self.tables))
        return n

    def resync_tables(self) -> None:
        """Full re-upload of every shard's host tables (the bulk-build /
        post-restore heal path — Engine.resync_tables parity). Resets
        device-authoritative words; fold first when they matter."""
        self.sync_tables()

    def fetch_session_vals(self, shard: int) -> np.ndarray:
        """One shard's device-authoritative NAT session rows (counters +
        last_seen): that shard's own piece of the mesh-stacked array, read
        from the chip that holds it and nothing of the others'."""
        vals = self.tables.nat.sessions.vals
        piece = next(p for p in vals.addressable_shards
                     if (p.index[0].start or 0) == shard)
        return np.asarray(piece.data)[0]

    def fold_device_authoritative(self) -> None:
        """Pull the device-WRITTEN words back into every shard's host
        mirrors (NAT session counters/last_seen, QoS token buckets) —
        the pre-checkpoint fetch, per shard. Engine parity including the
        uploaded-mask discipline: host rows the bounded drain has not
        shipped yet stay authoritative. Call behind quiesce()."""
        from bng_tpu.ops.qtable import (QW_FLAGS, QW_LAST_US, QW_TOKENS,
                                        way_rows)
        from bng_tpu.runtime.engine import Engine

        if self.tables is None:
            return
        sess_dev = np.asarray(self.tables.nat.sessions.vals)
        nbuckets = self.qos[0].geom.nbuckets
        qos_up_dev = way_rows(self.tables.qos_up.rows, nbuckets)
        qos_down_dev = way_rows(self.tables.qos_down.rows, nbuckets)
        for i in range(self.n):
            sessions = self.nat[i].sessions
            mask = Engine._uploaded_mask(sessions,
                                         sessions.used.astype(bool))
            sessions.vals[mask] = sess_dev[i][mask]
            for host, dev_rows in ((self.qos[i].up, qos_up_dev[i]),
                                   (self.qos[i].down, qos_down_dev[i])):
                live = Engine._uploaded_mask(
                    host, (host.rows[:, QW_FLAGS] & 1) != 0)
                host.rows[live, QW_TOKENS] = dev_rows[live, QW_TOKENS]
                host.rows[live, QW_LAST_US] = dev_rows[live, QW_LAST_US]

    def expire(self, now: int) -> int:
        """NAT session expiry sweep against each shard's device-
        authoritative last-seen words (Engine.expire per shard)."""
        total = 0
        for i in range(self.n):
            dev = (self.fetch_session_vals(i)
                   if self.tables is not None else None)
            total += self.nat[i].expire_sessions(int(now), device_vals=dev)
        return total

    def pending_dirty(self) -> int:
        """Dirty slots across every shard's drained host mirror — 0
        means the mesh device chain is current (Engine.pending_dirty
        parity; the auditor's drain-completion test)."""
        return sum(t.dirty_count() for t in self._host_tables())

    def shard_components(self, i: int) -> dict:
        """One shard's host authorities, keyed the way the checkpoint
        codec names components (runtime/checkpoint.py sharded save /
        restore both walk this)."""
        out = {"fastpath": self.fastpath[i], "nat": self.nat[i],
               "qos": self.qos[i], "antispoof": self.spoof[i]}
        if self.garden is not None:
            out["garden"] = self.garden[i]
        if self.pppoe is not None:
            out["pppoe"] = self.pppoe[i]
        if self.edge is not None:
            out["edge"] = self.edge[i]
        return out

    def clone_empty(self, n_shards: int | None = None) -> "ShardedCluster":
        """A fresh, EMPTY cluster with identical per-shard geometry —
        the blue/green standby and the checkpoint re-shard target. Same
        n (default) reuses this cluster's mesh so the jit caches hit;
        a different n builds its own mesh."""
        kw = dict(self._ctor_kwargs)
        if n_shards is not None and n_shards != self.n:
            kw["n_shards"] = n_shards
            # per-shard public IPs regenerate for the new topology when
            # the original list was auto-derived (None); an explicit
            # list must still cover the new shard count
            if kw["public_ips"] is not None \
                    and len(kw["public_ips"]) < n_shards:
                raise ValueError(
                    f"cannot re-shard to {n_shards} shards: only "
                    f"{len(kw['public_ips'])} public IPs configured")
            return ShardedCluster(**kw)
        return ShardedCluster(mesh=self.mesh, **kw)

    def stats_summary(self) -> dict:
        """Aggregate serving counters for `bng run` stats() — the
        engine-stats analog of the sharded path."""
        from bng_tpu.ops.nat44 import NST_DNAT, NST_SNAT

        t = self.telemetry
        nat = self.stats.get("nat")
        return {
            "shards": self.n,
            "steps": t.steps,
            "frames": int(t.frames.sum()),
            "tx": int(t.verdicts[:, 2].sum()),
            "fwd": int(t.verdicts[:, 3].sum()),
            # lanes NAT translated on the chips (SNAT and DNAT hits)
            "nat_fwd": (int(nat[NST_SNAT]) + int(nat[NST_DNAT])
                        if nat is not None else 0),
            "dropped": int(t.verdicts[:, 1].sum()),
            # legit slow-path punts only — missteers are split out
            # (same accounting as snapshot()'s pass_total)
            "passed": int(t.verdicts[:, 0].sum() - t.missteers.sum()),
            "missteers": int(t.missteers.sum()),
            "nat_punts": int(t.nat_punts.sum()),
            "psum_dhcp_hits": t.psum_dhcp_hits,
            "slow_errors": int(self.stats.get("slow_errors", 0)),
        }


class ShardedFastPathSink:
    """FastPathTables WRITE facade over a ShardedCluster: the DHCP
    server, PoolManager and composition root mutate 'the fast path'
    through the one interface they already use, and every row lands on
    its owner shard (broadcast for pool/server config — those are
    replicated cluster-wide). The single-writer discipline is preserved:
    this object routes, the per-shard FastPathTables stay the authority,
    and deltas drain through each shard's bounded update batch.

    Accepts a cluster OR a zero-arg resolver returning one: long-lived
    holders (the DHCP server, built once at app construction) must pass
    a resolver reading the composition root's live reference, or a
    blue/green swap would strand every later write on the RETIRED
    cluster while the standby serves."""

    def __init__(self, cluster):
        self._cluster = cluster

    @property
    def cluster(self) -> ShardedCluster:
        c = self._cluster
        return c() if callable(c) else c

    # pool/server config is global: broadcast (add_pool_all discipline)
    def add_pool(self, *a, **kw) -> None:
        for fp in self.cluster.fastpath:
            fp.add_pool(*a, **kw)

    def remove_pool(self, pool_id: int) -> None:
        for fp in self.cluster.fastpath:
            fp.remove_pool(pool_id)

    def set_server_config(self, mac, ip: int) -> None:
        self.cluster.set_server_config_all(mac, ip)

    # subscriber rows route to their owner shard
    def add_subscriber(self, mac, **kw) -> None:
        self.cluster.add_subscriber(mac, **kw)

    def remove_subscriber(self, mac) -> bool:
        return self.cluster.remove_subscriber(mac)

    def add_vlan_subscriber(self, s_tag: int, c_tag: int, **kw) -> None:
        self.cluster.add_vlan_subscriber(s_tag, c_tag, **kw)

    def remove_vlan_subscriber(self, s_tag: int, c_tag: int) -> bool:
        return self.cluster.remove_vlan_subscriber(s_tag, c_tag)

    def add_circuit_id_subscriber(self, circuit_id: bytes, **kw) -> None:
        self.cluster.add_circuit_id_subscriber(circuit_id, **kw)

    def remove_circuit_id_subscriber(self, circuit_id: bytes) -> bool:
        return self.cluster.remove_circuit_id_subscriber(circuit_id)

    def touch_lease(self, mac, lease_expiry: int) -> bool:
        return self.cluster.touch_lease(mac, lease_expiry)

    def get_subscriber(self, mac):
        return self.cluster.get_subscriber(mac)
