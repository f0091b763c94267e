"""Composition root + CLI: run / demo / stats / version.

Parity: cmd/bng — cobra run/demo/stats/version (main.go:48-62,421-439),
flag surface + YAML overlay where CLI wins (main.go:195-419, loadConfigFile
main.go:1420-1457), secret-file resolution keeping secrets out of ps
(resolveSecret main.go:1567), runBNG construction order
loader->antispoof->walledgarden->pools->deviceauth->DHCP->Nexus->peer-pool
->HA->BGP/BFD->RADIUS->policy->QoS->NAT(+logger)->PPPoE->DHCPv6->SLAAC->
resilience->metrics with LIFO cleanup (main.go:441-1380), demo mode's
eBPF-free full-lifecycle simulation (demo.go:46-120).

The TPU twist: where runBNG loads XDP programs, run() builds the device
Engine (fused Pallas/jnp pipeline + HBM tables) and drives it from a
packet source; everything else stays host-side control plane. As of
round 5 the full construction order is wired: deviceauth (4a), Nexus
HTTPAllocator + resilience FSM (4b), peer pool (4c), RADIUS accounting
(7b), PPPoE with the device data path (10c), the CoA/Disconnect
listener (10d), TLS/mTLS on the cluster wire, and App.tick as the 1 Hz
maintenance heartbeat for every periodic goroutine of the reference.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time

__version__ = "0.1.0"


@dataclasses.dataclass
class BNGConfig:
    """Flattened flag surface (main.go:195-419 subset, grouped)."""

    # dataplane
    server_ip: str = "10.0.0.1"
    server_mac: str = "02:aa:bb:cc:dd:01"
    batch_size: int = 256
    # ICI-sharded serving path (parallel/sharded.py, ISSUE 12): >1 makes
    # `bng run` drive an N-shard ShardedCluster instead of the single-
    # device Engine — tables hash-sharded over the mesh, the ring
    # classifier steering popped batches to owner shards, checkpoints/
    # blue-green swap/chaos audit all sharded-aware. On a machine with
    # the mesh is the first N attached devices (fewer is an error); under
    # JAX_PLATFORMS=cpu it is CPU-virtual (forced host device count, the
    # tier-1 posture).
    # batch_size is the AGGREGATE batch (split evenly across shards).
    shards: int = 1
    # per-shard table geometry for the sharded path (buckets per cuckoo
    # table; sized for the per-shard subscriber slice)
    shard_nbuckets: int = 1 << 10
    # single-device table capacities, in entries (0 = the table class's
    # own default, which is what every test builds). Each becomes a
    # bucket count through ops.table.nbuckets_for (~50% load, 4-way,
    # power of two). The reference ships 1M-entry subscriber and QoS
    # maps (bpf/maps.h:10) and 4M NAT sessions (bpf/nat44.c:38-40).
    max_subscribers: int = 0  # subscriber/VLAN/circuit-ID, QoS, antispoof, garden
    max_nat_sessions: int = 0  # NAT session + reverse tables
    max_nat_subscribers: int = 0  # subscriber -> port-block table
    # latency-tiered scheduler (runtime/scheduler.py): express DHCP lane +
    # depth-pipelined bulk lane instead of the monolithic pipelined loop
    scheduler_enabled: bool = False
    sched_express_batch: int = 64
    sched_express_max_wait_us: float = 200.0
    # AOT express OFFER path (ISSUE 13): minimal-program lane compiled
    # ahead of time for the express batch geometry, replies patched
    # into preassembled wire templates host-side; a geometry miss falls
    # back to the jit full-program path loudly
    # (bng_express_aot_miss_total + flight-recorder note). Also
    # disabled via BNG_EXPRESS_AOT=0.
    sched_express_aot: bool = True
    sched_bulk_depth: int = 2
    sched_drain_every: int = 1
    # slow-path fleet (control/fleet.py + control/admission.py): N
    # shared-nothing workers sharded by the ring's MAC hash, with
    # admission control in front. workers=1 keeps the single in-process
    # slow path (every integration supported); >1 fans DHCPv4 out.
    slowpath_workers: int = 1
    slowpath_worker_mode: str = "process"  # process | inline
    slowpath_inbox: int = 512  # per-worker admission inbox bound
    slowpath_deadline_ms: float = 50.0  # stale-DISCOVER shed deadline
    slowpath_slice: int = 1024  # per-worker lease-slice target size
    # watermark-driven live fleet elasticity (control/opsctl.py
    # FleetAutoscaler -> SlowPathFleet.resize at the tick boundary)
    slowpath_autoscale: bool = False
    slowpath_min_workers: int = 1
    slowpath_max_workers: int = 8
    # runtime ops control listener (`bng ctl` wire, control/opsctl.py):
    # fleet resize / rolling restart / engine swap on the LIVE process.
    # OPT-IN ("" = disabled, the default): the endpoint is unauthenticated
    # and mutates subscriber-serving state, so even loopback exposure —
    # any local process could resize/swap a production dataplane — is a
    # deployment decision. Enable with --ctl-listen 127.0.0.1:9092.
    ctl_listen: str = ""
    # pools (single primary pool via flags; more via YAML `pools:`)
    pool_cidr: str = "10.0.0.0/16"
    pool_gateway: str = ""
    dns_primary: str = "1.1.1.1"
    dns_secondary: str = "8.8.8.8"
    lease_time: int = 3600
    # per-MAC deterministic lease-time spread in [lt, lt*(1+frac)] —
    # de-synchronizes the expiry cliff a mass bring-up would otherwise
    # schedule (storm suite: lease_expiry_avalanche; PERF_NOTES §10)
    lease_jitter_frac: float = 0.0
    # per-sweep lease-reap bound (DHCPServer.cleanup_expired max_reaps;
    # per WORKER when a fleet runs): one synchronized expiry cliff costs
    # ceil(cliff/batch) ticks instead of starving one dataplane tick.
    # 0 = unbounded (the pre-storm-suite behavior)
    expire_batch: int = 8192
    pools: list = dataclasses.field(default_factory=list)
    # RADIUS
    radius_server: str = ""
    radius_secret: str = ""
    radius_secret_file: str = ""
    # RADIUS accounting (pkg/radius/accounting.go role); active whenever a
    # radius server is configured. Spool path "" = in-memory only.
    acct_interim_interval: int = 300
    acct_spool_path: str = ""
    # CoA/Disconnect listener (RFC 5176; pkg/radius/coa.go role) — on by
    # default when a radius server is configured, like the reference
    coa_enabled: bool = True
    coa_listen: str = "0.0.0.0:3799"
    # PPPoE (pkg/pppoe; wired like main.go:1063-1180)
    pppoe_enabled: bool = False
    pppoe_ac_name: str = "bng-tpu"
    pppoe_service_name: str = ""
    pppoe_auth: str = "chap"  # chap | pap | none
    # local credentials (YAML `pppoe-users: [{username, password}]`);
    # ignored when RADIUS is configured (RADIUS wins, reference behavior)
    pppoe_users: list = dataclasses.field(default_factory=list)
    # NAT
    nat_enabled: bool = True
    nat_public_ips: list = dataclasses.field(default_factory=lambda: ["203.0.113.1"])
    nat_ports_per_subscriber: int = 1024
    nat_log_path: str = ""
    nat_log_format: str = "json"
    nat_bulk_logging: bool = False
    # QoS
    qos_enabled: bool = True
    default_policy: str = "residential-100mbps"
    # walled garden
    walled_garden_enabled: bool = True
    portal_ip: str = "10.255.255.1"
    portal_port: int = 8080
    # DNS wire (control/dns_wire.py): UDP listener serving the resolver,
    # forwarding cache misses upstream with failover
    dns_enabled: bool = False
    dns_listen: str = "0.0.0.0:53"
    dns_upstreams: list = dataclasses.field(
        default_factory=lambda: ["8.8.8.8:53", "1.1.1.1:53"])
    # central Nexus allocator (pkg/nexus HTTPAllocator; main.go:628-756):
    # DHCP allocation tries Nexus first, local pools as fallback; also
    # the health signal the resilience partition FSM watches
    nexus_url: str = ""
    # peer-to-peer shared pool (pkg/pool, Demo G): the agreed range plus
    # node-id -> cluster-URL map (YAML `peer-pool-nodes:
    # [{node: n1, url: "http://..."}]`); "" cidr = peer pool off
    peer_pool_cidr: str = ""
    peer_pool_nodes: list = dataclasses.field(default_factory=list)
    # device->Nexus identity (pkg/deviceauth): none | psk | mtls
    device_auth_method: str = "none"
    device_auth_psk: str = ""
    device_auth_psk_file: str = ""
    device_auth_cert: str = ""
    device_auth_key: str = ""
    # HA
    ha_role: str = ""  # "", "active", "standby"
    ha_peer: str = ""  # active's cluster URL (http://host:port) for standbys
    # clustering (control/cluster_http.py wire)
    cluster_listen: str = ""  # "host:port" ("" = no listener; port 0 = any)
    # cluster-wire TLS (pkg/ha/sync.go:151-185 role). Listener side:
    # cert+key -> the cluster listener speaks TLS; client-ca -> demands
    # verified client certs (mTLS). Client side (ha_peer/store_peers over
    # https): ca/pins verify the peer, client cert+key is our identity.
    cluster_tls_cert: str = ""
    cluster_tls_key: str = ""
    cluster_tls_client_ca: str = ""
    cluster_tls_ca: str = ""
    cluster_tls_pins: list = dataclasses.field(default_factory=list)
    cluster_tls_server_name: str = ""
    cluster_tls_client_cert: str = ""
    cluster_tls_client_key: str = ""
    store_mode: str = "memory"  # memory | read | write (control/crdt.py)
    store_peers: list = dataclasses.field(default_factory=list)  # peer URLs
    # BGP
    bgp_enabled: bool = False
    bgp_local_as: int = 65000
    bgp_router_id: str = ""
    # FRR wiring: when true, BGP commands run through real `vtysh -c`
    # subprocesses (main.go:884-940, bgp.go:554-578); default keeps the
    # inert executor so `run` works without FRR installed
    bgp_vtysh: bool = False
    bgp_vtysh_path: str = "vtysh"
    # routing platform: "stub" (in-memory) | "linux" (iproute2/netlink —
    # real kernel routes/rules; needs CAP_NET_ADMIN)
    routing_platform: str = "stub"
    # checkpoint/warm-restart (runtime/checkpoint.py +
    # control/statestore.py): dir set -> restore-at-start (cold-start
    # fallback on reject) + SIGTERM snapshot; interval > 0 adds the
    # background cadence off the 1 Hz tick
    checkpoint_dir: str = ""
    checkpoint_interval_s: float = 0.0
    checkpoint_keep: int = 3
    # telemetry (bng_tpu/telemetry): span tracing + per-batch flight
    # recorder. Off by default (disarmed hooks cost one global load per
    # call site); BNG_TELEMETRY=1 arms it too (the env is how fleet
    # worker processes inherit the setting).
    telemetry_enabled: bool = False
    trace_dir: str = ""  # "" -> $BNG_TRACE_DIR or <tmp>/bng-flightrec
    trace_budget_us: float = 0.0  # latency-excursion dump trigger; 0=off
    # SLO engine (bng_tpu/telemetry/slo.py): live burn-rate evaluation
    # of per-stage latency budgets over the armed tracer's histograms.
    # Active only when telemetry is armed (no tracer -> nothing to
    # evaluate); breach -> slo_breach flight dump + bng_slo_* families.
    slo_enabled: bool = True
    slo_window_s: float = 30.0  # burn-rate window length
    slo_burn_windows: int = 2  # consecutive bad windows before a breach
    # per-stage budget overrides, "stage:limit_us[:per]" (default:
    # telemetry/slo.py DEFAULT_SLOS — envelopes 1-2 orders above the
    # CPU-dev means, the paper's 50us target on the fenced device stage)
    slo_budgets: list = dataclasses.field(default_factory=list)
    # metrics
    metrics_port: int = 9090
    metrics_enabled: bool = True
    # dhcpv6 / slaac
    dhcpv6_enabled: bool = True
    dhcpv6_prefix: str = "2001:db8:1::/64"
    # reply-source for framed DHCPv6 ("" = EUI-64 link-local of
    # server_mac); set a global address when clients reach us via a relay
    dhcpv6_server_ip: str = ""
    slaac_enabled: bool = True
    # device IPv6 stage (ops/v6.py): a subscriber's IPv6 data frames are
    # bound (IA_NA /128), policed on its rate plan and forwarded on the
    # chip; its table is sized from max_subscribers. Off: an IPv6 frame
    # is judged by antispoof and left to the host
    ipv6_fastpath: bool = False
    # device qinq stage (ops/qinq.py): the access VLANs end on the chip. A
    # forwarded upstream frame leaves without its tags, a forwarded
    # downstream frame with its subscriber's S- and C-tag (in front of the
    # PPPoE header where it has a session); the pair table is sized from
    # max_subscribers and filled from leases and sessions that came up
    # over tags. Off: a frame keeps the tags it came with
    qinq_enabled: bool = False
    # device edge stage (bng_tpu/edge): a wholesale / open-access box. An
    # upstream data frame leaves with the L2 destination of the next hop
    # its subscriber's class elects among the routing manager's upstreams
    # (one route row a subscriber, sized from max_subscribers, written
    # when a lease commits), and a frame of a subscriber under an active
    # warrant is mirrored to the intercept manager's exporter (the tap
    # table holds edge.MAX_WARRANTS rows). Off: no stage, no tables
    edge_enabled: bool = False
    # wire (AF_XDP attach ladder; runtime/xsk.py)
    wire_if: str = ""  # NIC to bind AF_XDP on ("" = in-memory ring only)
    wire_queue: int = 0
    # wire pump implementation (runtime/xsk.py WirePump): "" resolves
    # BNG_WIRE_PUMP (default scalar); "vector" runs the batch-native
    # pump over the native batch verbs (ISSUE 15)
    wire_pump: str = ""
    synthetic_subs: int = 0  # >0: generate DISCOVER/data traffic (smoke)
    # logging (main.go:1398-1418 zap production config role)
    log_level: str = "info"
    log_format: str = "json"  # json | console
    # misc
    node_id: str = "bng0"


def _sized(entries: int, *bucket_args: str) -> dict:
    """Constructor kwargs that size each named bucket count for
    `entries` keys; {} (the table's own default) when the config left
    the capacity unset."""
    if not entries:
        return {}
    from bng_tpu.ops.table import nbuckets_for

    return dict.fromkeys(bucket_args, nbuckets_for(entries))


# What a shard's table takes over an even share of a cluster total: keys
# split by FNV-1a32 of the address, a binomial whose fullest of four
# shards sits 0.5% over its share at 250,000 a shard (six sigma) and
# exactly on it for a pool of consecutive addresses (measured, PERF.md
# section 4, PR 42); a thirty-second covers both with room, and moves
# `nbuckets_for`'s power of two only within 3% under a boundary.
SHARD_HEADROOM = 1 / 32


def _shard_sized(entries: int, shards: int, unset: int) -> int:
    """A shard's bucket count for a cluster-wide capacity of `entries`
    (`nbuckets_for` of its share with the hash's headroom), or `unset`
    when the config left the capacity unset."""
    if not entries:
        return unset
    from bng_tpu.ops.table import nbuckets_for

    return nbuckets_for(math.ceil(entries / shards * (1 + SHARD_HEADROOM)))


def pppoe_sid(sess) -> str:
    """One Acct-Session-Id format for a PPPoE session — shared by
    accounting start/stop, the CoA locator, and HA replication keys
    (drifting copies would strand sessions in the standby store)."""
    return f"pppoe-{sess.session_id:04x}-{sess.client_mac.hex()}"


def resolve_secret(value: str, file_path: str) -> str:
    """main.go:1567: prefer --*-file so secrets stay out of ps."""
    if file_path:
        with open(file_path) as f:
            return f.read().strip()
    return value


def load_config_file(path: str, cli_set: set[str],
                     base: BNGConfig) -> BNGConfig:
    """YAML overlay applied only to fields NOT set on the CLI
    (main.go:1420-1457: CLI wins)."""
    import yaml
    with open(path) as f:
        data = yaml.safe_load(f) or {}
    for key, value in data.items():
        key = key.replace("-", "_")
        if key in cli_set or not hasattr(base, key):
            continue
        setattr(base, key, value)
    return base


from bng_tpu.analysis.sanitize import ctx_enter as _sanitize_ctx_enter
from bng_tpu.analysis.sanitize import owned_by as _owned_by


@_owned_by("loop", guard="_ctl")
class BNGApp:
    """Everything `bng run` constructs, with LIFO cleanup
    (main.go:441-1380).

    Ownership (BNG_SANITIZE): app state belongs to the loop context;
    any other context (ctl handler, scrape, HA sync) must hold `_ctl`
    to mutate — the @owned_by stamp makes a dropped `with self._ctl`
    an OwnershipViolation in sanitizer runs instead of a silent race."""

    def __init__(self, config: BNGConfig, clock=time.time):
        self.config = config
        self.clock = clock
        self._cleanup = []
        self._last_sync = 0.0
        self._last_expire = 0.0
        self._last_garden = 0.0
        self._last_acct_sync = 0.0
        self._last_acct_retry = 0.0
        # serializes CoA-listener-thread actions against the main loop's
        # slow path + maintenance sweeps (lease dict, QoS tables, demux
        # pending queue) — the goroutine-with-mutex role of the reference
        import threading as _threading

        self._ctl = _threading.Lock()
        self._syn_i = 0
        # sharded serving: the per-beat slow-path handler (demux or the
        # DHCP server) — the cluster takes it per call, unlike the
        # engine which owns a reference
        self._slow_path = None
        self.components: dict[str, object] = {}
        try:
            self._build()
        except BaseException:
            # a half-built app leaks live resources (listener threads,
            # bound sockets, AF_XDP attachments): run the LIFO cleanup for
            # whatever was already wired before re-raising
            self.close()
            raise

    def _on_close(self, fn) -> None:
        self._cleanup.append(fn)

    def _build(self) -> None:
        import ipaddress

        from bng_tpu.utils import structlog

        structlog.setup(self.config.log_level, self.config.log_format)
        self.log = structlog.get_logger("app", node_id=self.config.node_id)

        # 0. telemetry — armed FIRST so every later construction step
        # (fleet spawn exports BNG_TELEMETRY to workers; engine/scheduler
        # spans) sees the armed tracer. Metrics attach at step 13.
        import os as _os

        if self.config.telemetry_enabled or _os.environ.get(
                "BNG_TELEMETRY") == "1":
            from bng_tpu.telemetry import (FlightRecorder, RecorderConfig,
                                           spans as tele_spans)

            recorder = FlightRecorder(RecorderConfig(
                latency_budget_us=self.config.trace_budget_us,
                out_dir=self.config.trace_dir))
            tracer = self.components["telemetry"] = tele_spans.arm(
                tele_spans.Tracer(recorder=recorder))
            self._on_close(tele_spans.disarm)
            self.log.info("telemetry armed",
                          trace_dir=recorder.cfg.out_dir or "(default)",
                          budget_us=self.config.trace_budget_us)
            if self.config.slo_enabled:
                # the SLO engine rides the armed tracer: rolling
                # burn-rate windows over the stage histograms, ticked
                # by the 1 Hz heartbeat; breach -> slo_breach flight
                # dump + bng_slo_* (collect_slo at step 13)
                from bng_tpu.telemetry import slo as slo_mod

                budgets = (slo_mod.parse_budgets(
                    list(self.config.slo_budgets))
                    if self.config.slo_budgets else slo_mod.DEFAULT_SLOS)
                self.components["slo"] = slo_mod.SLOMonitor(
                    tracer, slos=budgets,
                    window_s=self.config.slo_window_s,
                    burn_windows=self.config.slo_burn_windows)
                self.log.info("slo monitor armed",
                              window_s=self.config.slo_window_s,
                              burn_windows=self.config.slo_burn_windows,
                              budgets=len(budgets))

        from bng_tpu.control import walledgarden as wg
        from bng_tpu.control.dhcp_server import DHCPServer
        from bng_tpu.control.metrics import BNGMetrics, MetricsCollector
        from bng_tpu.control.nat import NATManager
        from bng_tpu.control.nat_logging import (NATComplianceLogger,
                                                 NATLoggerConfig)
        from bng_tpu.control.nexus import NexusClient
        from bng_tpu.control.pool import Pool, PoolManager
        from bng_tpu.control.radius.policy import PolicyManager
        from bng_tpu.control.subscriber import SubscriberManager
        from bng_tpu.runtime.engine import AntispoofTables, Engine, QoSTables
        from bng_tpu.runtime.tables import FastPathTables
        from bng_tpu.utils.net import ip_to_u32, parse_mac

        cfg = self.config
        c = self.components

        # 1. device tables (the Loader.Load role, main.go:498-506).
        # --shards N promotes the ICI-sharded dataplane to the serving
        # path (ISSUE 12): an N-shard ShardedCluster replaces the
        # single-device Engine, and every fast-path write routes to its
        # owner shard through the ShardedFastPathSink facade. Features
        # whose wiring is engine-specific degrade with a warning
        # (tracked in sharded_blockers, exported like fleet_blockers).
        self.sharded_blockers: list[str] = []
        if cfg.shards > 1 and _os.environ.get(
                "JAX_PLATFORMS", "").lower() == "cpu":
            # asked for the CPU (tier-1 posture): force the host-device
            # mesh BEFORE any backend init (XLA_FLAGS
            # --xla_force_host_platform_device_count). Otherwise the
            # mesh is the attached devices, and fewer than N is an error
            # (parallel/sharded.py make_mesh).
            from bng_tpu.utils.jaxenv import force_cpu

            force_cpu(cfg.shards)
        # persistent compile cache, before the first compile and after
        # the CPU mesh is forced (the helper initialises the backend)
        from bng_tpu.utils.jaxenv import enable_compilation_cache

        cache_dir = enable_compilation_cache()
        if cache_dir:
            self.log.info("compile cache", dir=cache_dir)
        if cfg.shards > 1:
            from bng_tpu.parallel.sharded import (ShardedCluster,
                                                  ShardedFastPathSink)

            self.sharded_blockers = [name for flag, name in (
                (cfg.scheduler_enabled, "scheduler"),
                (cfg.pppoe_enabled, "pppoe"),
                (cfg.ipv6_fastpath, "ipv6-fastpath"),
                (cfg.qinq_enabled, "qinq"),
                (cfg.edge_enabled, "edge"),
                (cfg.wire_if, "wire"),
                (cfg.slowpath_workers > 1, "slowpath-fleet")) if flag]
            if self.sharded_blockers:
                # same posture as the fleet blockers: the sharded path
                # serves, the engine-specific feature degrades LOUDLY
                self.log.warning(
                    "sharded serving: engine-specific features disabled",
                    blockers=self.sharded_blockers, shards=cfg.shards)
            pub_ips = [ip_to_u32(ip) for ip in cfg.nat_public_ips]
            while len(pub_ips) < cfg.shards:
                # each shard must own its public IPs exclusively
                # (downstream ring steering is by-IP): extend the
                # configured block consecutively
                pub_ips.append((pub_ips[-1] + 1) if pub_ips
                               else ip_to_u32("203.0.113.1") + len(pub_ips))
            cluster = c["cluster"] = ShardedCluster(
                cfg.shards,
                batch_per_shard=max(8, cfg.batch_size // cfg.shards),
                sub_nbuckets=cfg.shard_nbuckets,
                vlan_nbuckets=max(64, cfg.shard_nbuckets // 4),
                cid_nbuckets=max(64, cfg.shard_nbuckets // 4),
                # --max-nat-sessions / --max-nat-subscribers are cluster
                # totals, as on one chip; unset, today's sizes stand
                nat_sessions_nbuckets=_shard_sized(
                    cfg.max_nat_sessions, cfg.shards, cfg.shard_nbuckets),
                nat_sub_nbuckets=_shard_sized(
                    cfg.max_nat_subscribers, cfg.shards, 256),
                qos_nbuckets=cfg.shard_nbuckets,
                spoof_nbuckets=cfg.shard_nbuckets,
                public_ips=pub_ips,
                garden_enabled=cfg.walled_garden_enabled,
                server_mac=parse_mac(cfg.server_mac))
            # resolver, NOT the object: a blue/green swap replaces
            # c["cluster"] and every later DHCP/pool write must follow
            # the flip to the serving cluster
            fastpath = c["fastpath_sink"] = ShardedFastPathSink(
                lambda: c["cluster"])
            self.log.info("sharded cluster built", shards=cfg.shards,
                          batch_per_shard=cluster.b,
                          nbuckets=cfg.shard_nbuckets,
                          nat_sessions_nbuckets=cluster.geom.nat.sessions.nbuckets,
                          nat_sub_nbuckets=cluster.geom.nat.sub_nat.nbuckets,
                          public_ips_per_shard=[len(m.public_ips)
                                                for m in cluster.nat])
        else:
            fastpath = c["fastpath"] = FastPathTables(**_sized(
                cfg.max_subscribers,
                "sub_nbuckets", "vlan_nbuckets", "cid_nbuckets"))
        fastpath.set_server_config(
            parse_mac(cfg.server_mac),
            ip_to_u32(cfg.server_ip))

        # 2. antispoof + walled garden (main.go:509-564)
        c["antispoof"] = AntispoofTables(
            **_sized(cfg.max_subscribers, "nbuckets"))
        if cfg.walled_garden_enabled:
            garden = c["walledgarden"] = wg.WalledGardenManager(
                wg.WalledGardenConfig(portal_ip=cfg.portal_ip,
                                      portal_port=cfg.portal_port),
                clock=self.clock)
            self._on_close(lambda: garden.check_expired())

        # 2b. DNS wire (pkg/dns role, now with a real socket): UDP listener
        # serving the resolver; walled-garden subscribers get the portal
        # for every name, everyone else forwards upstream with failover
        if cfg.dns_enabled:
            from bng_tpu.control.dns import DNSConfig, Resolver
            from bng_tpu.control.dns_wire import DNSServer, UDPForwarder

            dns_cfg = DNSConfig(upstreams=list(cfg.dns_upstreams),
                                walled_garden_redirect_ip=cfg.portal_ip)
            resolver = c["dns_resolver"] = Resolver(
                dns_cfg, forwarder=UDPForwarder(dns_cfg.upstreams,
                                                timeout=dns_cfg.timeout))
            host, _, port = cfg.dns_listen.partition(":")
            dns_srv = c["dns_server"] = DNSServer(
                resolver, host=host or "0.0.0.0", port=int(port or 53))
            dns_srv.start()
            self._on_close(dns_srv.stop)
            self.log.info("dns listener", addr=f"{dns_srv.addr[0]}:"
                                               f"{dns_srv.addr[1]}")

        # 3. pools (main.go:567-594)
        pool_mgr = c["pools"] = PoolManager(fastpath_tables=fastpath)
        pool_specs = cfg.pools or [{
            "cidr": cfg.pool_cidr, "gateway": cfg.pool_gateway,
            "lease_time": cfg.lease_time}]
        for i, spec in enumerate(pool_specs, start=1):
            if isinstance(spec, str):  # --pools 10.1.0.0/24 (CLI shorthand)
                spec = {"cidr": spec}
            net = ipaddress.ip_network(spec["cidr"])
            gw = spec.get("gateway") or str(net.network_address + 1)
            pool_mgr.add_pool(Pool(
                pool_id=i, network=int(net.network_address),
                prefix_len=net.prefixlen, gateway=ip_to_u32(gw),
                dns_primary=ip_to_u32(spec.get("dns_primary", cfg.dns_primary)),
                dns_secondary=ip_to_u32(spec.get("dns_secondary",
                                                 cfg.dns_secondary)),
                lease_time=int(spec.get("lease_time", cfg.lease_time)),
                client_class=int(spec.get("client_class", 0))))

        # 4. Nexus + subscriber orchestration (main.go:628-756 role)
        c["nexus"] = NexusClient(node_id=cfg.node_id, clock=self.clock)
        c["subscribers"] = SubscriberManager(clock=self.clock)

        # 4a. device identity for the Nexus wire (pkg/deviceauth;
        # main.go's deviceauth construction slot)
        if cfg.device_auth_method != "none":
            from bng_tpu.control import deviceauth as da

            if cfg.device_auth_method == "psk":
                c["deviceauth"] = da.PSKAuthenticator(
                    psk=cfg.device_auth_psk,
                    psk_file=cfg.device_auth_psk_file)
            elif cfg.device_auth_method == "mtls":
                c["deviceauth"] = da.MTLSAuthenticator(
                    cert_file=cfg.device_auth_cert,
                    key_file=cfg.device_auth_key)
            else:
                raise ValueError(
                    f"device_auth_method={cfg.device_auth_method!r}: "
                    f"expected 'none', 'psk' or 'mtls'")

        # 4b. central allocator client + partition resilience. The
        # adapter narrows HTTPAllocator's ip-string API to the DHCP
        # server's int contract, and goes straight to the local pool
        # while partitioned (one timeout per DISCOVER would melt the
        # slow path — the resilience FSM owns retry cadence instead).
        nexus_alloc = None
        resilience = None
        if cfg.nexus_url:
            from bng_tpu.control.cluster_http import http_nexus_transport
            from bng_tpu.control.nexus import HTTPAllocator
            from bng_tpu.control.resilience import ResilienceManager

            nexus_tls = (self._cluster_client_tls()
                         if cfg.nexus_url.startswith("https") else None)
            nexus_http = c["nexus_allocator"] = HTTPAllocator(
                cfg.nexus_url,
                http_nexus_transport(cfg.nexus_url, tls=nexus_tls),
                node_id=cfg.node_id)
            resilience = c["resilience"] = ResilienceManager(
                nexus_healthy=nexus_http.health_check)

            class _NexusAlloc:
                def allocate(self, owner):
                    if resilience.partitioned:
                        return None  # local-pool fallback, no timeout
                    try:
                        ip = nexus_http.allocate(owner)
                    except Exception:
                        return None
                    return ip_to_u32(ip) if ip else None

                def release(self, owner):
                    if resilience.partitioned:
                        return  # no 3s timeout per expired lease during
                        # an outage; heal-time reconciliation covers it
                    try:
                        nexus_http.release(owner)
                    except Exception:
                        pass

            nexus_alloc = _NexusAlloc()

        # 4c. peer-to-peer shared pool (pkg/pool/peer.go; Demo G):
        # HRW owner-or-forward over the cluster HTTP wire
        if cfg.peer_pool_cidr and cfg.peer_pool_nodes:
            from bng_tpu.control.cluster_http import HTTPPeerProxy
            from bng_tpu.control.peerpool import PeerPool, PoolRange

            net = ipaddress.ip_network(cfg.peer_pool_cidr)
            node_urls = {str(n["node"]): str(n["url"])
                         for n in cfg.peer_pool_nodes}
            if cfg.node_id not in node_urls:
                raise ValueError(
                    f"peer_pool_nodes must include this node "
                    f"({cfg.node_id!r}): peers agree on one member list")

            # proxies built ONCE per node: each would otherwise rebuild
            # its TLS context (cert/CA file reads) per forwarded request
            peer_proxies: dict[str, object] = {}

            def _peer_transport(node, _urls=node_urls):
                proxy = peer_proxies.get(node)
                if proxy is None:
                    url = _urls.get(node)
                    if url is None:
                        raise ConnectionError(f"unknown peer {node}")
                    proxy = peer_proxies[node] = HTTPPeerProxy(
                        url, tls=(self._cluster_client_tls()
                                  if url.startswith("https") else None))
                return proxy

            # PeerPool allocates network+1+idx (it skips the network
            # address itself): pass the RAW base, usable = hosts only
            c["peerpool"] = PeerPool(
                cfg.node_id, sorted(node_urls),
                PoolRange(network=int(net.network_address),
                          size=max(net.num_addresses - 2, 1)),
                transport=_peer_transport)
            self.log.info("peer pool", nodes=sorted(node_urls),
                          cidr=cfg.peer_pool_cidr)

        # 5. RADIUS (main.go:946-973)
        authenticator = None
        radius_server_cfgs: list = []  # picklable, reused by the fleet
        if cfg.radius_server:
            from bng_tpu.control.radius.client import (RadiusClient,
                                                       RadiusServerConfig)
            secret = resolve_secret(cfg.radius_secret, cfg.radius_secret_file)
            host, _, port = cfg.radius_server.partition(":")
            radius_server_cfgs = [RadiusServerConfig(
                host=host, auth_port=int(port or 1812),
                secret=secret.encode())]
            radius = c["radius"] = RadiusClient(servers=radius_server_cfgs)

            def authenticator(username="", password="", mac=b"",
                              circuit_id=b"", **kw):
                res = radius.authenticate(username, password, mac=mac,
                                          circuit_id=circuit_id)
                key = username or mac.hex()
                if res is None:
                    # every server timed out: degraded auth from the
                    # cached profile (radius_handler.go:134-489 role) —
                    # an outage must not evict paying subscribers
                    if resilience is not None:
                        cached = resilience.radius_handler.degraded_auth(
                            key, self.clock())
                        if cached is not None:
                            return {"qos_policy": cached.policy_name,
                                    "framed_ip": cached.framed_ip}
                    return None
                if not res.success:
                    return None  # a real REJECT is never served from cache
                if resilience is not None:
                    from bng_tpu.control.resilience import CachedProfile

                    resilience.radius_handler.cache_profile(CachedProfile(
                        username=key, policy_name=res.policy_name,
                        framed_ip=res.framed_ip, cached_at=self.clock()))
                # keys DHCPServer._request actually consumes: qos_policy
                # (Filter-Id -> policy, server.go:774-794 role) and
                # lease_time (Session-Timeout caps the lease)
                profile = {"qos_policy": res.policy_name,
                           "framed_ip": res.framed_ip,
                           **res.attributes}
                if res.session_timeout:
                    profile["lease_time"] = res.session_timeout
                return profile

        # 6. QoS (main.go:977-995)
        qos = None if cfg.shards > 1 else QoSTables(
            **_sized(cfg.max_subscribers, "nbuckets"))
        if qos is not None:
            c["qos"] = qos
        policies = c["policies"] = PolicyManager()
        qos_hook = None
        if cfg.qos_enabled:
            if cfg.shards > 1:
                # owner-shard routing: the policy row lands on the
                # subscriber's affinity shard (the only shard the ring
                # ever steers its traffic to)
                def qos_hook(ip, policy_name):
                    p = policies.get(policy_name or cfg.default_policy)
                    if p is not None:
                        c["cluster"].set_qos(
                            ip, down_bps=p.download_bps,
                            up_bps=p.upload_bps, priority=p.priority)
            else:
                def qos_hook(ip, policy_name):
                    p = policies.get(policy_name or cfg.default_policy)
                    if p is not None:
                        qos.set_subscriber(ip, p.download_bps, p.upload_bps,
                                           priority=p.priority)

        # 7. NAT + compliance logger (main.go:1000-1060). Sharded: NAT
        # state is chip-local per shard inside the cluster (subscriber-
        # affinity placement); the hook routes allocations to the owner.
        # The per-event compliance logger is engine-wiring and degrades
        # (documented in README "Sharded serving").
        nat = None
        nat_hook = None
        if cfg.shards > 1:
            if cfg.nat_enabled:
                def nat_hook(ip, now):
                    c["cluster"].allocate_nat(ip, int(now))
        elif cfg.nat_enabled:
            nat_logger = c["nat_logger"] = NATComplianceLogger(
                NATLoggerConfig(file_path=cfg.nat_log_path,
                                fmt=cfg.nat_log_format,
                                bulk_logging=cfg.nat_bulk_logging),
                clock=self.clock)
            self._on_close(nat_logger.close)
            nat = c["nat"] = NATManager(
                public_ips=[ip_to_u32(ip) for ip in cfg.nat_public_ips],
                ports_per_subscriber=cfg.nat_ports_per_subscriber,
                log_sink=nat_logger.log_device_event,
                **_sized(cfg.max_nat_sessions, "sessions_nbuckets"),
                **_sized(cfg.max_nat_subscribers, "sub_nat_nbuckets"))
            def nat_hook(ip, now):
                nat.allocate_nat(ip, int(now))
        else:
            # NAT off: the engine still threads a NAT table set, so it
            # gets the smallest one — no capacity applies to it
            nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                             sessions_nbuckets=256, sub_nat_nbuckets=64)

        # 7b. RADIUS accounting (accounting.go:410-497 role): start/stop
        # ride the DHCP lease lifecycle; interim/retry fire from App.tick.
        # Installed BEFORE the garden wiring so its hook chain (9b)
        # preserves accounting.
        acct = None
        if "radius" in c:
            from bng_tpu.control.radius.accounting import AccountingManager
            acct = c["accounting"] = AccountingManager(
                c["radius"],
                interim_interval_s=cfg.acct_interim_interval,
                spool_path=cfg.acct_spool_path or None,
                clock=self.clock)

        # 8. DHCP server, wired like main.go:642 + SetXxx hooks
        dhcp = c["dhcp"] = DHCPServer(
            server_mac=parse_mac(cfg.server_mac),
            server_ip=ip_to_u32(cfg.server_ip),
            pool_manager=pool_mgr, fastpath_tables=fastpath,
            allocator=nexus_alloc,
            authenticator=authenticator, qos_hook=qos_hook,
            nat_hook=nat_hook, clock=self.clock,
            lease_jitter_frac=cfg.lease_jitter_frac)
        if resilience is not None:
            # heal-time reconciliation (manager.go:342-528): the central
            # store answers who owns each partition-allocated IP, and the
            # loser of a conflict gets force-renumbered (its lease is
            # expired so the client re-DORAs onto a fresh address)
            from bng_tpu.utils.net import mac_to_u64, u32_to_ip

            def _central_lookup(ip_u32, _nx=c["nexus_allocator"]):
                try:
                    return _nx.lookup_by_ip(u32_to_ip(ip_u32))
                except Exception:
                    return None  # unreachable mid-heal: no verdict

            def _renumber(subscriber_id, _dhcp=dhcp):
                try:
                    mac = bytes.fromhex(subscriber_id)
                except ValueError:
                    return False
                lease = _dhcp.leases.get(mac_to_u64(mac))
                if lease is None:
                    return False
                lease.expiry = 0
                _dhcp.cleanup_expired(1)  # reaps only the forced lease
                return True

            resilience.central_lookup = _central_lookup
            resilience.renumber = _renumber
            # partition-time allocations feed the conflict detector so
            # heal-time reconciliation can renumber losers
            prev_res_acct = dhcp.accounting_hook

            def _res_lease(event, lease, sid, _res=resilience):
                if prev_res_acct is not None:
                    prev_res_acct(event, lease, sid)
                if event == "start":
                    _res.record_allocation(lease.mac.hex(), lease.ip,
                                           self.clock())

            dhcp.accounting_hook = _res_lease
        if acct is not None:
            from bng_tpu.utils.net import u32_to_ip as _u32ip

            prev_acct_hook = dhcp.accounting_hook  # chain (resilience 8a)

            def _acct_lease(event, lease, sid, _acct=acct):
                if prev_acct_hook is not None:
                    prev_acct_hook(event, lease, sid)
                if event == "start":
                    _acct.start(sid, username=lease.username
                                or _u32ip(lease.ip), framed_ip=lease.ip,
                                mac="-".join(f"{b:02X}" for b in lease.mac))
                elif event == "stop":
                    _acct.stop(sid)  # renew extends, it never stops

            dhcp.accounting_hook = _acct_lease

        # 9. engine: the TPU dataplane replacing the XDP attach. The
        # device-side garden gate compiles in only when the walled garden
        # is enabled (a disabled feature must cost zero per batch).
        garden_tables = None
        if cfg.walled_garden_enabled and cfg.shards <= 1:
            from bng_tpu.runtime.engine import GardenTables

            garden_tables = GardenTables(
                **_sized(cfg.max_subscribers, "nbuckets"))
        pppoe_tables = None
        if cfg.pppoe_enabled and cfg.shards <= 1:
            from bng_tpu.runtime.tables import PPPoEFastPathTables

            from bng_tpu.control.pppoe.server import PPPoEServerConfig

            # sized for the access concentrator's whole session space
            # (16-bit ids: the server below takes the same ceiling)
            pppoe_tables = c["pppoe_tables"] = PPPoEFastPathTables(
                **_sized(PPPoEServerConfig.max_sessions, "nbuckets"),
                server_mac=parse_mac(cfg.server_mac))
        v6_tables = None
        if cfg.ipv6_fastpath and cfg.shards <= 1:
            from bng_tpu.runtime.tables import V6FastPathTables

            v6_tables = c["v6_tables"] = V6FastPathTables(
                c["antispoof"], **_sized(cfg.max_subscribers, "nbuckets"))
        qinq_tables = None
        if cfg.qinq_enabled and cfg.shards <= 1:
            from bng_tpu.runtime.tables import QinQFastPathTables

            qinq_tables = c["qinq_tables"] = QinQFastPathTables(
                **_sized(cfg.max_subscribers, "nbuckets"))
            # a lease's pair reaches the table where the lease's other
            # rows are written: the in-process server's (`qinq` is a fleet
            # blocker below, so no worker ever owns a lease book here)
            dhcp.qinq = qinq_tables
        edge_tables = None
        if cfg.edge_enabled and cfg.shards <= 1:
            from bng_tpu.control.intercept import InterceptManager
            from bng_tpu.edge import (MAX_WARRANTS, EdgeTables,
                                      InterceptTapProgram, MirrorPump)

            # a route row a subscriber, a tap row a warrant: two sizes
            edge_tables = c["edge_tables"] = EdgeTables(
                **_sized(cfg.max_subscribers, "route_nbuckets"),
                **_sized(MAX_WARRANTS, "tap_nbuckets"))
            c["intercept"] = InterceptManager(clock=self.clock)
            c["tap_program"] = InterceptTapProgram(
                edge_tables, c["intercept"], clock=self.clock)
            # the retire's sink: mirrored lanes -> record_cc -> the
            # exporter of the warrant's delivery method
            c["mirror_pump"] = MirrorPump(c["tap_program"])
        if cfg.shards > 1:
            # the cluster IS the dataplane: drive_once feeds its steered
            # ring loop; the slow path is attached per beat (10b)
            self._slow_path = dhcp.handle_frame
        else:
            c["engine"] = Engine(
                fastpath=fastpath, nat=nat, qos=qos,
                antispoof=c["antispoof"],
                garden=garden_tables, pppoe=pppoe_tables, v6=v6_tables,
                qinq=qinq_tables, edge=edge_tables,
                mirror_sink=c.get("mirror_pump"),
                batch_size=cfg.batch_size, slow_path=dhcp.handle_frame,
                clock=self.clock)
            self.log.info("engine built", batch_size=cfg.batch_size,
                          nat=cfg.nat_enabled, qos=cfg.qos_enabled)
        if "telemetry" in c:
            import jax as _jax

            # flight records must name the backend that actually served
            # them — the gray-failure flag (a CPU fallback must never
            # read as a TPU run)
            c["telemetry"].recorder.set_backend(_jax.default_backend())

        # 9a. latency-tiered scheduler over the engine's two programs
        # (express DHCP / depth-pipelined bulk) — opt-in; drive_once then
        # feeds it frame-wise instead of the monolithic pipelined step
        if cfg.scheduler_enabled and cfg.shards <= 1:
            from bng_tpu.runtime.scheduler import (SchedulerConfig,
                                                   TieredScheduler)

            c["scheduler"] = TieredScheduler(c["engine"], SchedulerConfig(
                express_batch=cfg.sched_express_batch,
                express_max_wait_us=cfg.sched_express_max_wait_us,
                express_aot=cfg.sched_express_aot,
                bulk_batch=cfg.batch_size,
                bulk_depth=cfg.sched_bulk_depth,
                drain_every=cfg.sched_drain_every), clock=self.clock)
            self._on_close(c["scheduler"].close)
            self.log.info("scheduler built",
                          express_batch=cfg.sched_express_batch,
                          express_aot=cfg.sched_express_aot,
                          bulk_depth=cfg.sched_bulk_depth)

        # 9b. walled-garden enforcement sync. One MAC-state feed drives
        # BOTH enforcement points: the DEVICE gate (engine.garden — a
        # pre-auth subscriber's data traffic drops on-chip; beyond the
        # reference, whose garden maps reach no bpf program) and, when
        # enabled, the DNS resolver's per-client portal answers
        # (resolver.go:150-157 role). A MAC's garden state maps to its
        # lease IP at each garden transition AND each lease event (grant
        # applies the current state — covers garden-before-DHCP; stop
        # scrubs the IP so a reassigned address inherits nothing).
        if cfg.walled_garden_enabled:
            from bng_tpu.control.walledgarden import SubscriberState
            from bng_tpu.utils.net import u32_to_ip

            garden = c["walledgarden"]
            if cfg.shards > 1:
                # owner-shard routing facade: membership lands on the
                # subscriber's affinity shard, allowed destinations are
                # policy (replicated to every shard). Resolves the live
                # cluster per call so garden writes follow a swap.
                class _ShardedGardenGate:
                    def __init__(self, resolve):
                        self._resolve = resolve

                    def set_gardened(self, ip, gardened):
                        self._resolve().set_gardened(ip, gardened)

                    def allow_destination(self, ip, port=0, proto=0):
                        self._resolve().allow_garden_destination(
                            ip, port, proto)

                gt = _ShardedGardenGate(lambda: c["cluster"])
            else:
                gt = c["engine"].garden
            resolver = c.get("dns_resolver")
            # allowed destinations (manager.go:95-103): the portal on ANY
            # TCP port (the DNS-redirect flow lands on the original URL's
            # port 80/443, not just the portal's own listener) and every
            # DNS server a gardened client could plausibly query — the
            # addresses DHCP actually advertises (global + per-pool) plus
            # the garden config's allowlist; a gardened client whose
            # resolver the gate drops could never even reach the portal.
            gt.allow_destination(ip_to_u32(cfg.portal_ip), 0, 6)
            dns_ips = {cfg.dns_primary, cfg.dns_secondary,
                       *garden.config.allowed_dns}
            for spec in pool_specs:
                if isinstance(spec, dict):
                    dns_ips |= {spec.get("dns_primary", ""),
                                spec.get("dns_secondary", "")}
            for d in sorted(d for d in dns_ips if d):
                gt.allow_destination(ip_to_u32(d), 53, 0)

            def _apply_garden_ip(state, ip_u32, _resolver=resolver, _gt=gt):
                # DEVICE gate: only EXPLICIT garden membership drops
                # on-chip — UNKNOWN (never registered) stays unenforced,
                # or a default-on garden would drop every data packet of
                # subscribers the portal flow never touched.
                # DNS resolver: keeps the manager's own stricter contract
                # (everything non-PROVISIONED is gardened, UNKNOWN
                # included) — portal answers are harmless-if-wrong in the
                # way a device drop is not, and the reference's resolver
                # behaves this way (resolver.go:150-157).
                _gt.set_gardened(ip_u32, state in (
                    SubscriberState.WALLED_GARDEN, SubscriberState.BLOCKED))
                if _resolver is not None:
                    ip = u32_to_ip(ip_u32)
                    if state == SubscriberState.PROVISIONED:
                        _resolver.remove_walled_garden_client(ip)
                    else:
                        _resolver.add_walled_garden_client(ip)

            def _garden_sync(mac_u64, state, _dhcp=dhcp):
                lease = _dhcp.leases.get(mac_u64)
                if lease is not None:
                    _apply_garden_ip(state, lease.ip)

            garden.on_state_change(_garden_sync)

            prev_acct = dhcp.accounting_hook

            def _lease_sync(event, lease, sid, _garden=garden,
                            _resolver=resolver, _gt=gt):
                if prev_acct is not None:
                    prev_acct(event, lease, sid)
                if event in ("start", "renew"):
                    _apply_garden_ip(_garden.get_subscriber_state(lease.mac),
                                     lease.ip)
                elif event == "stop":
                    _gt.set_gardened(lease.ip, False)
                    if _resolver is not None:
                        _resolver.remove_walled_garden_client(
                            u32_to_ip(lease.ip))

            dhcp.accounting_hook = _lease_sync

        # 10. DHCPv6 + SLAAC (main.go:1063-1180)
        if cfg.dhcpv6_enabled:
            from bng_tpu.control.dhcpv6.server import (AddressPool6,
                                                       DHCPv6Server,
                                                       DHCPv6ServerConfig)
            server_ip6 = b""
            if cfg.dhcpv6_server_ip:
                server_ip6 = ipaddress.IPv6Address(
                    cfg.dhcpv6_server_ip).packed
            c["dhcpv6"] = DHCPv6Server(
                DHCPv6ServerConfig(server_mac=parse_mac(cfg.server_mac),
                                   server_ip6=server_ip6),
                address_pool=AddressPool6(cfg.dhcpv6_prefix,
                                          cfg.lease_time, cfg.lease_time * 2),
                clock=self.clock)
        if cfg.slaac_enabled:
            from bng_tpu.control.slaac import SLAACConfig, SLAACServer
            c["slaac"] = SLAACServer(SLAACConfig())

        # 10c. PPPoE server (pkg/pppoe; main.go:1063-1180 construction
        # role). Negotiation is host-side via PASS lanes; OPEN sessions
        # publish to the device tables (10's pppoe_tables) so DATA frames
        # decap/encap in the fused pipeline.
        if cfg.pppoe_enabled:
            from bng_tpu.control.pppoe.auth import (LocalVerifier,
                                                    RadiusVerifier)
            from bng_tpu.control.pppoe.codec import PROTO_CHAP, PROTO_PAP
            from bng_tpu.control.pppoe.server import (PPPoEServer,
                                                      PPPoEServerConfig)

            if "radius" in c:
                verifier = RadiusVerifier(c["radius"])
            else:
                creds = {}
                for u in cfg.pppoe_users:
                    if isinstance(u, dict):
                        creds[str(u["username"])] = str(u["password"]).encode()
                verifier = LocalVerifier(creds)
            auth_proto = {"chap": PROTO_CHAP, "pap": PROTO_PAP,
                          "none": 0}.get(cfg.pppoe_auth)
            if auth_proto is None:
                raise ValueError(f"pppoe_auth={cfg.pppoe_auth!r}: "
                                 f"expected 'chap', 'pap' or 'none'")

            def _pppoe_alloc(username, mac, _pools=pool_mgr):
                pool = _pools.classify(0)
                if pool is None:
                    return None
                try:
                    return pool.allocate(f"pppoe:{mac.hex()}")
                except Exception:
                    return None  # exhaustion -> Service-Unavailable PADT

            def _pppoe_release(ip, mac, _pools=pool_mgr):
                pool = _pools.pool_for_ip(ip)
                if pool is not None:
                    pool.release(ip)

            def _pppoe_open(sess, _acct=acct):
                # a RADIUS Framed-IP-Address bypasses _pppoe_alloc
                # (server.py _start_network prefers it); reserve it in the
                # owning pool or DHCP could hand the same address out.
                # allocate_specific is idempotent for the same owner, so
                # pool-allocated sessions cost one no-op re-claim.
                pool = pool_mgr.pool_for_ip(sess.assigned_ip)
                if pool is not None:
                    pool.allocate_specific(sess.assigned_ip,
                                           f"pppoe:{sess.client_mac.hex()}")
                pppoe_tables.session_up(sess)
                if qinq_tables is not None and len(sess.vlans) == 2:
                    qinq_tables.bind(sess.assigned_ip, *sess.vlans)
                if cfg.qos_enabled:
                    qos_hook(sess.assigned_ip,
                             sess.radius_attributes.get("qos_policy"))
                if cfg.nat_enabled:
                    nat.allocate_nat(sess.assigned_ip, int(self.clock()))
                if _acct is not None:
                    _acct.start(pppoe_sid(sess), username=sess.username,
                                framed_ip=sess.assigned_ip,
                                mac="-".join(f"{b:02X}"
                                             for b in sess.client_mac))

            def _pppoe_close(event, _acct=acct):
                sess = event.session
                pppoe_tables.session_down(event)
                if qinq_tables is not None and sess.assigned_ip:
                    qinq_tables.unbind(sess.assigned_ip)
                if cfg.qos_enabled and sess.assigned_ip:
                    qos.remove_subscriber(sess.assigned_ip)
                if cfg.nat_enabled and sess.assigned_ip:
                    nat.release_nat(sess.assigned_ip, int(self.clock()))
                if _acct is not None:
                    _acct.stop(pppoe_sid(sess))

            c["pppoe"] = PPPoEServer(
                PPPoEServerConfig(
                    ac_name=cfg.pppoe_ac_name,
                    service_name=cfg.pppoe_service_name,
                    server_mac=parse_mac(cfg.server_mac),
                    our_ip=ip_to_u32(cfg.server_ip),
                    dns_primary=ip_to_u32(cfg.dns_primary),
                    dns_secondary=ip_to_u32(cfg.dns_secondary),
                    auth_proto=auth_proto),
                verifier, _pppoe_alloc, release_ip=_pppoe_release,
                on_open=_pppoe_open, on_close=_pppoe_close)
            self.log.info("pppoe server", ac_name=cfg.pppoe_ac_name,
                          auth=cfg.pppoe_auth,
                          backend="radius" if "radius" in c else "local")

        # 10b. slow-path demux: the reference runs one socket+goroutine
        # per protocol server; here every PASSed frame lands on the ring's
        # one slow queue, so the engine's slow_path becomes a dispatcher
        # over whatever servers are enabled (v4 handled even alone)
        if cfg.dhcpv6_enabled or cfg.slaac_enabled or cfg.pppoe_enabled:
            from bng_tpu.control.slowpath import SlowPathDemux

            demux = c["slowpath"] = SlowPathDemux(
                dhcp=dhcp, dhcpv6=c.get("dhcpv6"), slaac=c.get("slaac"),
                pppoe=c.get("pppoe"), clock=self.clock)
            if cfg.shards > 1:
                self._slow_path = demux
            else:
                c["engine"].slow_path = demux
            if v6_tables is not None and "dhcpv6" in c:
                # an IA_NA lease reaches the device tables as a DHCPv4
                # lease reaches the fast-path cache. A Lease6 carries a
                # DUID and no MAC: the MAC is the requesting frame's,
                # which the demux has in hand (None for a relayed
                # message: the relay's MAC is not the subscriber's)
                from bng_tpu.ops.dhcp import AV_IP

                def _v6_lease(lease, _demux=demux, _fp=fastpath):
                    mac = _demux.dhcpv6_requester
                    if lease.is_pd or mac is None:
                        return
                    sub = _fp.get_subscriber(mac)
                    v6_tables.bind(mac, lease.address,
                                   ipv4=int(sub[AV_IP]) if sub is not None
                                   else 0)

                def _v6_release(lease):
                    if not lease.is_pd:
                        v6_tables.unbind(lease.address)

                c["dhcpv6"].on_lease = _v6_lease
                c["dhcpv6"].on_release = _v6_release

        # 10b2. slow-path fleet: shard DHCPv4 across N shared-nothing
        # workers (control/fleet.py). Workers own per-worker lease
        # slices carved from the parent pools and relay table writes
        # back through the single-writer drain; non-DHCPv4 slow frames
        # (v6/SLAAC/PPPoE) stay on the parent demux via the fallback.
        # Integrations that live on the parent's per-lease state
        # (PPPoE) are not yet fleet-aware: with any of them configured
        # the fleet is skipped so no integration silently degrades.
        # Fleet-aware and OFF the blocker list: `ha` (worker lease
        # events relay through the active's syncer push), `radius`
        # (per-worker RadiusClient on the MAC steering hash — ISSUE 19,
        # accounting start/stop riding the same lease-event relay, CoA
        # routed to the owning shard), `peer-pool` (parent-side only:
        # it mounts on the cluster HTTP server and health-checks in
        # tick — it never sits in the DHCP allocation path), and
        # `nexus` (ISSUE 20: each shard allocates against the shared
        # store through its own HTTPAllocator + partition FSM — lease
        # authority is per-MAC, and MAC steering makes that per-shard).
        self.fleet_blockers: list[str] = []
        if cfg.slowpath_workers > 1:
            blockers = [name for flag, name in (
                (cfg.pppoe_enabled, "pppoe"),
                (cfg.ipv6_fastpath, "ipv6-fastpath"),
                (cfg.qinq_enabled, "qinq"),
                (cfg.edge_enabled, "edge"),
                (cfg.shards > 1, "sharded")) if flag]
            if blockers:
                # more than a log line: the degradation is exported as
                # bng_slowpath_fleet_blocked (step 13), surfaced in the
                # `bng run` startup status and stats() — a capacity
                # config that silently collapsed to 1 worker is how
                # overload pages happen (blockers documented in README)
                self.fleet_blockers = blockers
                self.log.warning(
                    "slowpath fleet disabled: per-lease integrations "
                    "not yet fleet-aware", blockers=blockers,
                    workers=cfg.slowpath_workers)
            else:
                from bng_tpu.control.admission import AdmissionConfig
                from bng_tpu.control.fleet import FleetSpec, SlowPathFleet
                from bng_tpu.control.ha import SessionState as _HAState

                def _fleet_ha_lease(event, lease, sid, _c=c, _acct=acct):
                    # late-bound: HA (step 11) builds AFTER the fleet,
                    # so the hook reads c["ha"] at event time. Worker
                    # lease events ride the drained TableEventLog into
                    # this single-writer seam — push_change here is the
                    # fleet-side twin of the parent _ha_lease closure,
                    # and accounting start/stop the _acct_lease twin
                    # (octets stay device-authoritative: the tick bridge
                    # folds NAT counters by framed_ip, which is disjoint
                    # per shard, so per-shard folding is exact).
                    if _acct is not None:
                        from bng_tpu.utils.net import u32_to_ip as _uip
                        if event == "start":
                            _acct.start(
                                sid, username=lease.get("username")
                                or _uip(lease["ip"]),
                                framed_ip=lease["ip"],
                                mac="-".join(
                                    f"{b:02X}" for b in
                                    bytes.fromhex(lease["mac"])))
                        elif event == "stop":
                            _acct.stop(sid)
                    ha_sync = _c.get("ha")
                    if ha_sync is None or not hasattr(ha_sync,
                                                      "push_change"):
                        return
                    if event == "stop":
                        ha_sync.push_change(None, session_id=sid)
                    else:  # start / renew both RE-push (expiry tracks)
                        ha_sync.push_change(_HAState(
                            session_id=sid, mac=lease["mac"],
                            ip=lease["ip"], pool_id=lease["pool_id"],
                            username=lease.get("username") or "",
                            lease_expiry=float(lease["expiry"]),
                            qos_policy=lease.get("qos_policy") or "",
                            session_kind="ipoe",
                            updated_at=self.clock()))

                fallback = c.get("slowpath") or dhcp.handle_frame
                fspec = FleetSpec.from_pool_manager(
                    parse_mac(cfg.server_mac), ip_to_u32(cfg.server_ip),
                    pool_mgr, slice_size=cfg.slowpath_slice,
                    low_watermark=max(1, cfg.slowpath_slice // 4))
                if radius_server_cfgs:
                    # per-worker RADIUS sockets on the MAC steering
                    # hash (ISSUE 19): auth affinity = DHCP affinity
                    fspec.radius_servers = list(radius_server_cfgs)
                    fspec.radius_nas_id = cfg.node_id or "bng-tpu"
                    fspec.radius_nas_ip = ip_to_u32(cfg.server_ip)
                if cfg.nexus_url:
                    # per-worker Nexus allocators (ISSUE 20): lease
                    # authority through the shared store, one client +
                    # partition FSM per shard
                    fspec.nexus_url = cfg.nexus_url
                    fspec.nexus_node_id = cfg.node_id or "bng-tpu"
                    if cfg.nexus_url.startswith("https"):
                        fspec.nexus_tls = self._cluster_client_tls()
                fleet = c["fleet"] = SlowPathFleet(
                    fspec,
                    n_workers=cfg.slowpath_workers, pools=pool_mgr,
                    mode=cfg.slowpath_worker_mode,
                    admission=AdmissionConfig(
                        inbox_capacity=cfg.slowpath_inbox,
                        deadline_ms=cfg.slowpath_deadline_ms),
                    table_sink=fastpath, qos_hook=qos_hook,
                    nat_hook=nat_hook, lease_hook=_fleet_ha_lease,
                    fallback=fallback, clock=self.clock)
                c["engine"].slow_path_batch = fleet.handle_batch
                self._on_close(fleet.close)
                self.log.info("slowpath fleet up",
                              workers=cfg.slowpath_workers,
                              mode=cfg.slowpath_worker_mode,
                              inbox=cfg.slowpath_inbox)

        # 10d. CoA/Disconnect listener (RFC 5176; coa.go:119-240 +
        # coa_handler.go:175-460): dynamic authorization reaches BOTH
        # session kinds — DHCP leases (policy -> device QoS; disconnect
        # force-expires the lease) and PPPoE sessions (disconnect runs
        # the LCP/PADT teardown, frames ride the demux pending queue to
        # the wire).
        if cfg.radius_server and cfg.coa_enabled:
            from bng_tpu.control.radius.coa import CoAProcessor, CoAServer
            from bng_tpu.utils.net import mac_to_u64

            pppoe_srv = c.get("pppoe")
            # fleet-aware CoA (ISSUE 19): DHCPv4 leases live in the
            # workers when the fleet serves — the locators probe the
            # parent books first (PPPoE and non-fleet leases), then
            # route to the owning shard on the same MAC steering hash
            # (relay counted by the fleet when missteered)
            fleet_coa = c.get("fleet")

            def _find_by_ip(ip):
                for lease in dhcp.leases.values():
                    if lease.ip == ip:
                        return ("dhcp", lease)
                if pppoe_srv is not None:
                    for s in pppoe_srv.sessions.all():
                        if s.assigned_ip == ip:
                            return ("pppoe", s)
                if fleet_coa is not None:
                    r = fleet_coa.handle_coa("locate", ip=ip)
                    if r["found"]:
                        return ("fleet", r)
                return None

            def _find_by_sid(sid):
                for lease in dhcp.leases.values():
                    if lease.session_id == sid:
                        return ("dhcp", lease)
                if pppoe_srv is not None and sid.startswith("pppoe-"):
                    # inverse of pppoe_sid() — keep in lockstep
                    try:
                        num = int(sid.split("-")[1], 16)
                    except (IndexError, ValueError):
                        return None
                    s = pppoe_srv.sessions.get(num)
                    if s is not None:
                        return ("pppoe", s)
                if fleet_coa is not None and not sid.startswith("pppoe-"):
                    r = fleet_coa.handle_coa("locate", session_id=sid)
                    if r["found"]:
                        return ("fleet", r)
                return None

            def _find_by_mac(mac_str):
                try:
                    mac = bytes.fromhex(mac_str.replace("-", "")
                                        .replace(":", ""))
                except ValueError:
                    return None
                lease = dhcp.leases.get(mac_to_u64(mac))
                if lease is not None:
                    return ("dhcp", lease)
                if pppoe_srv is not None:
                    for s in pppoe_srv.sessions.all():
                        if s.client_mac == mac:
                            return ("pppoe", s)
                if fleet_coa is not None:
                    r = fleet_coa.handle_coa("locate", mac=mac)
                    if r["found"]:
                        return ("fleet", r)
                return None

            def _coa_qos(ip, policy_name):
                if qos_hook is None:
                    return False  # QoS disabled: a CoA rate change NAKs
                qos_hook(ip, policy_name)  # processor pre-validates name
                # record the new plan on the lease and re-push through
                # the hook chain so HA replication (and any other
                # lease-state consumer) sees the change — else failover
                # restores the PRE-CoA policy
                lease = next((l for l in dhcp.leases.values()
                              if l.ip == ip), None)
                if lease is not None:
                    lease.qos_policy = policy_name
                    if dhcp.accounting_hook is not None:
                        dhcp.accounting_hook("renew", lease,
                                             lease.session_id)
                elif fleet_coa is not None:
                    # the owning shard mutates its own lease; the renew
                    # event rides the drained relay into HA/accounting
                    fleet_coa.handle_coa("qos", ip=ip,
                                         policy_name=policy_name)
                return True

            def _coa_disconnect(handle):
                kind, obj = handle
                if kind == "dhcp":
                    obj.expiry = 0
                    dhcp.cleanup_expired(1)  # reaps only the forced lease
                    return True
                if kind == "fleet":
                    r = fleet_coa.handle_coa("disconnect", ip=obj["ip"])
                    return bool(r["found"])
                from bng_tpu.control.pppoe.session import TerminateCause

                frames = pppoe_srv.terminate(
                    obj.session_id, TerminateCause.ADMIN_RESET,
                    now=self.clock())
                if "slowpath" in c:
                    # PADT/LCP teardown frames ride the demux pending
                    # queue; drive_once injects them on the TX ring
                    c["slowpath"].requeue(frames)
                return True

            class _CoASession:  # adapt (kind, obj) to processor's .ip read
                pass

            def _wrap(found):
                if found is None:
                    return None
                kind, obj = found
                h = _CoASession()
                h.kind, h.obj = kind, obj
                if kind == "dhcp":
                    h.ip = obj.ip
                elif kind == "fleet":
                    h.ip = obj["ip"]
                else:
                    h.ip = obj.assigned_ip
                return h

            def _locked(fn):
                def run(*a):
                    with self._ctl:
                        return fn(*a)
                return run

            proc = CoAProcessor(
                find_by_session_id=_locked(lambda sid: _wrap(_find_by_sid(sid))),
                find_by_ip=_locked(lambda ip: _wrap(_find_by_ip(ip))),
                find_by_mac=_locked(lambda m: _wrap(_find_by_mac(m))),
                qos_update=_locked(_coa_qos),
                disconnect=_locked(
                    lambda h: _coa_disconnect((h.kind, h.obj))),
                policy_manager=policies)
            host, _, port = cfg.coa_listen.rpartition(":")
            coa = c["coa"] = CoAServer(
                resolve_secret(cfg.radius_secret,
                               cfg.radius_secret_file).encode(),
                proc, bind=(host or "0.0.0.0", int(port or 3799)))
            coa.start()
            self._on_close(coa.stop)
            self.log.info("coa listener", addr=f"{coa.addr[0]}:{coa.addr[1]}")

        # 11. HA pair (main.go:759-881)
        if cfg.ha_role:
            from bng_tpu.control.ha import (ActiveSyncer, InMemorySessionStore,
                                            Role, SessionState, StandbySyncer)
            store = c["ha_store"] = InMemorySessionStore()
            if cfg.ha_role == "active":
                ha_sync = c["ha"] = ActiveSyncer(store)
                self.log.info("ha role active")
                c["ha_role"] = Role.ACTIVE

                # feed the syncer from BOTH session lifecycles (the
                # reference integrates HASyncer with its servers —
                # sync.go:456 PushChange callers): without this the pair
                # replicates an always-empty store.
                def _nat_fields(ip):
                    blk = nat.blocks.get(ip) if cfg.nat_enabled else None
                    if blk is None:
                        return {}
                    return {"nat_public_ip": blk["public_ip"],
                            "nat_port_start": blk["port_start"],
                            "nat_port_end": blk["port_end"]}

                prev_ha_hook = dhcp.accounting_hook

                def _ha_lease(event, lease, sid, _ha=ha_sync):
                    if prev_ha_hook is not None:
                        prev_ha_hook(event, lease, sid)
                    if event in ("start", "renew"):
                        # renewals RE-push: the standby's lease_expiry
                        # must track extensions or failover treats a
                        # live subscriber as long-expired
                        _ha.push_change(SessionState(
                            session_id=sid, mac=lease.mac.hex(),
                            ip=lease.ip, pool_id=lease.pool_id,
                            circuit_id=lease.circuit_id.hex(),
                            username=lease.username,
                            lease_expiry=float(lease.expiry),
                            s_tag=lease.s_tag, c_tag=lease.c_tag,
                            qos_policy=lease.qos_policy,
                            session_kind="ipoe",
                            updated_at=self.clock(),
                            **_nat_fields(lease.ip)))
                    elif event == "stop":
                        _ha.push_change(None, session_id=sid)

                dhcp.accounting_hook = _ha_lease

                if "pppoe" in c:
                    pppoe_srv2 = c["pppoe"]
                    prev_po, prev_pc = pppoe_srv2.on_open, pppoe_srv2.on_close

                    def _ha_pppoe_open(sess, _ha=ha_sync):
                        if prev_po is not None:
                            prev_po(sess)
                        _ha.push_change(SessionState(
                            session_id=pppoe_sid(sess),
                            mac=sess.client_mac.hex(),
                            ip=sess.assigned_ip,
                            username=sess.username,
                            session_kind="pppoe",
                            updated_at=self.clock(),
                            **_nat_fields(sess.assigned_ip)))

                    def _ha_pppoe_close(event, _ha=ha_sync):
                        if prev_pc is not None:
                            prev_pc(event)
                        _ha.push_change(None,
                                        session_id=pppoe_sid(event.session))

                    pppoe_srv2.on_open = _ha_pppoe_open
                    pppoe_srv2.on_close = _ha_pppoe_close
            else:
                if cfg.ha_peer.startswith("http"):
                    # real wire: full sync + SSE deltas from the active's
                    # cluster listener (control/cluster_http.py)
                    from bng_tpu.control.cluster_http import HTTPActiveProxy

                    def _peer():
                        return HTTPActiveProxy(
                            cfg.ha_peer,
                            on_stream_end=lambda: c["ha"].disconnect(),
                            tls=self._cluster_client_tls())
                else:
                    def _peer():
                        raise ConnectionError(
                            f"HA peer unreachable: {cfg.ha_peer}")
                c["ha"] = StandbySyncer(store, transport=_peer)
                self.log.info("ha role standby", peer=cfg.ha_peer)
                c["ha_role"] = Role.STANDBY

        # 11b. replicated store + cluster listener (pkg/nexus CLSet modes)
        if cfg.store_mode != "memory" or cfg.store_peers:
            from bng_tpu.control.crdt import DistributedStore
            from bng_tpu.control.cluster_http import HTTPStorePeer

            cstore = c["cluster_store"] = DistributedStore(
                cfg.node_id, mode=cfg.store_mode, clock=self.clock)
            for url in cfg.store_peers:
                cstore.add_peer(HTTPStorePeer(
                    url, tls=(self._cluster_client_tls()
                              if url.startswith("https") else None)))
        if cfg.cluster_listen:
            from bng_tpu.control.cluster_http import ClusterServer

            server_tls = None
            if cfg.cluster_tls_cert or cfg.cluster_tls_key:
                from bng_tpu.control.ztp_tls import ServerTLSConfig

                server_tls = ServerTLSConfig(
                    cert_file=cfg.cluster_tls_cert,
                    key_file=cfg.cluster_tls_key,
                    client_ca_file=cfg.cluster_tls_client_ca)
            host, _, port = cfg.cluster_listen.rpartition(":")
            srv = ClusterServer(host or "127.0.0.1", int(port or 0),
                                tls=server_tls)
            if cfg.ha_role == "active":
                srv.mount_ha(c["ha"])
            if "cluster_store" in c:
                srv.mount_store(c["cluster_store"])
            if "peerpool" in c:
                srv.mount_pool(c["peerpool"])
            c["cluster_server"] = srv.start()
            self.log.info("cluster listener up", url=srv.url,
                          ha=bool(srv.ha), store=srv.store is not None)
            self._on_close(srv.close)

        # 11c. the wire: packet ring + AF_XDP attach ladder (the XDP-attach
        # role, loader.go:294-315). Always build the ring when a wire or
        # synthetic source is requested; the attach mode is whatever rung
        # the environment supports (zerocopy -> copy -> in-memory).
        if cfg.shards > 1 and (cfg.wire_if or cfg.synthetic_subs):
            # sharded serving ring: built BY the cluster so the steering
            # tables (NAT public-IP ownership, owner-shard hash) are
            # registered — shard i's batch region holds shard i's
            # subscribers and the common case never punts. AF_XDP attach
            # is an engine-path feature for now (sharded_blockers).
            # sized from the batch: a shard's region of one window must
            # fit the ring's depth, and two windows stay in flight (the
            # default 1024/4096 refused --batch-size 8192 over 4 shards)
            b = c["cluster"].b
            depth = max(1024, 1 << (b - 1).bit_length())
            nframes = max(4096, 1 << (4 * depth * cfg.shards - 1).bit_length())
            ring = c["ring"] = c["cluster"].make_ring(
                nframes=nframes, frame_size=2048, depth=depth)
            self.log.info("sharded ring built", ring=type(ring).__name__,
                          depth=depth, nframes=nframes)
            self._on_close(ring.close)
            self._on_close(lambda: c["cluster"].flush_pipeline(
                self._slow_path))
        elif cfg.wire_if or cfg.synthetic_subs:
            from bng_tpu.runtime import xsk as xsk_mod
            from bng_tpu.runtime.ring import make_ring

            # the tiered scheduler consumes frames via rx_pop (two lanes
            # retire out of dispatch order — the native ring's FIFO
            # assemble..complete contract can't express that), so prefer
            # the Python ring when the scheduler owns the loop. A real
            # wire attach needs the native UMEM, which wins: forcing a
            # PyRing would silently downgrade the NIC to in-memory mode,
            # so with wire_if set the ring stays native and drive_once
            # falls back to the pipelined engine loop (warned there).
            if cfg.wire_if and "scheduler" in c:
                self.log.warning(
                    "scheduler enabled with a wire interface: native ring "
                    "required for AF_XDP, scheduler will be bypassed in "
                    "the drive loop")
            ring = c["ring"] = make_ring(
                frame_size=2048,
                prefer_native=bool(cfg.wire_if) or "scheduler" not in c)
            att = xsk_mod.open_wire(ring, ifname=cfg.wire_if,
                                    queue=cfg.wire_queue,
                                    pump_path=cfg.wire_pump or None)
            c["wire_attachment"] = att
            self.log.info("wire attach", mode=att.mode,
                          ring=type(ring).__name__,
                          interface=cfg.wire_if or "(none)",
                          detail=att.detail)
            if cfg.wire_if and att.mode == xsk_mod.MODE_MEMORY:
                # a REQUESTED NIC landed on the memory rung: the ring
                # keeps serving, so every counter looks healthy while
                # zero packets touch the wire — dump the flight ring
                # (TRIG_WIRE_FALLBACK) and say it loudly; the
                # bng_wire_rung gauge pins it for dashboards
                from bng_tpu.telemetry import recorder as rec_mod
                from bng_tpu.telemetry import spans as tele_sp

                self.log.warning(
                    "wire attach FELL BACK to the memory rung — this is "
                    "NOT wire serving", interface=cfg.wire_if,
                    detail=att.detail)
                tele_sp.trigger(rec_mod.TRIG_WIRE_FALLBACK,
                                f"requested {cfg.wire_if!r} landed on the "
                                f"memory rung: {att.detail}")
            if att.xsk is not None:
                # an AF_XDP socket only RECEIVES via an xskmap redirect
                # program; load ours through the kernel verifier. TX works
                # without it, so a missing CAP_BPF degrades (logged), it
                # does not abort the attach ladder.
                from bng_tpu.runtime import xdp_redirect

                try:
                    c["xdp_redirect"] = xdp_redirect.XdpRedirect(
                        cfg.wire_if, {cfg.wire_queue: att.xsk.fd})
                    self.log.info("xdp redirect loaded",
                                  interface=cfg.wire_if,
                                  queue=cfg.wire_queue)
                except OSError as e:
                    self.log.warning("xdp redirect unavailable (RX via "
                                     "kernel needs CAP_BPF)", error=str(e))
            # LIFO shutdown: flush the pipelined batch (needs the ring),
            # then detach the socket + redirect, then free the ring/UMEM
            self._on_close(ring.close)
            if att.xsk is not None:
                self._on_close(att.xsk.close)
            if "xdp_redirect" in c:
                self._on_close(c["xdp_redirect"].close)
            self._on_close(lambda: c["engine"].flush_pipeline())

        # 12. routing + BGP (main.go:884-940). The platform and the FRR
        # executor are both flag-gated: stub/inert by default (run works
        # with no FRR and no CAP_NET_ADMIN), real when asked for.
        if cfg.routing_platform == "linux":
            from bng_tpu.control.routing import (IPRoute2Platform,
                                                 RoutingManager)
            c["routing"] = RoutingManager(platform=IPRoute2Platform())
            self.log.info("routing platform", kind="linux-iproute2")
        elif cfg.routing_platform == "stub":
            from bng_tpu.control.routing import RoutingManager, StubPlatform
            c["routing"] = RoutingManager(platform=StubPlatform())
        else:  # a typo must not silently disable multi-ISP routing
            raise ValueError(
                f"routing_platform={cfg.routing_platform!r}: "
                f"expected 'stub' or 'linux'")
        if edge_tables is not None:
            # the routing manager's upstreams steer for real: a subscriber's
            # route row holds the gateway MAC its class elects among them
            # (the source's RouteSubscriberToISP, one policy route a
            # subscriber at session start). Upstreams come from
            # `routing.add_upstream`, their L2 next hops from
            # `route_program.set_neighbor`, the tables a class may use from
            # `route_program.class_tables`
            from bng_tpu.edge import CLASS_CODES, RouteProgram

            route_prog = c["route_program"] = RouteProgram(
                edge_tables, c["routing"])
            route_prog.attach()
            # the in-process server commits every lease: `edge` is a fleet
            # blocker (step 10b2), so no worker ever owns a lease book here
            klass_of = {code: name for name, code in CLASS_CODES.items()}
            prev_edge_hook = dhcp.accounting_hook

            def _edge_lease(event, lease, sid, _rp=route_prog):
                if prev_edge_hook is not None:
                    prev_edge_hook(event, lease, sid)
                if event == "start":
                    _rp.bind_subscriber(lease.ip, klass_of.get(
                        lease.client_class, "residential"))
                elif event == "stop":
                    _rp.unbind_subscriber(lease.ip)

            dhcp.accounting_hook = _edge_lease
        if cfg.bgp_enabled:
            from bng_tpu.control.routing import (BGPConfig, BGPController,
                                                 vtysh_executor)
            if cfg.bgp_vtysh:
                executor = vtysh_executor(cfg.bgp_vtysh_path)
                self.log.info("bgp executor", kind="vtysh",
                              binary=cfg.bgp_vtysh_path)
            else:
                executor = lambda cmd: ""  # noqa: E731 — inert by default
            c["bgp"] = BGPController(
                BGPConfig(local_as=cfg.bgp_local_as,
                          router_id=cfg.bgp_router_id),
                executor=executor)

        # 13. metrics (main.go:1214-1241)
        if cfg.metrics_enabled:
            metrics = c["metrics"] = BNGMetrics()
            collector = c["collector"] = MetricsCollector(metrics)
            # engine/cluster sources read c[...] at scrape time, never a
            # captured reference: a blue/green swap replaces the object
            # mid-run and the dashboard must follow the flip
            if cfg.shards > 1:
                collector.add_source(
                    lambda: metrics.collect_sharded(c["cluster"]))
            else:
                collector.add_source(
                    lambda: metrics.collect_engine(c["engine"].stats))
            collector.add_source(lambda: metrics.collect_dhcp_server(dhcp.stats))
            if self.fleet_blockers:
                metrics.record_fleet_blocked(self.fleet_blockers)
            if cfg.walled_garden_enabled and cfg.shards <= 1:
                collector.add_source(
                    lambda: metrics.collect_garden(c["engine"].stats))
            if "scheduler" in c:
                sched = c["scheduler"]
                # histograms are fed live at dispatch/retire; the gauges
                # come from the 5s scrape like every other source
                sched.metrics = metrics
                collector.add_source(
                    lambda: metrics.collect_scheduler(sched))
            if "fleet" in c:
                fleet_c = c["fleet"]
                collector.add_source(
                    lambda: metrics.collect_fleet(fleet_c))
            if "wire_attachment" in c:
                # rung identity + pump accounting; reads c[...] at
                # scrape time so a re-attach follows the flip
                collector.add_source(
                    lambda: metrics.collect_wire(
                        c.get("wire_attachment")))
            if "telemetry" in c:
                tele_tr = c["telemetry"]
                # bng_stage_latency_us renders live from the tracer's
                # histograms at scrape; the counters ride the 5s loop
                metrics.attach_telemetry(tele_tr)
                collector.add_source(
                    lambda: metrics.collect_telemetry(tele_tr))
            if "slo" in c:
                slo_mon = c["slo"]
                # burn-rate verdicts + configured budgets per stage:
                # collect_slo reads one locked monitor snapshot
                collector.add_source(
                    lambda: metrics.collect_slo(slo_mon))
            if cfg.dns_enabled:
                collector.add_source(lambda: metrics.collect_dns(
                    dns_srv.stats, resolver.stats()))
            collector.add_source(lambda: metrics.collect_pools(
                {str(pid): st for pid, st in pool_mgr.stats().items()}))
            # exhaustion counters read c[...] at scrape time (nil-safe):
            # a fleet resize or engine swap must not strand a captured ref
            collector.add_source(lambda: metrics.collect_exhaustion(
                dhcpv6=c.get("dhcpv6"), nat=c.get("nat"),
                fleet=c.get("fleet")))
            self._on_close(collector.stop)

        # 14. checkpoint/warm-restart (runtime/checkpoint.py +
        # control/statestore.py). Restore-at-start hydrates the host
        # mirrors + lease book + HA store and re-uploads via the bulk
        # path (zero slow-path DHCP exchanges); a corrupt or mismatched
        # checkpoint is REJECTED and the process cold-starts, logged. A
        # standby bootstraps its session store + last_seq from the
        # checkpoint, then catches up via replay_since on first connect.
        if cfg.checkpoint_dir:
            from bng_tpu.control.statestore import (CheckpointStore,
                                                    PeriodicCheckpointer)
            from bng_tpu.runtime import checkpoint as ckpt_mod

            store = c["checkpoint_store"] = CheckpointStore(
                cfg.checkpoint_dir)
            ha_sync = c.get("ha")
            if store.has_checkpoints():
                try:
                    snap, path = store.load_latest()
                    if cfg.shards > 1:
                        # sharded restore: slot-exact at matching
                        # topology, owner-routed re-shard on N->M (the
                        # fleet lease-book discipline); a single-engine
                        # snapshot rejects to cold start
                        rows = ckpt_mod.restore_sharded_checkpoint(
                            snap, c["cluster"], dhcp=dhcp, ha=ha_sync,
                            fleet=c.get("fleet"),
                            now=int(self.clock()))
                    else:
                        rows = ckpt_mod.restore_checkpoint(
                            snap, engine=c["engine"], dhcp=dhcp,
                            ha=ha_sync, fleet=c.get("fleet"))
                    c["checkpoint_restored"] = rows
                    self.log.info("warm restart from checkpoint",
                                  path=str(path), seq=snap.seq,
                                  rows={k: v for k, v in rows.items() if v})
                    if "metrics" in c:
                        c["metrics"].record_restore(rows)
                except ckpt_mod.CheckpointError as e:
                    c["checkpoint_error"] = str(e)
                    self.log.warning(
                        "checkpoint restore rejected; cold start",
                        error=str(e))
                    if "metrics" in c:
                        c["metrics"].record_restore({}, outcome="rejected")

            def _snapshot(seq, now, _dhcp=dhcp, _ha=ha_sync):
                # c["engine"]/c["cluster"] read at snapshot time: after
                # a blue/green swap the checkpoint must fold device
                # words from the SERVING chain, not the retired one's
                if cfg.shards > 1:
                    return ckpt_mod.build_sharded_checkpoint(
                        c["cluster"], seq, now, dhcp=_dhcp, ha=_ha,
                        fleet=c.get("fleet"), node_id=cfg.node_id)
                return ckpt_mod.build_checkpoint(
                    seq, now, engine=c["engine"],
                    scheduler=c.get("scheduler"), dhcp=_dhcp, ha=_ha,
                    fleet=c.get("fleet"), node_id=cfg.node_id)

            ckptr = c["checkpointer"] = PeriodicCheckpointer(
                store, _snapshot, interval_s=cfg.checkpoint_interval_s,
                keep=cfg.checkpoint_keep, metrics=c.get("metrics"),
                clock=self.clock)
            if "collector" in c:
                c["collector"].add_source(
                    lambda: c["metrics"].collect_checkpoint(ckptr))

        # 15. zero-downtime ops (control/opsctl.py): the transition
        # queue the run loop drains at batch boundaries (`bng ctl`
        # submits into it over the --ctl-listen wire, started by the
        # serve loop like the metrics endpoint) and, when asked, the
        # watermark autoscaler driving live fleet elasticity from tick.
        from bng_tpu.control.opsctl import (AutoscaleConfig, FleetAutoscaler,
                                            OpsController)

        c["ops"] = OpsController(self)
        if cfg.slowpath_autoscale and "fleet" in c:
            c["autoscaler"] = FleetAutoscaler(
                c["fleet"],
                AutoscaleConfig(min_workers=max(1, cfg.slowpath_min_workers),
                                max_workers=max(1, cfg.slowpath_max_workers)),
                clock=self.clock)
            self.log.info("fleet autoscaler armed",
                          min=cfg.slowpath_min_workers,
                          max=cfg.slowpath_max_workers)

    # -- zero-downtime transitions (ops verbs; serialized on _ctl) -------

    def fleet_resize(self, n: int) -> dict:
        """Live fleet elasticity: grow/shrink the slow-path fleet to `n`
        workers at a batch boundary — no restart, no dropped in-flight
        DORAs (control/fleet.py resize)."""
        with self._ctl:
            return self._fleet_resize_locked(int(n))

    def _fleet_resize_locked(self, n: int) -> dict:
        fleet = self.components.get("fleet")
        if fleet is None:
            why = (f"blocked by {self.fleet_blockers}"
                   if self.fleet_blockers else
                   "not configured (--slowpath-workers <= 1)")
            return {"op": "fleet_resize", "outcome": "rejected",
                    "error": f"no slow-path fleet: {why}"}
        report = fleet.resize(n)
        if "metrics" in self.components:
            self.components["metrics"].record_transition(report)
            self.components["metrics"].slowpath_workers.set(fleet.n)
        self.log.info("fleet resize", **{k: report.get(k) for k in
                                         ("from", "to", "outcome",
                                          "leases_moved", "offers_moved")})
        return report

    def fleet_rolling_restart(self) -> dict:
        """Replace fleet workers one shard at a time (drain-then-transfer
        per shard; heals chaos-killed inline workers) — the live-deploy
        verb (control/fleet.py rolling_restart)."""
        with self._ctl:
            fleet = self.components.get("fleet")
            if fleet is None:
                return {"op": "fleet_rolling_restart",
                        "outcome": "rejected",
                        "error": "no slow-path fleet configured"}
            report = fleet.rolling_restart()
            if "metrics" in self.components:
                self.components["metrics"].record_transition(report)
            self.log.info("fleet rolling restart",
                          outcome=report.get("outcome"),
                          replaced=report.get("replaced"),
                          lost=report.get("lost"))
            return report

    def engine_swap(self) -> dict:
        """Blue/green engine swap: hydrate a standby from an in-memory
        snapshot, replay the delta, audit, flip atomically — rollback on
        any failure with the active untouched (runtime/ops.py). On the
        sharded serving path the standby is a ShardedCluster hydrated
        from a sharded snapshot, partition-audited before the flip."""
        from bng_tpu.runtime.ops import blue_green_swap, sharded_blue_green_swap

        with self._ctl:
            if "cluster" in self.components:
                report = sharded_blue_green_swap(
                    self.components,
                    metrics=self.components.get("metrics"),
                    node_id=self.config.node_id, clock=self.clock)
            else:
                report = blue_green_swap(
                    self.components,
                    metrics=self.components.get("metrics"),
                    node_id=self.config.node_id)
            self.log.info("engine swap", outcome=report.get("outcome"),
                          delta_rows=report.get("delta_rows"),
                          error=report.get("error"))
            return report

    def ops_status(self) -> dict:
        """GET /ops/status payload: what a transition would act on.
        Runs on the HTTP handler thread — takes _ctl so it never reads
        fleet state mid-mutation (stats_snapshot iterates sets/lists the
        loop thread's transitions rebind)."""
        with self._ctl:
            c = self.components
            out: dict = {"node_id": self.config.node_id,
                         "fleet_blocked": self.fleet_blockers,
                         "ops": c["ops"].stats_snapshot()
                         if "ops" in c else None}
            fleet = c.get("fleet")
            if fleet is not None:
                fs = fleet.stats_snapshot()
                out["fleet"] = {k: fs[k] for k in (
                    "workers", "mode", "resizes", "rolling_restarts",
                    "dead_workers")}
            auto = c.get("autoscaler")
            if auto is not None:
                out["autoscaler"] = {"decisions": auto.decisions,
                                     "min": auto.cfg.min_workers,
                                     "max": auto.cfg.max_workers}
            return out

    def _cluster_client_tls(self):
        """Client-side TLSConfig for https cluster peers, or None when no
        TLS material is configured (plaintext peers keep working)."""
        cfg = self.config
        if not (cfg.cluster_tls_ca or cfg.cluster_tls_pins
                or cfg.cluster_tls_client_cert):
            return None
        from bng_tpu.control.ztp_tls import TLSConfig

        return TLSConfig(
            ca_cert_file=cfg.cluster_tls_ca,
            pinned_certs=list(cfg.cluster_tls_pins),
            server_name=cfg.cluster_tls_server_name,
            # pins without a CA: self-signed cluster certs (the common
            # operator deployment) — pinning carries the trust. With no
            # pins the chain check must stay on (CA file or system roots)
            # or the config would authenticate nobody.
            require_valid_chain=not cfg.cluster_tls_pins
            or bool(cfg.cluster_tls_ca),
            client_cert_file=cfg.cluster_tls_client_cert,
            client_key_file=cfg.cluster_tls_client_key)

    def close(self) -> None:
        """LIFO cleanup (main.go:1301-1379)."""
        for fn in reversed(self._cleanup):
            try:
                fn()
            except Exception:
                pass
        self._cleanup.clear()

    def drive_once(self) -> int:
        """One dataplane beat: pump the AF_XDP socket (kernel RX -> ring,
        ring TX verdicts -> kernel) when a real rung is attached, feed the
        synthetic source (if configured), and run a double-buffered engine
        step over the ring. Returns frames moved (the run loop sleeps
        when this stays 0)."""
        ring = self.components.get("ring")
        if ring is None:
            return 0
        from bng_tpu.telemetry import spans as tele

        tele.beat_begin()  # the container every lap below tiles
        try:
            return self._drive_beat(ring)
        finally:
            tele.beat_end()

    def _drive_beat(self, ring) -> int:
        from bng_tpu.telemetry import spans as tele

        att = self.components.get("wire_attachment")
        pumped = 0
        if att is not None and att.xsk is not None:
            pumped = att.xsk.pump()  # kernel -> ring before the step
        if self.config.synthetic_subs:
            self._push_synthetic(ring)
        cluster = self.components.get("cluster")
        sched = self.components.get("scheduler")
        engine = self.components.get("engine")
        if engine is not None and engine is not self._rungs_built_for:
            # before this loop's first window (and again after an engine
            # swap): one program a rung of the fused step's ladder
            with self._ctl:
                self._build_step_rungs(engine, sched, ring)
        if cluster is not None:
            # the promoted serving path: double-buffered sharded ring
            # loop — ring-steered owner-shard batches, depth-2 windows
            # in flight, slow-path punts handled lane-aligned
            now = self.clock()
            with self._ctl:
                moved = self.components["cluster"].process_ring_pipelined(
                    ring, int(now), int(now * 1e6) & 0xFFFFFFFF,
                    slow_path=self._slow_path)
        elif sched is not None and hasattr(ring, "rx_pop"):
            with self._ctl:
                moved = self._drive_scheduler(ring, sched)
        else:
            # scheduler off, or a native ring (batch assemble..complete is
            # its contract; the two-lane out-of-order retire needs the
            # frame-wise rx_pop only PyRing provides)
            if sched is not None and not self._warned_no_rx_pop:
                self._warned_no_rx_pop = True
                self.log.warning("scheduler enabled but ring has no rx_pop; "
                                 "using pipelined engine loop")
            with self._ctl:
                moved = self.components["engine"].process_ring_pipelined(ring)
        # PPPoE negotiation extras beyond the one-inline-reply slow
        # contract (CHAP-Success + IPCP Conf-Req in one beat), plus the
        # fleet workers' pending frames relayed by the parent. A full
        # TX ring re-queues the remainder for the next beat (the FSM
        # retransmit would recover anyway, but without the drop).
        # Under _ctl: a CoA disconnect may extend the queue
        # concurrently, and drain's swap must not lose its frames.
        for src in (self.components.get("slowpath"),
                    self.components.get("fleet")):
            if src is None:
                continue
            with self._ctl:
                pending = src.drain_pending()
                t0 = tele.t() if pending else None
                for i, frame in enumerate(pending):
                    if ring.tx_inject(frame, from_access=True):
                        moved += 1
                    else:
                        # re-queue the WHOLE un-injected remainder,
                        # order-preserving, via the public API
                        src.requeue(pending[i:], front=True)
                        break
                tele.lap(tele.TX, t0)
        if att is not None and att.xsk is not None:
            pumped += att.xsk.pump()  # verdicts -> kernel after the step
        return moved + pumped

    _warned_no_rx_pop = False
    _rungs_built_for = None  # the engine whose step ladder is built

    def _build_step_rungs(self, engine, sched, ring) -> None:
        """Start-up builds one program a rung: `--batch-size` is the
        largest window a step takes, and a step runs at the narrowest
        rung of the ladder down from it that holds its window
        (runtime/engine.py step_rungs). Every rung this app's loop can
        reach is built and run once over an inert window here, so no
        window waits for a compile: up to the bulk batch on the
        scheduler's loop, up to the ring's depth on the engine's (an
        assembled window is never longer)."""
        t0 = time.time()
        if sched is not None and hasattr(ring, "rx_pop"):
            sched.build_bulk_rungs()
        else:
            engine.build_step_rungs(min(engine.B, ring.depth))
        self._rungs_built_for = engine
        self.log.info("step ladder built", batch_size=engine.B,
                      seconds=round(time.time() - t0, 3))

    def _drive_scheduler(self, ring, sched) -> int:
        """One scheduler beat over the ring: RX frames into the lanes,
        poll (express first, bulk ring-managed), completions back out.
        TX/FWD device output and slow-path replies are injected on the TX
        ring; PASS frames were already handled inside the scheduler's
        retire (slow path runs there), so nothing touches the slow ring.
        """
        from bng_tpu.runtime.ring import FLAG_DHCP_CTRL, FLAG_FROM_ACCESS
        from bng_tpu.runtime.scheduler import LANE_BULK, LANE_EXPRESS
        from bng_tpu.telemetry import spans as tele

        moved = 0
        t0 = tele.t()
        budget = sched.bulk.cfg.batch * sched.bulk.cfg.depth
        for _ in range(budget):
            got = ring.rx_pop()
            if got is None:
                break
            frame, fl = got
            fa = (fl & FLAG_FROM_ACCESS) != 0
            # the ring already classified at rx_push (FLAG_DHCP_CTRL) —
            # pass the lane so submit() skips a second header parse
            lane = (LANE_EXPRESS if fa and (fl & FLAG_DHCP_CTRL)
                    else LANE_BULK)
            sched.submit(frame, from_access=fa, lane=lane)
            # ingested frames count as movement even before their lane
            # closes — otherwise the run loop's moved==0 idle sleep (1ms)
            # would stretch a sub-ms express deadline close
            moved += 1
        if moved:
            tele.lap(tele.RING, t0)
        moved += sched.poll()
        if moved == 0 and (len(sched.express) or len(sched.bulk)):
            # frames are waiting on a deadline close: keep the run loop
            # hot (no idle sleep) so the close fires at max_wait_us, not
            # at sleep granularity
            moved = 1
        done = sched.drain_completions()
        t0 = tele.t() if done else None
        for c in done:
            if c.frame is None:
                continue
            if c.verdict in ("tx", "fwd", "slow"):
                # slow completions carry the handler's reply frame; a full
                # TX ring drops it (the client's retransmit recovers, the
                # reference's socket-write failure mode) and counts it:
                # ring.stats()["tx_refused"]
                ring.tx_inject(c.frame, from_access=c.from_access)
        tele.lap(tele.TX, t0)
        return moved

    def _push_synthetic(self, ring, per_beat: int = 16) -> None:
        """Rotating-MAC DISCOVER source (the loadtest generator's role,
        here for `bng-tpu run --synthetic-subs N` smoke runs)."""
        from bng_tpu.control import dhcp_codec, packets

        n_subs = self.config.synthetic_subs
        for _ in range(per_beat):
            i = self._syn_i % n_subs
            self._syn_i += 1
            mac = (0x02B70000 << 16 | i).to_bytes(6, "big")
            p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER,
                                         xid=self._syn_i & 0xFFFFFFFF)
            p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST,
                              bytes([1, 3, 6, 51, 54])))
            f = packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                   p.encode().ljust(320, b"\x00"))
            if not ring.rx_push(f, from_access=True):
                break  # ring full: back off until the engine drains

    # maintenance cadences (seconds): how often each slow sweep runs when
    # tick() is called every second. Mirrors the reference's goroutine
    # intervals: lease cleanup 60s (pkg/dhcp/server.go:1100), NAT expiry
    # 60s (the bpf timeout sweep role), garden 30s, accounting interim
    # honors its own interval so tick just has to fire it regularly.
    EXPIRE_EVERY_S = 60.0
    GARDEN_EVERY_S = 30.0
    ACCT_SYNC_EVERY_S = 60.0
    ACCT_RETRY_EVERY_S = 30.0

    def tick(self, now: float | None = None) -> None:
        """The run loop's 1 Hz maintenance heartbeat — every periodic
        goroutine of the reference's runBNG collapsed into one driver:

        - HA standby reconnect (backoff) + CRDT anti-entropy
        - DHCP lease cleanup (server.go:1100-1163) and NAT session expiry
          against device-authoritative last-seen (nat44.c:49-53 timeouts)
        - RADIUS accounting interim + spool retry (accounting.go:410-497)
        - walled-garden expiry checker (walledgarden/manager.go role)
        - PPPoE keepalive/timeout sweep + SLAAC unsolicited RAs, whose
          generated frames TX-inject on the ring (socket-write role)
        """
        now = now if now is not None else self.clock()
        with self._ctl:
            self._tick_locked(now)

    def _tick_locked(self, now: float) -> None:
        from bng_tpu.telemetry import spans as tele

        c = self.components
        ha = c.get("ha")
        if ha is not None and hasattr(ha, "tick"):  # StandbySyncer only
            ha.tick(now)
        cstore = c.get("cluster_store")
        if cstore is not None and now - self._last_sync >= cstore.sync_interval:
            self._last_sync = now
            cstore.tick()

        ring = c.get("ring")

        # protocol-server ticks that EMIT frames: PPPoE echo/teardown,
        # SLAAC periodic RAs. Without a ring (pure control-plane app, or
        # tests poking tick directly) the frames are dropped — there is
        # no wire to write to.
        pppoe = c.get("pppoe")
        if pppoe is not None:
            t0 = tele.t()  # a walk over every session: a beat waits for it
            for frame in pppoe.tick(now):
                if ring is not None:
                    ring.tx_inject(frame, from_access=True)
            tele.lap(tele.SLOW, t0)
        slaac = c.get("slaac")
        if slaac is not None:
            for frame in slaac.tick(now):
                if ring is not None:
                    ring.tx_inject(frame, from_access=True)

        # slow sweeps on their own cadence; the reap bound keeps one
        # synchronized lease cliff from starving this tick (leftovers
        # are reaped by the next sweeps — see cleanup_expired)
        if now - self._last_expire >= self.EXPIRE_EVERY_S:
            self._last_expire = now
            budget = self.config.expire_batch or None
            c["dhcp"].cleanup_expired(int(now), max_reaps=budget)
            if c.get("dhcpv6") is not None:
                t0 = tele.t()  # a walk over every lease: a beat waits for it
                c["dhcpv6"].cleanup_expired(now, max_reaps=budget)
                tele.lap(tele.SLOW, t0)
            if "cluster" in c:
                c["cluster"].expire(int(now))
            else:
                c["engine"].expire(int(now))
            if "tap_program" in c:
                # warrants past their window leave the device's tap table,
                # warrants that came in since the last sweep are armed
                c["intercept"].expire_warrants(max_reaps=budget)
                c["tap_program"].sync()
            fleet = c.get("fleet")
            if fleet is not None:
                # fleet workers own their lease books; the sweep fans
                # out and the release table-events replay here
                fleet.expire(int(now), max_reaps=budget)
        garden = c.get("walledgarden")
        if garden is not None and now - self._last_garden >= self.GARDEN_EVERY_S:
            self._last_garden = now
            garden.check_expired()

        # partition FSM (resilience/manager.go:221-341) + peer health
        # (pool/peer.go:541-631); both rate-limit internally
        res = c.get("resilience")
        if res is not None:
            acct_mgr = c.get("accounting")
            res.tick(now, acct_send=(
                (lambda rec: acct_mgr.client.send_accounting(**rec))
                if acct_mgr is not None else None))
        pool = c.get("peerpool")
        if pool is not None:
            pool.health_check(now)

        # background checkpoint cadence (never raises; failures count +
        # rate-limited log inside PeriodicCheckpointer.tick)
        ckptr = c.get("checkpointer")
        if ckptr is not None:
            ckptr.tick(now)

        # live SLO burn-rate window (telemetry/slo.py): evaluates only
        # when a window elapsed; a breach fires the slo_breach flight
        # dump and is logged here so the operator sees WHICH stage
        slo_mon = c.get("slo")
        if slo_mon is not None:
            breached = slo_mon.tick(now)
            if breached:
                self.log.warning("slo breach", stages=sorted(breached),
                                 window_s=slo_mon.window_s)

        # watermark-driven fleet elasticity: the autoscaler recommends,
        # the SAME resize verb the operator uses executes (already under
        # _ctl here — tick() took it)
        auto = c.get("autoscaler")
        if auto is not None and "fleet" in c:
            target = auto.target(now)
            if target is not None and target != c["fleet"].n:
                if "metrics" in c:
                    c["metrics"].ops_autoscaler_target.set(target)
                try:
                    self._fleet_resize_locked(target)
                except Exception as e:  # noqa: BLE001
                    # an autoscaler-triggered resize failure must not
                    # take the dataplane loop (and the whole process)
                    # down — that is the outage this layer exists to
                    # prevent; cooldown paces the retry
                    self.log.error("autoscaler resize failed",
                                   target=target,
                                   error=f"{type(e).__name__}: {e}")

        acct = c.get("accounting")
        if acct is not None:
            # bridge device-authoritative NAT octet counters into the
            # accounting sessions before interims fire, else every interim
            # and stop reports zero usage (the reference reads its
            # per-subscriber counters the same way before each interim)
            if acct.sessions and now - self._last_acct_sync >= self.ACCT_SYNC_EVERY_S:
                self._last_acct_sync = now
                if "cluster" in c:
                    # sharded: fold every shard's device-authoritative
                    # session words (a subscriber's NAT state lives on
                    # exactly its affinity shard, so the per-shard dicts
                    # are disjoint)
                    cl = c["cluster"]
                    octets = {}
                    if cl.tables is not None:
                        for i in range(cl.n):
                            octets.update(cl.nat[i].subscriber_octets(
                                cl.fetch_session_vals(i)))
                else:
                    octets = c["engine"].nat.subscriber_octets(
                        c["engine"].fetch_session_vals())
                for s in list(acct.sessions.values()):
                    got = octets.get(s.framed_ip)
                    if got is not None:
                        acct.update_counters(s.session_id, got[0], got[1],
                                             got[2], got[3])
            # interims and spool retries are blocking sends (timeout x
            # retries per record/session): run both on their own cadence,
            # not 1 Hz, or a dead accounting server stalls the whole
            # heartbeat every second (interim_tick re-blocks for every
            # still-due session until the server answers)
            if now - self._last_acct_retry >= self.ACCT_RETRY_EVERY_S:
                self._last_acct_retry = now
                acct.interim_tick(now)
                acct.retry_tick()

    def stats(self) -> dict:
        out = {"version": __version__, "node_id": self.config.node_id}
        eng = self.components.get("engine")
        if eng is not None:
            out["engine"] = {
                "batches": eng.stats.batches, "tx": eng.stats.tx,
                "passed": eng.stats.passed, "dropped": eng.stats.dropped,
                # frames NAT punted for a new flow, by what became of them
                "new_flows": dataclasses.asdict(eng.newflows.stats)}
        cluster = self.components.get("cluster")
        if cluster is not None:
            out["sharded"] = cluster.stats_summary()
            out["sharded"]["new_flows"] = dataclasses.asdict(
                cluster.newflows.stats)
            # each shard's pool, before `allocate_nat` returns None: what
            # its host mirror holds and what its addresses have left
            out["sharded"]["per_shard_nat"] = [
                {k: p[k] for k in ("nat_sessions", "nat_blocks", "nat_pool")}
                for p in cluster.telemetry.snapshot()["per_shard"]]
            ring = self.components.get("ring")
            if ring is not None:
                rs = ring.stats()
                out["sharded"]["steering"] = {
                    k: int(rs.get(k, 0))
                    for k in ("steer_pub_hit", "steer_pub_miss")}
            if self.sharded_blockers:
                out["sharded_blockers"] = list(self.sharded_blockers)
        dhcp = self.components.get("dhcp")
        if dhcp is not None:
            out["dhcp"] = {k: getattr(dhcp.stats, k) for k in
                           ("discover", "offer", "request", "ack", "nak",
                            "release") if hasattr(dhcp.stats, k)}
        pools = self.components.get("pools")
        if pools is not None:
            out["pools"] = pools.stats()
        pppoe = self.components.get("pppoe")
        if pppoe is not None and eng is not None:
            out["pppoe"] = {
                "sessions": len(pppoe.sessions),  # atomic vs CoA thread
                "opened": pppoe.stats.sessions_opened,
                "closed": pppoe.stats.sessions_closed,
                "auth_failures": pppoe.stats.auth_failure,
                "device": {"decap": int(eng.stats.pppoe[0]),
                           "encap": int(eng.stats.pppoe[1])}}
        v6_tables = self.components.get("v6_tables")
        if v6_tables is not None and eng is not None:
            fwd_up, fwd_down, miss, ctrl = (int(x) for x in eng.stats.v6)
            out["ipv6_fastpath"] = {
                "bound": v6_tables.by_addr.count,
                "device": {"fwd_up": fwd_up, "fwd_down": fwd_down,
                           "miss": miss, "ctrl": ctrl}}
        qinq_tables = self.components.get("qinq_tables")
        if qinq_tables is not None and eng is not None:
            push, pop, miss, oversize = (int(x) for x in eng.stats.qinq)
            out["qinq"] = {
                "pairs": qinq_tables.by_ip.count,
                "refused": qinq_tables.refused,
                "device": {"push": push, "pop": pop, "miss": miss,
                           "oversize": oversize}}
        edge_tables = self.components.get("edge_tables")
        if edge_tables is not None and eng is not None:
            mirrored, filtered, rewrites, misses = (
                int(x) for x in eng.stats.edge)
            out["edge"] = {
                "routes": edge_tables.route.count,
                "taps": edge_tables.tap.count,
                "sink": dict(self.components["mirror_pump"].stats),
                "device": {"mirrored": mirrored, "filtered": filtered,
                           "rewrites": rewrites, "route_miss": misses}}
        nat = self.components.get("nat")
        if nat is not None:  # registered only when nat_enabled
            out["nat"] = {"sessions": nat.sessions.count,
                          "blocks": len(nat.blocks)}
        fleet = self.components.get("fleet")
        if fleet is not None:
            out["slowpath_fleet"] = fleet.stats_snapshot()
        if self.fleet_blockers:
            # the configured-but-degraded state must be visible wherever
            # an operator looks first (stats, metrics, startup banner)
            out["slowpath_fleet_blocked"] = list(self.fleet_blockers)
        res = self.components.get("resilience")
        if res is not None:
            out["resilience"] = {"state": res.state.value,
                                 "degraded_auth": res.degraded_auth_active}
        coa = self.components.get("coa")
        if coa is not None:
            out["coa"] = {**coa.stats, **coa.processor.stats}
        return out


# ---------------------------------------------------------------------------
# demo mode (demo.go:46-120): full lifecycle, no device required
# ---------------------------------------------------------------------------

def run_demo(subscriber_count: int = 3, out=None, clock=time.time) -> dict:
    """ONT discovery -> walled garden -> activation -> session, with stub
    auth/allocator — 'No eBPF required' (demo.go:47-58); here: no TPU
    required either (pure host path)."""
    from bng_tpu.control.nexus import (NexusClient, NTEEntity,
                                       SubscriberEntity, VLANAllocator)
    from bng_tpu.control.pon import DiscoveryEvent, PONConfig, PONManager
    from bng_tpu.control.direct import DirectAuthenticator
    from bng_tpu.control.subscriber import SessionKind, SubscriberManager
    from bng_tpu.control.walledgarden import WalledGardenManager

    def log(msg):
        print(msg, file=out if out is not None else sys.stdout)

    nexus = NexusClient(clock=clock)
    vlans = VLANAllocator()
    pon = PONManager(PONConfig(), nexus, vlans, clock=clock)
    garden = WalledGardenManager(clock=clock)
    auth = DirectAuthenticator(nexus=nexus, clock=clock)

    class DemoAllocator:
        def __init__(self):
            self.next = 10
        def allocate(self, sid):
            ip = f"10.1.0.{self.next}"
            self.next += 1
            return ip
        def release(self, sid):
            return True

    class GardenBridge:
        def add(self, session):
            garden.add_to_walled_garden(session.mac or "02:00:00:00:00:00")
        def remove(self, session):
            garden.release_from_walled_garden(session.mac or "02:00:00:00:00:00")

    subs = SubscriberManager(authenticator=auth, allocator=DemoAllocator(),
                             walled_garden=GardenBridge(), clock=clock)

    results = {"provisioned": 0, "active": 0, "walled": 0}
    for i in range(1, subscriber_count + 1):
        serial = f"DEMO-ONT-{i:03d}"
        mac = f"02:de:e0:00:00:{i:02x}"
        log(f"--- subscriber {i}: ONT {serial} ---")

        # 1. ONT appears; operator pre-approved it in Nexus
        nexus.ntes.put(serial, NTEEntity(id=serial, serial=serial,
                                         approved=True))
        r = pon.handle_discovery(DiscoveryEvent(serial=serial))
        log(f"  provisioned: s_tag={r.s_tag} c_tag={r.c_tag}")
        results["provisioned"] += 1

        # 2. subscriber record exists for odd ONTs; evens hit the garden
        if i % 2:
            nexus.subscribers.put(f"sub-{i}", SubscriberEntity(
                id=f"sub-{i}", mac=mac, nte_id=serial,
                circuit_id=f"olt1/1/{i}", qos_policy="residential-100mbps"))

        s = subs.create_session(SessionKind.IPOE, mac=mac,
                                circuit_id=f"olt1/1/{i}")
        if subs.authenticate(s.id):
            ip = subs.assign_address(s.id)
            subs.activate(s.id)
            log(f"  ACTIVE: {s.subscriber_id} ip={ip}")
            results["active"] += 1
        else:
            log("  WALLED GARDEN: unknown subscriber, portal redirect on")
            results["walled"] += 1

    log(f"demo complete: {results}")
    return results


def run_loadtest(args) -> int:
    """Build a self-contained engine + slow-path stack and load-test it
    (the dhcp-loadtest CLI role; validation gating per main.go:90-93)."""
    import ipaddress

    from bng_tpu.control.dhcp_server import DHCPServer
    from bng_tpu.control.nat import NATManager
    from bng_tpu.control.pool import Pool, PoolManager
    from bng_tpu.loadtest import BenchmarkConfig, DHCPBenchmark
    from bng_tpu.runtime.engine import Engine
    from bng_tpu.runtime.tables import FastPathTables
    from bng_tpu.utils.net import ip_to_u32, parse_mac

    net = ipaddress.ip_network(args.pool_cidr)
    server_ip = int(net.network_address + 1)
    server_mac = parse_mac("02:aa:bb:cc:dd:01")
    from bng_tpu.ops.table import nbuckets_for

    # size the subscriber table for the MAC working set at <50% load
    sub_nb = nbuckets_for(args.macs)
    # update_slots must cover a full warmup batch of inserts per step or
    # the device cache lags the host table and renewals miss spuriously
    fastpath = FastPathTables(sub_nbuckets=sub_nb, vlan_nbuckets=1 << 10,
                              cid_nbuckets=1 << 10, max_pools=16, stash=256,
                              update_slots=max(256, 2 * args.batch_size))
    fastpath.set_server_config(server_mac, server_ip)
    pools = PoolManager(fastpath)
    pools.add_pool(Pool(pool_id=1, network=int(net.network_address),
                        prefix_len=net.prefixlen, gateway=server_ip,
                        dns_primary=ip_to_u32("1.1.1.1"), lease_time=86400))
    nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                     sessions_nbuckets=256, sub_nat_nbuckets=64)
    server = DHCPServer(server_mac, server_ip, pools, fastpath_tables=fastpath)
    engine = Engine(fastpath, nat, batch_size=args.batch_size,
                    slow_path=server.handle_frame)
    tracer = None
    if getattr(args, "trace", False):
        # --trace: arm the telemetry tracer BEFORE the fleet spawns — a
        # process-mode fleet exports BNG_TELEMETRY to its children at
        # construction, which is how worker processes know to build the
        # per-frame histograms the `worker` stage merges. The report
        # gains the per-stage latency breakdown.
        from bng_tpu.telemetry import spans as tele_spans

        tracer = tele_spans.arm(tele_spans.Tracer())
    fleet = None
    workers = getattr(args, "workers", 1) or 1
    if workers > 1:
        # slow-path fleet: DHCPv4 slow lanes fan out to N worker
        # processes; the parent DHCPServer above is bypassed (workers
        # own the lease books) but stays as the engine's per-frame
        # fallback for anything the fleet doesn't shard
        from bng_tpu.control.admission import AdmissionConfig
        from bng_tpu.control.fleet import FleetSpec, SlowPathFleet

        fleet = SlowPathFleet(
            FleetSpec.from_pool_manager(server_mac, server_ip, pools),
            n_workers=workers, pools=pools,
            mode=getattr(args, "fleet_mode", "process"),
            # inbox sized past the harness batch: the loadtest measures
            # throughput, the dedicated overload tests measure shedding
            admission=AdmissionConfig(
                inbox_capacity=max(512, 2 * args.batch_size)),
            table_sink=fastpath)
        engine.slow_path_batch = fleet.handle_batch
    target = engine
    if getattr(args, "scheduler", False):
        from bng_tpu.runtime.scheduler import SchedulerConfig, TieredScheduler

        target = TieredScheduler(engine, SchedulerConfig(
            bulk_batch=args.batch_size))

    # --wire: drive the batches through the full wire loop (inject at
    # the far end -> kernel rings -> WirePump -> UMEM ring -> engine ->
    # WirePump -> far end) instead of the engine's batch interface
    # (ISSUE 15). `--wire` alone runs the memory-rung SimKernelRings
    # loopback (no privileges needed); `--wire IFNAME` walks the real
    # attach ladder and needs --wire-peer to see replies.
    wire = getattr(args, "wire", None)
    wire_cleanup: list = []
    wire_pump = None
    wire_mode = ""
    if wire is not None:
        if getattr(args, "scheduler", False):
            print("loadtest: --wire and --scheduler are incompatible "
                  "(the native ring's batch assemble..complete contract "
                  "has no rx_pop)", file=sys.stderr)
            return 2
        from bng_tpu.loadtest import WireLoopTarget
        from bng_tpu.runtime import xsk as xsk_mod
        from bng_tpu.runtime.ring import NativeRing

        nframes = 1 << max(12, (4 * args.batch_size - 1).bit_length())
        depth = 1 << max(10, (2 * args.batch_size - 1).bit_length())
        try:
            wire_ring = NativeRing(nframes=nframes, frame_size=2048,
                                   depth=depth)
        except RuntimeError as e:
            print(f"loadtest: --wire needs the native ring: {e}",
                  file=sys.stderr)
            return 2
        wire_cleanup.append(wire_ring.close)
        pump_path = getattr(args, "wire_pump", "") or None
        att = (xsk_mod.open_wire(wire_ring, ifname=wire,
                                 pump_path=pump_path)
               if wire != "mem" else None)
        if att is not None and att.xsk is not None:
            peer = getattr(args, "wire_peer", "")
            if not peer:
                print("loadtest: --wire on a live rung needs --wire-peer "
                      "IFNAME (the far end to inject/collect on)",
                      file=sys.stderr)
                return 2
            import socket as so

            from bng_tpu.runtime import xdp_redirect

            wire_cleanup.append(att.xsk.close)
            try:
                redir = xdp_redirect.XdpRedirect(wire, {0: att.xsk.fd})
                wire_cleanup.append(redir.close)
            except OSError as e:
                print(f"loadtest: xdp redirect failed (CAP_BPF): {e}",
                      file=sys.stderr)
                return 2
            txs = so.socket(so.AF_PACKET, so.SOCK_RAW)
            txs.bind((peer, 0))
            rxs = so.socket(so.AF_PACKET, so.SOCK_RAW, so.htons(0x0003))
            rxs.bind((peer, 0))
            rxs.setblocking(False)
            wire_cleanup.extend((txs.close, rxs.close))

            def _inject(frames, _s=txs):
                for f in frames:
                    _s.send(f)

            def _collect(_s=rxs):
                out = []
                while True:
                    try:
                        out.append(_s.recv(4096))
                    except (BlockingIOError, OSError):
                        break
                return out

            wire_pump = att.xsk.wire_pump
            wire_mode = att.mode
            target = WireLoopTarget(engine, wire_ring, wire_pump,
                                    _inject, _collect)
        else:
            if att is not None:
                # a REQUESTED NIC fell back: say it loudly, then serve
                # the memory rung anyway (the loadtest still measures
                # the pump loop; bng_wire_rung would pin it in `run`)
                print(f"loadtest: wire attach fell back to the memory "
                      f"rung: {att.detail}", file=sys.stderr)
            kern = xsk_mod.SimKernelRings(wire_ring, headroom=256,
                                          ring_size=depth)
            wire_pump = xsk_mod.WirePump(wire_ring, kern, path=pump_path)
            wire_mode = "memory"
            target = WireLoopTarget(engine, wire_ring, wire_pump,
                                    kern.inject_many, kern.drain_egress,
                                    tick=kern.deliver)

    cfg = BenchmarkConfig(
        batch_size=args.batch_size, duration_s=args.duration,
        warmup_s=args.warmup, unique_macs=args.macs,
        enable_renewals=args.renewals, renewal_ratio=args.renewal_ratio,
        rps_limit=args.rps)
    bench = DHCPBenchmark(target, cfg, log=lambda s: print(s, file=sys.stderr))
    try:
        res = bench.run()
        # counted degradations ride the result (storm-suite hygiene):
        # shed-by-reason from admission, exhaustion verdicts by resource
        if fleet is not None:
            res.shed = dict(fleet.admission.stats.shed)
        degraded = {}
        if server.stats.pool_exhausted:
            degraded["dhcp_pool"] = server.stats.pool_exhausted
        if fleet is not None:
            slice_exhausted = fleet.pool_exhausted_total()
            if slice_exhausted:
                degraded["fleet_slice"] = slice_exhausted
        for resource, count in nat.exhausted.items():
            if count:
                degraded[f"nat_{resource}"] = count
        res.degraded = degraded
    finally:
        if tracer is not None:
            from bng_tpu.telemetry import spans as tele_spans

            tele_spans.disarm()
        if fleet is not None:
            fleet_snap = fleet.stats_snapshot()
            fleet.close()
        for fn in reversed(wire_cleanup):
            try:
                fn()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass

    stage_breakdown = tracer.breakdown() if tracer is not None else {}
    if tracer is not None:
        # SLO verdict over the per-stage breakdown (telemetry/slo.py):
        # the same vocabulary the storm budgets and `bng run`'s live
        # monitor gate on; it rides the JSON report
        from bng_tpu.telemetry import slo as slo_mod

        res.slo = slo_mod.evaluate(tracer.breakdown(lanes=True))
    if args.json_out:
        out = res.to_dict()
        if fleet is not None:
            out["fleet"] = fleet_snap
        if tracer is not None:
            out["stage_breakdown"] = stage_breakdown
        if wire_pump is not None:
            out["wire"] = {"mode": wire_mode, "pump_path": wire_pump.path,
                           "pump_stats": dict(wire_pump.pump_stats),
                           "unmatched": target.unmatched}
        print(json.dumps(out, indent=2))
    else:
        print(res.summary())
        if wire_pump is not None:
            st = wire_pump.pump_stats
            print(f"Wire:              rung={wire_mode} "
                  f"pump={wire_pump.path} rx={st['rx']} tx={st['tx']} "
                  f"submit_fail={st['rx_submit_fail']} "
                  f"tx_overflow={st['tx_overflow']}")
        if fleet is not None:
            adm = fleet_snap["admission"]
            print(f"Fleet:             {fleet_snap['workers']} workers, "
                  f"{adm['admitted']} admitted, "
                  f"{sum(adm['shed'].values())} shed")
        if tracer is not None:
            print("Stage breakdown (us):")
            for stage, s in stage_breakdown.items():
                print(f"  {stage:<12} p50 {s['p50_us']:>9.1f}   "
                      f"p99 {s['p99_us']:>9.1f}   n {s['count']}")
            if not res.slo["ok"]:
                print(f"SLO BREACHED: {', '.join(res.slo['breaches'])}")
    if args.validate:
        failures = res.meets_targets(cfg)
        for f in failures:
            print(f"TARGET FAILED: {f}", file=sys.stderr)
        return 1 if failures else 0
    return 0


def _trace_dora(args):
    """Build a self-contained engine (+scheduler/+inline fleet) stack,
    arm a span-event-keeping tracer, and drive a full DORA exchange for
    `--macs` subscribers plus a renewal round that hits the device fast
    path — the canonical traced workload `bng trace dump/export` ships.
    Returns (tracer, recorder) with the tracer DISARMED again."""
    import ipaddress

    from bng_tpu.control import dhcp_codec, packets
    from bng_tpu.control.dhcp_server import DHCPServer
    from bng_tpu.control.nat import NATManager
    from bng_tpu.control.pool import Pool, PoolManager
    from bng_tpu.runtime.engine import Engine
    from bng_tpu.runtime.tables import FastPathTables
    from bng_tpu.telemetry import FlightRecorder, RecorderConfig
    from bng_tpu.telemetry import spans as tele
    from bng_tpu.utils.net import ip_to_u32, parse_mac

    net = ipaddress.ip_network(args.pool_cidr)
    server_ip = int(net.network_address + 1)
    server_mac = parse_mac("02:aa:bb:cc:dd:01")
    fastpath = FastPathTables(sub_nbuckets=1 << 10, vlan_nbuckets=64,
                              cid_nbuckets=64, max_pools=4,
                              update_slots=max(256, 2 * args.batch_size))
    fastpath.set_server_config(server_mac, server_ip)
    pools = PoolManager(fastpath)
    pools.add_pool(Pool(pool_id=1, network=int(net.network_address),
                        prefix_len=net.prefixlen, gateway=server_ip,
                        dns_primary=ip_to_u32("1.1.1.1"), lease_time=3600))
    nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                     sessions_nbuckets=256, sub_nat_nbuckets=64)
    server = DHCPServer(server_mac, server_ip, pools,
                        fastpath_tables=fastpath)
    engine = Engine(fastpath, nat, batch_size=args.batch_size,
                    slow_path=server.handle_frame)
    fleet = None
    if args.workers > 1:
        from bng_tpu.control.admission import AdmissionConfig
        from bng_tpu.control.fleet import FleetSpec, SlowPathFleet

        # inline workers: deterministic, and the worker-stage histogram
        # still exercises the cross-worker merge path. A generous
        # deadline keeps compile-cold first batches from being shed.
        fleet = SlowPathFleet(
            FleetSpec.from_pool_manager(server_mac, server_ip, pools),
            n_workers=args.workers, pools=pools, mode="inline",
            admission=AdmissionConfig(
                inbox_capacity=max(512, 2 * args.batch_size),
                deadline_ms=60_000.0),
            table_sink=fastpath)
        engine.slow_path_batch = fleet.handle_batch
    target = engine
    if args.scheduler:
        from bng_tpu.runtime.scheduler import (SchedulerConfig,
                                               TieredScheduler)

        target = TieredScheduler(engine, SchedulerConfig(
            bulk_batch=args.batch_size))

    recorder = FlightRecorder(RecorderConfig(out_dir=args.trace_dir))
    import jax

    recorder.set_backend(jax.default_backend())
    tracer = tele.Tracer(recorder=recorder, keep_events=1 << 14)

    def discover(mac, xid):
        p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER, xid=xid)
        return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                  p.encode().ljust(320, b"\x00"))

    def request(mac, offer_frame, xid):
        od = packets.decode(offer_frame)
        off = dhcp_codec.decode(od.payload)
        p = dhcp_codec.build_request(mac, dhcp_codec.REQUEST, xid=xid,
                                     requested_ip=off.yiaddr,
                                     server_id=od.src_ip)
        return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                  p.encode().ljust(320, b"\x00"))

    macs = [(0x02C0 << 32 | i).to_bytes(6, "big") for i in range(args.macs)]
    with tele.armed(tracer):
        for base in range(0, len(macs), args.batch_size):
            chunk = macs[base : base + args.batch_size]
            res = target.process([discover(m, 0x1000 + base + k)
                                  for k, m in enumerate(chunk)])
            offers = {i: f for i, f in res["slow"] if f is not None}
            offers.update({i: f for i, f in res.get("tx", [])})
            reqs = [request(m, offers[k], 0x2000 + base + k)
                    for k, m in enumerate(chunk) if k in offers]
            if reqs:
                target.process(reqs)
        # renewal round: cached DISCOVERs answered on device (the
        # trace shows the fast path next to the slow one)
        target.process([discover(m, 0x3000 + k)
                        for k, m in enumerate(macs[: args.batch_size])])
        if hasattr(target, "flush"):
            target.flush()
    if fleet is not None:
        fleet.close()
    return tracer, recorder


def run_trace(args) -> int:
    """`bng trace status|dump|export` — operator verbs over the
    telemetry subsystem. `status` lists flight dumps in the trace dir;
    `dump` runs a traced DORA exchange and writes a flight-recorder
    dump; `export --format chrome` emits Chrome-trace/Perfetto JSON of
    the exchange's spans."""
    import os

    from bng_tpu.telemetry import chrome_trace, default_trace_dir

    if args.trace_cmd == "status":
        out_dir = args.trace_dir or default_trace_dir()
        dumps = []
        if os.path.isdir(out_dir):
            for name in sorted(os.listdir(out_dir)):
                if not name.startswith("flight-") or not name.endswith(".json"):
                    continue
                path = os.path.join(out_dir, name)
                entry = {"file": name, "bytes": os.path.getsize(path)}
                try:
                    with open(path) as f:
                        d = json.load(f)
                    entry.update(reason=d.get("reason"),
                                 backend=d.get("meta", {}).get("backend"),
                                 records=len(d.get("records", ())))
                except (OSError, ValueError):
                    entry["error"] = "unreadable"
                dumps.append(entry)
        print(json.dumps({
            "trace_dir": out_dir,
            "armed_env": os.environ.get("BNG_TELEMETRY") == "1",
            "dumps": dumps,
        }, indent=2))
        return 0

    tracer, recorder = _trace_dora(args)
    if args.trace_cmd == "dump":
        path = recorder.dump("cli", "bng trace dump DORA exchange",
                             path=args.out or None)
        if path is None:
            print("trace dump: write failed", file=sys.stderr)
            return 1
        print(json.dumps({"dump": path,
                          "records": int(tracer.seq),
                          "stage_breakdown": tracer.breakdown()}, indent=2))
        return 0
    # export
    if args.format != "chrome":
        print(f"trace export: unknown format {args.format!r} "
              f"(supported: chrome)", file=sys.stderr)
        return 2
    trace = chrome_trace(tracer, label="bng-tpu DORA")
    out_path = args.out or os.path.join(
        args.trace_dir or default_trace_dir(), "dora-trace.json")
    if os.path.dirname(out_path):
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(trace, f)
    n_x = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
    print(json.dumps({"trace": out_path, "events": n_x,
                      "stages": sorted({e["name"] for e in
                                        trace["traceEvents"]
                                        if e.get("ph") == "X"})}, indent=2))
    return 0


def run_ctl(args) -> int:
    """`bng ctl` — runtime control of a LIVE `bng run` process over its
    --ctl-listen wire (control/opsctl.py): `fleet resize N`,
    `fleet rolling-restart`, `engine swap`, `status`. Prints the
    transition report; rc=0 on ok/noop, 1 on a rejected/failed/rolled-
    back transition, 2 when the process is unreachable."""
    from bng_tpu.control.opsctl import ctl_request

    if args.ctl_cmd == "status":
        op, body = "status", None
    elif args.ctl_cmd == "fleet":
        if args.fleet_cmd == "resize":
            op, body = "fleet/resize", {"n": args.n}
        else:
            op, body = "fleet/rolling-restart", {}
    else:  # engine swap
        op, body = "engine/swap", {}
    try:
        _code, doc = ctl_request(args.ctl_addr, op, body)
    except OSError as e:  # URLError subclasses OSError
        print(f"ctl: cannot reach {args.ctl_addr}: {e}", file=sys.stderr)
        return 2
    print(json.dumps(doc, indent=2, sort_keys=True))
    if op == "status":
        return 0
    return 0 if doc.get("outcome") in ("ok", "noop") else 1


def run_checkpoint(args) -> int:
    """`bng checkpoint save|restore|info` — operator verbs over the
    warm-restart store. save/restore build the full app from the same
    flag surface as `run` (the snapshot must see the same table
    geometry the running process uses); info only reads headers."""
    from bng_tpu.control.statestore import CheckpointStore

    cfg = _config_from_args(args)
    if not cfg.checkpoint_dir:
        print("checkpoint: --checkpoint-dir is required", file=sys.stderr)
        return 2
    if args.ckpt_cmd == "info":
        infos = [i._asdict() for i in CheckpointStore(cfg.checkpoint_dir).list()]
        print(json.dumps(infos, indent=2))
        return 0

    app = BNGApp(cfg)
    try:
        if args.ckpt_cmd == "save":
            # snapshot of THIS freshly-built process (warm-restored from
            # the dir's newest checkpoint when one exists) — it cannot
            # see a separately-running daemon's live state; a running
            # `bng run` snapshots via SIGTERM or its own cadence
            print("checkpoint save: snapshotting a freshly built app "
                  "(not any running daemon — use SIGTERM or "
                  "--checkpoint-interval-s for that)", file=sys.stderr)
            ckptr = app.components["checkpointer"]
            path = ckptr.save_now(reason="cli")
            s = ckptr.stats
            print(json.dumps({
                "path": str(path), "seq": s["last_seq"],
                "bytes": s["last_bytes"],
                "duration_s": round(s["last_duration_s"], 3)}))
            return 0
        # restore: _build already hydrated (or rejected) — report it
        err = app.components.get("checkpoint_error")
        if err:
            print(f"checkpoint restore REJECTED: {err}", file=sys.stderr)
            return 1
        rows = app.components.get("checkpoint_restored")
        if rows is None:
            print(f"checkpoint restore: no checkpoint in "
                  f"{cfg.checkpoint_dir}", file=sys.stderr)
            return 1
        out = {"restored_rows": rows}
        if getattr(args, "audit", False):
            # --audit: prove the hydrated authorities agree BEFORE the
            # snapshot is trusted to serve traffic. rc=2 on ANY
            # violation — a bad checkpoint must never silently serve.
            from bng_tpu.chaos.invariants import audit_app

            report = audit_app(app)
            out["audit"] = report.to_dict()
            print(json.dumps(out, indent=2))
            if not report.ok:
                print("checkpoint restore --audit: invariant "
                      f"violations {report.violations_by_kind()} — "
                      "refusing this snapshot", file=sys.stderr)
                return 2
            return 0
        print(json.dumps(out, indent=2))
        return 0
    finally:
        app.close()


def run_chaos(args) -> int:
    """`bng chaos run|audit` — the fault-injection harness
    (bng_tpu/chaos): `run` executes the scripted scenario suite (plus an
    optional fault soak) and prints a bit-deterministic JSON report —
    two runs with one --seed emit identical bytes; `audit` builds the
    app from the normal run flags and proves the cross-authority
    invariants hold (rc=2 on any violation)."""
    if args.chaos_cmd == "audit":
        from bng_tpu.chaos.invariants import audit_app

        app = BNGApp(_config_from_args(args))
        try:
            report = audit_app(app)
            print(json.dumps(report.to_dict(), indent=2, sort_keys=True))
            return 0 if report.ok else 2
        finally:
            app.close()

    # the scenario suite is CPU-deterministic by contract (two runs of
    # one --seed must emit identical bytes) and the sharded swap
    # scenario needs a multi-device mesh: pin the hermetic CPU backend
    # with 8 virtual devices BEFORE anything initializes a backend —
    # the same guard the test conftest and dryrun_multichip use
    from bng_tpu.utils.jaxenv import force_cpu

    force_cpu(8)
    from bng_tpu.chaos.runner import (canonical_json, run_report,
                                      scenario_catalog)

    if getattr(args, "list", False):
        for name, desc in scenario_catalog():
            print(f"{name:<28} {desc}")
        return 0
    # metrics=None: the one-shot CLI run has no scrape endpoint to serve
    # the bng_chaos_* families from — the report IS the output. A live
    # `bng run` process soaking via the runner passes its own BNGMetrics.
    names = [args.scenario] if args.scenario else None
    try:
        report = run_report(args.seed, names=names,
                            soak_epochs=args.soak_epochs,
                            storm_scale=args.storm_scale)
    except ValueError as e:
        print(f"chaos run: {e}", file=sys.stderr)
        print("scenario catalog:", file=sys.stderr)
        for name, desc in scenario_catalog():
            print(f"  {name:<28} {desc}", file=sys.stderr)
        return 2
    text = canonical_json(report)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0 if report["ok"] else 1


def _cluster_wave(coord, n_subs: int, chunk: int = 512) -> dict:
    """Drive a synthetic DORA wave through the cluster front door —
    the `bng cluster run --subscribers N` smoke traffic. Returns the
    wave verdict (leased / unique / shed) for the status output."""
    from bng_tpu.control import dhcp_codec, packets
    from bng_tpu.loadtest.harness import StormFrameFactory

    fac = StormFrameFactory(coord.server_ip)
    macs = [(0x02D6 << 32 | i).to_bytes(6, "big") for i in range(n_subs)]
    leased: dict[bytes, int] = {}
    now = coord.clock()
    for ci in range(0, n_subs, chunk):
        cmacs = macs[ci:ci + chunk]
        out = coord.handle_batch(
            [(i, fac.discover(m, ci + i + 1)) for i, m in enumerate(cmacs)],
            now=now)
        offers: dict[bytes, int] = {}
        for (_l, rep), m in zip(out, cmacs):
            if rep is not None:
                p = dhcp_codec.decode(packets.decode(rep).payload)
                if p.msg_type == dhcp_codec.OFFER:
                    offers[m] = p.yiaddr
        req = [m for m in cmacs if m in offers]
        out = coord.handle_batch(
            [(i, fac.request(m, offers[m], 0x100000 + ci + i))
             for i, m in enumerate(req)], now=now)
        for (_l, rep), m in zip(out, req):
            if rep is not None:
                p = dhcp_codec.decode(packets.decode(rep).payload)
                if p.msg_type == dhcp_codec.ACK:
                    leased[m] = p.yiaddr
    return {"subscribers": n_subs, "leased": len(leased),
            "unique_ips": len(set(leased.values())),
            "shed": coord.shed_frames,
            "ok": (len(leased) == n_subs
                   and len(set(leased.values())) == n_subs)}


def _plan_summary(plan) -> dict:
    from bng_tpu.utils.net import u32_to_ip

    return {
        "space": f"{u32_to_ip(plan.space_network)}/{plan.space_prefix_len}",
        "block_prefix_len": plan.block_prefix_len,
        "blocks": plan.n_blocks,
        "epoch": plan.epoch,
        "addresses": plan.total_addresses(),
        "members": {
            iid: {"blocks": [f"{u32_to_ip(b.network)}/{b.prefix_len}"
                             for b in p.blocks],
                  "addresses": p.addresses(),
                  "nat": [list(plan.nat_range(b)) for b in p.blocks]}
            for iid, p in sorted(plan.members.items())},
        "free_blocks": [f"{u32_to_ip(b.network)}/{b.prefix_len}"
                        for b in plan.free],
    }


def run_cluster(args) -> int:
    """`bng cluster run|status` — the cluster-of-BNGs front door
    (bng_tpu/cluster). `run` composes N instances behind one FNV-1a32
    steering door (inline in this process, or one child process per
    instance), optionally drives a synthetic DORA wave, and prints or
    serves the coordinator status + bng_cluster_* metrics; `status`
    reads the carve plan back out of a checkpoint (or a status file a
    `run` wrote) without building anything."""
    from bng_tpu.utils.net import ip_to_u32

    if args.cluster_cmd == "status":
        if args.from_checkpoint:
            from bng_tpu.cluster import ClusterPlan
            from bng_tpu.runtime.checkpoint import (CheckpointError,
                                                    decode_checkpoint)

            try:
                with open(args.from_checkpoint, "rb") as f:
                    ckpt = decode_checkpoint(f.read())
            except (OSError, CheckpointError) as e:
                print(f"cluster status: {e}", file=sys.stderr)
                return 2
            comp = ckpt.meta.get("components", {}).get("cluster_plan")
            if not comp:
                print("cluster status: checkpoint carries no "
                      "cluster_plan component", file=sys.stderr)
                return 1
            try:
                plan = ClusterPlan.from_dict(comp)
            except (KeyError, TypeError, ValueError) as e:
                print(f"cluster status: corrupt carve plan: {e!r}",
                      file=sys.stderr)
                return 2
            print(json.dumps(_plan_summary(plan), indent=2,
                             sort_keys=True))
            return 0
        if args.status_file:
            try:
                with open(args.status_file) as f:
                    print(f.read().rstrip())
            except OSError as e:
                print(f"cluster status: {e}", file=sys.stderr)
                return 2
            return 0
        print("cluster status: --from-checkpoint or --status-file "
              "required (a live `cluster run` writes the latter)",
              file=sys.stderr)
        return 2

    # -- cluster join ------------------------------------------------
    # run this box as a FULL SERVING MEMBER of a remote coordinator's
    # carve (ISSUE 20): announce with capped-backoff retries, hydrate
    # the carved blocks from the coordinator's handoff stream, bring up
    # a local fleet+engine stack, serve steered batches over the
    # fabric, and ship lease/HA deltas back on every reply
    if args.join:
        import socket as _socket

        from bng_tpu.cluster.coordinator import DEFAULT_FABRIC_PSK
        from bng_tpu.cluster.fabric import UDPTransport
        from bng_tpu.cluster.member import MemberRuntime
        from bng_tpu.control.deviceauth import PSKAuthenticator
        from bng_tpu.control.metrics import BNGMetrics

        host_s, _, port_s = args.join.rpartition(":")
        try:
            hub = (host_s or "127.0.0.1", int(port_s))
        except ValueError:
            print(f"cluster run: bad --join {args.join!r} "
                  f"(want HOST:PORT)", file=sys.stderr)
            return 2
        hostname = _socket.gethostname()
        node_id = args.node_id or f"bng-{hostname}"
        ep = UDPTransport(node_id, PSKAuthenticator(
            psk=args.fabric_psk or DEFAULT_FABRIC_PSK))
        ep.add_peer("coordinator", hub)
        member = MemberRuntime(
            ep, node_id, hostname,
            join_deadline_s=args.join_deadline,
            log=lambda m: print(m, file=sys.stderr))
        metrics = BNGMetrics()
        print(f"cluster join: {node_id} (host {hostname}) -> "
              f"{hub[0]}:{hub[1]}", file=sys.stderr)
        last_state = member.state
        ticks = 0
        try:
            while True:
                member.tick()
                st = member.status()
                metrics.record_member(st)
                if member.state != last_state:
                    print(f"cluster join: {last_state} -> "
                          f"{member.state} (epoch {member.epoch}, "
                          f"{member.join_retries} retries)",
                          file=sys.stderr)
                    last_state = member.state
                if member.state == "gave_up":
                    return 1
                ticks += 1
                if args.once and (member.state == "serving"
                                  or ticks >= 3):
                    print(json.dumps(st, indent=2, sort_keys=True,
                                     default=str))
                    return 0 if member.state == "serving" else 1
                if args.status_file and ticks % 10 == 0:
                    with open(args.status_file, "w") as f:
                        f.write(json.dumps(st, indent=2, sort_keys=True,
                                           default=str) + "\n")
                time.sleep(0.05)
        except KeyboardInterrupt:
            return 0
        finally:
            member.close()

    # -- cluster run -------------------------------------------------
    from bng_tpu.cluster import ClusterCoordinator
    from bng_tpu.control.metrics import BNGMetrics

    net_s, _, plen_s = args.space.partition("/")
    try:
        space_net, space_plen = ip_to_u32(net_s), int(plen_s or "10")
    except (OSError, ValueError) as e:
        print(f"cluster run: bad --space {args.space!r}: {e}",
              file=sys.stderr)
        return 2
    fabric_bind: tuple = ("127.0.0.1", 0)
    if args.listen:
        lh, _, lp = args.listen.rpartition(":")
        try:
            fabric_bind = (lh or "127.0.0.1", int(lp))
        except ValueError:
            print(f"cluster run: bad --listen {args.listen!r} "
                  f"(want HOST:PORT)", file=sys.stderr)
            return 2
    # the fabric lane rides --listen or process mode (process members
    # beat over UDP; inline members stay on the in-process oracle
    # unless a hub address asks for remote joiners)
    use_fabric = bool(args.listen) or args.mode == "process"
    coord = ClusterCoordinator(
        mode=args.mode, space_network=space_net,
        space_prefix_len=space_plen,
        nat_base=ip_to_u32(args.nat_base) if args.nat_base else 0,
        nat_total=args.nat_total, n_workers=args.workers,
        sub_nbuckets=args.sub_nbuckets,
        fabric=use_fabric, fabric_psk=args.fabric_psk,
        fabric_bind=fabric_bind)
    if use_fabric and coord.fabric_transport is not None:
        fa = coord.fabric_transport.addr
        print(f"cluster fabric: listening on {fa[0]}:{fa[1]}",
              file=sys.stderr)
    metrics = BNGMetrics()
    expected_remotes: dict = {}
    for spec_s in (args.expect_remote or ()):
        iid, _, rhost = spec_s.partition("=")
        if not iid:
            print(f"cluster run: bad --expect-remote {spec_s!r} "
                  f"(want ID=HOST)", file=sys.stderr)
            return 2
        expected_remotes[iid] = rhost or iid
    try:
        coord.add_instances([f"bng-{i:02d}" for i in range(args.instances)],
                            remotes=expected_remotes)
        out: dict = {}
        if args.subscribers:
            out["wave"] = _cluster_wave(coord, args.subscribers)
        status = coord.status()
        metrics.record_cluster(status)
        out["status"] = status
        if args.checkpoint_out:
            from bng_tpu.runtime.checkpoint import (build_checkpoint,
                                                    encode_checkpoint)

            ckpt = build_checkpoint(1, time.time(), cluster_plan=coord)
            with open(args.checkpoint_out, "wb") as f:
                f.write(encode_checkpoint(ckpt))
            out["checkpoint"] = args.checkpoint_out
        text = json.dumps(out, indent=2, sort_keys=True, default=str)
        if args.status_file:
            with open(args.status_file, "w") as f:
                f.write(text + "\n")
        print(text)
        if args.once:
            wave = out.get("wave")
            return 0 if (wave is None or wave["ok"]) else 1
        # serve: the HA/membership machinery ticks at 1 Hz (the same
        # cadence App.tick gives a single instance) until interrupted
        print(f"cluster serving: {args.instances} instances "
              f"({args.mode}); ^C to stop", file=sys.stderr)
        # with a fabric the tick must outpace the membership beats and
        # the handoff retransmit timer; without one, 1 Hz (App.tick's
        # cadence for a single instance) is plenty
        tick_s = 0.1 if use_fabric else 1.0
        try:
            last_status = 0.0
            while True:
                time.sleep(tick_s)
                coord.tick()
                if time.time() - last_status >= 1.0:
                    last_status = time.time()
                    status = coord.status()
                    metrics.record_cluster(status)
                    if args.status_file:
                        with open(args.status_file, "w") as f:
                            f.write(json.dumps(status, indent=2,
                                               sort_keys=True,
                                               default=str) + "\n")
        except KeyboardInterrupt:
            pass
        return 0
    finally:
        coord.close()


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

_RUN_FLAG_HELP = {
    "edge_enabled": "compile the edge stage into the fused step: an "
                    "upstream data frame leaves for the gateway MAC its "
                    "subscriber's class elects among the routing manager's "
                    "upstreams (a route row a subscriber, sized from "
                    "--max-subscribers), and the frames of a subscriber "
                    "under an active warrant are mirrored to the intercept "
                    "manager's exporter; refused by name under --shards and "
                    "beside a slow-path fleet",
    "batch_size": "the largest window one fused step takes (default "
                  "{default} lanes); a shorter window runs at the "
                  "narrowest rung of the ladder down from it (by 8, floor "
                  "128, at most three), and start-up builds one program a "
                  "rung before the first window",
    "nat_public_ips": "the CGNAT pool, one address an argument (default "
                      "{default}); under --shards every address is used "
                      "and each is owned by one shard: the list is dealt "
                      "in contiguous runs in the order given, so list "
                      "consecutive addresses (fewer addresses than shards: "
                      "the block is extended consecutively)",
    "max_nat_sessions": "NAT session capacity in entries, sizing the "
                        "session and reverse tables (default {default}: "
                        "the table's own size); under --shards the "
                        "cluster's total, each shard sized for its share "
                        "with 1/32 of headroom for the hash (unset: "
                        "--shard-nbuckets)",
    "max_nat_subscribers": "subscribers behind NAT, sizing the port-block "
                           "table (default {default}: the table's own "
                           "size); under --shards the cluster's total, "
                           "split as --max-nat-sessions (unset: 256 "
                           "buckets a shard)",
}


def _add_run_flags(p: argparse.ArgumentParser) -> None:
    defaults = BNGConfig()
    for f in dataclasses.fields(BNGConfig):
        flag = "--" + f.name.replace("_", "-")
        default = getattr(defaults, f.name)
        text = _RUN_FLAG_HELP.get(f.name, "").format(default=default) or None
        if isinstance(default, bool):
            p.add_argument(flag, dest=f.name, default=None, help=text,
                           action=argparse.BooleanOptionalAction)
        elif isinstance(default, list):
            p.add_argument(flag, dest=f.name, default=None, nargs="*",
                           help=text)
        else:
            p.add_argument(flag, dest=f.name, default=None, help=text,
                           type=type(default))
    p.add_argument("--config", dest="config_file", default="")


def _config_from_args(args) -> BNGConfig:
    cfg = BNGConfig()
    cli_set = set()
    for f in dataclasses.fields(BNGConfig):
        v = getattr(args, f.name, None)
        if v is not None:
            setattr(cfg, f.name, v)
            cli_set.add(f.name)
    if args.config_file:
        cfg = load_config_file(args.config_file, cli_set, cfg)
    return cfg


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="bng-tpu", description="TPU-native BNG dataplane")
    sub = parser.add_subparsers(dest="command")

    runp = sub.add_parser("run", help="run the BNG (full stack)")
    _add_run_flags(runp)
    runp.add_argument("--once", action="store_true",
                      help="build everything, print stats, exit (smoke mode)")

    demop = sub.add_parser("demo", help="device-free lifecycle demo")
    demop.add_argument("--subscribers", type=int, default=3)

    statsp = sub.add_parser("stats", help="print stats for a built app")
    _add_run_flags(statsp)

    # dhcp-loadtest parity (test/load/cmd/dhcp-loadtest/main.go:27-40)
    loadp = sub.add_parser("loadtest", help="DHCP load test against the "
                           "device pipeline + slow path")
    loadp.add_argument("--duration", type=float, default=10.0,
                       help="measured duration, seconds")
    loadp.add_argument("--warmup", type=float, default=1.0,
                       help="warmup duration, seconds (excluded)")
    loadp.add_argument("--batch-size", type=int, default=256,
                       help="lanes per device batch (the concurrency knob)")
    loadp.add_argument("--macs", type=int, default=10_000,
                       help="unique MAC cardinality (steers fast/slow split)")
    loadp.add_argument("--rps", type=int, default=0,
                       help="target requests/sec (0 = unlimited)")
    loadp.add_argument("--renewals", default=True,
                       action=argparse.BooleanOptionalAction)
    loadp.add_argument("--renewal-ratio", type=float, default=0.8)
    loadp.add_argument("--pool-cidr", default="10.0.0.0/16")
    loadp.add_argument("--json", action="store_true", dest="json_out")
    loadp.add_argument("--validate", action="store_true",
                       help="exit non-zero if performance targets not met")
    loadp.add_argument("--scheduler", action="store_true",
                       help="drive the latency-tiered scheduler instead of "
                            "the engine's batch interface")
    loadp.add_argument("--workers", type=int, default=1,
                       help="slow-path fleet worker count (>1 fans DHCPv4 "
                            "slow lanes out to worker processes)")
    loadp.add_argument("--fleet-mode", default="process",
                       choices=("process", "inline"),
                       help="fleet execution mode (inline = deterministic, "
                            "no child processes)")
    loadp.add_argument("--trace", action="store_true",
                       help="arm the telemetry tracer for the run and "
                            "report the per-stage latency breakdown")
    loadp.add_argument("--wire", nargs="?", const="mem", default=None,
                       metavar="IFNAME",
                       help="drive batches through the full wire loop "
                            "(kernel rings -> WirePump -> UMEM ring -> "
                            "engine -> wire) instead of the engine batch "
                            "interface; bare --wire runs the memory-rung "
                            "SimKernel loopback, --wire IFNAME walks the "
                            "real AF_XDP attach ladder")
    loadp.add_argument("--wire-pump", default="",
                       choices=("", "scalar", "vector"),
                       help="wire pump implementation (default: "
                            "BNG_WIRE_PUMP, scalar)")
    loadp.add_argument("--wire-peer", default="",
                       help="far-end interface for a live --wire rung "
                            "(veth peer to inject/collect on)")

    # telemetry subsystem (bng_tpu/telemetry)
    tracep = sub.add_parser("trace", help="telemetry: flight-recorder "
                            "status/dumps and Chrome-trace export of a "
                            "traced DORA exchange")
    trace_sub = tracep.add_subparsers(dest="trace_cmd", required=True)
    for verb, hlp in (("status", "list flight-recorder dumps in the "
                                 "trace dir"),
                      ("dump", "run a traced DORA exchange and write a "
                               "flight-recorder dump"),
                      ("export", "run a traced DORA exchange and export "
                                 "its spans (--format chrome loads in "
                                 "Perfetto / chrome://tracing)")):
        vp = trace_sub.add_parser(verb, help=hlp)
        vp.add_argument("--trace-dir", default="",
                        help="flight-dump dir (default $BNG_TRACE_DIR "
                             "or <tmp>/bng-flightrec)")
        if verb == "status":
            continue
        vp.add_argument("--out", default="", help="output file path")
        vp.add_argument("--format", default="chrome",
                        help="export format (chrome)")
        vp.add_argument("--macs", type=int, default=32,
                        help="subscribers to DORA through the trace")
        vp.add_argument("--batch-size", type=int, default=64)
        vp.add_argument("--pool-cidr", default="10.0.0.0/16")
        vp.add_argument("--scheduler", action="store_true",
                        help="drive the tiered scheduler (express/bulk "
                             "lanes appear as trace threads)")
        vp.add_argument("--workers", type=int, default=1,
                        help="inline fleet workers (>1 adds the worker "
                             "stage + scatter/gather spans)")

    # warm-restart snapshots (runtime/checkpoint.py + statestore.py)
    ckptp = sub.add_parser("checkpoint",
                           help="save/restore/inspect warm-restart "
                                "snapshots of the device tables")
    ckpt_sub = ckptp.add_subparsers(dest="ckpt_cmd", required=True)
    for verb, hlp in (("save", "build a fresh app (warm-restored from "
                               "the dir if possible) and snapshot IT — "
                               "a running daemon snapshots via SIGTERM "
                               "or --checkpoint-interval-s"),
                      ("restore", "build the app, hydrate from the "
                                  "latest checkpoint, report row counts"),
                      ("info", "list checkpoints in --checkpoint-dir "
                               "(header-only; flags corrupt files)")):
        vp = ckpt_sub.add_parser(verb, help=hlp)
        _add_run_flags(vp)
        if verb == "restore":
            vp.add_argument("--audit", action="store_true",
                            help="run the cross-authority invariant "
                                 "auditor after hydration; exit rc=2 on "
                                 "any violation (a bad snapshot must "
                                 "never silently serve traffic)")

    # chaos harness + invariant auditor (bng_tpu/chaos)
    chaosp = sub.add_parser("chaos", help="fault-injection scenarios and "
                                          "cross-authority invariant audits")
    chaos_sub = chaosp.add_subparsers(dest="chaos_cmd", required=True)
    crun = chaos_sub.add_parser(
        "run", help="run the scripted chaos scenarios (+ optional fault "
                    "soak); deterministic JSON report, rc=1 on failure")
    crun.add_argument("--seed", type=int, default=1,
                      help="fault-schedule seed; same seed -> identical "
                           "schedules and byte-identical report")
    crun.add_argument("--scenario", default="",
                      help="run one scenario by name (default: all)")
    crun.add_argument("--soak-epochs", type=int, default=0,
                      help="also run the seeded fault soak for N epochs "
                           "(traffic + generated faults + audit/epoch)")
    crun.add_argument("--out", default="",
                      help="also write the report JSON to this file")
    crun.add_argument("--list", action="store_true",
                      help="print the scenario catalog (one line each) "
                           "and exit")
    crun.add_argument("--storm-scale", type=float, default=1.0,
                      help="scale factor for the storm scenarios' "
                           "subscriber counts (1.0 = the published "
                           "storms: flash crowd at 100k)")
    caud = chaos_sub.add_parser(
        "audit", help="build the app from run flags and audit the state "
                      "authorities; rc=2 on any violation")
    _add_run_flags(caud)

    # cluster-of-BNGs front door (bng_tpu/cluster)
    clup = sub.add_parser(
        "cluster", help="compose N BNG instances into one cluster: "
                        "disjoint pool carve, HA standbys, FNV-1a32 "
                        "MAC steering (bng_tpu/cluster)")
    clu_sub = clup.add_subparsers(dest="cluster_cmd", required=True)
    clrun = clu_sub.add_parser(
        "run", help="carve the space, build the instances and serve "
                    "(or --once: print status and exit)")
    clrun.add_argument("--instances", type=int, default=4,
                       help="founding member count (default 4)")
    clrun.add_argument("--mode", choices=("inline", "process"),
                       default="inline",
                       help="inline = all instances in this process "
                            "(deterministic); process = one child per "
                            "instance")
    clrun.add_argument("--space", default="10.0.0.0/10",
                       help="cluster address space CIDR to carve "
                            "(default 10.0.0.0/10)")
    clrun.add_argument("--nat-base", default="",
                       help="first NAT public IP (block index maps to "
                            "NAT slice; default: no NAT ranges)")
    clrun.add_argument("--nat-total", type=int, default=0,
                       help="NAT public IP count across the space")
    clrun.add_argument("--workers", type=int, default=1,
                       help="slow-path workers per instance")
    clrun.add_argument("--sub-nbuckets", type=int, default=0,
                       help="per-instance fast-path subscriber buckets "
                            "(0 = slow-path only)")
    clrun.add_argument("--subscribers", type=int, default=0,
                       help="drive a synthetic DORA wave of N "
                            "subscribers through the front door")
    clrun.add_argument("--once", action="store_true",
                       help="print status (+ wave verdict) and exit "
                            "instead of serving")
    clrun.add_argument("--status-file", default="",
                       help="write status JSON here (refreshed each "
                            "tick while serving)")
    clrun.add_argument("--checkpoint-out", default="",
                       help="write a checkpoint carrying the carve "
                            "plan to this file")
    # ISSUE 19: the cluster control fabric (UDP membership lane)
    clrun.add_argument("--listen", default="",
                       help="HOST:PORT for the fabric hub: process "
                            "members beat here over authenticated UDP "
                            "and remote `--join`ers announce themselves "
                            "(process mode; port 0 = ephemeral)")
    clrun.add_argument("--join", default="",
                       help="HOST:PORT of a running coordinator's "
                            "--listen: join its carve as a full remote "
                            "serving member — hydrate the carved blocks "
                            "over the fabric handoff stream and serve "
                            "them from this box")
    clrun.add_argument("--join-deadline", type=float, default=60.0,
                       help="give up the join (capped-backoff retries) "
                            "after this many seconds (default 60)")
    clrun.add_argument("--expect-remote", action="append", default=[],
                       metavar="ID=HOST",
                       help="declare a remote member slot in the "
                            "founding carve (repeatable): blocks deal "
                            "to it on the host axis now, and the slot "
                            "comes alive when that box --join's")
    clrun.add_argument("--fabric-psk", default="",
                       help="pre-shared key authenticating fabric "
                            "datagrams (>=16 chars; default: the dev "
                            "PSK — set your own off-box)")
    clrun.add_argument("--node-id", default="",
                       help="member id to announce when --join'ing "
                            "(default bng-<hostname>)")
    clstat = clu_sub.add_parser(
        "status", help="print cluster status: the carve plan from a "
                       "checkpoint, or a status file a run wrote")
    clstat.add_argument("--from-checkpoint", default="",
                        help="read the carve plan out of this "
                             "checkpoint file")
    clstat.add_argument("--status-file", default="",
                        help="print the status JSON a `cluster run "
                             "--status-file` wrote")

    # runtime ops control (control/opsctl.py wire)
    ctlp = sub.add_parser(
        "ctl", help="zero-downtime ops on a LIVE `bng run` process "
                    "(fleet resize / rolling restart / engine swap)")
    ctlp.add_argument("--ctl-addr", default="127.0.0.1:9092",
                      help="the live process's --ctl-listen address")
    ctl_sub = ctlp.add_subparsers(dest="ctl_cmd", required=True)
    ctl_sub.add_parser("status", help="what a transition would act on")
    cfp = ctl_sub.add_parser("fleet", help="slow-path fleet transitions")
    cf_sub = cfp.add_subparsers(dest="fleet_cmd", required=True)
    rzp = cf_sub.add_parser(
        "resize", help="grow/shrink the fleet live — re-carves lease "
                       "slices and re-shards books without dropping "
                       "in-flight DORAs")
    rzp.add_argument("n", type=int, help="target worker count")
    cf_sub.add_parser(
        "rolling-restart", help="replace workers one shard at a time "
                                "(drain-then-transfer per shard)")
    cep = ctl_sub.add_parser("engine", help="engine transitions")
    ce_sub = cep.add_subparsers(dest="engine_cmd", required=True)
    ce_sub.add_parser(
        "swap", help="blue/green engine swap: snapshot-hydrated standby "
                     "+ delta replay + audited atomic flip (rollback on "
                     "failure)")

    checkp = sub.add_parser(
        "check", help="bngcheck: dataplane-invariant static analyzer "
                      "(rc=1 on any non-baselined finding)")
    from bng_tpu.analysis.cli import add_check_args, run_check
    add_check_args(checkp)

    sub.add_parser("version", help="print version")

    args = parser.parse_args(argv)

    if args.command == "version":
        print(f"bng-tpu {__version__}")
        return 0
    if args.command == "check":
        return run_check(args)
    if args.command == "demo":
        run_demo(args.subscribers)
        return 0
    if args.command == "loadtest":
        return run_loadtest(args)
    if args.command == "checkpoint":
        return run_checkpoint(args)
    if args.command == "chaos":
        return run_chaos(args)
    if args.command == "cluster":
        return run_cluster(args)
    if args.command == "ctl":
        return run_ctl(args)
    if args.command == "trace":
        return run_trace(args)
    if args.command in ("run", "stats"):
        app = BNGApp(_config_from_args(args))
        try:
            if args.command == "stats" or args.once:
                print(json.dumps(app.stats(), indent=2, default=str))
                return 0
            # Serve until interrupted: metrics + collector loops live in
            # threads; the engine is driven by the packet source the
            # operator attaches (synthetic source in tests/bench).
            collector = app.components.get("collector")
            if collector is not None:
                collector.start()
                port = collector.serve_http(app.config.metrics_port)
                print(f"metrics on :{port}/metrics", file=sys.stderr)
            srv = app.components.get("cluster_server")
            if srv is not None:
                print(f"cluster on {srv.url}", file=sys.stderr)
            if app.fleet_blockers:
                # startup status must say it, not just a log line: the
                # configured worker count is NOT what is running
                print(f"slowpath fleet BLOCKED (single-worker): "
                      f"{','.join(app.fleet_blockers)} not yet "
                      f"fleet-aware — see README 'Slow-path fleet'",
                      file=sys.stderr)
            if getattr(app, "sharded_blockers", None):
                print(f"sharded serving: "
                      f"{','.join(app.sharded_blockers)} disabled "
                      f"(engine-path features) — see README "
                      f"'Sharded serving'", file=sys.stderr)
            if app.config.shards > 1:
                print(f"sharded dataplane: {app.config.shards} shards "
                      f"(ring-steered owner batches)", file=sys.stderr)
            ops = app.components.get("ops")
            if ops is not None and app.config.ctl_listen:
                from bng_tpu.control.opsctl import OpsServer

                chost, _, cport = app.config.ctl_listen.rpartition(":")
                try:
                    osrv = app.components["ops_server"] = OpsServer(
                        ops, chost or "127.0.0.1", int(cport or 0)).start()
                    app._on_close(osrv.close)
                    print(f"ctl on {osrv.addr[0]}:{osrv.addr[1]} "
                          f"(bng ctl --ctl-addr "
                          f"{osrv.addr[0]}:{osrv.addr[1]} ...)",
                          file=sys.stderr)
                except OSError as e:
                    print(f"ctl listener unavailable ({e}); "
                          f"runtime ops disabled", file=sys.stderr)
            # SIGTERM -> final checkpoint then clean exit. The handler
            # only sets a flag: the save runs on the loop thread below,
            # never from signal context (the drive loop may hold _ctl —
            # a snapshot from the handler would deadlock on it).
            ckptr = app.components.get("checkpointer")
            if ckptr is not None:
                import signal

                stop_flag = {"sigterm": False}
                signal.signal(signal.SIGTERM,
                              lambda *_: stop_flag.update(sigterm=True))
            # main loop: busy-drive the ring when one exists, 1 Hz
            # cluster maintenance either way
            has_ring = app.components.get("ring") is not None
            last_tick = 0.0
            _sanitize_ctx_enter("loop")  # sanitizer ownership context
            while True:
                if ckptr is not None and stop_flag["sigterm"]:
                    with app._ctl:
                        ckptr.save_now(reason="sigterm")
                    return 0
                moved = app.drive_once()
                if ops is not None:
                    # operator transitions run HERE — at the batch
                    # boundary, on the loop thread — never on the HTTP
                    # handler thread that requested them
                    moved += ops.run_pending()
                now_t = time.time()
                if now_t - last_tick >= 1.0:
                    last_tick = now_t
                    app.tick(now_t)
                if moved == 0:
                    time.sleep(0.001 if has_ring else 1.0)
        except KeyboardInterrupt:
            return 0
        finally:
            app.close()
    parser.print_help()
    return 1


if __name__ == "__main__":
    sys.exit(main())
