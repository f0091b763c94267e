"""Slow-path DHCPv4 server — the central integration point.

Parity: pkg/dhcp/server.go. The Go server is where RADIUS auth, QoS, NAT,
Nexus allocation and fast-path cache updates all meet (SURVEY.md §3.3);
this server has the same shape with pluggable hooks:

- handle_frame dispatch: server.go:302-383
- handleDiscover allocation cascade (nexus-lookup -> nexus-allocate ->
  local pool): server.go:398-553
- handleRequest (auth + lease + fast-path cache + qos + nat + acct):
  server.go:556-861
- handleRelease teardown: server.go:864-983
- updateFastPathCache: server.go:1057-1097 (nil-safe: works with
  tables=None, like the loader==nil path)
- lease cleanup loop: server.go:1100-1163

Wire I/O is frames-in/frames-out (bytes): the engine feeds PASS-verdict
lanes here and transmits returned frames, exactly like the kernel's
XDP_PASS -> UDP socket path.
"""

from __future__ import annotations

import struct
import time
from dataclasses import dataclass, field
from typing import Callable

from bng_tpu.chaos.faults import fault_point
from bng_tpu.control import dhcp_codec, packets
from bng_tpu.control.dhcp_codec import (
    ACK,
    DECLINE,
    DISCOVER,
    INFORM,
    NAK,
    OFFER,
    RELEASE,
    REQUEST,
    DHCPPacket,
)
from bng_tpu.control.pool import Pool, PoolExhaustedError, PoolManager
from bng_tpu.utils.net import mac_to_u64, u32_to_ip
from bng_tpu.utils.structlog import ErrorLog


@dataclass
class Lease:
    """Parity: the Lease built in server.go:657-705."""

    mac: bytes
    ip: int
    pool_id: int
    expiry: int
    circuit_id: bytes = b""
    remote_id: bytes = b""
    s_tag: int = 0
    c_tag: int = 0
    session_id: str = ""
    client_class: int = 0
    username: str = ""
    qos_policy: str = ""  # applied rate plan (HA failover restores it)


@dataclass
class ServerStats:
    discover: int = 0
    offer: int = 0
    request: int = 0
    ack: int = 0
    nak: int = 0
    release: int = 0
    decline: int = 0
    inform: int = 0
    auth_reject: int = 0
    expired_cleaned: int = 0
    # allocation attempts refused because every pool (or the worker's
    # slice) was exhausted — the DISCOVER stays unanswered per the
    # protocol, but the degradation is COUNTED and rate-limit logged
    # (Yuan-class hygiene), never silent
    pool_exhausted: int = 0


class DHCPServer:
    def __init__(
        self,
        server_mac: bytes,
        server_ip: int,
        pool_manager: PoolManager,
        fastpath_tables=None,  # FastPathTables | None (nil-safe)
        authenticator: Callable[..., dict | None] | None = None,  # RADIUS role
        qos_hook: Callable[[int, str], None] | None = None,  # (ip, policy)
        nat_hook: Callable[[int, int], None] | None = None,  # (ip, now)
        release_hook: Callable[[Lease], None] | None = None,
        accounting_hook: Callable[[str, Lease, str], None] | None = None,  # (event, lease, sid)
        allocator=None,  # distributed allocator (Nexus role); optional
        lease_time_cap: int | None = None,
        clock: Callable[[], float] = time.time,
        lease_jitter_frac: float = 0.0,
    ):
        self.server_mac = server_mac
        self.server_ip = server_ip
        self.pools = pool_manager
        self.tables = fastpath_tables
        self.authenticator = authenticator
        self.qos_hook = qos_hook
        self.nat_hook = nat_hook
        self.release_hook = release_hook
        self.accounting_hook = accounting_hook
        self.allocator = allocator
        self.lease_time_cap = lease_time_cap
        self.lease_jitter_frac = lease_jitter_frac
        self.clock = clock
        # the address -> S/C-tag table of the device's qinq stage
        # (runtime.tables.QinQFastPathTables); the composition root sets
        # it under `bng run --qinq-enabled`. Nil-safe like `tables`
        self.qinq = None
        self.leases: dict[int, Lease] = {}  # mac_u64 -> Lease
        self.leases_by_cid: dict[bytes, int] = {}  # circuit_id -> mac_u64
        self._offers: dict[int, tuple[int, int]] = {}  # mac -> (ip, pool_id)
        self.stats = ServerStats()
        self._session_seq = 0
        # (pool_id, lease_time, include_lease) -> (options list, TLV bytes)
        self._reply_opts_cache: dict[tuple, tuple[list, bytes]] = {}
        # (msg_type, static-options key) -> ReplyTemplate: the whole
        # BOOTREPLY payload preassembled, per-client words patched in at
        # render time (dhcp_codec.ReplyTemplate) — the hot encode path
        self._reply_template_cache: dict[tuple, dhcp_codec.ReplyTemplate] = {}
        self._exhaust_log = ErrorLog(
            "dhcp-pool", "DHCP pool exhausted — DISCOVER left unanswered")

    # ------------------------------------------------------------------
    def handle_frame(self, raw: bytes) -> bytes | None:
        """Process one slow-path frame; returns a reply frame or None."""
        try:
            dec = packets.decode(raw)
            if dec.proto != 17 or dec.dst_port != 67:
                return None
            req = dhcp_codec.decode(dec.payload)
        except (ValueError, IndexError, Exception):
            return None
        if req.op != 1:
            return None
        reply = self.handle_packet(req, vlans=dec.vlans, src_mac=dec.src_mac)
        if reply is None:
            return None
        return self._frame_for_reply(req, reply, dec)

    def handle_packet(self, req: DHCPPacket, vlans: list[int] | None = None,
                      src_mac: bytes = b"") -> DHCPPacket | None:
        """Dispatch (parity: handleDHCP, server.go:302-383)."""
        t = req.msg_type
        vlans = vlans or []
        if t == DISCOVER:
            return self._discover(req, vlans)
        if t == REQUEST:
            return self._request(req, vlans)
        if t == RELEASE:
            self._release(req)
            return None
        if t == DECLINE:
            self._decline(req)
            return None
        if t == INFORM:
            return self._inform(req)
        return None

    # ------------------------------------------------------------------
    def _now(self) -> int:
        return int(self.clock())

    def _mac_key(self, req: DHCPPacket) -> int:
        return mac_to_u64(req.chaddr[:6])

    def _find_lease(self, req: DHCPPacket) -> Lease | None:
        """Lease lookup by circuit-id then MAC (server.go:386-395)."""
        cid, _ = req.option82()
        if cid:
            mk = self.leases_by_cid.get(cid)
            if mk is not None:
                return self.leases.get(mk)
        return self.leases.get(self._mac_key(req))

    def _line_of(self, vlans: list[int], profile: dict) -> tuple[int, int]:
        """The S- and C-tag a lease is behind: the authenticator's, else,
        under the 1:1 VLAN model (`qinq` set: a pair is one subscriber's
        line), those of a double-tagged request. Without that model a
        request's tags name no subscriber (a service VLAN is shared), and
        a VLAN-tier row from them would answer every client behind it."""
        s_tag, c_tag = profile.get("s_tag", 0), profile.get("c_tag", 0)
        if not (s_tag or c_tag) and self.qinq is not None and len(vlans) == 2:
            s_tag, c_tag = vlans
        return s_tag, c_tag

    def _allocate_ip(self, req: DHCPPacket, client_class: int) -> tuple[int, int] | None:
        """Allocation cascade (parity: handleDiscover, server.go:398-553):
        distributed allocator first, then local pool."""
        mac = req.chaddr[:6]
        owner = mac.hex()
        if self.allocator is not None:
            got = self.allocator.allocate(owner)
            if got is not None:
                ip = got if isinstance(got, int) else got[0]
                pool = self.pools.pool_for_ip(ip)
                if pool is not None and pool.allocate_specific(ip, owner):
                    return ip, pool.pool_id
        pool = self.pools.classify(client_class)
        if pool is None:
            return None
        try:
            return pool.allocate(owner), pool.pool_id
        except PoolExhaustedError as e:
            # DISCOVER stays unanswered (server.go:529), but the
            # degradation is counted + rate-limit logged, never silent
            self.stats.pool_exhausted += 1
            self._exhaust_log.report(e, mac=owner)
            return None

    def _discover(self, req: DHCPPacket, vlans: list[int]) -> DHCPPacket | None:
        self.stats.discover += 1
        lease = self._find_lease(req)
        if lease is not None:
            ip, pool_id = lease.ip, lease.pool_id
        else:
            mk = self._mac_key(req)
            if mk in self._offers:
                ip, pool_id = self._offers[mk]
            else:
                got = self._allocate_ip(req, client_class=0)
                if got is None:
                    return None  # exhausted: stay silent (server.go:529)
                ip, pool_id = got
                self._offers[mk] = (ip, pool_id)
        pool = self.pools.pools[pool_id]
        self.stats.offer += 1
        return self._build_reply(req, OFFER, ip, pool)

    def _request(self, req: DHCPPacket, vlans: list[int]) -> DHCPPacket | None:
        """Parity: handleRequest (server.go:556-861)."""
        self.stats.request += 1
        now = self._now()
        mk = self._mac_key(req)
        mac = req.chaddr[:6]
        requested = req.requested_ip or req.ciaddr

        # authenticate new sessions (RADIUS role, server.go:595-627)
        profile: dict = {}
        lease = self.leases.get(mk)
        if lease is None and self.authenticator is not None:
            cid, rid = req.option82()
            result = self.authenticator(mac=mac, circuit_id=cid, remote_id=rid)
            if result is None:
                self.stats.auth_reject += 1
                self.stats.nak += 1
                return self._build_nak(req)
            profile = result

        # validate/confirm the address
        if lease is not None and (requested == 0 or requested == lease.ip):
            ip, pool_id = lease.ip, lease.pool_id
        else:
            offered = self._offers.get(mk)
            if offered is not None and (requested == 0 or requested == offered[0]):
                ip, pool_id = offered
            elif requested:
                pool = self.pools.pool_for_ip(requested)
                if pool is None or not pool.allocate_specific(requested, mac.hex()):
                    self.stats.nak += 1
                    return self._build_nak(req)
                ip, pool_id = requested, pool.pool_id
            else:
                self.stats.nak += 1
                return self._build_nak(req)

        pool = self.pools.pools[pool_id]
        lease_time = profile.get("lease_time", pool.lease_time)
        if self.lease_time_cap:
            lease_time = min(lease_time, self.lease_time_cap)
        lease_time = self._jittered_lease_time(lease_time, mk)
        cid, rid = req.option82()
        existing = self.leases.get(mk)
        is_renewal = existing is not None and existing.ip == ip
        if is_renewal:
            # RFC 2131 renewal: extend the session, don't create a new one
            # (a fresh session per REQUEST would leak accounting sessions)
            lease = existing
            lease.expiry = now + lease_time
            if lease.circuit_id and lease.circuit_id != cid:
                # subscriber moved access ports: drop the stale circuit-id
                # index + fast-path row or a future port user inherits it
                self.leases_by_cid.pop(lease.circuit_id, None)
                if self.tables is not None:
                    self.tables.remove_circuit_id_subscriber(lease.circuit_id)
            lease.circuit_id, lease.remote_id = cid, rid
            line = self._line_of(vlans, {})
            if any(line) and line != (lease.s_tag, lease.c_tag):
                # the subscriber moved to another line: the old pair's
                # VLAN-tier row goes (bind below moves the pair itself)
                if self.tables is not None and (lease.s_tag or lease.c_tag):
                    self.tables.remove_vlan_subscriber(lease.s_tag,
                                                       lease.c_tag)
                lease.s_tag, lease.c_tag = line
        else:
            if existing is not None:
                # same MAC granted a different IP: the old lease's address
                # and accounting session must be torn down, not orphaned
                old_pool = self.pools.pools.get(existing.pool_id)
                if old_pool is not None:
                    old_pool.release(existing.ip)
                if existing.circuit_id:
                    self.leases_by_cid.pop(existing.circuit_id, None)
                if self.accounting_hook is not None:
                    self.accounting_hook("stop", existing, existing.session_id)
                if self.qinq is not None:
                    self.qinq.unbind(existing.ip)
            self._session_seq += 1
            s_tag, c_tag = self._line_of(vlans, profile)
            lease = Lease(
                mac=mac, ip=ip, pool_id=pool_id, expiry=now + lease_time,
                circuit_id=cid, remote_id=rid,
                s_tag=s_tag, c_tag=c_tag,
                session_id=f"bng-{now:x}-{self._session_seq:06x}",
                username=profile.get("username", ""),
                qos_policy=profile.get("qos_policy", ""),
            )
        self.leases[mk] = lease
        if cid:
            self.leases_by_cid[cid] = mk
        self._offers.pop(mk, None)

        # fast-path cache population (server.go:708, 1057-1097)
        self._update_fastpath(lease, pool)

        # QoS + NAT wiring (server.go:774-814) — new sessions only
        if not is_renewal:
            if self.qos_hook is not None:
                self.qos_hook(ip, profile.get("qos_policy", ""))
            if self.nat_hook is not None:
                self.nat_hook(ip, now)
            if self.accounting_hook is not None:
                self.accounting_hook("start", lease, lease.session_id)
        elif self.accounting_hook is not None:
            # renewals fire their own event: no new accounting session,
            # but consumers tracking lease state (HA replication's
            # lease_expiry) must see the extension or a standby holds a
            # stale expiry forever
            self.accounting_hook("renew", lease, lease.session_id)

        self.stats.ack += 1
        return self._build_reply(req, ACK, ip, pool, lease_time=lease_time)

    def _release(self, req: DHCPPacket) -> None:
        """Full teardown (parity: handleRelease, server.go:864-983)."""
        self.stats.release += 1
        mk = self._mac_key(req)
        lease = self.leases.pop(mk, None)
        if lease is None:
            return
        if lease.circuit_id:
            self.leases_by_cid.pop(lease.circuit_id, None)
        pool = self.pools.pools.get(lease.pool_id)
        if pool is not None:
            pool.release(lease.ip)
        if self.tables is not None:
            self.tables.remove_subscriber(lease.mac)
            if lease.circuit_id:
                self.tables.remove_circuit_id_subscriber(lease.circuit_id)
            if lease.s_tag or lease.c_tag:
                self.tables.remove_vlan_subscriber(lease.s_tag, lease.c_tag)
        if self.qinq is not None:
            self.qinq.unbind(lease.ip)
        if self.allocator is not None:
            self.allocator.release(lease.mac.hex())
        if self.release_hook is not None:
            self.release_hook(lease)
        if self.accounting_hook is not None:
            self.accounting_hook("stop", lease, lease.session_id)

    def _decline(self, req: DHCPPacket) -> None:
        """Client detected an address conflict (server.go dispatch)."""
        self.stats.decline += 1
        ip = req.requested_ip
        if not ip:
            return
        pool = self.pools.pool_for_ip(ip)
        if pool is not None:
            pool.decline(ip)
        mk = self._mac_key(req)
        lease = self.leases.pop(mk, None)
        if lease is not None and self.tables is not None:
            self.tables.remove_subscriber(lease.mac)
        if lease is not None and self.qinq is not None:
            # the client comes back for another address on the same line
            if lease.s_tag or lease.c_tag:
                self.tables.remove_vlan_subscriber(lease.s_tag, lease.c_tag)
            self.qinq.unbind(lease.ip)

    def _inform(self, req: DHCPPacket) -> DHCPPacket | None:
        self.stats.inform += 1
        pool = self.pools.pool_for_ip(req.ciaddr) if req.ciaddr else None
        if pool is None:
            pool = self.pools.classify(0)
        if pool is None:
            return None
        # ACK without yiaddr/lease time (RFC 2131 §4.3.5)
        reply = self._build_reply(req, ACK, 0, pool, include_lease=False)
        return reply

    # ------------------------------------------------------------------
    def _update_fastpath(self, lease: Lease, pool: Pool) -> None:
        """Populate device tables (parity: updateFastPathCache +
        circuit-ID maps, server.go:1057-1097, 716-771). Nil-safe."""
        if self.tables is None:
            return
        self.tables.add_subscriber(
            lease.mac, pool_id=pool.pool_id, ip=lease.ip,
            lease_expiry=lease.expiry, client_class=lease.client_class,
        )
        if lease.circuit_id:
            self.tables.add_circuit_id_subscriber(
                lease.circuit_id, pool_id=pool.pool_id, ip=lease.ip,
                lease_expiry=lease.expiry, client_class=lease.client_class,
            )
        if self.qinq is not None and lease.s_tag and lease.c_tag:
            if not self.qinq.bind(lease.ip, lease.s_tag, lease.c_tag):
                # the registry holds the pair for another subscriber: this
                # lease is behind no line, and neither row is written
                lease.s_tag = lease.c_tag = 0
        if lease.s_tag or lease.c_tag:
            self.tables.add_vlan_subscriber(
                lease.s_tag, lease.c_tag, pool_id=pool.pool_id, ip=lease.ip,
                lease_expiry=lease.expiry, client_class=lease.client_class,
            )

    # -- checkpoint/warm-restart (runtime/checkpoint.py) ----------------
    def export_leases(self) -> dict:
        """JSON-serializable lease book for the checkpoint meta blob.
        Bytes fields go out as hex; _offers (unanswered OFFERs) are
        transient and deliberately dropped — a client mid-DORA across a
        restart just re-DISCOVERs."""
        return {
            "session_seq": self._session_seq,
            "leases": [{
                "mac": l.mac.hex(), "ip": l.ip, "pool_id": l.pool_id,
                "expiry": l.expiry, "circuit_id": l.circuit_id.hex(),
                "remote_id": l.remote_id.hex(), "s_tag": l.s_tag,
                "c_tag": l.c_tag, "session_id": l.session_id,
                "client_class": l.client_class, "username": l.username,
                "qos_policy": l.qos_policy,
            } for l in self.leases.values()],
        }

    def export_offers(self) -> list[dict]:
        """The in-flight DORA state: un-ACKed OFFERs, JSON-safe. A
        checkpoint restart deliberately drops these (export_leases — the
        client re-DISCOVERs), but a LIVE transition (fleet resize,
        rolling restart) transfers them so a client whose OFFER is
        outstanding completes its DORA against the new owner."""
        return [{"mac": f"{mk:012x}", "ip": int(ip), "pool_id": int(pid)}
                for mk, (ip, pid) in self._offers.items()]

    def restore_offers(self, entries: list[dict]) -> int:
        """Re-arm transferred OFFERs: re-claim each offered address in
        its pool under the client's owner tag (exactly what _discover's
        allocate did on the old worker) and re-index _offers so the
        client's REQUEST lands on the offered-path, not a NAK. An
        address this server's pools cannot claim (not granted here —
        e.g. a raced re-allocation) drops the offer: the client retries
        its DORA, which is the checkpoint-restart behavior."""
        restored = 0
        for o in entries:
            mk = int(o["mac"], 16)
            ip, pid = int(o["ip"]), int(o["pool_id"])
            pool = self.pools.pools.get(pid)
            if pool is None or not pool.allocate_specific(
                    ip, o["mac"].lower()):
                continue
            self._offers[mk] = (ip, pid)
            restored += 1
        return restored

    @staticmethod
    def parse_lease_state(state: dict) -> tuple[int, list["Lease"]]:
        """export_leases() output -> (session_seq, Lease list), touching
        no server state. The restore pre-check runs this before any
        mutation so a corrupt lease book rejects all-or-nothing."""
        leases = [Lease(
            mac=bytes.fromhex(d["mac"]), ip=int(d["ip"]),
            pool_id=int(d["pool_id"]), expiry=int(d["expiry"]),
            circuit_id=bytes.fromhex(d.get("circuit_id", "")),
            remote_id=bytes.fromhex(d.get("remote_id", "")),
            s_tag=int(d.get("s_tag", 0)), c_tag=int(d.get("c_tag", 0)),
            session_id=d.get("session_id", ""),
            client_class=int(d.get("client_class", 0)),
            username=d.get("username", ""),
            qos_policy=d.get("qos_policy", ""))
            for d in state.get("leases", [])]
        return int(state.get("session_seq", 0)), leases

    def restore_leases(self, state: dict) -> int:
        """Rebuild the lease book from export_leases() output: the lease
        dict, the circuit-id index, and pool occupancy (each restored IP
        is re-claimed in its pool so fresh DORAs can never double-assign
        an address a restored subscriber still holds). The fast-path
        device rows ride the table checkpoint, not this path. Returns
        the number of leases restored."""
        seq, leases = self.parse_lease_state(state)
        self._session_seq = max(self._session_seq, seq)
        for lease in leases:
            mk = mac_to_u64(lease.mac)
            self.leases[mk] = lease
            if lease.circuit_id:
                self.leases_by_cid[lease.circuit_id] = mk
            pool = self.pools.pools.get(lease.pool_id)
            if pool is not None:
                pool.allocate_specific(lease.ip, lease.mac.hex())
        return len(leases)

    # expiry-jitter quantization: per-MAC lease times land in one of
    # this many buckets spread over [lt, lt*(1+jitter_frac)], so a mass
    # bring-up cannot manufacture a synchronized expiry cliff — and the
    # reply-template cache stays bounded at BUCKETS entries per pool
    # instead of one per subscriber
    LEASE_JITTER_BUCKETS = 16

    def _jittered_lease_time(self, lt: int, mk: int) -> int:
        """Deterministic per-MAC lease-time spread. Only ever EXTENDS the
        base lease time: the client renews at T1 = lt/2 of the value it
        was told, so shortening server-side would strand renewals."""
        frac = self.lease_jitter_frac
        if frac <= 0.0 or lt <= 0:
            return lt
        step = int(lt * frac) // self.LEASE_JITTER_BUCKETS
        if step <= 0:
            return lt
        # golden-ratio multiply: cheap, deterministic, uniform enough to
        # spread consecutive MACs across all buckets
        bucket = ((mk * 0x9E3779B97F4A7C15) >> 33) \
            % self.LEASE_JITTER_BUCKETS
        return lt + bucket * step

    def cleanup_expired(self, now: int | None = None,
                        max_reaps: int | None = None) -> int:
        """Lease expiry sweep (parity: server.go:1100-1163).

        `max_reaps` bounds the teardown work of ONE sweep (pool release,
        fast-path row removal, NAT/accounting hooks are the expensive
        part, not the scan): a synchronized lease cliff then costs
        ceil(cliff/max_reaps) ticks instead of starving one dataplane
        tick for the whole cliff. Leases past the bound stay expired and
        are reaped by the next sweep; every intermediate state keeps the
        cross-authority invariants (a not-yet-reaped lease still owns
        its address everywhere)."""
        now = now if now is not None else self._now()
        fp = fault_point("dhcp.expire")
        if fp is not None and fp.kind == "skew":
            # chaos: skewed expiry clock — early expiry costs a re-DORA
            # (service), never a double allocation (consistency)
            now = int(now + fp.arg)
        dead = []
        for mk, l in self.leases.items():
            if l.expiry < now:
                dead.append(mk)
                if max_reaps is not None and len(dead) >= max_reaps:
                    break
        for mk in dead:
            lease = self.leases.pop(mk)
            if lease.circuit_id:
                self.leases_by_cid.pop(lease.circuit_id, None)
            pool = self.pools.pools.get(lease.pool_id)
            if pool is not None:
                pool.release(lease.ip)
            if self.tables is not None:
                self.tables.remove_subscriber(lease.mac)
                if lease.circuit_id:
                    self.tables.remove_circuit_id_subscriber(lease.circuit_id)
                if lease.s_tag or lease.c_tag:
                    self.tables.remove_vlan_subscriber(lease.s_tag, lease.c_tag)
            if self.qinq is not None:
                self.qinq.unbind(lease.ip)
            if self.allocator is not None:
                self.allocator.release(lease.mac.hex())
            if self.release_hook is not None:
                self.release_hook(lease)
            if self.accounting_hook is not None:
                self.accounting_hook("stop", lease, lease.session_id)
            self.stats.expired_cleaned += 1
        return len(dead)

    # ------------------------------------------------------------------
    def _static_reply_options(self, pool: Pool, lt: int,
                              include_lease: bool) -> tuple[list, bytes, tuple]:
        """The reply options after MSG_TYPE are a function of (pool, lease
        config) only — build once per key, cache the list AND its encoded
        TLV suffix (the slow path's hottest allocation). Returns
        (options, tlv_bytes, cache_key); the key also keys the full
        reply templates."""
        # keyed on the option-relevant VALUES, so a reconfigured pool (or a
        # future runtime server-IP change — OPT_SERVER_ID is baked into the
        # cached bytes) can never serve a stale cached suffix
        key = (pool.pool_id, lt, include_lease, pool.prefix_len,
               pool.gateway, pool.dns_primary, pool.dns_secondary,
               self.server_ip)
        hit = self._reply_opts_cache.get(key)
        if hit is not None:
            return hit[0], hit[1], key
        from bng_tpu.utils.net import prefix_to_mask

        opts = [(dhcp_codec.OPT_SERVER_ID, struct.pack("!I", self.server_ip))]
        if include_lease:
            opts.append((dhcp_codec.OPT_LEASE_TIME, struct.pack("!I", lt)))
        opts.append((dhcp_codec.OPT_SUBNET_MASK, struct.pack("!I", prefix_to_mask(pool.prefix_len))))
        opts.append((dhcp_codec.OPT_ROUTER, struct.pack("!I", pool.gateway)))
        if pool.dns_primary:
            dns = struct.pack("!I", pool.dns_primary)
            if pool.dns_secondary:
                dns += struct.pack("!I", pool.dns_secondary)
            opts.append((dhcp_codec.OPT_DNS, dns))
        if include_lease:
            opts.append((dhcp_codec.OPT_RENEWAL_TIME, struct.pack("!I", lt // 2)))
            opts.append((dhcp_codec.OPT_REBIND_TIME, struct.pack("!I", (lt * 7) // 8)))
        hit = (opts, dhcp_codec.encode_options(opts))
        # bound the cache: per-subscriber lease times (authenticator
        # profiles) could otherwise grow it without limit
        if len(self._reply_opts_cache) >= 1024:
            self._reply_opts_cache.pop(next(iter(self._reply_opts_cache)))
        self._reply_opts_cache[key] = hit
        return hit[0], hit[1], key

    def _reply_template(self, msg_type: int, pool: Pool, lt: int,
                        include_lease: bool) -> dhcp_codec.ReplyTemplate:
        static_opts, static_raw, key = self._static_reply_options(
            pool, lt, include_lease)
        tkey = (msg_type,) + key
        tmpl = self._reply_template_cache.get(tkey)
        if tmpl is not None:
            return tmpl
        mt_raw = bytes((dhcp_codec.OPT_MSG_TYPE, 1, msg_type))
        tmpl = dhcp_codec.ReplyTemplate(
            [(dhcp_codec.OPT_MSG_TYPE, bytes([msg_type]))] + static_opts,
            siaddr=self.server_ip, options_raw=mt_raw + static_raw)
        if len(self._reply_template_cache) >= 1024:
            self._reply_template_cache.pop(
                next(iter(self._reply_template_cache)))
        self._reply_template_cache[tkey] = tmpl
        return tmpl

    def _build_reply(self, req: DHCPPacket, msg_type: int, ip: int, pool: Pool,
                     lease_time: int | None = None, include_lease: bool = True) -> DHCPPacket:
        lt = lease_time if lease_time is not None else pool.lease_time
        ciaddr = req.ciaddr if msg_type == ACK else 0
        tmpl = self._reply_template(msg_type, pool, lt, include_lease)
        p = DHCPPacket(
            op=2, xid=req.xid, flags=req.flags, ciaddr=ciaddr,
            yiaddr=ip, siaddr=self.server_ip, giaddr=req.giaddr, chaddr=req.chaddr,
        )
        # fresh list, shared option tuples: the snapshot identity check
        # keeps the template render valid until a caller mutates options
        p.options = list(tmpl.options)
        p.set_encoded(tmpl.render(req.xid, req.chaddr, yiaddr=ip,
                                  flags=req.flags, ciaddr=ciaddr,
                                  giaddr=req.giaddr))
        return p

    def _build_nak(self, req: DHCPPacket) -> DHCPPacket:
        p = DHCPPacket(op=2, xid=req.xid, flags=req.flags, giaddr=req.giaddr, chaddr=req.chaddr)
        p.options.append((dhcp_codec.OPT_MSG_TYPE, bytes([NAK])))
        p.options.append((dhcp_codec.OPT_SERVER_ID, struct.pack("!I", self.server_ip)))
        return p

    def _frame_for_reply(self, req: DHCPPacket, reply: DHCPPacket,
                         dec: packets.DecodedPacket) -> bytes:
        """L2/L3 reply addressing, mirroring the fast path (c:721-756)."""
        payload = reply.encode()
        if req.giaddr:
            return packets.udp_packet(
                src_mac=self.server_mac, dst_mac=dec.src_mac,
                src_ip=self.server_ip, dst_ip=req.giaddr,
                src_port=67, dst_port=67, payload=payload, vlans=dec.vlans or None,
            )
        use_bcast = bool(req.flags & 0x8000) or req.ciaddr == 0
        dst_mac = b"\xff" * 6 if use_bcast else req.chaddr[:6]
        return packets.udp_packet(
            src_mac=self.server_mac, dst_mac=dst_mac,
            src_ip=self.server_ip, dst_ip=0xFFFFFFFF,
            src_port=67, dst_port=68, payload=payload, vlans=dec.vlans or None,
        )
