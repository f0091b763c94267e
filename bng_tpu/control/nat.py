"""Host-side CGNAT manager — the pkg/nat role plus the kernel's new-flow path.

In the reference, new-flow port allocation happens *in* the eBPF datapath
with benign races (bpf/nat44.c:408-528) while pkg/nat/manager.go carves
port blocks and populates maps. In the TPU build the device punts new
flows (verdict PASS), and this manager — the single writer — performs:

- RFC 6431 port-block allocation per subscriber
  (parity: AllocateNAT, pkg/nat/manager.go:398-495)
- RFC 4787 Endpoint-Independent Mapping (parity: get_eim_mapping,
  bpf/nat44.c:469-528), including port parity preservation for RTP
  (NAT_FLAG_PORT_PARITY, bpf/nat44.c:419,438)
- session + reverse row insertion into the device tables
- idle-session expiry with per-protocol/state timeouts
  (parity: timeouts, bpf/nat44.c:49-53)
- compliance event log records (parity: nat_log_rb ring buffer events,
  bpf/nat44.c:531-562 / pkg/nat/logging.go)
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np
import jax.numpy as jnp

from bng_tpu.chaos.faults import fault_point
from bng_tpu.ops.nat44 import (
    BV_FLAGS,
    BV_IN_USE,
    BV_NEXT_PORT,
    BV_PORT_END,
    BV_PORT_START,
    BV_PUBLIC_IP,
    BV_SUB_ID,
    FLAG_EIM,
    FLAG_PORT_PARITY,
    NATGeom,
    NATTables,
    REVERSE_WORDS,
    SESSION_WORDS,
    SUBNAT_WORDS,
    SV_BYTES_IN,
    SV_BYTES_OUT,
    SV_CREATED,
    SV_DEST_IP,
    SV_DEST_PORT,
    SV_LAST_SEEN,
    SV_NAT_IP,
    SV_NAT_PORT,
    SV_ORIG_IP,
    SV_ORIG_PORT,
    SV_PKTS_IN,
    SV_PKTS_OUT,
    SV_PROTO,
    SV_STATE,
    NAT_STATE_NEW,
    NAT_STATE_CLOSING,
)
from bng_tpu.ops.parse import PROTO_ICMP, PROTO_TCP, PROTO_UDP
from bng_tpu.ops.table import (HostTable, TableGeom, TableUpdate,
                               apply_update, placed)
from bng_tpu.telemetry import spans as tele
from bng_tpu.utils.structlog import ErrorLog

# timeouts in seconds (parity: bpf/nat44.c:49-53)
UDP_TIMEOUT_S = 120
TCP_TRANSIENT_TIMEOUT_S = 240
TCP_EST_TIMEOUT_S = 7200
ICMP_TIMEOUT_S = 60

# log events (parity: enum nat_log_event, bpf/nat44.c:74-82)
(LOG_SESSION_CREATE, LOG_SESSION_DELETE, LOG_PORT_BLOCK_ASSIGN,
 LOG_PORT_BLOCK_RELEASE, LOG_PORT_EXHAUSTION, LOG_HAIRPIN, LOG_ALG_TRIGGER) = range(1, 8)


@dataclasses.dataclass
class NATLogEntry:
    """Parity: struct nat_log_entry (bpf/nat44.c:193-205)."""

    timestamp: int
    event_type: int
    subscriber_id: int
    private_ip: int
    public_ip: int
    private_port: int
    public_port: int
    dest_ip: int
    dest_port: int
    protocol: int
    flags: int = 0


def apply_nat_updates(tables: NATTables, upd: tuple) -> NATTables:
    sessions, reverse, sub_nat, hairpin, alg, config = upd
    return NATTables(
        sessions=apply_update(tables.sessions, sessions),
        reverse=apply_update(tables.reverse, reverse),
        sub_nat=apply_update(tables.sub_nat, sub_nat),
        hairpin_ips=hairpin,
        alg_ports=alg,
        config=config,
    )


class NATExhaustedError(Exception):
    """Carrier for the rate-limited exhaustion log lines (the allocator
    itself returns None/0 — degraded service, not an exception path)."""


class NATManager:
    def __init__(
        self,
        public_ips: list[int],
        ports_per_subscriber: int = 1024,
        port_range: tuple[int, int] = (1024, 65535),
        flags: int = FLAG_EIM,
        sessions_nbuckets: int = 1 << 14,
        sub_nat_nbuckets: int = 1 << 10,
        stash: int = 64,
        update_slots: int = 512,
        log_sink: Callable[[NATLogEntry], None] | None = None,
    ):
        self.sessions = HostTable(sessions_nbuckets, key_words=4, val_words=SESSION_WORDS, stash=stash, name="nat_sessions")
        self.reverse = HostTable(sessions_nbuckets, key_words=4,
                                 val_words=REVERSE_WORDS, stash=stash,
                                 name="nat_reverse",
                                 # pre-ISSUE-11 checkpoints carried bare
                                 # 4-word key rows; live 8 is a pure pad
                                 compat_val_pad_from=(4,))
        self.sub_nat = HostTable(sub_nat_nbuckets, key_words=1, val_words=SUBNAT_WORDS, stash=stash, name="subscriber_nat")
        self.hairpin = np.zeros((256,), dtype=np.uint32)
        self.alg = np.zeros((64,), dtype=np.uint32)
        self.flags = flags
        self.port_range = port_range
        self.ports_per_subscriber = ports_per_subscriber
        self.public_ips = list(public_ips)
        self.update_slots = update_slots
        self.log_sink = log_sink
        self.geom = NATGeom(
            sessions=TableGeom(sessions_nbuckets, stash),
            reverse=TableGeom(sessions_nbuckets, stash),
            sub_nat=TableGeom(sub_nat_nbuckets, stash),
        )
        # block carving state: per public IP, next block start + free list of
        # released block starts (blocks are uniform size, so reuse is exact)
        self._next_block: dict[int, int] = {ip: port_range[0] for ip in self.public_ips}
        self._free_blocks: dict[int, list[int]] = {ip: [] for ip in self.public_ips}
        self._ip_round_robin = 0
        # EIM host authority: (int_ip, int_port, proto) -> [ext_ip, ext_port, refcount]
        self.eim: dict[tuple[int, int, int], list[int]] = {}
        # allocated external ports: (pub_ip, ext_port, proto) -> eim key
        self._ext_ports: dict[tuple[int, int, int], tuple] = {}
        # per-subscriber block bookkeeping: priv_ip -> dict
        self.blocks: dict[int, dict] = {}
        self._sub_id_seq = 1
        # degraded-verdict counters (Yuan-class hygiene): a refused
        # block carve or port allocation drops the flow by design, but
        # the decision is counted + rate-limit logged, never silent
        self.exhausted = {"block": 0, "port": 0}
        self._exhaust_log = ErrorLog(
            "cgnat", "CGNAT allocator exhausted — flow/subscriber refused")

    # -- logging --
    def _log(self, event: int, sub_id: int, priv_ip: int, pub_ip: int,
             priv_port: int, pub_port: int, dest_ip: int, dest_port: int,
             proto: int, now: int, flags: int = 0) -> None:
        if self.log_sink:
            self.log_sink(NATLogEntry(now, event, sub_id, priv_ip, pub_ip,
                                      priv_port, pub_port, dest_ip, dest_port, proto, flags))

    # -- port block allocation (parity: pkg/nat/manager.go:398-495) --
    def allocate_nat(self, private_ip: int, now: int = 0) -> dict | None:
        """Carve a port block for a subscriber and install subscriber_nat."""
        if private_ip in self.blocks:
            return self.blocks[private_ip]
        n = self.ports_per_subscriber
        for _ in range(len(self.public_ips)):
            pub_ip = self.public_ips[self._ip_round_robin % len(self.public_ips)]
            if self._free_blocks[pub_ip]:
                start = self._free_blocks[pub_ip].pop()
            else:
                start = self._next_block[pub_ip]
                if start + n - 1 > self.port_range[1]:
                    self._ip_round_robin += 1
                    continue
                self._next_block[pub_ip] = start + n
            sub_id = self._sub_id_seq
            self._sub_id_seq += 1
            block = {
                "public_ip": pub_ip,
                "port_start": start,
                "port_end": start + n - 1,
                "next_port": start,
                "subscriber_id": sub_id,
                "private_ip": private_ip,
            }
            self.blocks[private_ip] = block
            row = np.zeros((SUBNAT_WORDS,), dtype=np.uint32)
            row[BV_PUBLIC_IP] = pub_ip
            row[BV_PORT_START] = start
            row[BV_PORT_END] = start + n - 1
            row[BV_NEXT_PORT] = start
            row[BV_SUB_ID] = sub_id
            self.sub_nat.insert([private_ip], row)
            self._log(LOG_PORT_BLOCK_ASSIGN, sub_id, private_ip, pub_ip,
                      0, start, 0, start + n - 1, 0, now)
            return block
        # every public IP's port space is fully carved: the subscriber
        # gets no NAT (degraded verdict) — counted, never silent
        self.exhausted["block"] += 1
        self._exhaust_log.report(
            NATExhaustedError(f"no free port block for {private_ip:#x} "
                              f"across {len(self.public_ips)} public IPs"),
            resource="block")
        return None  # pool exhausted

    def restore_block(self, private_ip: int, public_ip: int,
                      port_start: int, port_end: int, now: int = 0) -> bool:
        """Re-install a subscriber's EXACT port block — the HA-failover
        restore path (failover.go:400-500 consumes the replicated
        SessionState's nat fields): the promoted node must answer for
        the same public mappings the failed active advertised, or every
        established flow's return traffic blackholes. Returns False if
        the block is unknown geometry or already claimed."""
        if private_ip in self.blocks:
            return True  # idempotent
        if public_ip not in self._next_block:
            return False  # not one of OUR public IPs
        if port_end - port_start + 1 != self.ports_per_subscriber:
            return False
        # carve the range out of the allocator's bookkeeping so later
        # fresh allocations can never hand the same ports out again
        if port_start in self._free_blocks[public_ip]:
            self._free_blocks[public_ip].remove(port_start)
        elif port_start >= self._next_block[public_ip]:
            # advance the cursor past the restored block, returning any
            # skipped-over blocks to the free list
            cur = self._next_block[public_ip]
            while cur < port_start:
                self._free_blocks[public_ip].append(cur)
                cur += self.ports_per_subscriber
            self._next_block[public_ip] = port_start + self.ports_per_subscriber
        else:
            return False  # inside an already-allocated region
        sub_id = self._sub_id_seq
        self._sub_id_seq += 1
        block = {
            "public_ip": public_ip,
            "port_start": port_start,
            "port_end": port_end,
            "next_port": port_start,
            "subscriber_id": sub_id,
            "private_ip": private_ip,
        }
        self.blocks[private_ip] = block
        row = np.zeros((SUBNAT_WORDS,), dtype=np.uint32)
        row[BV_PUBLIC_IP] = public_ip
        row[BV_PORT_START] = port_start
        row[BV_PORT_END] = port_end
        row[BV_NEXT_PORT] = port_start
        row[BV_SUB_ID] = sub_id
        self.sub_nat.insert([private_ip], row)
        self._log(LOG_PORT_BLOCK_ASSIGN, sub_id, private_ip, public_ip,
                  0, port_start, 0, port_end, 0, now)
        return True

    def bulk_allocate_nat(self, private_ips, now: int = 0) -> int:
        """Carve port blocks for many subscribers at once (1M-scale build).

        Same carving policy as allocate_nat (round-robin public IPs,
        sequential blocks, free-list reuse) but assembles all subscriber_nat
        rows and installs them with one vectorized bulk_insert instead of a
        per-key Python cuckoo walk. Skips per-block compliance logging —
        this is the bench/restore path, not live allocation. Returns the
        number of blocks created.
        """
        fresh = [int(ip) for ip in private_ips if int(ip) not in self.blocks]
        if not fresh:
            return 0
        n = self.ports_per_subscriber
        keys = np.zeros((len(fresh), 1), dtype=np.uint32)
        rows = np.zeros((len(fresh), SUBNAT_WORDS), dtype=np.uint32)
        made = 0
        for i, priv in enumerate(fresh):
            block = None
            for _ in range(len(self.public_ips)):
                pub_ip = self.public_ips[self._ip_round_robin % len(self.public_ips)]
                if self._free_blocks[pub_ip]:
                    start = self._free_blocks[pub_ip].pop()
                else:
                    start = self._next_block[pub_ip]
                    if start + n - 1 > self.port_range[1]:
                        self._ip_round_robin += 1
                        continue
                    self._next_block[pub_ip] = start + n
                block = {
                    "public_ip": pub_ip, "port_start": start,
                    "port_end": start + n - 1, "next_port": start,
                    "subscriber_id": self._sub_id_seq, "private_ip": priv,
                }
                self._sub_id_seq += 1
                break
            if block is None:
                break  # pool exhausted; remaining rows stay zero and are trimmed
            self.blocks[priv] = block
            keys[made, 0] = priv
            rows[made, BV_PUBLIC_IP] = block["public_ip"]
            rows[made, BV_PORT_START] = block["port_start"]
            rows[made, BV_PORT_END] = block["port_end"]
            rows[made, BV_NEXT_PORT] = block["next_port"]
            rows[made, BV_IN_USE] = 0
            rows[made, BV_SUB_ID] = block["subscriber_id"]
            made += 1
        if made:
            self.sub_nat.bulk_insert(keys[:made], rows[:made])
        return made

    def bulk_flows(self, src_ips, dst_ips, src_ports, dst_ports, protos,
                   pkt_len: int, now: int):
        """Vectorized session+reverse build for bench-scale flow setup.

        Requires blocks already allocated for every src_ip (allocate_nat /
        bulk_allocate_nat) and 5-tuples unique within the batch and fresh.
        Under FLAG_EIM (RFC 4787 endpoint-independent mapping), flows
        sharing an internal endpoint (src_ip, src_port, proto) share ONE
        external mapping — existing EIM mappings are reused and refcounted,
        new endpoints get sequential ports from the subscriber's block.
        Without FLAG_EIM, each flow gets its own port (plain NAPT).
        Parity probing (RFC 4787 port parity) is the live slow path's job
        (handle_new_flow).

        Returns (nat_ips, nat_ports, ok) arrays; ok=False lanes had no
        block or an exhausted block.
        """
        src_ips = np.atleast_1d(np.asarray(src_ips, dtype=np.uint32))
        nf = len(src_ips)
        dst_ips = np.broadcast_to(np.asarray(dst_ips, dtype=np.uint32), (nf,))
        src_ports = np.broadcast_to(np.asarray(src_ports, dtype=np.uint32), (nf,))
        dst_ports = np.broadcast_to(np.asarray(dst_ports, dtype=np.uint32), (nf,))
        protos = np.broadcast_to(np.asarray(protos, dtype=np.uint32), (nf,))
        dstp = np.where(protos == PROTO_ICMP, 0, dst_ports).astype(np.uint32)

        def _assign_sequential(ips_arr):
            """Per-subscriber sequential port assignment for `ips_arr` units.

            Returns (nat_ip, nat_port, ok) per unit and advances next_port.
            """
            nu = len(ips_arr)
            uq, inv = np.unique(ips_arr, return_inverse=True)
            blks = [self.blocks.get(int(ip)) for ip in uq]
            has = np.array([b is not None for b in blks], dtype=bool)
            pub = np.array([b["public_ip"] if b else 0 for b in blks], dtype=np.uint32)
            pend = np.array([b["port_end"] if b else 0 for b in blks], dtype=np.int64)
            pnext = np.array([b["next_port"] if b else 0 for b in blks], dtype=np.int64)
            counts = np.bincount(inv, minlength=len(uq))
            order = np.argsort(inv, kind="stable")
            group_starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
            ranks = np.empty((nu,), dtype=np.int64)
            ranks[order] = np.arange(nu) - np.repeat(group_starts, counts)
            port = pnext[inv] + ranks
            u_ok = has[inv] & (port <= pend[inv])
            u_ip = np.where(u_ok, pub[inv], 0).astype(np.uint32)
            u_port = np.where(u_ok, port, 0).astype(np.uint32)
            for i, b in enumerate(blks):  # advance counters per subscriber
                if b is not None and counts[i]:
                    b["next_port"] = int(min(pnext[i] + counts[i], pend[i] + 1))
            return u_ip, u_port, u_ok

        if self.flags & FLAG_EIM:
            # one external mapping per unique internal endpoint, the
            # endpoints in (address, port, protocol) order; where port and
            # protocol fit their wire widths the three sort as one word
            if not ((src_ports >> 16) | (protos >> 8)).any():
                word = ((src_ips.astype(np.uint64) << np.uint64(24))
                        | (src_ports.astype(np.uint64) << np.uint64(8))
                        | protos.astype(np.uint64))
                uq, ep_inv = np.unique(word, return_inverse=True)
                uq_ep = np.stack([uq >> np.uint64(24),
                                  (uq >> np.uint64(8)) & np.uint64(0xFFFF),
                                  uq & np.uint64(0xFF)], axis=1).astype(np.uint32)
            else:
                ep = np.stack([src_ips, src_ports, protos], axis=1)
                uq_ep, ep_inv = np.unique(ep, axis=0, return_inverse=True)
            n_ep = len(uq_ep)
            keys = list(zip(*(uq_ep[:, c].tolist() for c in range(3))))
            ep_ip = np.zeros((n_ep,), dtype=np.uint32)
            ep_port = np.zeros((n_ep,), dtype=np.uint32)
            reused = np.zeros((n_ep,), dtype=bool)
            if self.eim:  # a fresh manager holds no mapping to reuse
                for j, k in enumerate(keys):
                    m = self.eim.get(k)
                    if m is not None:
                        reused[j] = True
                        ep_ip[j], ep_port[j] = m[0], m[1]
            ep_ok = reused.copy()
            new_j = np.nonzero(~reused)[0]
            if len(new_j):
                n_ip, n_port, n_ok = _assign_sequential(uq_ep[new_j, 0])
                ep_ip[new_j], ep_port[new_j], ep_ok[new_j] = n_ip, n_port, n_ok
            nat_ip = ep_ip[ep_inv]
            nat_port = ep_port[ep_inv]
            ok = ep_ok[ep_inv]
            # refcount bookkeeping per endpoint: the reused ones one by
            # one, the new ones as two bulk dict updates
            ep_counts = np.bincount(ep_inv, minlength=n_ep)
            for j in np.nonzero(reused)[0]:
                self.eim[keys[j]][2] += int(ep_counts[j])
            made = np.nonzero(ep_ok & ~reused)[0]
            made_keys = [keys[j] for j in made.tolist()]
            ext = list(zip(ep_ip[made].tolist(), ep_port[made].tolist()))
            self.eim.update(zip(made_keys, (
                [ip, port, n] for (ip, port), n
                in zip(ext, ep_counts[made].tolist()))))
            self._ext_ports.update(zip(
                ((ip, port, k[2]) for (ip, port), k in zip(ext, made_keys)),
                made_keys))
        else:
            nat_ip, nat_port, ok = _assign_sequential(src_ips)

        sel = np.nonzero(ok)[0]
        if len(sel):
            skey = self._session_keys(src_ips, dst_ips, src_ports, dstp, protos)
            rows, rkey, rrows = self._flow_rows(skey, src_ports, dstp, nat_ip,
                                                nat_port, pkt_len, now)
            self.sessions.bulk_insert(skey[sel], rows[sel])
            self.reverse.bulk_insert(rkey[sel], rrows[sel])
        return nat_ip, nat_port, ok

    def pool_stats(self) -> dict:
        """The pool as an operator needs it before `allocate_nat` returns
        None: addresses owned, port blocks in use, port blocks still free
        (never carved, or released: every carved block is in `blocks` or
        on a free list)."""
        lo, hi = self.port_range
        room = len(self.public_ips) * ((hi - lo + 1) // self.ports_per_subscriber)
        return {"addresses": len(self.public_ips),
                "blocks_used": len(self.blocks),
                "blocks_free": room - len(self.blocks)}

    def release_nat(self, private_ip: int, now: int = 0) -> bool:
        block = self.blocks.pop(private_ip, None)
        if block is None:
            return False
        self.sub_nat.delete([private_ip])
        # drop this subscriber's EIM mappings + sessions
        for key in [k for k in self.eim if k[0] == private_ip]:
            ext_ip, ext_port, _ = self.eim.pop(key)
            self._ext_ports.pop((ext_ip, ext_port, key[2]), None)
        # purge live session + reverse rows before the block can be
        # recycled: a stale reverse row on a reused port would DNAT the
        # new subscriber's inbound traffic to the old private IP
        for s in np.nonzero(self.sessions.used)[0]:
            key = self.sessions.keys[s]
            if int(key[0]) != private_ip:
                continue
            v = self.sessions.vals[s]
            dst_ip, ports, proto_k = int(key[1]), int(key[2]), int(key[3])
            r_src_port = 0 if proto_k == PROTO_ICMP else ports & 0xFFFF
            nat_ip, nat_port = int(v[SV_NAT_IP]), int(v[SV_NAT_PORT])
            self.sessions.delete(key.copy())
            self.reverse.delete(self._key(dst_ip, nat_ip, r_src_port, nat_port, proto_k))
        # return the port block for reuse (RFC 6431 block recycling)
        self._free_blocks.setdefault(block["public_ip"], []).append(
            block["port_start"])
        self._log(LOG_PORT_BLOCK_RELEASE, block["subscriber_id"], private_ip,
                  block["public_ip"], 0, block["port_start"], 0, block["port_end"], 0, now)
        return True

    # -- EIM + port allocation (parity: bpf/nat44.c:408-528, host-exact) --
    def _allocate_port(self, block: dict, orig_port: int, proto: int) -> int:
        parity = self.flags & FLAG_PORT_PARITY
        start, end = block["port_start"], block["port_end"]
        span = end - start + 1
        port = block["next_port"]
        for _ in range(span):
            if port > end:
                port = start
            cand = port
            port += 1
            if parity and ((cand & 1) != (orig_port & 1)):
                continue
            if (block["public_ip"], cand, proto) in self._ext_ports:
                continue
            block["next_port"] = port
            return cand
        return 0  # exhaustion

    def _get_eim(self, int_ip: int, int_port: int, proto: int, block: dict, now: int) -> tuple[int, int] | None:
        key = (int_ip, int_port, proto)
        m = self.eim.get(key)
        if m is not None:
            m[2] += 1
            return m[0], m[1]
        ext_port = self._allocate_port(block, int_port, proto)
        if ext_port == 0:
            return None
        self.eim[key] = [block["public_ip"], ext_port, 1]
        self._ext_ports[(block["public_ip"], ext_port, proto)] = key
        return block["public_ip"], ext_port

    # -- new-flow punt handling (the device's egress-miss path) --
    @staticmethod
    def _key(src_ip, dst_ip, src_port, dst_port, proto):
        return [src_ip, dst_ip, ((src_port & 0xFFFF) << 16) | (dst_port & 0xFFFF), proto]

    @staticmethod
    def _session_keys(src_ips, dst_ips, src_ports, dst_ports, protos) -> np.ndarray:
        """`_key` of many flows: [n, 4] uint32 from uint32 columns."""
        return np.stack(
            [src_ips, dst_ips,
             ((src_ports & 0xFFFF) << np.uint32(16)) | (dst_ports & 0xFFFF),
             protos], axis=1).astype(np.uint32)

    @staticmethod
    def _flow_rows(skey: np.ndarray, src_ports, dst_ports, nat_ip, nat_port,
                   pkt_len, now: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The session rows, reverse keys and reverse rows of new flows with
        session keys `skey`, mapped to (`nat_ip`, `nat_port`): uint32
        columns, an ICMP flow's `dst_ports` already 0, so that its reverse
        key is (0, echo id) (parity: nat44.c:846-851 -- ingress src_port=0,
        dst_port=id)."""
        rows = np.zeros((len(skey), SESSION_WORDS), dtype=np.uint32)
        rows[:, SV_NAT_IP] = nat_ip
        rows[:, SV_NAT_PORT] = nat_port
        rows[:, SV_ORIG_IP] = skey[:, 0]
        rows[:, SV_ORIG_PORT] = src_ports
        rows[:, SV_DEST_IP] = skey[:, 1]
        rows[:, SV_DEST_PORT] = dst_ports
        rows[:, SV_CREATED] = now
        rows[:, SV_LAST_SEEN] = now
        rows[:, SV_STATE] = NAT_STATE_NEW
        rows[:, SV_PROTO] = skey[:, 3]
        rows[:, SV_PKTS_OUT] = 1
        rows[:, SV_BYTES_OUT] = pkt_len
        rkey = np.stack(
            [skey[:, 1], nat_ip,
             ((dst_ports & 0xFFFF) << np.uint32(16)) | (nat_port & 0xFFFF),
             skey[:, 3]], axis=1).astype(np.uint32)
        rrows = np.zeros((len(skey), REVERSE_WORDS), dtype=np.uint32)
        rrows[:, :4] = skey
        return rows, rkey, rrows

    def handle_new_flow(self, src_ip: int, dst_ip: int, src_port: int,
                        dst_port: int, proto: int, pkt_len: int, now: int,
                        is_hairpin: bool = False) -> tuple[int, int] | None:
        """Create session + reverse rows for a punted first packet: the
        batch of one of `handle_new_flows`.

        Returns (nat_ip, nat_port) or None (no allocation / exhaustion).
        ICMP key convention matches the device: egress (echo_id, 0).
        """
        got = self.handle_new_flows([src_ip], [dst_ip], [src_port], [dst_port],
                                    [proto], [pkt_len], now, is_hairpin)[0]
        if isinstance(got, Exception):
            raise got
        return got

    def handle_new_flows(self, src_ips, dst_ips, src_ports, dst_ports, protos,
                         pkt_lens, now: int, is_hairpin: bool = False) -> list:
        """Open the flows a retired window punted, in one batch: the state
        it leaves is the one `handle_new_flow` would, called on each flow
        in lane order.

        The batch's session keys are hashed and probed once: a flow whose
        session exists gets the mapping it holds (a second packet racing
        the apply), a key that repeats inside the batch is created once
        and answered twice. The mapping (EIM, the port from the block) is
        dict work and stays a loop in lane order, so the ports are the
        ones the one-by-one path gives. The session and reverse rows are
        two arrays and one placement a table (`HostTable.insert_many`).
        One compliance record a session, in lane order.

        Returns per flow (nat_ip, nat_port), None (no block, or its block
        is full), or the RuntimeError of a table that had no room for that
        flow's row alone (the rest of the batch is created).
        """
        col = [np.asarray(c, dtype=np.uint32)
               for c in (src_ips, dst_ips, src_ports, dst_ports, protos)]
        col[3] = np.where(col[4] == PROTO_ICMP, 0, col[3]).astype(np.uint32)
        skey = self._session_keys(*col)
        keys = list(map(tuple, skey.tolist()))
        flows = zip(*(c.tolist() for c in col))
        probed = self.sessions.probe(skey)
        held = probed[0].tolist()
        answers: list = [None] * len(keys)
        made: list[int] = []  # the flows to create, in lane order
        first: dict[tuple, int] = {}  # a created flow's key -> its lane
        logs: list[tuple] = []  # (lane, _log's arguments), in lane order
        eim = self.flags & FLAG_EIM
        for i, (src_ip, dst_ip, src_port, dst_port, proto) in enumerate(flows):
            block = self.blocks.get(src_ip)
            if block is None:
                continue
            if held[i] >= 0:
                row = self.sessions.vals[held[i]]
                answers[i] = (int(row[SV_NAT_IP]), int(row[SV_NAT_PORT]))
                continue
            if keys[i] in first:
                answers[i] = answers[first[keys[i]]]
                continue
            if eim:
                got = self._get_eim(src_ip, src_port, proto, block, now)
            else:
                p = self._allocate_port(block, src_port, proto)
                got = (block["public_ip"], p) if p else None
            if got is None:
                logs.append((i, (LOG_PORT_EXHAUSTION, block["subscriber_id"],
                                 src_ip, block["public_ip"], src_port, 0,
                                 dst_ip, dst_port, proto, now)))
                self.exhausted["port"] += 1
                self._exhaust_log.report(
                    NATExhaustedError(f"port block {block['port_start']}-"
                                      f"{block['port_end']} full for subscriber "
                                      f"{block['subscriber_id']}"),
                    resource="port")
                continue
            first[keys[i]] = i
            answers[i] = got
            made.append(i)
            logs.append((i, (LOG_SESSION_CREATE, block["subscriber_id"], src_ip,
                             got[0], src_port, got[1], dst_ip, dst_port, proto,
                             now, 1 if is_hairpin else 0)))
        if made:
            self._place_flows(made, col, skey, probed, answers, pkt_lens, now)
        for i, args in logs:
            if not isinstance(answers[i], Exception):
                self._log(*args)
        return answers

    def _place_flows(self, made: list[int], col: list, skey: np.ndarray,
                     probed: tuple, answers: list, pkt_lens, now: int) -> None:
        """The session and reverse rows of the flows `made` (lanes of the
        batch, their mappings in `answers`) as two arrays, one placement a
        table. A flow a table had no room for gets the error as its answer,
        and a flow without its session row gets no reverse row."""
        sel = np.asarray(made)
        nat = np.array([answers[i] for i in made], dtype=np.uint32)
        rows, rkey, rrows = self._flow_rows(
            skey[sel], col[2][sel], col[3][sel], nat[:, 0], nat[:, 1],
            np.asarray(pkt_lens, dtype=np.uint32)[sel], now)
        walked, failed = self.sessions.insert_many(
            skey[sel], rows, tuple(a[sel] for a in probed))
        kept = [j for j in range(len(made)) if j not in failed]
        r_walked, r_failed = self.reverse.insert_many(rkey[kept], rrows[kept])
        for j, e in r_failed.items():
            failed[kept[j]] = e
        for j, e in failed.items():
            answers[made[j]] = e
        tele.new_flows(creates=1, singles=len(
            set(walked.tolist()) | {kept[j] for j in r_walked.tolist()}))

    # -- expiry (host sweep over device-authoritative last_seen) --
    def expire_sessions(self, now: int, device_vals: np.ndarray | None = None) -> int:
        """Remove idle sessions. device_vals: fetched session value array
        (device-authoritative counters/last_seen); defaults to host mirror.

        The candidate scan is vectorized: per-slot timeouts come from one
        numpy pass over the occupied rows' proto/state words, and the
        Python loop below runs only over the already-expired indices — at
        the 1M-session target a full sweep with a per-slot Python body
        was the cost of the sweep, not the deletions."""
        fp = fault_point("nat.expire")
        if fp is not None and fp.kind == "skew":
            # chaos: the expiry clock jumps (NTP step / host suspend);
            # the sweep must stay consistent in BOTH directions
            now = int(now + fp.arg)
        vals = device_vals if device_vals is not None else self.sessions.vals
        used = self.sessions.used
        expired = 0
        occupied = np.nonzero(used)[0]
        if len(occupied) == 0:
            return 0
        rows = vals[occupied]
        proto_c = rows[:, SV_PROTO]
        state_c = rows[:, SV_STATE]
        last_c = rows[:, SV_LAST_SEEN].astype(np.int64)
        timeout_c = np.full(len(occupied), UDP_TIMEOUT_S, dtype=np.int64)
        timeout_c[proto_c == PROTO_ICMP] = ICMP_TIMEOUT_S
        timeout_c[proto_c == PROTO_TCP] = np.where(
            state_c[proto_c == PROTO_TCP] == 1,
            TCP_EST_TIMEOUT_S, TCP_TRANSIENT_TIMEOUT_S)
        timeout_c = np.where(state_c == NAT_STATE_CLOSING,
                             np.minimum(timeout_c, TCP_TRANSIENT_TIMEOUT_S),
                             timeout_c)
        for s in occupied[(now - last_c) > timeout_c]:
            v = vals[s]
            key = self.sessions.keys[s].copy()
            src_ip, dst_ip = int(key[0]), int(key[1])
            ports = int(key[2])
            proto_k = int(key[3])
            src_port, dst_port = ports >> 16, ports & 0xFFFF
            nat_ip, nat_port = int(v[SV_NAT_IP]), int(v[SV_NAT_PORT])
            self.sessions.delete(key)
            r_src_port = 0 if proto_k == PROTO_ICMP else dst_port
            self.reverse.delete(self._key(dst_ip, nat_ip, r_src_port, nat_port, proto_k))
            # EIM refcount decrement; free the port when unreferenced
            ekey = (src_ip, src_port, proto_k)
            m = self.eim.get(ekey)
            if m is not None:
                m[2] -= 1
                if m[2] <= 0:
                    self.eim.pop(ekey)
                    self._ext_ports.pop((m[0], m[1], proto_k), None)
            blk = self.blocks.get(src_ip)
            self._log(LOG_SESSION_DELETE, blk["subscriber_id"] if blk else 0,
                      src_ip, nat_ip, src_port, nat_port, dst_ip, dst_port, proto_k, now)
            expired += 1
        return expired

    def subscriber_octets(self, device_vals: np.ndarray | None = None
                          ) -> dict[int, tuple[int, int, int, int]]:
        """Per-subscriber (bytes_in, bytes_out, pkts_in, pkts_out) summed
        over live sessions — the per-subscriber counter feed the reference
        reads for interim accounting. device_vals: engine-fetched
        device-authoritative rows (Engine.fetch_session_vals)."""
        vals = device_vals if device_vals is not None else self.sessions.vals
        occ = np.nonzero(self.sessions.used)[0]
        if len(occ) == 0:
            return {}
        rows = vals[occ]
        ips = rows[:, SV_ORIG_IP].astype(np.int64)
        uniq, inv = np.unique(ips, return_inverse=True)
        out: dict[int, tuple[int, int, int, int]] = {}
        sums = [np.bincount(inv, weights=rows[:, w].astype(np.float64),
                            minlength=len(uniq)).astype(np.int64)
                for w in (SV_BYTES_IN, SV_BYTES_OUT, SV_PKTS_IN, SV_PKTS_OUT)]
        for i, ip in enumerate(uniq):
            out[int(ip)] = (int(sums[0][i]), int(sums[1][i]),
                            int(sums[2][i]), int(sums[3][i]))
        return out

    # -- hairpin / ALG config --
    def add_hairpin_ip(self, ip: int) -> None:
        free = np.nonzero(self.hairpin == 0)[0]
        if len(free) == 0:
            raise RuntimeError("hairpin table full")
        self.hairpin[free[0]] = ip

    def add_alg_port(self, port: int, proto: int) -> None:
        free = np.nonzero(self.alg == 0)[0]
        if len(free) == 0:
            raise RuntimeError("alg table full")
        self.alg[free[0]] = ((port & 0xFFFF) << 16) | (proto & 0xFF)

    # -- device sync --
    def config_array(self) -> np.ndarray:
        return np.array([self.flags, self.port_range[0], self.port_range[1],
                         self.ports_per_subscriber], dtype=np.uint32)

    def device_tables(self) -> NATTables:
        return NATTables(
            sessions=self.sessions.device_state(),
            reverse=self.reverse.device_state(),
            sub_nat=self.sub_nat.device_state(),
            hairpin_ips=jnp.asarray(self.hairpin),
            alg_ports=jnp.asarray(self.alg),
            config=jnp.asarray(self.config_array()),
        )

    def make_updates(self) -> tuple:
        return (
            self.sessions.make_update(self.update_slots),
            self.reverse.make_update(self.update_slots),
            self.sub_nat.make_update(self.update_slots),
            *self._placed_config(),
        )

    def _placed_config(self) -> tuple:
        """hairpin / alg / config as every update batch carries them: the
        device copies, placed again when the host's bytes changed
        (ops/table.py placed)."""
        return (placed(self, "hairpin", self.hairpin),
                placed(self, "alg", self.alg),
                placed(self, "config", self.config_array()))

    # -- checkpoint/warm-restart (runtime/checkpoint.py) ----------------
    _CKPT_TABLES = ("sessions", "reverse", "sub_nat")

    def checkpoint_state(self) -> tuple[dict, dict]:
        """(meta, arrays): the three cuckoo mirrors slot-exact, the dense
        hairpin/alg config, and ALL of the Python allocator bookkeeping —
        block cursors, free lists, EIM refcounts, per-subscriber blocks.
        A restore that kept only the table rows would re-hand out ports
        that live sessions still map (the restore_block hazard)."""
        meta = {
            "geom": {t: getattr(self, t).checkpoint_geom()
                     for t in self._CKPT_TABLES},
            "flags": int(self.flags),
            "port_range": list(self.port_range),
            "ports_per_subscriber": int(self.ports_per_subscriber),
            "public_ips": [int(ip) for ip in self.public_ips],
            "next_block": [[int(ip), int(p)]
                           for ip, p in self._next_block.items()],
            "free_blocks": [[int(ip), [int(s) for s in starts]]
                            for ip, starts in self._free_blocks.items()],
            "ip_round_robin": int(self._ip_round_robin),
            "sub_id_seq": int(self._sub_id_seq),
            "eim": [[int(k[0]), int(k[1]), int(k[2]),
                     int(m[0]), int(m[1]), int(m[2])]
                    for k, m in self.eim.items()],
            "blocks": [[int(ip), int(b["public_ip"]), int(b["port_start"]),
                        int(b["port_end"]), int(b["next_port"]),
                        int(b["subscriber_id"])]
                       for ip, b in self.blocks.items()],
        }
        arrays = {f"{t}.{k}": v
                  for t in self._CKPT_TABLES
                  for k, v in getattr(self, t).checkpoint_arrays().items()}
        arrays["hairpin"] = self.hairpin
        arrays["alg"] = self.alg
        return meta, arrays

    @staticmethod
    def parse_checkpoint_meta(meta: dict) -> dict:
        """Parse/validate the checkpointed allocator bookkeeping into
        plain structures WITHOUT touching self. The restore pre-check
        runs this before any mirror mutates (KeyError/ValueError/
        TypeError propagate to the all-or-nothing gate); restore_state
        applies the result."""
        return {
            "flags": int(meta["flags"]),
            "port_range": (int(meta["port_range"][0]),
                           int(meta["port_range"][1])),
            "ports_per_subscriber": int(meta["ports_per_subscriber"]),
            "public_ips": [int(ip) for ip in meta["public_ips"]],
            "next_block": {int(ip): int(p) for ip, p in meta["next_block"]},
            "free_blocks": {int(ip): [int(s) for s in starts]
                            for ip, starts in meta["free_blocks"]},
            "ip_round_robin": int(meta["ip_round_robin"]),
            "sub_id_seq": int(meta["sub_id_seq"]),
            "eim": {(int(a), int(b), int(c)): [int(d), int(e), int(f)]
                    for a, b, c, d, e, f in meta["eim"]},
            "blocks": {
                int(ip): {"public_ip": int(pub), "port_start": int(start),
                          "port_end": int(end), "next_port": int(nxt),
                          "subscriber_id": int(sid), "private_ip": int(ip)}
                for ip, pub, start, end, nxt, sid in meta["blocks"]},
        }

    def restore_state(self, meta: dict, arrays: dict) -> dict[str, int]:
        """Hydrate from a checkpoint (reject-on-mismatch on table
        geometry). NAT policy knobs (flags, port range, public IPs) come
        from the checkpoint — the restored mappings are only valid under
        the configuration that created them. Caller must follow with a
        full device upload (resync_tables)."""
        parsed = self.parse_checkpoint_meta(meta)  # parse BEFORE mutating
        rows = {}
        for t in self._CKPT_TABLES:
            rows[t] = getattr(self, t).restore_arrays(
                {k: arrays[f"{t}.{k}"] for k in ("keys", "vals", "used")},
                meta["geom"][t])
        self.hairpin[:] = arrays["hairpin"]
        self.alg[:] = arrays["alg"]
        self.flags = parsed["flags"]
        self.port_range = parsed["port_range"]
        self.ports_per_subscriber = parsed["ports_per_subscriber"]
        self.public_ips = parsed["public_ips"]
        self._next_block = parsed["next_block"]
        self._free_blocks = parsed["free_blocks"]
        self._ip_round_robin = parsed["ip_round_robin"]
        self._sub_id_seq = parsed["sub_id_seq"]
        self.eim = parsed["eim"]
        # _ext_ports is derived state: rebuild, never trust two copies
        self._ext_ports = {(m[0], m[1], k[2]): k
                           for k, m in self.eim.items()}
        self.blocks = parsed["blocks"]
        rows["blocks"] = len(self.blocks)
        rows["eim"] = len(self.eim)
        return rows

    def empty_updates(self) -> tuple:
        """No-op table-delta batch (dirty tracking untouched) for the
        scheduler's no-drain bulk steps; pending session deltas stay
        queued for the next drain-cadence step. The scatter buffers come
        from the empty_update caches; hairpin/alg/config are compared
        with what was last placed on every call, because the step
        applies them wholesale (a snapshot taken once would revert live
        NAT config between drains)."""
        return (
            self.sessions.empty_update(self.update_slots),
            self.reverse.empty_update(self.update_slots),
            self.sub_nat.empty_update(self.update_slots),
            *self._placed_config(),
        )
