"""Host-side fast-path table management — the pkg/ebpf/loader.go role.

The reference's Loader owns typed Go mirrors of every eBPF map and all CRUD
(pkg/ebpf/loader.go:74-661: AddSubscriber, AddPool, SetServerConfig,
circuit-ID ops). Here the same surface manages numpy mirrors of the HBM
cuckoo tables plus the dense pool/server-config arrays, and emits bounded
TableUpdate batches that the jitted device step scatters into HBM — the
replacement for bpf_map_update_elem syscalls.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from bng_tpu.ops.dhcp import (
    ASSIGN_WORDS,
    POOL_WORDS,
    SERVER_WORDS,
    AV_POOL_ID,
    AV_IP,
    AV_VLAN,
    AV_CLASS,
    AV_LEASE_EXP,
    AV_FLAGS,
    PV_NETWORK,
    PV_PREFIX,
    PV_GATEWAY,
    PV_DNS1,
    PV_DNS2,
    PV_LEASE_T,
    PV_VALID,
    SC_MAC_HI,
    SC_MAC_LO,
    SC_IP,
    CID_KEY_LEN,
    DHCPGeom,
    DHCPTables,
)
from bng_tpu.ops.antispoof import (AB_IPV4, AB_MODE, AB_V6_0, AB_VALIDS,
                                    VALID_V6)
from bng_tpu.ops.pppoe import (
    PPPOE_WORDS,
    PS_IP,
    PS_MAC_HI,
    PS_MAC_LO,
    PS_SESSION_ID,
)
from bng_tpu.ops.qinq import QINQ_WORDS, QV_C_TAG, QV_S_TAG
from bng_tpu.ops.table import (HostTable, TableGeom, TableUpdate,
                               apply_update, placed)
from bng_tpu.ops.v6 import V6_WORDS, VA_IPV4, VA_MAC_HI, VA_MAC_LO
from bng_tpu.utils.net import mac_to_u64, split_u64


def mac_key_rows(macs_u64) -> np.ndarray:
    """[N] MAC-as-u64 -> [N, 2] (hi, lo) uint32 key rows, the layout every
    MAC-keyed table probes with (bulk twin of utils.net.split_u64)."""
    macs_u64 = np.asarray(macs_u64, dtype=np.uint64)
    return np.stack([(macs_u64 >> np.uint64(32)).astype(np.uint32),
                     (macs_u64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)],
                    axis=1)


def pack_cid_host(circuit_id: bytes) -> np.ndarray:
    """32-byte (padded/truncated) circuit-id -> 8 big-endian uint32 words.

    Must match ops.dhcp.pack_cid_words; parity with the fixed 32-byte key of
    bpf/maps.h:216-220 (truncate long, zero-pad short).
    """
    buf = (circuit_id[:CID_KEY_LEN] + b"\x00" * CID_KEY_LEN)[:CID_KEY_LEN]
    return np.frombuffer(buf, dtype=">u4").astype(np.uint32)


class FastPathUpdates(NamedTuple):
    """Per-step bounded update batch for all DHCP-path tables (pytree)."""

    sub: TableUpdate
    vlan: TableUpdate
    cid: TableUpdate
    pools: jax.Array  # [P, POOL_WORDS] full (tiny) refresh
    server: jax.Array  # [SERVER_WORDS]


def apply_fastpath_updates(tables: DHCPTables, upd: FastPathUpdates) -> DHCPTables:
    """Jit-side application of one update batch."""
    return DHCPTables(
        sub=apply_update(tables.sub, upd.sub),
        vlan=apply_update(tables.vlan, upd.vlan),
        cid=apply_update(tables.cid, upd.cid),
        pools=upd.pools,
        server=upd.server,
    )


class FastPathTables:
    """Host authority for subscriber/VLAN/circuit-ID/pool/server tables."""

    def __init__(
        self,
        sub_nbuckets: int = 1 << 15,
        vlan_nbuckets: int = 1 << 12,
        cid_nbuckets: int = 1 << 12,
        max_pools: int = 256,
        stash: int = 64,
        update_slots: int = 256,
    ):
        self.sub = HostTable(sub_nbuckets, key_words=2, val_words=ASSIGN_WORDS, stash=stash, name="subscriber_pools")
        self.vlan = HostTable(vlan_nbuckets, key_words=1, val_words=ASSIGN_WORDS, stash=stash, name="vlan_subscriber_pools")
        self.cid = HostTable(cid_nbuckets, key_words=8, val_words=ASSIGN_WORDS, stash=stash, name="circuit_id_subscribers")
        self.pools = np.zeros((max_pools, POOL_WORDS), dtype=np.uint32)
        self.server = np.zeros((SERVER_WORDS,), dtype=np.uint32)
        self.update_slots = update_slots
        self.geom = DHCPGeom(
            sub=TableGeom(sub_nbuckets, stash),
            vlan=TableGeom(vlan_nbuckets, stash),
            cid=TableGeom(cid_nbuckets, stash),
        )

    # -- CRUD (parity: pkg/ebpf/loader.go AddSubscriber :352, AddPool :402,
    #    SetServerConfig :444, AddVLANSubscriber :470, circuit-ID ops :556+) --
    @staticmethod
    def _assignment(pool_id, ip, lease_expiry, vlan_id, client_class, flags):
        v = np.zeros((ASSIGN_WORDS,), dtype=np.uint32)
        v[AV_POOL_ID] = pool_id
        v[AV_IP] = ip
        v[AV_VLAN] = vlan_id
        v[AV_CLASS] = client_class
        v[AV_LEASE_EXP] = lease_expiry
        v[AV_FLAGS] = flags
        return v

    @staticmethod
    def _assignments(n, pool_ids, ips, lease_expiries, vlan_ids,
                     client_classes, flags) -> np.ndarray:
        """`_assignment` for a bulk build: [n, ASSIGN_WORDS] rows."""
        vals = np.zeros((n, ASSIGN_WORDS), dtype=np.uint32)
        vals[:, AV_POOL_ID] = pool_ids
        vals[:, AV_IP] = ips
        vals[:, AV_VLAN] = vlan_ids
        vals[:, AV_CLASS] = client_classes
        vals[:, AV_LEASE_EXP] = lease_expiries
        vals[:, AV_FLAGS] = flags
        return vals

    def add_subscriber(self, mac, pool_id: int, ip: int, lease_expiry: int,
                       vlan_id: int = 0, client_class: int = 0, flags: int = 0) -> None:
        key = mac_to_u64(mac) if not isinstance(mac, int) else mac
        lo, hi = split_u64(key)
        self.sub.insert([hi, lo], self._assignment(pool_id, ip, lease_expiry, vlan_id, client_class, flags))

    def add_subscribers_bulk(self, macs_u64, pool_ids, ips, lease_expiries,
                             vlan_ids=0, client_classes=0, flags=0) -> None:
        """Vectorized batch insert for reference-scale table builds.

        The reference sizes subscriber maps for 1M entries
        (/root/reference/bpf/maps.h:10); a per-subscriber Python insert loop
        makes that infeasible, so the bench/restore path assembles key/value
        arrays and hands them to HostTable.bulk_insert (8 vectorized
        placement passes). MACs must be unique and not already present.
        Follow with device_tables() for a full upload.
        """
        keys = mac_key_rows(macs_u64)
        self.sub.bulk_insert(keys, self._assignments(
            len(keys), pool_ids, ips, lease_expiries, vlan_ids,
            client_classes, flags))

    def remove_subscriber(self, mac) -> bool:
        key = mac_to_u64(mac) if not isinstance(mac, int) else mac
        lo, hi = split_u64(key)
        return self.sub.delete([hi, lo])

    def get_subscriber(self, mac):
        key = mac_to_u64(mac) if not isinstance(mac, int) else mac
        lo, hi = split_u64(key)
        return self.sub.lookup([hi, lo])

    def add_vlan_subscriber(self, s_tag: int, c_tag: int, pool_id: int, ip: int,
                            lease_expiry: int, client_class: int = 0, flags: int = 0) -> None:
        self.vlan.insert([(s_tag << 16) | c_tag],
                         self._assignment(pool_id, ip, lease_expiry, 0, client_class, flags))

    def add_vlan_subscribers_bulk(self, s_tags, c_tags, pool_ids, ips,
                                  lease_expiries, client_classes=0,
                                  flags=0) -> None:
        """`add_subscribers_bulk`'s twin for `vlan_subscriber_pools`: a
        row a {s_tag, c_tag} pair, the first tier of the device's lookup
        (pairs unique and not already present)."""
        keys = ((np.asarray(s_tags, dtype=np.uint32) << np.uint32(16))
                | np.asarray(c_tags, dtype=np.uint32))
        self.vlan.bulk_insert(keys[:, None], self._assignments(
            len(keys), pool_ids, ips, lease_expiries, 0, client_classes,
            flags))

    def remove_vlan_subscriber(self, s_tag: int, c_tag: int) -> bool:
        return self.vlan.delete([(s_tag << 16) | c_tag])

    def add_circuit_id_subscriber(self, circuit_id: bytes, pool_id: int, ip: int,
                                  lease_expiry: int, client_class: int = 0, flags: int = 0) -> None:
        self.cid.insert(pack_cid_host(circuit_id),
                        self._assignment(pool_id, ip, lease_expiry, 0, client_class, flags))

    def remove_circuit_id_subscriber(self, circuit_id: bytes) -> bool:
        return self.cid.delete(pack_cid_host(circuit_id))

    def add_pool(self, pool_id: int, network: int, prefix_len: int, gateway: int,
                 dns_primary: int = 0, dns_secondary: int = 0, lease_time: int = 3600) -> None:
        if pool_id >= len(self.pools):
            raise ValueError(f"pool_id {pool_id} >= max_pools {len(self.pools)}")
        row = self.pools[pool_id]
        row[PV_NETWORK] = network
        row[PV_PREFIX] = prefix_len
        row[PV_GATEWAY] = gateway
        row[PV_DNS1] = dns_primary
        row[PV_DNS2] = dns_secondary
        row[PV_LEASE_T] = lease_time
        row[PV_VALID] = 1

    def remove_pool(self, pool_id: int) -> None:
        self.pools[pool_id] = 0

    def set_server_config(self, mac, ip: int) -> None:
        key = mac_to_u64(mac) if not isinstance(mac, int) else mac
        lo, hi = split_u64(key)
        self.server[SC_MAC_HI] = hi
        self.server[SC_MAC_LO] = lo
        self.server[SC_IP] = ip

    def touch_lease(self, mac, lease_expiry: int) -> bool:
        """Refresh a subscriber's lease expiry in place."""
        key = mac_to_u64(mac) if not isinstance(mac, int) else mac
        lo, hi = split_u64(key)
        return self.sub.update_val_words([hi, lo], AV_LEASE_EXP, [lease_expiry])

    # -- device sync --
    def device_tables(self) -> DHCPTables:
        """Full upload (startup)."""
        return DHCPTables(
            sub=self.sub.device_state(),
            vlan=self.vlan.device_state(),
            cid=self.cid.device_state(),
            pools=jnp.asarray(self.pools),
            server=jnp.asarray(self.server),
        )

    def make_updates(self) -> FastPathUpdates:
        """Drain dirty slots into one bounded per-step update batch."""
        return FastPathUpdates(
            sub=self.sub.make_update(self.update_slots),
            vlan=self.vlan.make_update(self.update_slots),
            cid=self.cid.make_update(self.update_slots),
            pools=placed(self, "pools", self.pools),
            server=placed(self, "server", self.server),
        )

    def empty_updates(self) -> FastPathUpdates:
        """A no-op table-delta batch that does NOT consume dirty tracking.

        The latency scheduler's bulk lane passes this on every step: the
        express lane is the single consumer of the real fastpath drain
        (one authoritative device DHCP chain), and the bulk lane's DHCP
        leaves are a read replica. The sub/vlan/cid scatter buffers are
        cached; pools/server are compared with what was last placed on
        every call (ops/table.py placed) — the step applies those dense
        arrays wholesale, so the replica tracks live pool/server config
        even between replica refreshes."""
        return FastPathUpdates(
            sub=self.sub.empty_update(self.update_slots),
            vlan=self.vlan.empty_update(self.update_slots),
            cid=self.cid.empty_update(self.update_slots),
            pools=placed(self, "pools", self.pools),
            server=placed(self, "server", self.server),
        )

    def dirty_count(self) -> int:
        return self.sub.dirty_count() + self.vlan.dirty_count() + self.cid.dirty_count()

    # -- checkpoint/warm-restart (runtime/checkpoint.py) ----------------
    _CKPT_TABLES = ("sub", "vlan", "cid")

    def checkpoint_state(self) -> tuple[dict, dict]:
        """(meta, arrays) for the whole DHCP fast-path authority: the
        three cuckoo mirrors slot-exact plus the dense pool/server
        config. Array keys are '<table>.<array>' namespaced."""
        meta = {"geom": {t: getattr(self, t).checkpoint_geom()
                         for t in self._CKPT_TABLES},
                "max_pools": len(self.pools)}
        arrays = {f"{t}.{k}": v
                  for t in self._CKPT_TABLES
                  for k, v in getattr(self, t).checkpoint_arrays().items()}
        arrays["pools"] = self.pools
        arrays["server"] = self.server
        return meta, arrays

    def restore_state(self, meta: dict, arrays: dict) -> dict[str, int]:
        """Hydrate from a checkpoint; ValueError on geometry mismatch.
        Caller must follow with a full device upload (resync_tables)."""
        rows = {}
        for t in self._CKPT_TABLES:
            rows[t] = getattr(self, t).restore_arrays(
                {k: arrays[f"{t}.{k}"] for k in ("keys", "vals", "used")},
                meta["geom"][t])
        if arrays["pools"].shape != self.pools.shape:
            raise ValueError(
                f"checkpoint pools shape {arrays['pools'].shape} != "
                f"{self.pools.shape}")
        self.pools[:] = arrays["pools"]
        self.server[:] = arrays["server"]
        rows["pools"] = int(np.count_nonzero(self.pools[:, PV_VALID]))
        return rows


class PPPoEFastPathTables:
    """Host side of the device PPPoE session tables (ops.pppoe).

    The PPPoE control plane (control.pppoe.server) negotiates sessions in
    userspace; established sessions are published here so session-stage
    DATA frames decap/encap on device. session_up/session_down plug
    directly into PPPoEServer's on_open/on_close hooks — the same
    slow-path-populates-cache shape as DHCP's updateFastPathCache
    (pkg/dhcp/server.go:1057-1097).
    """

    def __init__(self, nbuckets: int = 1 << 12, stash: int = 64,
                 update_slots: int = 128,
                 server_mac: bytes = b"\x02\xbb\x00\x00\x00\x01"):
        # pre-ISSUE-11 checkpoints carried 6-word session rows; live 8 is
        # a pure zero-pad (PS_* indices unchanged) — warm restarts keep
        # working across the widening
        self.by_sid = HostTable(nbuckets, key_words=1, val_words=PPPOE_WORDS,
                                stash=stash, name="pppoe_by_sid",
                                compat_val_pad_from=(6,))
        self.by_ip = HostTable(nbuckets, key_words=1, val_words=PPPOE_WORDS,
                               stash=stash, name="pppoe_by_ip",
                               compat_val_pad_from=(6,))
        self.geom = TableGeom(nbuckets, stash)
        self.update_slots = update_slots
        # AC MAC, stamped as L2 source of every encapped downstream frame
        # (pppoe_encap's server_mac argument — (hi16, lo32) words)
        self.server_mac = np.array(
            [int.from_bytes(server_mac[:2], "big"),
             int.from_bytes(server_mac[2:], "big")], dtype=np.uint32)

    def session_up(self, sess) -> None:
        """on_open hook: publish an OPEN session to the device tables."""
        row = np.zeros((PPPOE_WORDS,), dtype=np.uint32)
        row[PS_SESSION_ID] = sess.session_id
        row[PS_MAC_HI] = int.from_bytes(sess.client_mac[:2], "big")
        row[PS_MAC_LO] = int.from_bytes(sess.client_mac[2:], "big")
        row[PS_IP] = sess.assigned_ip or 0
        self.by_sid.insert([sess.session_id], row)
        if sess.assigned_ip:
            self.by_ip.insert([sess.assigned_ip], row)

    def sessions_up_bulk(self, session_ids, macs_u64, ips) -> None:
        """Publish many OPEN sessions at once (a warm restart, a takeover,
        a provisioning run): `session_up`'s rows through `bulk_insert`.
        As after any bulk build, the next upload is a whole one
        (`Engine.resync_tables`)."""
        sids = np.asarray(session_ids, dtype=np.uint32)
        ips = np.asarray(ips, dtype=np.uint32)
        rows = np.zeros((len(sids), PPPOE_WORDS), dtype=np.uint32)
        rows[:, PS_SESSION_ID] = sids
        rows[:, [PS_MAC_HI, PS_MAC_LO]] = mac_key_rows(macs_u64)
        rows[:, PS_IP] = ips
        self.by_sid.bulk_insert(sids[:, None], rows)
        self.by_ip.bulk_insert(ips[:, None], rows)

    def session_down(self, event) -> None:
        """on_close hook (takes the server's TeardownEvent)."""
        sess = getattr(event, "session", event)
        self.by_sid.delete([sess.session_id])
        if sess.assigned_ip:
            self.by_ip.delete([sess.assigned_ip])

    def make_updates(self):
        return (self.by_sid.make_update(self.update_slots),
                self.by_ip.make_update(self.update_slots))

    def empty_updates(self):
        """No-op update pair for scheduler no-drain bulk steps (cached)."""
        return (self.by_sid.empty_update(self.update_slots),
                self.by_ip.empty_update(self.update_slots))

    # -- checkpoint/warm-restart (runtime/checkpoint.py) ----------------
    def checkpoint_state(self) -> tuple[dict, dict]:
        meta = {"geom": {"by_sid": self.by_sid.checkpoint_geom(),
                         "by_ip": self.by_ip.checkpoint_geom()}}
        arrays = {f"{t}.{k}": v
                  for t in ("by_sid", "by_ip")
                  for k, v in getattr(self, t).checkpoint_arrays().items()}
        arrays["server_mac"] = self.server_mac
        return meta, arrays

    def restore_state(self, meta: dict, arrays: dict) -> dict[str, int]:
        rows = {}
        for t in ("by_sid", "by_ip"):
            rows[t] = getattr(self, t).restore_arrays(
                {k: arrays[f"{t}.{k}"] for k in ("keys", "vals", "used")},
                meta["geom"][t])
        self.server_mac[:] = arrays["server_mac"]
        return rows


class QinQFastPathTables:
    """Host side of the device `qinq` stage (ops/qinq.py): the table from a
    subscriber's IPv4 address to its S- and C-tag, and the one writer of it.

    Every write goes through `registry` (control/qinq.py `QinQMapper`, the
    pkg/qinq registry): it holds which subscriber has which pair, refuses a
    pair another subscriber holds and a pair outside the configured ranges,
    and moves a subscriber that comes up on another line. The subscriber's
    id there is its address. A lease (`DHCPServer`, where it writes
    `vlan_subscriber_pools`) and a PPPoE session (`session_up`'s caller)
    land in `bind` / `unbind`; `bulk_bind` is the same write for a
    provisioning run or a warm restart at the subscriber tables' size.
    """

    def __init__(self, nbuckets: int = 1 << 12, stash: int = 64,
                 update_slots: int = 128):
        from bng_tpu.control.qinq import QinQConfig, QinQMapper

        self.by_ip = HostTable(nbuckets, key_words=1, val_words=QINQ_WORDS,
                               stash=stash, name="qinq_by_ip")
        self.geom = TableGeom(nbuckets, stash)
        self.update_slots = update_slots
        # the device pushes two tags or none: a single tag registers nowhere
        self.registry = QinQMapper(QinQConfig(allow_single_tagged=False))
        self.refused = 0  # binds the registry said no to

    @staticmethod
    def _rows(s_tags, c_tags) -> np.ndarray:
        rows = np.zeros((len(s_tags), QINQ_WORDS), dtype=np.uint32)
        rows[:, QV_S_TAG] = s_tags
        rows[:, QV_C_TAG] = c_tags
        return rows

    def bind(self, ip: int, s_tag: int, c_tag: int) -> bool:
        """The subscriber at `ip` is behind this pair from now on (a pair
        it held before is free again). False, and nothing written: the
        registry refused the pair; the subscriber is served untagged and
        the device counts its downstream frames as misses."""
        from bng_tpu.control.qinq import VLANPair

        try:
            self.registry.register(VLANPair(int(s_tag), int(c_tag)), int(ip))
        except ValueError:
            self.refused += 1
            return False
        self.by_ip.insert([ip], self._rows([s_tag], [c_tag])[0])
        return True

    def unbind(self, ip: int) -> bool:
        """Release, expiry or session down. False: no pair was held."""
        self.registry.unregister_subscriber(int(ip))
        return self.by_ip.delete([ip])

    def pair_of(self, ip: int) -> tuple[int, int] | None:
        row = self.by_ip.lookup([ip])
        return None if row is None else (int(row[QV_S_TAG]), int(row[QV_C_TAG]))

    def bulk_bind(self, ips, s_tags, c_tags) -> None:
        """A pair each for subscribers that hold none yet, at the
        1M-subscriber scale. As after any bulk build, the next upload is
        a whole one (`Engine.resync_tables`)."""
        ips = np.asarray(ips, dtype=np.uint32)
        s_tags = np.asarray(s_tags, dtype=np.uint32)
        c_tags = np.asarray(c_tags, dtype=np.uint32)
        self.registry.register_bulk(s_tags, c_tags, ips)
        self.by_ip.bulk_insert(ips[:, None], self._rows(s_tags, c_tags))

    # -- checkpoint/warm-restart (runtime/checkpoint.py) ----------------
    def checkpoint_state(self) -> tuple[dict, dict]:
        return ({"geom": {"by_ip": self.by_ip.checkpoint_geom()}},
                {f"by_ip.{k}": v
                 for k, v in self.by_ip.checkpoint_arrays().items()})

    def restore_state(self, meta: dict, arrays: dict) -> dict[str, int]:
        """The table slot for slot, and the registry rebuilt from it."""
        rows = self.by_ip.restore_arrays(
            {k: arrays[f"by_ip.{k}"] for k in ("keys", "vals", "used")},
            meta["geom"]["by_ip"])
        live = np.nonzero(self.by_ip.used)[0]
        self.registry.clear()
        self.registry.register_bulk(self.by_ip.vals[live, QV_S_TAG],
                                    self.by_ip.vals[live, QV_C_TAG],
                                    self.by_ip.keys[live, 0])
        return {"by_ip": rows}


def v6_words(addr) -> np.ndarray:
    """A 16-byte IPv6 address -> 4 big-endian uint32 words."""
    return np.frombuffer(bytes(addr), dtype=">u4").astype(np.uint32)


class V6FastPathTables:
    """Host side of the device IPv6 stage (ops/v6.py): a subscriber's IA_NA
    /128 lives in two places, and this is the one writer of both — the v6
    words of the MAC's antispoof binding row (the upstream check, and the
    row's IPv4 address as QoS key) and the by-address table (the downstream
    lookup, whose value is that IPv4 address).

    The DHCPv6 server's lease hooks land here (`bind` / `unbind`: the
    slow-path-populates-cache shape of pkg/antispoof/manager.go:200-283
    AddBindingV6), and `bulk_bind` is the same write for a provisioning run
    or a warm restart at the subscriber tables' size.
    """

    def __init__(self, antispoof, nbuckets: int = 1 << 12, stash: int = 64,
                 update_slots: int = 128):
        self.antispoof = antispoof  # runtime.engine.AntispoofTables
        self.by_addr = HostTable(nbuckets, key_words=4, val_words=V6_WORDS,
                                 stash=stash, name="v6_by_addr")
        self.geom = TableGeom(nbuckets, stash)
        self.update_slots = update_slots

    @staticmethod
    def _addr_rows(ipv4s, macs_u64) -> np.ndarray:
        rows = np.zeros((len(macs_u64), V6_WORDS), dtype=np.uint32)
        rows[:, VA_IPV4] = ipv4s
        rows[:, [VA_MAC_HI, VA_MAC_LO]] = mac_key_rows(macs_u64)
        return rows

    def bind(self, mac, addr: bytes, ipv4: int = 0) -> None:
        """One IA_NA lease: the MAC's binding row gets the /128 (keeping
        the row's mode, or the default mode for a MAC bound for the first
        time) and the by-address row appears. A MAC that held another /128
        is renumbered: the old address stops matching with this update.
        `ipv4` names the subscriber's QoS buckets where the binding row
        holds no IPv4 address yet."""
        key = mac_to_u64(mac) if not isinstance(mac, int) else int(mac)
        lo, hi = split_u64(key)
        bindings = self.antispoof.bindings
        row = bindings.lookup([hi, lo])
        if row is not None and row[AB_VALIDS] & VALID_V6:
            self.by_addr.delete(row[AB_V6_0:AB_V6_0 + 4])
        mode = int(row[AB_MODE]) if row is not None else int(
            self.antispoof.config[0])
        words = v6_words(addr)
        self.antispoof.add_binding_v6(key, words, mode)
        if row is None or not row[AB_IPV4]:
            bindings.update_val_words([hi, lo], AB_IPV4, [ipv4])
        else:
            ipv4 = int(row[AB_IPV4])
        self.by_addr.insert(words, self._addr_rows([ipv4], [key])[0])

    def unbind(self, addr: bytes) -> bool:
        """Release, decline or expiry of one IA_NA lease: the by-address
        row goes, and the binding row it names loses its v6 words (a row
        that held nothing else goes whole). False: no such address."""
        words = v6_words(addr)
        val = self.by_addr.lookup(words)
        if val is None:
            return False
        self.by_addr.delete(words)
        key = [val[VA_MAC_HI], val[VA_MAC_LO]]
        bindings = self.antispoof.bindings
        row = bindings.lookup(key)
        if row is not None and np.array_equal(row[AB_V6_0:AB_V6_0 + 4], words):
            row[AB_V6_0:AB_V6_0 + 4] = 0
            row[AB_VALIDS] &= ~np.uint32(VALID_V6)
            if row[AB_VALIDS]:
                bindings.insert(key, row)
            else:
                bindings.delete(key)
        return True

    def bulk_bind(self, macs_u64, ipv4s, addr_words, mode: int) -> None:
        """Dual-stack bindings for MACs not bound yet, at the
        1M-subscriber scale: each antispoof row with both addresses and
        each by-address row in one vectorized pass. As after any bulk
        build, the next upload is a whole one (`Engine.resync_tables`)."""
        addr_words = np.asarray(addr_words, dtype=np.uint32).reshape(-1, 4)
        self.antispoof.bulk_add_bindings(macs_u64, ipv4s, mode,
                                         ipv6_words=addr_words)
        self.by_addr.bulk_insert(addr_words,
                                 self._addr_rows(ipv4s, macs_u64))

    # -- checkpoint/warm-restart (runtime/checkpoint.py): the binding rows
    # ride the antispoof component; this is the by-address table --------
    def checkpoint_state(self) -> tuple[dict, dict]:
        return ({"geom": {"by_addr": self.by_addr.checkpoint_geom()}},
                {f"by_addr.{k}": v
                 for k, v in self.by_addr.checkpoint_arrays().items()})

    def restore_state(self, meta: dict, arrays: dict) -> dict[str, int]:
        return {"by_addr": self.by_addr.restore_arrays(
            {k: arrays[f"by_addr.{k}"] for k in ("keys", "vals", "used")},
            meta["geom"]["by_addr"])}
