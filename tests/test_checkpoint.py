"""Checkpoint/warm-restart subsystem tests.

Fast tier: binary format gates (truncation, checksum, schema), the
versioned store's atomic-rename/fallback/prune behavior, host-mirror
round trips without a device program, HA bootstrap-then-replay, the
periodic cadence (including the never-raise failure path), and the
vectorized NAT expiry sweep.

Slow tier (-m slow / make verify-slow): the full engine round trip —
DORA + NAT flow through the fused pipeline, snapshot at the quiesce
barrier, restore into a FRESH engine, and fast-path parity with zero
slow-path DHCP exchanges.
"""

import json
import struct

import numpy as np
import pytest

from bng_tpu.control.dhcp_server import DHCPServer, Lease
from bng_tpu.control.ha import (ActiveSyncer, InMemorySessionStore,
                                SessionState, StandbySyncer)
from bng_tpu.control.nat import (ICMP_TIMEOUT_S, NATManager,
                                 TCP_EST_TIMEOUT_S, TCP_TRANSIENT_TIMEOUT_S,
                                 UDP_TIMEOUT_S)
from bng_tpu.control.pool import Pool, PoolManager
from bng_tpu.control.statestore import CheckpointStore, PeriodicCheckpointer
from bng_tpu.ops.nat44 import (NAT_STATE_CLOSING, SV_LAST_SEEN, SV_PROTO,
                               SV_STATE)
from bng_tpu.ops.parse import PROTO_ICMP, PROTO_TCP, PROTO_UDP
from bng_tpu.runtime.checkpoint import (MAGIC, Checkpoint, CheckpointError,
                                        build_checkpoint, decode_checkpoint,
                                        encode_checkpoint,
                                        restore_checkpoint)
from bng_tpu.runtime.tables import FastPathTables, PPPoEFastPathTables
from bng_tpu.utils.net import ip_to_u32, mac_to_u64, parse_mac

SERVER_MAC = bytes.fromhex("02aabbccdd01")
SERVER_IP = ip_to_u32("10.0.0.1")
T0 = 1_753_000_000


class FakeClock:
    def __init__(self, t=T0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _sample_ckpt():
    return Checkpoint(
        meta={"seq": 7, "created_at": 123.5, "node_id": "n1",
              "components": {}},
        arrays={"a": np.arange(12, dtype=np.uint32).reshape(3, 4),
                "b": np.ones((5,), dtype=np.uint8)})


def _patch_header(data: bytes, **fields) -> bytes:
    """Re-write header fields (forging schema versions etc.) keeping the
    payload bytes identical; the header CRC is recomputed so only the
    forged FIELD trips validation, not the checksum."""
    import zlib

    hlen, _ = struct.unpack_from("<II", data, len(MAGIC))
    start = len(MAGIC) + 8
    hdr = json.loads(data[start : start + hlen])
    hdr.update(fields)
    new = json.dumps(hdr, separators=(",", ":")).encode()
    return data[: len(MAGIC)] \
        + struct.pack("<II", len(new), zlib.crc32(new) & 0xFFFFFFFF) \
        + new + data[start + hlen :]


class TestFormat:
    def test_roundtrip(self):
        ck = _sample_ckpt()
        got = decode_checkpoint(encode_checkpoint(ck))
        assert got.meta == ck.meta
        assert got.seq == 7
        assert np.array_equal(got.arrays["a"], ck.arrays["a"])
        assert got.arrays["a"].dtype == np.uint32
        assert np.array_equal(got.arrays["b"], ck.arrays["b"])

    def test_bad_magic_rejected(self):
        data = b"NOTACKPT" + encode_checkpoint(_sample_ckpt())[8:]
        with pytest.raises(CheckpointError, match="magic"):
            decode_checkpoint(data)

    def test_truncated_payload_rejected(self):
        data = encode_checkpoint(_sample_ckpt())
        with pytest.raises(CheckpointError, match="truncated"):
            decode_checkpoint(data[:-5])

    def test_bad_checksum_rejected(self):
        data = bytearray(encode_checkpoint(_sample_ckpt()))
        data[-1] ^= 0xFF  # flip a payload byte
        with pytest.raises(CheckpointError, match="crc32"):
            decode_checkpoint(bytes(data))

    def test_wrong_schema_version_rejected(self):
        data = _patch_header(encode_checkpoint(_sample_ckpt()),
                             schema_version=99)
        with pytest.raises(CheckpointError, match="schema version 99"):
            decode_checkpoint(data)

    def test_header_bitflip_rejected(self):
        """The header carries seq/geometry — a flipped digit there must
        trip the header CRC, not restore silently-wrong state."""
        data = bytearray(encode_checkpoint(_sample_ckpt()))
        data[len(MAGIC) + 8 + 5] ^= 0x01  # inside the header JSON
        with pytest.raises(CheckpointError, match="header crc32"):
            decode_checkpoint(bytes(data))


class TestStore:
    def test_versioned_save_and_latest(self, tmp_path):
        st = CheckpointStore(tmp_path)
        assert st.next_seq() == 1
        ck1 = _sample_ckpt()
        ck1.meta["seq"] = 1
        p1 = st.save(ck1)
        ck2 = _sample_ckpt()
        ck2.meta["seq"] = 2
        ck2.arrays["a"] = ck2.arrays["a"] + 1
        st.save(ck2)
        assert st.next_seq() == 3
        got, path = st.load_latest()
        assert got.seq == 2
        assert np.array_equal(got.arrays["a"], ck2.arrays["a"])
        assert p1.exists()  # older versions retained until prune
        # no stray temp files after atomic rename
        assert not list(tmp_path.glob(".tmp-*"))

    def test_corrupt_newest_falls_back_to_older(self, tmp_path):
        st = CheckpointStore(tmp_path)
        ck1 = _sample_ckpt()
        ck1.meta["seq"] = 1
        st.save(ck1)
        ck2 = _sample_ckpt()
        ck2.meta["seq"] = 2
        p2 = st.save(ck2)
        raw = bytearray(p2.read_bytes())
        raw[-1] ^= 0xFF
        p2.write_bytes(bytes(raw))
        got, path = st.load_latest()
        assert got.seq == 1  # torn newest degraded, not fatal
        infos = st.list()
        assert infos[0].error is not None and "crc32" in infos[0].error
        assert infos[1].error is None

    def test_all_corrupt_raises_clearly(self, tmp_path):
        st = CheckpointStore(tmp_path)
        p = st.save(_sample_ckpt())
        p.write_bytes(b"garbage")
        with pytest.raises(CheckpointError, match="no restorable"):
            st.load_latest()
        with pytest.raises(CheckpointError, match="no checkpoints"):
            CheckpointStore(tmp_path / "empty").load_latest()

    def test_stray_filename_ignored(self, tmp_path):
        """A hand-copied `ckpt-latest.bngckpt` must not shadow the real
        newest file or collapse next_seq to 0."""
        st = CheckpointStore(tmp_path)
        ck = _sample_ckpt()
        ck.meta["seq"] = 3
        p = st.save(ck)
        (tmp_path / "ckpt-latest.bngckpt").write_bytes(p.read_bytes())
        assert st.next_seq() == 4
        got, path = st.load_latest()
        assert path == p
        assert [i.seq for i in st.list()] == [3]

    def test_prune_keeps_newest(self, tmp_path):
        st = CheckpointStore(tmp_path)
        for seq in range(1, 6):
            ck = _sample_ckpt()
            ck.meta["seq"] = seq
            st.save(ck)
        assert st.prune(keep=2) == 3
        assert [i.seq for i in st.list()] == [5, 4]


def _mk_stack(clock=None, sub_nbuckets=256):
    fp = FastPathTables(sub_nbuckets=sub_nbuckets, vlan_nbuckets=64,
                        cid_nbuckets=64, max_pools=8)
    fp.set_server_config(SERVER_MAC, SERVER_IP)
    pools = PoolManager(fp)
    pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.0.0.0"),
                        prefix_len=24, gateway=SERVER_IP,
                        dns_primary=ip_to_u32("1.1.1.1"), lease_time=3600))
    nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                     sessions_nbuckets=256, sub_nat_nbuckets=64)
    dhcp = DHCPServer(SERVER_MAC, SERVER_IP, pools, fastpath_tables=fp,
                      nat_hook=lambda ip, now: nat.allocate_nat(ip, now),
                      clock=clock or FakeClock())
    return fp, nat, dhcp, pools


class TestHostMirrorRoundTrip:
    def test_manager_roundtrip_without_engine(self):
        fp, nat, dhcp, pools = _mk_stack()
        mac = bytes.fromhex("02c0ffee0001")
        sub_ip = ip_to_u32("10.0.0.10")
        fp.add_subscriber(mac, 1, sub_ip, T0 + 3600)
        fp.add_vlan_subscriber(100, 200, 1, sub_ip, T0 + 3600)
        fp.add_circuit_id_subscriber(b"olt1/1/1", 1, sub_ip, T0 + 3600)
        nat.allocate_nat(sub_ip, T0)
        nat.handle_new_flow(sub_ip, ip_to_u32("8.8.8.8"), 5555, 443,
                            int(PROTO_TCP), 100, T0)
        mk = mac_to_u64(mac)
        dhcp.leases[mk] = Lease(mac=mac, ip=sub_ip, pool_id=1,
                                expiry=T0 + 3600, circuit_id=b"olt1/1/1",
                                session_id="bng-1-000001", qos_policy="gold")
        dhcp.leases_by_cid[b"olt1/1/1"] = mk
        dhcp._session_seq = 9
        pppoe = PPPoEFastPathTables()

        class Sess:
            session_id, client_mac, assigned_ip = 7, b"\x02" * 6, sub_ip

        pppoe.session_up(Sess())

        ck = decode_checkpoint(encode_checkpoint(build_checkpoint(
            3, float(T0), fastpath=fp, nat=nat, pppoe=pppoe, dhcp=dhcp,
            node_id="bng0")))

        fp2, nat2, dhcp2, pools2 = _mk_stack()
        pppoe2 = PPPoEFastPathTables()
        rows = restore_checkpoint(ck, fastpath=fp2, nat=nat2, pppoe=pppoe2,
                                  dhcp=dhcp2)
        assert rows["fastpath.sub"] == 1 and rows["fastpath.vlan"] == 1
        assert rows["nat.sessions"] == 1 and rows["nat.blocks"] == 1
        assert rows["pppoe.by_sid"] == 1
        assert rows["dhcp.leases"] == 1
        for t in ("sub", "vlan", "cid"):
            assert np.array_equal(getattr(fp2, t).keys, getattr(fp, t).keys)
            assert np.array_equal(getattr(fp2, t).vals, getattr(fp, t).vals)
            assert np.array_equal(getattr(fp2, t).used, getattr(fp, t).used)
        assert np.array_equal(fp2.pools, fp.pools)
        assert np.array_equal(fp2.server, fp.server)
        assert nat2.blocks == nat.blocks
        assert nat2.eim == nat.eim
        assert nat2._ext_ports == nat._ext_ports
        assert nat2._next_block == nat._next_block
        assert nat2._sub_id_seq == nat._sub_id_seq
        lease = dhcp2.leases[mk]
        assert lease.ip == sub_ip and lease.qos_policy == "gold"
        assert dhcp2.leases_by_cid[b"olt1/1/1"] == mk
        assert dhcp2._session_seq == 9
        # pool occupancy restored: the lease's IP cannot be re-assigned
        assert pools2.pools[1].used == 1
        # a fresh allocation on the RESTORED NAT can never reuse the
        # restored subscriber's port block
        blk2 = nat2.allocate_nat(ip_to_u32("10.0.0.11"), T0)
        assert blk2["port_start"] != nat.blocks[sub_ip]["port_start"]

    def test_geometry_mismatch_rejected_before_mutation(self):
        fp, nat, dhcp, _ = _mk_stack()
        fp.add_subscriber(b"\x02" * 6, 1, ip_to_u32("10.0.0.9"), T0)
        ck = build_checkpoint(1, float(T0), fastpath=fp, nat=nat)
        fp2 = FastPathTables(sub_nbuckets=512, vlan_nbuckets=64,
                             cid_nbuckets=64, max_pools=8)
        nat2 = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                          sessions_nbuckets=256, sub_nat_nbuckets=64)
        nat2.allocate_nat(ip_to_u32("10.0.0.50"), T0)
        before = nat2.sub_nat.vals.copy()
        with pytest.raises(CheckpointError, match="geometry"):
            restore_checkpoint(ck, fastpath=fp2, nat=nat2)
        # the reject happened BEFORE any mirror write: nat2 untouched
        assert np.array_equal(nat2.sub_nat.vals, before)
        assert nat2.blocks  # allocator bookkeeping intact

    def test_missing_component_rejected(self):
        fp, nat, dhcp, _ = _mk_stack()
        ck = build_checkpoint(1, float(T0), fastpath=fp, nat=nat, dhcp=dhcp)
        fp2, nat2, _, _ = _mk_stack()
        with pytest.raises(CheckpointError, match="dhcp"):
            restore_checkpoint(ck, fastpath=fp2, nat=nat2)

    def test_scaling_state_rides_payload_not_header(self):
        """The lease book / NAT bookkeeping / HA store are per-row state:
        they must live in the CRC-covered payload blobs, leaving the
        header size independent of the subscriber count."""
        fp, nat, dhcp, _ = _mk_stack()
        for i in range(50):
            mac = (0x02AA000000 << 8 | i).to_bytes(6, "big")
            dhcp.leases[mac_to_u64(mac)] = Lease(
                mac=mac, ip=ip_to_u32("10.0.0.1") + i, pool_id=1,
                expiry=T0 + 3600, session_id=f"bng-{i}")
        ck = build_checkpoint(1, float(T0), fastpath=fp, nat=nat, dhcp=dhcp)
        assert ck.meta["components"]["dhcp"] == {"__payload_json__": True}
        assert "dhcp/__payload_json__" in ck.arrays
        data = encode_checkpoint(ck)
        hlen = struct.unpack_from("<II", data, len(MAGIC))[0]
        assert hlen < 8192  # geometry only — no per-lease rows
        # a bit flip INSIDE the relocated lease blob is payload-CRC'd
        blob_bytes = bytes(np.asarray(ck.arrays["dhcp/__payload_json__"]))
        off = data.rindex(blob_bytes)
        raw = bytearray(data)
        raw[off + 10] ^= 0xFF
        with pytest.raises(CheckpointError, match="crc32"):
            decode_checkpoint(bytes(raw))

    def test_corrupt_ha_session_rejected_before_mutation(self):
        """A session dict missing its required field must reject in the
        verify phase, before any table mirror was touched."""
        fp, nat, dhcp, _ = _mk_stack()
        fp.add_subscriber(b"\x02" * 6, 1, ip_to_u32("10.0.0.9"), T0)
        active = ActiveSyncer(InMemorySessionStore())
        active.push_change(SessionState(session_id="s1", ip=1))
        ck = build_checkpoint(1, float(T0), fastpath=fp, ha=active)
        blob = json.loads(bytes(np.asarray(ck.arrays["ha/__payload_json__"])))
        del blob["sessions"][0]["session_id"]  # required field gone
        ck.arrays["ha/__payload_json__"] = np.frombuffer(
            json.dumps(blob).encode(), dtype=np.uint8).copy()

        fp2, _, _, _ = _mk_stack()
        ha2 = ActiveSyncer(InMemorySessionStore())
        with pytest.raises(CheckpointError, match="ha"):
            restore_checkpoint(ck, fastpath=fp2, ha=ha2)
        assert fp2.sub.count == 0  # untouched
        assert len(ha2.store) == 0

    def test_missing_pppoe_server_mac_rejected(self):
        pppoe = PPPoEFastPathTables()
        ck = build_checkpoint(1, float(T0), pppoe=pppoe)
        del ck.arrays["pppoe/server_mac"]
        with pytest.raises(CheckpointError, match="server_mac"):
            restore_checkpoint(ck, pppoe=PPPoEFastPathTables())

    def test_corrupt_nat_meta_rejected_before_mutation(self):
        """A CRC-valid checkpoint whose NAT bookkeeping fails to parse
        must reject in the verify phase — never after the fastpath
        mirrors were already overwritten."""
        fp, nat, dhcp, _ = _mk_stack()
        sub_ip = ip_to_u32("10.0.0.10")
        fp.add_subscriber(b"\x02" * 6, 1, sub_ip, T0)
        nat.allocate_nat(sub_ip, T0)
        ck = build_checkpoint(1, float(T0), fastpath=fp, nat=nat)
        blob = json.loads(bytes(np.asarray(ck.arrays["nat/__payload_json__"])))
        del blob["eim"]  # version-skew-shaped damage, still valid JSON
        ck.arrays["nat/__payload_json__"] = np.frombuffer(
            json.dumps(blob).encode(), dtype=np.uint8).copy()

        fp2, nat2, _, _ = _mk_stack()
        before = fp2.sub.keys.copy()
        with pytest.raises(CheckpointError, match="nat"):
            restore_checkpoint(ck, fastpath=fp2, nat=nat2)
        assert np.array_equal(fp2.sub.keys, before)  # untouched
        assert fp2.sub.count == 0


class TestHACheckpoint:
    def test_standby_bootstraps_then_replays(self):
        active = ActiveSyncer(InMemorySessionStore())
        for i in range(5):
            active.push_change(SessionState(session_id=f"s{i}",
                                            ip=0x0A000000 + i))
        ck = decode_checkpoint(encode_checkpoint(
            build_checkpoint(1, 0.0, ha=active)))

        store = InMemorySessionStore()
        standby = StandbySyncer(store, transport=lambda: active)
        rows = restore_checkpoint(ck, ha=standby)
        assert rows["ha.sessions"] == 5
        assert standby.last_seq == 5
        # changes since the checkpoint arrive via REPLAY, not full sync
        active.push_change(SessionState(session_id="s9", ip=0x0A000063))
        active.push_change(None, session_id="s0")
        standby.tick(0.0)
        assert standby.connected
        assert standby.stats["full_syncs"] == 0
        assert standby.stats["deltas"] == 2
        assert store.get("s9") is not None and store.get("s0") is None

    def test_stale_checkpoint_falls_back_to_full_sync(self):
        active = ActiveSyncer(InMemorySessionStore(), replay_buffer=4)
        active.push_change(SessionState(session_id="s1", ip=1))
        ck = build_checkpoint(1, 0.0, ha=active)  # seq=1
        for i in range(2, 12):  # wrap the replay buffer past seq 1
            active.push_change(SessionState(session_id=f"s{i}", ip=i))
        standby = StandbySyncer(InMemorySessionStore(),
                                transport=lambda: active)
        restore_checkpoint(ck, ha=standby)
        standby.tick(0.0)
        assert standby.stats["full_syncs"] == 1  # replay gap -> resync
        assert len(standby.store) == 11

    def test_restarted_active_resumes_seq(self):
        active = ActiveSyncer(InMemorySessionStore())
        for i in range(3):
            active.push_change(SessionState(session_id=f"s{i}", ip=i))
        ck = build_checkpoint(1, 0.0, ha=active)
        active2 = ActiveSyncer(InMemorySessionStore())
        restore_checkpoint(ck, ha=active2)
        assert active2._seq == 3
        assert len(active2.store) == 3
        # a standby exactly at the checkpoint seq needs no resync
        assert active2.replay_since(3) == []


class TestPeriodicCheckpointer:
    def _fp_snapshot_fn(self):
        fp, nat, dhcp, _ = _mk_stack()
        return lambda seq, now: build_checkpoint(seq, now, fastpath=fp)

    def test_cadence_and_retention(self, tmp_path):
        clock = FakeClock()
        ckptr = PeriodicCheckpointer(CheckpointStore(tmp_path),
                                     self._fp_snapshot_fn(), interval_s=10.0,
                                     keep=2, clock=clock)
        assert ckptr.tick(clock()) is not None  # first tick saves
        assert ckptr.tick(clock()) is None  # not due again yet
        clock.advance(10.1)
        assert ckptr.tick(clock()) is not None
        for _ in range(4):
            clock.advance(10.1)
            ckptr.tick(clock())
        assert ckptr.stats["saves"] == 6
        assert len(ckptr.store.list()) == 2  # retention applied
        assert ckptr.store.next_seq() == 7  # seq stays monotonic

    def test_background_failure_counts_and_never_raises(self, tmp_path):
        clock = FakeClock()

        def boom(seq, now):
            raise OSError("disk full")

        ckptr = PeriodicCheckpointer(CheckpointStore(tmp_path), boom,
                                     interval_s=1.0, clock=clock)
        for _ in range(3):
            clock.advance(1.1)
            assert ckptr.tick(clock()) is None  # swallowed, counted
        assert ckptr.stats["failures"] == 3
        assert "disk full" in ckptr.stats["last_error"]
        # the manual path (CLI / SIGTERM) propagates instead
        with pytest.raises(OSError):
            ckptr.save_now(reason="cli")
        # staleness metric: never-succeeded reads as a GROWING age from
        # checkpointer start, not a perpetually-fresh 0
        from bng_tpu.control.metrics import BNGMetrics

        m = BNGMetrics()
        m.collect_checkpoint(ckptr, now=clock())
        assert m.ckpt_last_success_age.value() > 3.0


class TestVectorizedExpiry:
    def test_per_protocol_timeouts(self):
        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         sessions_nbuckets=256, sub_nat_nbuckets=64)
        now = T0
        specs = [  # (src_ip, proto, state, idle_s, should_expire)
            (1, PROTO_UDP, 0, UDP_TIMEOUT_S + 1, True),
            (2, PROTO_UDP, 0, UDP_TIMEOUT_S - 1, False),
            (3, PROTO_TCP, 1, TCP_EST_TIMEOUT_S + 1, True),
            (4, PROTO_TCP, 1, TCP_EST_TIMEOUT_S - 1, False),
            (5, PROTO_TCP, 0, TCP_TRANSIENT_TIMEOUT_S + 1, True),
            (6, PROTO_ICMP, 0, ICMP_TIMEOUT_S + 1, True),
            (7, PROTO_ICMP, 0, ICMP_TIMEOUT_S - 1, False),
            # CLOSING caps the established timeout at transient
            (8, PROTO_TCP, NAT_STATE_CLOSING,
             TCP_TRANSIENT_TIMEOUT_S + 1, True),
        ]
        for ip, proto, state, idle, _ in specs:
            nat.allocate_nat(ip, now - idle)
            got = nat.handle_new_flow(ip, ip_to_u32("8.8.8.8"), 40000, 443,
                                      int(proto), 100, now - idle)
            assert got is not None
            slot = nat.sessions._find_slot(np.asarray(
                nat._key(ip, ip_to_u32("8.8.8.8"),
                         40000, 0 if proto == PROTO_ICMP else 443,
                         int(proto)), dtype=np.uint32))
            nat.sessions.vals[slot, SV_STATE] = state
            nat.sessions.vals[slot, SV_LAST_SEEN] = now - idle
            assert int(nat.sessions.vals[slot, SV_PROTO]) == int(proto)
        expected = sum(1 for *_x, e in specs if e)
        assert nat.expire_sessions(now) == expected
        assert nat.sessions.count == len(specs) - expected
        # survivors intact, expired gone (reverse rows too)
        assert nat.sessions.count == nat.reverse.count
        assert nat.expire_sessions(now) == 0  # idempotent

    def test_empty_sweep(self):
        nat = NATManager(public_ips=[1], sessions_nbuckets=256,
                         sub_nat_nbuckets=64)
        assert nat.expire_sessions(T0) == 0


class TestFoldDeviceAuthoritative:
    def test_fold_skips_not_yet_uploaded_rows(self):
        """A host NAT session the bounded drain has not scattered yet
        reads back zeros from HBM — the pre-checkpoint fold must keep
        the NEWER host row, not clobber it with the stale device slot.
        (No jit dispatch: engine construction uploads, then we mutate
        the host side only — fast-tier safe.)"""
        from bng_tpu.runtime.engine import Engine

        clock = FakeClock()
        fp, nat, dhcp, _ = _mk_stack(clock, sub_nbuckets=128)
        sub_ip = ip_to_u32("10.0.0.77")
        nat.allocate_nat(sub_ip, T0)
        # uploaded session: on device since engine construction
        nat.handle_new_flow(sub_ip, ip_to_u32("1.1.1.1"), 1111, 80,
                            int(PROTO_UDP), 64, T0)
        engine = Engine(fp, nat, batch_size=8, clock=clock)
        assert nat.sessions.dirty_count() == 0  # init upload drained all
        # NEW session after the upload: dirty, device slot still zeros
        nat.handle_new_flow(sub_ip, ip_to_u32("2.2.2.2"), 2222, 80,
                            int(PROTO_UDP), 64, T0 + 5)
        key = np.asarray(nat._key(sub_ip, ip_to_u32("2.2.2.2"),
                                  2222, 80, int(PROTO_UDP)),
                         dtype=np.uint32)
        slot = nat.sessions._find_slot(key)
        row_before = nat.sessions.vals[slot].copy()
        assert row_before.any()
        engine.fold_device_authoritative()
        # pending host row survived; the uploaded row got device values
        assert np.array_equal(nat.sessions.vals[slot], row_before)
        up_key = np.asarray(nat._key(sub_ip, ip_to_u32("1.1.1.1"),
                                     1111, 80, int(PROTO_UDP)),
                            dtype=np.uint32)
        up_slot = nat.sessions._find_slot(up_key)
        dev = engine.fetch_session_vals()
        assert np.array_equal(nat.sessions.vals[up_slot], dev[up_slot])


# ---------------------------------------------------------------------------
# slow tier: full engine round trip (compile-heavy -> make verify-slow)
# ---------------------------------------------------------------------------

def _mk_engine_stack(clock, sub_nbuckets=256):
    from bng_tpu.runtime.engine import (AntispoofTables, Engine, QoSTables)

    fp, nat, dhcp, pools = _mk_stack(clock, sub_nbuckets=sub_nbuckets)
    qos = QoSTables(nbuckets=256)
    spoof = AntispoofTables(nbuckets=256)
    engine = Engine(fp, nat, qos, spoof, batch_size=8,
                    slow_path=dhcp.handle_frame, clock=clock)
    return engine, dhcp, nat, fp


def _client_frame(mac, msg_type, **kw):
    from bng_tpu.control import dhcp_codec, packets

    pkt = dhcp_codec.build_request(mac, msg_type, **kw)
    pkt.options.append((dhcp_codec.OPT_PARAM_REQ_LIST,
                        bytes([1, 3, 6, 51, 54])))
    return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                              pkt.encode().ljust(320, b"\x00"))


class TestEngineRoundTrip:
    def test_save_restore_fastpath_parity(self, tmp_path):
        from bng_tpu.control import dhcp_codec, packets

        clock = FakeClock()
        engine, dhcp, nat, fp = _mk_engine_stack(clock)
        mac = bytes.fromhex("02c0ffee0042")
        sub_remote = ip_to_u32("93.184.216.34")

        # live traffic: full DORA (slow path populates the device cache)
        r = engine.process([_client_frame(mac, dhcp_codec.DISCOVER)])
        offer = dhcp_codec.decode(packets.decode(r["slow"][0][1]).payload)
        ip = offer.yiaddr
        engine.process([_client_frame(mac, dhcp_codec.REQUEST,
                                      requested_ip=ip, server_id=SERVER_IP)])
        # NAT conntrack-hybrid: packet 1 punts, packet 2 device-SNATs
        f = packets.udp_packet(mac, SERVER_MAC, ip, sub_remote, 40000, 443,
                               b"data")
        engine.process([f])
        r = engine.process([f])
        nat_port = packets.decode(r["fwd"][0][1]).src_port

        # snapshot at the quiesce barrier, through the versioned store
        store = CheckpointStore(tmp_path)
        ckptr = PeriodicCheckpointer(
            store, lambda seq, now: build_checkpoint(
                seq, now, engine=engine, dhcp=dhcp), clock=clock)
        ckptr.save_now(reason="test")

        # ---- fresh process: restore, expect ZERO slow-path DHCP ----
        clock2 = FakeClock(clock())
        engine2, dhcp2, nat2, fp2 = _mk_engine_stack(clock2)
        snap, _ = store.load_latest()
        rows = restore_checkpoint(snap, engine=engine2, dhcp=dhcp2)
        assert rows["fastpath.sub"] == 1
        assert rows["nat.sessions"] == 1
        assert rows["dhcp.leases"] == 1

        # table-content equality across the restart
        for t in ("sub", "vlan", "cid"):
            assert np.array_equal(getattr(fp2, t).keys,
                                  getattr(fp, t).keys)
            assert np.array_equal(getattr(fp2, t).vals,
                                  getattr(fp, t).vals)
        assert np.array_equal(nat2.sessions.keys, nat.sessions.keys)
        assert nat2.blocks == nat.blocks and nat2.eim == nat.eim

        # DISCOVER answered ON DEVICE — no DHCP slow-path exchange
        r = engine2.process([_client_frame(mac, dhcp_codec.DISCOVER)])
        assert len(r["tx"]) == 1 and r["slow"] == []
        dev_offer = dhcp_codec.decode(packets.decode(r["tx"][0][1]).payload)
        assert dev_offer.msg_type == dhcp_codec.OFFER
        assert dev_offer.yiaddr == ip
        assert dhcp2.stats.discover == 0 and dhcp2.stats.offer == 0

        # restored NAT session device-SNATs with the SAME mapping
        r = engine2.process([f])
        assert len(r["fwd"]) == 1
        d = packets.decode(r["fwd"][0][1])
        assert d.src_ip == ip_to_u32("203.0.113.1")
        assert d.src_port == nat_port

        # renewal REQUEST also on-device
        r = engine2.process([_client_frame(mac, dhcp_codec.REQUEST,
                                           requested_ip=ip,
                                           server_id=SERVER_IP)])
        assert len(r["tx"]) == 1
        assert dhcp2.stats.request == 0

    def test_scheduler_quiesce_barrier(self):
        from bng_tpu.runtime.scheduler import SchedulerConfig, TieredScheduler

        clock = FakeClock()
        # distinct DHCP-table geometry: the express dispatch below
        # compiles a B=8 shape into the geometry-keyed shared jit cache,
        # and test_hlo_structure's compile-shape-budget test counts the
        # shapes of the DEFAULT-geometry callable — don't pollute it
        engine, dhcp, nat, fp = _mk_engine_stack(clock, sub_nbuckets=128)
        sched = TieredScheduler(engine, SchedulerConfig(express_batch=8),
                                clock=clock)
        from bng_tpu.control import dhcp_codec

        mac = bytes.fromhex("02c0ffee0099")
        # leave frames QUEUED (below batch, before the deadline): quiesce
        # must ship and retire them, not strand them
        for i in range(3):
            sched.submit(_client_frame(mac, dhcp_codec.DISCOVER),
                         from_access=True)
        retired = sched.quiesce()
        assert retired == 3
        assert len(sched.express) == 0 and len(sched.bulk) == 0
        assert len(sched._express_ring) == 0 and len(sched._bulk_ring) == 0
        # a snapshot right at the barrier sees a consistent cut
        ck = build_checkpoint(1, clock(), engine=engine, scheduler=sched,
                              dhcp=dhcp)
        assert "fastpath" in ck.meta["components"]
