"""Vectorized host serving path (ISSUE 14): byte-identity matrix.

The contract under test: BNG_HOST_PATH=vector does the SAME work as the
scalar per-frame path with batch-native NumPy — same classifications,
same steering, same admission verdicts AND counters, same ring outputs
byte for byte, same express replies — over a corpus that includes every
edge the scalar oracles guard (runts, truncated VLAN tags, QinQ, the
PPPoE LCP/IPCP precedence edge from the PR 12 fix, relayed giaddr
frames, fragments, non-DHCP port-67 transit, random junk). The scalar
functions are the oracle; any divergence is a correctness bug.

Markers: `hostpath` (make verify-hostpath, <60s); the compile-heavy
end-to-end scheduler A/B is additionally @slow (the tier-1 budget
satellite).
"""

from __future__ import annotations

import numpy as np
import pytest

from bng_tpu.control import packets
from bng_tpu.control.admission import (AdmissionConfig, AdmissionController,
                                       peek_dhcp)
from bng_tpu.control.dhcp_codec import (ACK, DISCOVER, INFORM, RELEASE,
                                        REQUEST, ExpressWireTemplate,
                                        build_request)
from bng_tpu.runtime import hostpath
from bng_tpu.runtime.ring import (FLAG_FROM_ACCESS, PyRing, VERDICT_DROP,
                                  VERDICT_TX, classify_dhcp, shard_of)

pytestmark = pytest.mark.hostpath


# ---------------------------------------------------------------------------
# the frame corpus
# ---------------------------------------------------------------------------

def _vlan_wrap(frame: bytes, tags) -> bytes:
    out = frame[:12]
    for tpid, vid in tags:
        out += tpid.to_bytes(2, "big") + vid.to_bytes(2, "big")
    return out + frame[12:]

def _discover(rng, mac, relayed=False, tags=(), bcast=True, t=DISCOVER):
    p = build_request(mac, t, xid=int(rng.integers(1 << 31)),
                      giaddr=(0x0A000001 if relayed else 0), broadcast=bcast)
    # standard 300-byte BOOTP padding (the bench's _discover_row shape;
    # the express fixed-offset option scan requires the padded tail)
    f = packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                           p.encode().ljust(300, b"\x00"))
    return _vlan_wrap(f, tags) if tags else f

def _pppoe(proto: int, inner: bytes = b"") -> bytes:
    return (b"\x02" * 6 + b"\x04" * 6 + b"\x88\x64" + b"\x11\x00"
            + (1).to_bytes(2, "big")
            + (len(inner) + 2).to_bytes(2, "big")
            + proto.to_bytes(2, "big") + inner)

def _fragment(src, dst) -> bytes:
    f = bytearray(packets.udp_packet(b"\x02" * 6, b"\x04" * 6, src, dst,
                                     68, 67, b"x" * 60))
    f[20] = 0x20  # MF flag: fragmented, no parseable L4
    return bytes(f)


def build_corpus(seed: int = 7) -> list:
    rng = np.random.default_rng(seed)
    inner_ip = packets.udp_packet(b"\x02" * 6, b"\x04" * 6, 0x0A0A0A0A,
                                  0x08080808, 1234, 80, b"y" * 40)[14:]
    corpus = []
    for i in range(30):
        mac = b"\x02" + bytes(int(x) for x in rng.integers(0, 255, 5))
        t = [DISCOVER, REQUEST, RELEASE, INFORM][i % 4]
        corpus.append(_discover(rng, mac, t=t))
        corpus.append(_discover(rng, mac, relayed=True, t=t))
        corpus.append(_discover(rng, mac, tags=[(0x8100, 10)], bcast=False))
        corpus.append(_discover(rng, mac, tags=[(0x88A8, 5), (0x8100, 7)]))
        corpus.append(packets.udp_packet(
            b"\x02" * 6, b"\x04" * 6, int(rng.integers(1 << 32)),
            int(rng.integers(1 << 32)), int(rng.integers(1024, 65535)),
            443, b"x" * int(rng.integers(20, 300))))
    # PPPoE session data vs control — the PR 12 precedence edge: the
    # PPP-proto compare must be the full 16-bit 0x0021, never
    # `hi<<8 | (lo==0x21)`; LCP (0xC021) and IPCP (0x8021) frames whose
    # LOW byte is 0x21 must fall to the sticky MAC hash
    corpus.append(_pppoe(0x0021, inner_ip))
    corpus.append(_pppoe(0xC021, b"\x01\x01\x00\x04"))
    corpus.append(_pppoe(0x8021, b"\x01\x01\x00\x04"))
    corpus.append(_pppoe(0x0021))  # session data, truncated inner
    # port-67 transit that is NOT DHCP (no BOOTP magic)
    corpus.append(packets.udp_packet(b"\x02" * 6, b"\x04" * 6, 5, 6, 68,
                                     67, b"notdhcp" * 40))
    corpus.append(_fragment(7, 8))
    # runts / truncations of every shape above
    for f in list(corpus[:12]):
        for cut in (0, 5, 13, 14, 16, 17, 18, 20, 22, 33, 41, 60, 240,
                    len(f) - 1):
            corpus.append(f[:cut])
    for _ in range(30):
        corpus.append(bytes(rng.integers(
            0, 255, int(rng.integers(1, 300)), dtype=np.uint8).tolist()))
    return corpus


CORPUS = build_corpus()
PUB_IPS = {0x04040404: 1, 0x08080808: 2, 0x01010101: 99}


# ---------------------------------------------------------------------------
# kernel identity vs the scalar oracles
# ---------------------------------------------------------------------------

class TestKernelIdentity:
    def test_classify(self):
        buf, lens = hostpath.pack_rows(CORPUS)
        got = hostpath.classify_dhcp_batch(buf, lens.astype(np.int64))
        for i, f in enumerate(CORPUS):
            assert int(got[i]) == classify_dhcp(f), (i, f.hex())

    @pytest.mark.parametrize("n_shards", [1, 2, 4, 8, 64])
    @pytest.mark.parametrize("from_access", [True, False])
    def test_shard_of(self, n_shards, from_access):
        buf, lens = hostpath.pack_rows(CORPUS)
        lens = lens.astype(np.int64)
        fl = np.full(len(CORPUS),
                     FLAG_FROM_ACCESS if from_access else 0, np.uint32)
        if from_access:
            fl |= hostpath.classify_dhcp_batch(buf, lens)
        keys = np.sort(np.fromiter(PUB_IPS.keys(), dtype=np.uint64))
        vals = np.array([PUB_IPS[int(k)] for k in keys], dtype=np.int64)
        got = hostpath.shard_of_batch(buf, lens, fl, n_shards, keys, vals)
        for i, f in enumerate(CORPUS):
            assert int(got[i]) == shard_of(f, int(fl[i]), n_shards,
                                           PUB_IPS), (n_shards, i, f.hex())

    def test_peek_dhcp(self):
        buf, lens = hostpath.pack_rows(CORPUS)
        msg, mac, parsed = hostpath.peek_dhcp_batch(buf,
                                                    lens.astype(np.int64))
        for i, f in enumerate(CORPUS):
            sp = peek_dhcp(f)
            if sp is None:
                assert not parsed[i], (i, f.hex())
            else:
                assert parsed[i], (i, f.hex())
                assert (int(msg[i]), int(mac[i])) == sp, (i, f.hex())

    def test_fnv(self):
        from bng_tpu.utils.net import fnv1a32

        rows = np.frombuffer(
            b"".join(f[:6].ljust(6, b"\0") for f in CORPUS if f),
            dtype=np.uint8).reshape(-1, 6)
        got = hostpath.fnv1a32_cols(rows)
        for i, row in enumerate(rows):
            assert int(got[i]) == fnv1a32(row.tobytes())

    def test_pack_roundtrip(self):
        frames = [f for f in CORPUS if f]
        buf, lens = hostpath.pack_rows(frames)
        for i, f in enumerate(frames):
            assert buf[i, : len(f)].tobytes() == f
            assert not buf[i, len(f):].any()
            assert lens[i] == len(f)

    def test_pack_rejects_oversize(self):
        out = np.zeros((2, 16), np.uint8)
        with pytest.raises(ValueError, match="exceeds staging slot"):
            hostpath.pack_into([b"x" * 17, b"y"], out,
                               np.zeros(2, np.uint32))

    def test_staging_pool_clears_stale_rows(self):
        pool = hostpath.StagingPool(16, depth=2)
        for _ in range(2):  # cycle the whole pool with 3-row batches
            pool.stage([b"aaaa", b"bbbb", b"cccc"], 8)
        pkt, length = pool.stage([b"zz"], 8)
        assert length[0] == 2 and not pkt[1:].any() and not length[1:].any()

    def test_staging_pool_ensure_depth_grows_live_rings(self):
        # review finding: configurable scheduler depths must widen the
        # cycle — a buffer may not be handed out again until at least
        # `depth` later stage() calls have cycled past it
        pool = hostpath.StagingPool(8, depth=2)
        a, _ = pool.stage([b"a"], 4)
        pool.ensure_depth(5)
        assert pool.depth == 5
        seen = [a] + [pool.stage([b"x"], 4)[0] for _ in range(4)]
        assert all(x is not a for x in seen[1:])  # 4 distinct successors
        b, _ = pool.stage([b"y"], 4)
        assert b is a  # cycles back only after depth=5 hand-outs
        pool.ensure_depth(3)  # never shrinks
        assert pool.depth == 5


# ---------------------------------------------------------------------------
# PyRing: end-to-end byte identity
# ---------------------------------------------------------------------------

def _drive_ring(host_path: str, n_shards: int, sharded: bool,
                B: int = 64, slot: int = 512, depth: int = 64,
                nframes: int = 256, frame_size: int = 600) -> list:
    r = PyRing(nframes=nframes, frame_size=frame_size, depth=depth,
               n_shards=n_shards, host_path=host_path)
    for ip, s in PUB_IPS.items():
        if s < n_shards:
            r.steer_pub_ip(ip, s)
    src = [f for f in CORPUS if len(f) <= min(slot, frame_size)]
    log = [("pushed", r.rx_push_batch(src[:100], from_access=True)
            + r.rx_push_batch(src[100:140], from_access=False))]
    rng = np.random.default_rng(3)
    for _ in range(50):
        if not r.rx_pending():
            break
        out = np.zeros((B, slot), np.uint8)
        ol = np.zeros(B, np.uint32)
        fl = np.zeros(B, np.uint32)
        n = (r.assemble_sharded(out, ol, fl) if sharded
             else r.assemble(out, ol, fl))
        if n == 0:
            break
        nn = B if sharded else n
        log.append(("asm", n, out.tobytes(), ol.tobytes(), fl.tobytes()))
        v = rng.integers(0, 4, nn).astype(np.uint8)
        reply = np.zeros((nn, slot), np.uint8)
        rl = rng.integers(20, slot, nn).astype(np.uint32)
        for k in range(nn):
            reply[k, : rl[k]] = rng.integers(0, 255, int(rl[k]))
        r.complete(v, reply, rl, nn)
    while True:
        got = r.tx_pop() or r.fwd_pop() or r.slow_pop()
        if got is None:
            break
        log.append(("pop", got[0], got[1]))
    log.append(("stats", tuple(sorted(r.stats().items()))))
    log.append(("free", r.free_frames()))
    return log


class TestRingIdentity:
    @pytest.mark.parametrize("n_shards", [1, 2, 8])
    def test_assemble_complete_pop(self, n_shards):
        assert (_drive_ring("scalar", n_shards, False)
                == _drive_ring("vector", n_shards, False))

    @pytest.mark.parametrize("n_shards", [2, 4])
    def test_sharded_assemble(self, n_shards):
        assert (_drive_ring("scalar", n_shards, True, B=n_shards * 32)
                == _drive_ring("vector", n_shards, True, B=n_shards * 32))

    def test_pressure_paths(self):
        # tiny ring: free-pool pressure at push, queue overflow at
        # complete — the scalar-fallback decisions must match exactly
        def drive(hp):
            r = PyRing(nframes=20, frame_size=600, depth=6, n_shards=2,
                       host_path=hp)
            src = [f for f in CORPUS if 0 < len(f) <= 500]
            log = [("pushed", r.rx_push_batch(src[:40])),
                   ("stats", tuple(sorted(r.stats().items())))]
            out = np.zeros((16, 512), np.uint8)
            ol = np.zeros(16, np.uint32)
            fl = np.zeros(16, np.uint32)
            n = r.assemble(out, ol, fl)
            reply = np.zeros((n, 512), np.uint8)
            r.complete(np.full(n, VERDICT_TX, np.uint8), reply,
                       np.full(n, 100, np.uint32), n)
            log.append(("stats2", tuple(sorted(r.stats().items())),
                        r.free_frames()))
            while True:
                p = r.tx_pop()
                if p is None:
                    break
                log.append(p)
            return log
        assert drive("scalar") == drive("vector")

    def test_tx_pop_batch_identity(self):
        def drive(hp):
            r = PyRing(nframes=64, frame_size=600, depth=32, host_path=hp)
            r.rx_push_batch([f for f in CORPUS if 20 < len(f) < 500][:20])
            o = np.zeros((32, 512), np.uint8)
            ln = np.zeros(32, np.uint32)
            g = np.zeros(32, np.uint32)
            n = r.assemble(o, ln, g)
            rep = np.zeros((n, 512), np.uint8)
            rep[:, :77] = 9
            r.complete(np.full(n, VERDICT_TX, np.uint8), rep,
                       np.full(n, 77, np.uint32), n)
            return r.tx_pop_batch(5) + r.tx_pop_batch()
        assert drive("scalar") == drive("vector")

    def test_oversized_reply_spill(self):
        # device reply wider than the UMEM slot: the vector path spills
        # to bytes; payloads must still match the scalar path
        def drive(hp):
            r = PyRing(nframes=16, frame_size=128, depth=8, host_path=hp)
            r.rx_push_batch([b"\x01" * 60, b"\x02" * 60])
            o = np.zeros((8, 256), np.uint8)
            ln = np.zeros(8, np.uint32)
            g = np.zeros(8, np.uint32)
            n = r.assemble(o, ln, g)
            rep = np.arange(8 * 256, dtype=np.uint32).astype(np.uint8)
            rep = rep.reshape(8, 256)
            r.complete(np.full(n, VERDICT_TX, np.uint8), rep,
                       np.full(n, 200, np.uint32), n)  # 200 > 128 slot
            return r.tx_pop_batch() + [r.tx_pop()]
        assert drive("scalar") == drive("vector")

    @pytest.mark.parametrize("batch", [
        [b"", b""],                     # ALL-empty: flat would be size 0
        [b"", b"", b"\x01\x02\x03"],    # empty mixed with a runt
    ])
    def test_zero_length_frames_accepted_like_scalar(self, batch):
        # review finding: empty and all-empty batches must not index a
        # zero-width matrix or an empty flat buffer — the scalar oracle
        # ACCEPTS zero-length frames (shard 0, slow path)
        outs = {}
        for hp in ("scalar", "vector"):
            r = PyRing(nframes=16, frame_size=128, depth=8, n_shards=2,
                       host_path=hp)
            got = r.rx_push_batch(list(batch))
            outs[hp] = (got, r.rx_pending(),
                        tuple(sorted(r.stats().items())))
        assert outs["scalar"] == outs["vector"]
        assert outs["scalar"][0] == len(batch)

    def test_vector_zero_tail_reuse(self):
        # a slot that held a LONG frame then a short one must not leak
        # the long occupant's tail into a later assemble
        r = PyRing(nframes=4, frame_size=256, depth=4, host_path="vector")
        out = np.zeros((4, 256), np.uint8)
        ol = np.zeros(4, np.uint32)
        fl = np.zeros(4, np.uint32)
        r.rx_push_batch([b"\xaa" * 200])
        n = r.assemble(out, ol, fl)
        r.complete(np.full(n, VERDICT_DROP, np.uint8),
                   np.zeros((n, 256), np.uint8), np.zeros(n, np.uint32), n)
        r.rx_push_batch([b"\xbb" * 10])
        out[:] = 0xEE  # dirty caller staging too
        n = r.assemble(out, ol, fl)
        assert n == 1 and ol[0] == 10
        assert out[0, :10].tobytes() == b"\xbb" * 10
        assert not out[0, 10:].any()


# ---------------------------------------------------------------------------
# admission: batched admit identity
# ---------------------------------------------------------------------------

def _admission_frames():
    rng = np.random.default_rng(5)
    macs = [b"\x02" + bytes(int(x) for x in rng.integers(0, 255, 5))
            for _ in range(64)]
    frames = [_discover(rng, m, t=[DISCOVER, REQUEST, RELEASE, INFORM][i % 4])
              for i, m in enumerate(macs)]
    frames.append(b"\x00" * 40)  # unparsable
    frames.append(packets.udp_packet(b"\x02" * 6, b"\x04" * 6, 1, 2, 99,
                                     443, b"zz"))  # non-DHCP
    return macs, frames


def _run_admission(vec: bool, scenario: str):
    macs, frames = _admission_frames()
    cfg = AdmissionConfig(inbox_capacity=32, request_hard_capacity=48,
                          deadline_ms=50, offer_ttl_s=10)
    ac = AdmissionController(cfg, clock=lambda: 1000.0)
    for m in macs[:10]:
        ac.note_offer(int.from_bytes(m, "big"), now=999.0)
    for m in macs[10:20]:
        ac.note_ack(int.from_bytes(m, "big"))
    for m in macs[5:8]:  # expired offers (ttl 10s)
        ac.note_offer(int.from_bytes(m, "big"), now=980.0)
    now = 1000.0
    n = len(frames)
    workers = np.array([i % 3 for i in range(n)], dtype=np.int64)
    if scenario == "unpressured":
        enq = np.full(n, now - 0.001)
    elif scenario == "no_enq":
        enq = None
    elif scenario == "deadline":
        enq = np.array([now - (0.2 if i % 3 == 0 else 0.001)
                        for i in range(n)])
    else:  # inbox pressure: the scalar-fallback path
        cfg.inbox_capacity = 4
        enq = np.full(n, now - 0.001)
    if vec:
        buf, lens = hostpath.pack_rows(frames)
        out = ac.admit_batch(frames, workers, buf, lens.astype(np.int64),
                             now, enq).tolist()
    else:
        depth: dict = {}
        out = []
        for i, f in enumerate(frames):
            w = int(workers[i])
            ok, _ = ac.admit(f, depth.get(w, 0), now,
                             None if enq is None else float(enq[i]))
            out.append(ok)
            if ok:
                depth[w] = depth.get(w, 0) + 1
    return out, ac.stats_snapshot(), sorted(ac._offered.items())


class TestAdmissionIdentity:
    @pytest.mark.parametrize("scenario", ["unpressured", "no_enq",
                                          "deadline", "pressure"])
    def test_verdicts_counters_state(self, scenario):
        assert _run_admission(False, scenario) == _run_admission(True,
                                                                 scenario)

    def test_admit_batch_without_buf_packs_lazily(self):
        # buf=None: the breached subset is packed on demand
        macs, frames = _admission_frames()
        cfg = AdmissionConfig(deadline_ms=50, offer_ttl_s=10)
        ac = AdmissionController(cfg, clock=lambda: 1000.0)
        n = len(frames)
        enq = np.array([1000.0 - (0.2 if i % 2 == 0 else 0.001)
                        for i in range(n)])
        got = ac.admit_batch(frames, np.zeros(n, np.int64), None,
                             hostpath.frame_lens(frames), 1000.0, enq)
        ref = _run_admission(False, "deadline")  # not same inputs; just
        del ref  # ensure the lazy path ran without error
        assert got.dtype == bool and len(got) == n

    def test_leased_mac_stale_offer_never_evicted(self):
        # review finding: scalar is_known short-circuits on _leased and
        # never evicts the mac's stale _offered entry; the batch path
        # must leave identical state (offer_cap FIFO order depends on it)
        mac = 0x02AABBCCDD01
        outs = {}
        for vec in (False, True):
            ac = AdmissionController(
                AdmissionConfig(offer_ttl_s=10), clock=lambda: 1000.0)
            ac.note_ack(mac)
            ac.note_offer(mac, now=900.0)  # stale re-offer while leased
            if vec:
                known = ac.is_known_batch(
                    np.array([mac], dtype=np.uint64), 1000.0)
                assert bool(known[0])
            else:
                assert ac.is_known(mac, 1000.0)
            outs[vec] = sorted(ac._offered.items())
        assert outs[False] == outs[True] == [(mac, 900.0)]

    def test_chaos_armed_falls_back_to_scalar(self):
        # an armed fault plan must route admit_batch through the
        # per-frame oracle so fault_point hit accounting is preserved
        from bng_tpu.chaos import faults
        from bng_tpu.chaos.faults import FaultInjector, FaultPlan, FaultSpec

        macs, frames = _admission_frames()
        ac = AdmissionController(AdmissionConfig(), clock=lambda: 1000.0)
        n = len(frames)
        plan = FaultPlan(specs=[FaultSpec(
            point="admission.admit", kind="force_shed", at_hit=2)])
        inj = FaultInjector(plan)
        faults.arm(inj)
        try:
            got = ac.admit_batch(frames, np.zeros(n, np.int64), None,
                                 hostpath.frame_lens(frames), 1000.0,
                                 None)
        finally:
            faults.disarm()
        # exactly hit #2 shed by chaos — per-frame hit order preserved
        assert not got[1] and got.sum() == n - 1
        assert ac.stats.shed.get("chaos", 0) == 1


# ---------------------------------------------------------------------------
# fleet: vector pre-pass identity
# ---------------------------------------------------------------------------

def _build_fleet(host_path: str, fallback: bool):
    from bng_tpu.control.fleet import FleetSpec, SlowPathFleet
    from bng_tpu.control.pool import Pool, PoolManager

    prev = hostpath.HOST_PATH
    hostpath.HOST_PATH = host_path
    try:
        pm = PoolManager()
        pm.add_pool(Pool(pool_id=1, network=(10 << 24), prefix_len=16,
                         gateway=(10 << 24) | 1, lease_time=600))
        fb = (lambda frame: b"FB" + frame[:4]) if fallback else None
        fl = SlowPathFleet(
            FleetSpec.from_pool_manager(b"\x00\x11\x22\x33\x44\x55",
                                        (10 << 24) | 1, pm),
            3, pm, mode="inline", fallback=fb, clock=lambda: 1000.0)
    finally:
        hostpath.HOST_PATH = prev
    assert fl.host_path == host_path
    return fl


class TestFleetIdentity:
    @pytest.mark.parametrize("fallback", [False, True])
    def test_handle_batch(self, fallback):
        rng = np.random.default_rng(9)
        macs = [b"\x02" + bytes(int(x) for x in rng.integers(0, 255, 5))
                for _ in range(120)]
        items, lane = [], 0
        for m in macs:
            items.append((lane, _discover(rng, m)))
            lane += 1
            if lane % 7 == 0:
                items.append((lane, packets.udp_packet(
                    m, b"\x04" * 6, 5, 6, 99, 443, b"v6ish")))
                lane += 1
        reqs = [(i, _discover(rng, m, t=REQUEST))
                for i, m in enumerate(macs[:40])]
        outs = {}
        for hp in ("scalar", "vector"):
            fl = _build_fleet(hp, fallback)
            r1 = fl.handle_batch(list(items))
            r2 = fl.handle_batch(list(reqs))  # REQUEST-after-OFFER path
            outs[hp] = (r1, r2, fl.admission.stats_snapshot(),
                        fl.fallback_frames)
        assert outs["scalar"] == outs["vector"]

    def test_runt_steering(self):
        # frames shorter than 12 bytes steer to worker 0 on both paths
        items = [(0, b"\x01\x02"), (1, _discover(np.random.default_rng(1),
                                                 b"\x02abcde"))]
        outs = {}
        for hp in ("scalar", "vector"):
            fl = _build_fleet(hp, False)
            outs[hp] = (fl.handle_batch(list(items)),
                        fl.admission.stats_snapshot())
        assert outs["scalar"] == outs["vector"]


# ---------------------------------------------------------------------------
# express wire template: batched render identity
# ---------------------------------------------------------------------------

class TestRenderBatchIdentity:
    @pytest.mark.parametrize("relayed,use_bcast,tags", [
        (False, True, ()),
        (False, False, ()),
        (True, False, ()),
        (False, True, [(0x8100, 12)]),
        (False, False, [(0x88A8, 3), (0x8100, 9)]),
    ])
    def test_groups(self, relayed, use_bcast, tags):
        from bng_tpu.ops.express import parse_express

        rng = np.random.default_rng(11)
        tmpl = ExpressWireTemplate(
            server_mac=b"\x02\xaa\xbb\xcc\xdd\x01",
            server_ip=0x0A000001, gateway=0x0A000001, dns1=0x01010101,
            dns2=0x08080808, lease_t=3600, mask=0xFFFF0000,
            reply_type=ACK)
        frames = []
        for k in range(17):
            mac = b"\x02" + bytes(int(x) for x in rng.integers(0, 255, 5))
            f = _discover(rng, mac, relayed=relayed, tags=list(tags),
                          bcast=use_bcast)
            frames.append(f)
        descs = [parse_express(f) for f in frames]
        assert all(d is not None for d in descs)
        d0 = descs[0]
        yiaddrs = rng.integers(1, 1 << 32, len(frames)).astype(np.uint32)
        want = [tmpl.render(f, d.vlan_off, d.dhcp_off, relayed,
                            use_bcast, int(y))
                for f, d, y in zip(frames, descs, yiaddrs)]
        fmat, _ = hostpath.pack_rows(frames)
        got = tmpl.render_batch(fmat, d0.vlan_off, d0.dhcp_off, relayed,
                                use_bcast, yiaddrs)
        assert got == want


# ---------------------------------------------------------------------------
# engine staging identity
# ---------------------------------------------------------------------------

class TestEngineStaging:
    def _engines(self):
        from bng_tpu.control.nat import NATManager
        from bng_tpu.runtime.engine import Engine
        from bng_tpu.runtime.tables import FastPathTables

        out = {}
        for hp in ("scalar", "vector"):
            prev = hostpath.HOST_PATH
            hostpath.HOST_PATH = hp
            try:
                fp = FastPathTables(sub_nbuckets=1 << 8,
                                    vlan_nbuckets=1 << 6,
                                    cid_nbuckets=1 << 6)
                out[hp] = Engine(fp, NATManager(public_ips=[0xCB007101]),
                                 batch_size=32, pkt_slot=256)
            finally:
                hostpath.HOST_PATH = prev
        return out

    def test_pack_frames_identity(self):
        engines = self._engines()
        frames = [f for f in CORPUS if 0 < len(f) <= 256][:30]
        a = engines["scalar"]._pack_frames(frames, 32)
        b = engines["vector"]._pack_frames(frames, 32)
        assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
        # pooled buffer reuse keeps the padding region clean
        b2 = engines["vector"]._pack_frames(frames[:3], 32)
        a2 = engines["scalar"]._pack_frames(frames[:3], 32)
        assert (a2[0] == b2[0]).all() and (a2[1] == b2[1]).all()

    def test_pack_frames_oversize_raises(self):
        engines = self._engines()
        for eng in engines.values():
            with pytest.raises(ValueError, match="pkt_slot"):
                eng._pack_frames([b"x" * 300], 32)


# ---------------------------------------------------------------------------
# chaos parity: armed plans force the scalar oracles everywhere
# ---------------------------------------------------------------------------

class TestChaosParity:
    def test_fleet_scalar_under_armed_plan(self):
        from bng_tpu.chaos import faults
        from bng_tpu.chaos.faults import FaultInjector, FaultPlan, FaultSpec

        rng = np.random.default_rng(4)
        items = [(i, _discover(rng, b"\x02" + bytes(
            int(x) for x in rng.integers(0, 255, 5))))
            for i in range(24)]
        outs = {}
        for hp in ("scalar", "vector"):
            fl = _build_fleet(hp, False)
            plan = FaultPlan(specs=[FaultSpec(
                point="admission.admit", kind="force_shed", at_hit=5)])
            faults.arm(FaultInjector(plan))
            try:
                r = fl.handle_batch(list(items))
            finally:
                faults.disarm()
            outs[hp] = (r, fl.admission.stats_snapshot())
        # hit #5 shed by chaos in BOTH paths: the vector path detected
        # the armed plan and ran the per-frame oracle
        assert outs["scalar"] == outs["vector"]
        assert outs["scalar"][1]["shed"].get("chaos") == 1


# ---------------------------------------------------------------------------
# scheduler end-to-end A/B
# ---------------------------------------------------------------------------

class TestSchedulerExpressAB:
    def test_express_replies_identical(self):
        from bng_tpu.control.nat import NATManager
        from bng_tpu.runtime.engine import Engine
        from bng_tpu.runtime.scheduler import SchedulerConfig, TieredScheduler
        from bng_tpu.runtime.tables import FastPathTables
        from bng_tpu.utils.net import ip_to_u32

        now = 1_753_000_000
        rng = np.random.default_rng(2)
        results = {}
        for hp in ("scalar", "vector"):
            prev = hostpath.HOST_PATH
            hostpath.HOST_PATH = hp
            try:
                fp = FastPathTables(sub_nbuckets=1 << 10,
                                    vlan_nbuckets=1 << 6,
                                    cid_nbuckets=1 << 6, max_pools=8)
                fp.set_server_config(bytes.fromhex("02aabbccdd01"),
                                     ip_to_u32("10.0.0.1"))
                fp.add_pool(1, ip_to_u32("10.0.0.0"), 16,
                            ip_to_u32("10.0.0.1"), ip_to_u32("1.1.1.1"),
                            ip_to_u32("8.8.8.8"), 86400)
                macs = []
                for i in range(64):
                    mac = (0x02AA00000000 + i).to_bytes(6, "big")
                    macs.append(mac)
                    fp.add_subscriber(mac, 1, ip_to_u32("10.0.1.0") + i,
                                      now + 86400)
                engine = Engine(fp, NATManager(public_ips=[0xCB007101]),
                                batch_size=64,
                                pkt_slot=512,
                                clock=lambda: float(now))
                sched = TieredScheduler(engine, SchedulerConfig(
                    express_batch=16), clock=lambda: float(now))
            finally:
                hostpath.HOST_PATH = prev
            frames = [_discover(rng, macs[i % 64]) for i in range(16)]
            rng = np.random.default_rng(2)  # same frames both cohorts
            frames = [_discover(rng, macs[i % 64]) for i in range(16)]
            out = sched.process(frames)
            results[hp] = sorted(out["tx"]), sorted(out["dropped"])
        assert results["scalar"] == results["vector"]
