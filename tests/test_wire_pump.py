"""Wire pump (ISSUE 15): the batch-native vector pump against the
scalar per-frame oracle, plus the wire serving proof.

Three layers, PR-14 discipline throughout:

1. **Bit-identity corpus** — the vector pump (native batch verbs,
   headroom-aware descriptors) must be indistinguishable from the
   scalar per-frame loop over every edge case: partial fill, a full
   kernel fill ring, TX stall + retry, headroom offsets (including 0),
   forged RX lengths, an rx-full ring. Identity covers moved-frame
   order, verdict routing, egress bytes, pump_stats AND ring stats.
2. **Satellite pins** — the frame-accounting leak fix (a failed submit
   must return its UMEM frame or the fill pool drains permanently) and
   the explicit `_tx_pending` bound with counted overflow drops.
3. **Wire serving** — the memory-rung twin of the veth proof: DORA +
   NAT new-flow punt + QoS drop + PPPoE session data through
   `Engine.process_ring_pipelined` over the full kernel-rings -> pump
   -> UMEM ring -> engine -> pump loop, far-end replies byte-exact
   across both pump implementations. The live AF_XDP copy-mode rung on
   veth runs the same four scenarios when privileges allow (slow tier).
"""

import time

import numpy as np
import pytest

from bng_tpu.chaos import faults
from bng_tpu.control import dhcp_codec, packets
from bng_tpu.runtime import xsk
from bng_tpu.runtime.ring import NativeRing, load_native
from bng_tpu.utils.net import ip_to_u32

pytestmark = pytest.mark.wire

needs_native = pytest.mark.skipif(load_native() is None,
                                  reason="no C++ toolchain")

SERVER_MAC = bytes.fromhex("02aabbccdd01")
SERVER_IP = ip_to_u32("10.0.0.1")
T0 = 1_753_000_000


# ---------------------------------------------------------------------------
# corpus harness: one scripted scenario, executed on both pump paths
# ---------------------------------------------------------------------------

def _mk(path, *, nframes=64, frame_size=512, depth=32, headroom=128,
        ring_size=32, tx_room=None, tx_pending_cap=4096):
    ring = NativeRing(nframes=nframes, frame_size=frame_size, depth=depth)
    kern = xsk.SimKernelRings(ring, headroom=headroom, ring_size=ring_size,
                              tx_room=tx_room)
    pump = xsk.WirePump(ring, kern, path=path,
                        tx_pending_cap=tx_pending_cap)
    return ring, kern, pump


def _discover(i):
    mac = (0x02C0FFEE0000 + i).to_bytes(6, "big")
    p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER, xid=0x1000 + i)
    return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                              p.encode().ljust(300, b"\x00"))


def _data(i, size=96):
    return packets.udp_packet(
        b"\x02" * 6, b"\x04" * 6, 0x0A000000 + i, 0x08080808,
        1024 + i, 443, bytes([i % 256]) * size)


def _mixed(n, seed=0):
    """DHCP control + UDP data interleaved: the classify/steer path on
    submit must route identically on both pumps."""
    return [(_discover(seed + i) if i % 3 == 0 else _data(seed + i))
            for i in range(n)]


def _reflect(ring, budget=32, slot=512, pattern=(2,)):
    """Host-only ring consumer: assemble, stamp verdicts from `pattern`
    cycled by lane (2=TX 3=FWD 1=DROP 0=PASS), complete. Returns the
    assembled (bytes, flags, verdict) rows — frame ORDER is part of the
    identity contract."""
    out = np.zeros((budget, slot), dtype=np.uint8)
    ln = np.zeros(budget, dtype=np.uint32)
    fl = np.zeros(budget, dtype=np.uint32)
    n = ring.assemble(out, ln, fl)
    rows = []
    if n:
        verdict = np.array([pattern[i % len(pattern)] for i in range(n)],
                           dtype=np.uint8)
        ring.complete(verdict, out[:n], ln[:n], n)
        rows = [(bytes(out[i, :ln[i]]), int(fl[i]), int(verdict[i]))
                for i in range(n)]
        # PASS lanes land on the slow ring, outside the pump's loop —
        # drain them so frame accounting closes
        while ring.slow_pop() is not None:
            pass
    return rows


def _run(path, cfg, script):
    """Execute `script` ops against a fresh (ring, kernel, pump) stack
    and trace EVERYTHING observable."""
    ring, kern, pump = _mk(path, **cfg)
    trace = []
    for op in script:
        kind = op[0]
        if kind == "inject":
            kern.inject_many(op[1])
        elif kind == "inject_claim":
            kern.inject(op[1], claim_len=op[2])
        elif kind == "pump":
            trace.append(("moved", pump.pump(budget=op[1])))
        elif kind == "deliver":
            kern.deliver()
        elif kind == "reflect":
            trace.append(("rows", _reflect(ring, pattern=op[1])))
        elif kind == "drain":
            trace.append(("egress", kern.drain_egress()))
        else:  # pragma: no cover - script typo guard
            raise AssertionError(kind)
    trace.append(("stats", dict(pump.pump_stats)))
    trace.append(("ring", ring.stats()))
    trace.append(("free", ring.free_frames()))
    trace.append(("pending", pump.tx_pending()))
    last = pump.last_path
    ring.close()
    return trace, last


def _round(n=8, budget=16, pattern=(2, 3, 1), seed=0):
    """One full wire round: inject -> pump (rx) -> reflect -> pump (tx)
    -> drain."""
    return [("inject", _mixed(n, seed=seed)), ("pump", budget),
            ("deliver",), ("pump", budget), ("reflect", pattern),
            ("pump", budget), ("drain",)]


CORPUS = {
    "steady_state": (
        {},
        _round(8, seed=0) + _round(8, seed=8) + _round(8, seed=16)),
    "partial_fill": (
        {},
        _round(3, budget=16, seed=0) + _round(1, budget=16, seed=3)
        + _round(0, budget=16, seed=4)),
    "full_fill_ring": (
        # kernel rings far smaller than the budget: fill pushes must
        # come back partial and the pump must hand the excess frames
        # straight back to the pool
        {"ring_size": 8, "nframes": 64},
        _round(6, budget=32, seed=0) + _round(6, budget=32, seed=6)),
    "tx_stall_retry": (
        # kernel TX accepts 3/round: pending descriptors must retry in
        # order across rounds on both paths
        {"tx_room": 3},
        _round(6, pattern=(2,), seed=0) + _round(6, pattern=(2,), seed=6)
        + _round(0, pattern=(2,), seed=12)),
    "headroom_zero": (
        {"headroom": 0},
        _round(8, seed=0) + _round(8, seed=8)),
    "headroom_deep": (
        # frame_size 512, headroom 256: room is 256 bytes — the
        # copy-mode shape at its tightest
        {"headroom": 256},
        _round(6, seed=0) + _round(6, seed=6)),
    "forged_rx_len": (
        # kernel-misbehavior guard: a claimed length that cannot fit
        # the chunk room (512-128=384) must drop AND recycle; the
        # boundary length (exactly 384) must pass
        {},
        [("inject", _mixed(2, seed=0)),
         ("inject_claim", b"z" * 64, 500),
         ("inject_claim", b"y" * 64, 384),
         ("inject_claim", b"x" * 64, 385),
         ("pump", 16), ("deliver",), ("pump", 16),
         ("reflect", (2,)), ("pump", 16), ("drain",)]),
    "rx_ring_full": (
        # ring rx queue depth 8 < injected 14: the overflow submits
        # must fail rx-full and recycle on both paths
        {"depth": 8, "ring_size": 32},
        [("inject", _mixed(14, seed=0)), ("pump", 16), ("deliver",),
         ("pump", 16), ("reflect", (2,)), ("pump", 16), ("drain",)]
        + _round(4, seed=20)),
}


@needs_native
class TestBitIdentityCorpus:
    """vector == scalar over every edge case: same assembled frame
    order+flags, same verdict routing, same egress bytes, same
    pump_stats, same ring stats, same frame accounting."""

    @pytest.mark.parametrize("name", sorted(CORPUS))
    def test_identity(self, name):
        cfg, script = CORPUS[name]
        scalar, last_s = _run("scalar", cfg, script)
        vector, last_v = _run("vector", cfg, script)
        assert last_s == "scalar"
        assert last_v == "vector", "vector cohort silently ran scalar"
        for (ks, vs), (kv, vv) in zip(scalar, vector):
            assert (ks, vs) == (kv, vv), (
                f"{name}: first divergence at {ks!r}:\n"
                f"  scalar: {vs!r}\n  vector: {vv!r}")
        assert scalar == vector

    def test_corpus_actually_exercises_the_edges(self):
        """The corpus must HIT the paths it claims to pin (an edge case
        that never fires pins nothing)."""
        cfg, script = CORPUS["forged_rx_len"]
        trace, _ = _run("vector", cfg, script)
        stats = dict(trace)["stats"]
        assert stats["rx_submit_fail"] == 2  # 500 and 385, not 384
        cfg, script = CORPUS["rx_ring_full"]
        trace, _ = _run("vector", cfg, script)
        assert dict(trace)["ring"]["rx_full"] >= 1
        cfg, script = CORPUS["tx_stall_retry"]
        trace, _ = _run("vector", cfg, script)
        stats = dict(trace)["stats"]
        assert stats["tx"] == 12  # every stalled descriptor retried out
        cfg, script = CORPUS["full_fill_ring"]
        trace, _ = _run("vector", cfg, script)
        assert dict(trace)["free"] > 0


@needs_native
class TestFrameAccounting:
    """The ISSUE-15 satellite pins."""

    @pytest.mark.parametrize("path", ["scalar", "vector"])
    def test_forged_len_storm_does_not_drain_the_pool(self, path):
        """The leak fix: a dropped RX frame must return to the fill
        pool. Pre-fix, each forged-length frame leaked one UMEM frame —
        16 frames of pressure on a 16-frame pool drained it permanently.
        Post-fix the pump survives indefinitely and still serves."""
        ring, kern, pump = _mk(path, nframes=16, ring_size=16)
        for i in range(50):  # >> nframes: pre-fix this wedges at i=16
            kern.inject(b"q" * 64, claim_len=500)
            pump.pump(budget=8)
            kern.deliver()
        assert pump.pump_stats["rx_submit_fail"] == 50
        # the pool is whole: a good frame still traverses end to end
        good = _data(7)
        kern.inject(good)
        pump.pump(budget=8)
        kern.deliver()
        pump.pump(budget=8)
        rows = _reflect(ring)
        assert [r[0] for r in rows] == [good]
        pump.pump(budget=8)
        assert kern.drain_egress() == [good]
        ring.close()

    @pytest.mark.parametrize("path", ["scalar", "vector"])
    def test_garbage_rx_addr_dropped_identically(self, path):
        """Kernel-misbehavior guard, address edition: an RX descriptor
        whose address lies OUTSIDE the UMEM must be dropped without
        touching memory (pre-fix the scalar path memmove'd from/to the
        forged address — out-of-bounds write) and without recycling a
        frame that was never ours, counted as rx_submit_fail + the
        ring's bad_desc on BOTH paths."""
        ring, kern, pump = _mk(path)
        bad = np.zeros(1, dtype=np.uint64)
        badl = np.zeros(1, dtype=np.uint32)
        bad[0] = ring.umem_size + 4096  # forged: past the UMEM end
        badl[0] = 64
        kern._rx_a.push(bad, 1)  # white-box: forge the raw descriptor
        kern._rx_l.push(badl, 1)
        free_before = ring.free_frames()
        pump.pump(budget=8)
        assert pump.pump_stats["rx_submit_fail"] == 1
        assert ring.stats()["bad_desc"] == 1
        # pool accounting exact: the fill phase took its frames, and the
        # forged address neither leaked one nor recycled one that was
        # never ours
        assert ring.free_frames() == free_before - pump.pump_stats["filled"]
        # the stack still serves: a good frame round-trips
        good = _data(9)
        kern.inject(good)
        pump.pump(budget=8)
        kern.deliver()
        pump.pump(budget=8)
        rows = _reflect(ring)
        assert [r[0] for r in rows] == [good]
        ring.close()

    @pytest.mark.parametrize("path", ["scalar", "vector"])
    def test_tx_pending_bounded_and_overflow_counted(self, path):
        """The pending-TX queue is explicitly bounded: a stalled kernel
        TX ring drops (and counts, and recycles) beyond the cap instead
        of growing without limit."""
        ring, kern, pump = _mk(path, tx_room=0, tx_pending_cap=4)
        sent = []
        for rnd in range(3):
            frames = [_data(rnd * 8 + i) for i in range(8)]
            sent.append(frames)
            kern.inject_many(frames)
            pump.pump(budget=16)
            kern.deliver()
            pump.pump(budget=16)
            _reflect(ring, pattern=(2,))
            pump.pump(budget=16)
            assert pump.tx_pending() <= 4
        assert pump.pump_stats["tx_overflow"] == 3 * 8 - 4
        assert pump.pump_stats["tx"] == 0
        # dropped frames were recycled, not leaked: un-stall and the 4
        # RETAINED (oldest) descriptors egress, then serving continues
        kern.tx_room = None
        pump.pump(budget=16)
        assert kern.drain_egress() == sent[0][:4]
        assert pump.tx_pending() == 0
        good = _data(99)
        kern.inject(good)
        pump.pump(budget=16)
        kern.deliver()
        pump.pump(budget=16)
        _reflect(ring, pattern=(2,))
        pump.pump(budget=16)
        assert kern.drain_egress() == [good]
        ring.close()

    def test_chaos_armed_rounds_take_the_scalar_path(self):
        """Fault-point hit accounting is per-call: an armed plan forces
        the scalar oracle (the PR-14 fleet/admission mold), and the
        selection is re-evaluated every round."""
        ring, kern, pump = _mk("vector")
        kern.inject_many(_mixed(4))
        pump.pump(budget=8)
        assert pump.last_path == "vector"
        with faults.armed(faults.FaultPlan(seed=1), log=False):
            pump.pump(budget=8)
            assert pump.last_path == "scalar"
        pump.pump(budget=8)
        assert pump.last_path == "vector"
        assert pump.path == "vector"  # construction identity unchanged
        ring.close()


class TestSelector:
    def test_env_selector_validates(self, monkeypatch):
        monkeypatch.setattr(xsk, "WIRE_PUMP", "bogus")
        with pytest.raises(ValueError, match="BNG_WIRE_PUMP"):
            xsk.resolved_wire_pump()

    @needs_native
    def test_explicit_bad_path_refused(self):
        ring = NativeRing(nframes=16, frame_size=256, depth=8)
        kern = xsk.SimKernelRings(ring, ring_size=8)
        with pytest.raises(ValueError, match="unknown wire pump"):
            xsk.WirePump(ring, kern, path="turbo")
        ring.close()


class TestWireTelemetry:
    def test_wire_stages_in_the_fixed_vocabulary(self):
        from bng_tpu.telemetry import spans as tele
        from bng_tpu.telemetry.slo import DEFAULT_SLOS

        assert "wire_rx" in tele.STAGE_NAMES
        assert "wire_tx" in tele.STAGE_NAMES
        budgeted = {s.stage for s in DEFAULT_SLOS}
        assert {"wire_rx", "wire_tx"} <= budgeted

    @needs_native
    def test_pump_laps_the_wire_stages(self):
        from bng_tpu.telemetry import FlightRecorder, RecorderConfig
        from bng_tpu.telemetry import spans as tele

        ring, kern, pump = _mk("vector")
        tr = tele.Tracer(recorder=FlightRecorder(RecorderConfig()))
        tele.arm(tr)
        try:
            kern.inject_many(_mixed(4))
            pump.pump(budget=8)
            kern.deliver()
            pump.pump(budget=8)
        finally:
            tele.disarm()
        bd = tr.breakdown()
        assert bd["wire_rx"]["count"] == 2
        assert bd["wire_tx"]["count"] == 2
        ring.close()

    def test_wire_fallback_trigger_dumps_flight_ring(self, tmp_path):
        from bng_tpu.telemetry import FlightRecorder, RecorderConfig
        from bng_tpu.telemetry import recorder as rec_mod
        from bng_tpu.telemetry import spans as tele

        rec = FlightRecorder(RecorderConfig(out_dir=str(tmp_path)))
        tele.arm(tele.Tracer(recorder=rec))
        try:
            path = tele.trigger(rec_mod.TRIG_WIRE_FALLBACK,
                                "requested 'eth9' landed on memory")
        finally:
            tele.disarm()
        assert path and rec.triggers.get(rec_mod.TRIG_WIRE_FALLBACK) == 1

    @needs_native
    def test_collect_wire_metrics(self):
        from bng_tpu.control.metrics import BNGMetrics

        ring, kern, pump = _mk("vector")
        kern.inject_many(_mixed(4))
        pump.pump(budget=8)
        kern.deliver()
        pump.pump(budget=8)
        att = xsk.WireAttachment(xsk.MODE_MEMORY, None, "no iface")
        m = BNGMetrics()
        m.collect_wire(att, pump=pump)
        text = m.registry.expose()
        assert 'bng_wire_rung{mode="memory"} 1' in text
        assert 'bng_wire_rung{mode="zerocopy"} 0' in text
        assert 'bng_wire_pump_path{path="vector"} 1' in text
        assert 'bng_wire_frames_total{dir="rx"} 4' in text
        assert "bng_wire_filled_total" in text
        assert "bng_wire_tx_overflow_total 0" in text
        assert "bng_wire_tx_pending 0" in text
        ring.close()


class TestWireLoopTargetXid:
    """The loadtest wire target matches replies to request lanes by
    BOOTP xid — the wire hands back frames, not lane indexes."""

    def test_request_reply_and_vlan_tolerance(self):
        from bng_tpu.loadtest import WireLoopTarget

        mac = bytes.fromhex("02c0ffee0030")
        req = _dhcp(mac, dhcp_codec.DISCOVER, xid=0xABCD1234)
        assert WireLoopTarget._xid(req, reply=False) == 0xABCD1234
        assert WireLoopTarget._xid(req, reply=True) is None  # op=1
        # single VLAN tag between L2 and the IP header
        tagged = req[:12] + b"\x81\x00\x00\x64" + req[12:]
        assert WireLoopTarget._xid(tagged, reply=False) == 0xABCD1234
        assert WireLoopTarget._xid(b"\x00" * 13, reply=False) is None
        assert WireLoopTarget._xid(_data(0), reply=False) is None


# ---------------------------------------------------------------------------
# wire serving: the four-scenario proof (memory-rung twin, tier-1)
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self, t=T0):
        self.t = float(t)

    def __call__(self):
        return self.t


class _Sess:
    session_id = 0x0042
    client_mac = bytes.fromhex("02c0ffee0101")
    assigned_ip = ip_to_u32("10.0.0.50")


def _serving_stack():
    """The full production stack of the veth proof, memory-rung twin:
    DHCP + NAT + QoS + PPPoE behind one Engine."""
    from bng_tpu.control.dhcp_server import DHCPServer
    from bng_tpu.control.nat import NATManager
    from bng_tpu.control.pool import Pool, PoolManager
    from bng_tpu.runtime.engine import Engine, QoSTables
    from bng_tpu.runtime.tables import FastPathTables, PPPoEFastPathTables

    clock = _Clock()
    fastpath = FastPathTables(sub_nbuckets=512, vlan_nbuckets=64,
                              cid_nbuckets=64, max_pools=16)
    fastpath.set_server_config(SERVER_MAC, SERVER_IP)
    pools = PoolManager(fastpath)
    pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.0.0.0"),
                        prefix_len=24, gateway=SERVER_IP,
                        dns_primary=ip_to_u32("1.1.1.1"), lease_time=3600))
    nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                     sessions_nbuckets=256, sub_nat_nbuckets=64)
    qos = QoSTables(nbuckets=256)
    pp = PPPoEFastPathTables(nbuckets=64, stash=8, server_mac=SERVER_MAC)
    server = DHCPServer(SERVER_MAC, SERVER_IP, pools,
                        fastpath_tables=fastpath, clock=clock)
    engine = Engine(fastpath, nat, qos, pppoe=pp, batch_size=8,
                    slow_path=server.handle_frame, clock=clock)
    pp.session_up(_Sess())
    nat.allocate_nat(_Sess.assigned_ip, T0)
    nat.allocate_nat(ip_to_u32("10.0.0.55"), T0)
    nat.allocate_nat(ip_to_u32("10.0.0.60"), T0)
    qos.set_subscriber(ip_to_u32("10.0.0.60"), down_bps=8000, up_bps=8000,
                       up_burst=1500, down_burst=1500)
    return engine, server, nat, qos


def _pppoe_data(sport=40000):
    from bng_tpu.control.pppoe import codec
    from bng_tpu.ops import pppoe as P

    inner = packets.udp_packet(_Sess.client_mac, SERVER_MAC,
                               _Sess.assigned_ip, ip_to_u32("8.8.8.8"),
                               sport, 53, b"q" * 32)[14:]
    ppp = codec.ppp_frame(P.PPP_IPV4, inner)
    pppoe = codec.PPPoEPacket(code=0, session_id=_Sess.session_id,
                              payload=ppp).encode()
    return codec.eth_frame(SERVER_MAC, _Sess.client_mac,
                           codec.ETH_PPPOE_SESSION, pppoe)


def _qos_frame():
    """One 442-byte frame of the shaped subscriber's established flow
    (10.0.0.60 -> 8.8.8.8:9999, 1500-byte token bucket)."""
    return packets.udp_packet(bytes.fromhex("02c0ffee0020"), SERVER_MAC,
                              ip_to_u32("10.0.0.60"), ip_to_u32("8.8.8.8"),
                              1111, 9999, b"x" * 400)


def _dhcp(mac, msg_type, xid, **kw):
    p = dhcp_codec.build_request(mac, msg_type, xid=xid, **kw)
    p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST,
                      bytes([1, 3, 6, 51, 54])))
    return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                              p.encode().ljust(320, b"\x00"))


def _drive_wire_scenarios(engine, ring, kern, pump):
    """Run the four acceptance scenarios through the FULL wire loop
    (far-end inject -> kernel rings -> pump -> UMEM ring -> engine ->
    pump -> far-end drain). Returns {scenario: [egress frames]}."""

    def roundtrip(frames, rounds=6):
        kern.inject_many(frames)
        got = []
        for _ in range(rounds):
            pump.pump(budget=16)
            kern.deliver()
            engine.process_ring_pipelined(ring)
            engine.flush_pipeline(ring)
            pump.pump(budget=16)
            got.extend(kern.drain_egress())
        return got

    out = {}
    mac = bytes.fromhex("02c0ffee0001")
    # 1. DORA: DISCOVER #1 -> slow-path OFFER; REQUEST -> ACK (lease
    #    installed); DISCOVER #2 -> answered on device
    offers = roundtrip([_dhcp(mac, dhcp_codec.DISCOVER, xid=0x11)])
    assert len(offers) == 1, "no OFFER egressed the wire"
    offer = dhcp_codec.decode(packets.decode(offers[0]).payload)
    assert offer.msg_type == dhcp_codec.OFFER
    acks = roundtrip([_dhcp(mac, dhcp_codec.REQUEST, xid=0x12,
                            requested_ip=offer.yiaddr,
                            server_id=SERVER_IP)])
    assert dhcp_codec.decode(
        packets.decode(acks[0]).payload).msg_type == dhcp_codec.ACK
    tx_before = engine.stats.tx
    offers2 = roundtrip([_dhcp(mac, dhcp_codec.DISCOVER, xid=0x13)])
    assert engine.stats.tx == tx_before + 1  # on-device, not slow path
    out["dora"] = offers + acks + offers2

    # 2. NAT: packet 1 punts, the host creates its session, and the same
    #    packet egresses SNATed on its second pass through the chip (PR 53;
    #    nat44.c:686-801); packet 2 SNATs on device, to the same bytes
    sub_ip = ip_to_u32("10.0.0.55")
    f = packets.udp_packet(bytes.fromhex("02c0ffee0010"), SERVER_MAC,
                           sub_ip, ip_to_u32("93.184.216.34"), 40000, 443,
                           b"nat-payload")
    passed_before = engine.stats.passed
    first = roundtrip([f])
    assert len(first) == 1, "packet 1 of a new flow must egress translated"
    assert engine.stats.passed == passed_before + 1  # it did punt, once
    natted = roundtrip([f])
    assert natted == first and engine.stats.passed == passed_before + 1
    d = packets.decode(natted[0])
    assert d.src_ip == ip_to_u32("203.0.113.1")  # SNAT applied
    out["nat"] = first + natted

    # 3. QoS: an ESTABLISHED flow (its first frame punts and goes round,
    #    then device SNAT+shape): the 1500-byte bucket passes some
    #    ~442-byte frames to the wire and the over-budget drops never egress
    assert len(roundtrip([_qos_frame()])) == 1  # punt creates the session
    dropped_before = engine.stats.dropped
    shaped = roundtrip([_qos_frame() for _ in range(4)])
    n_dropped = engine.stats.dropped - dropped_before
    assert n_dropped >= 1, "QoS never dropped"
    assert len(shaped) == 4 - n_dropped >= 1
    out["qos"] = shaped

    # 4. PPPoE: data frame 1 punts (inner-flow NAT miss) and goes round,
    #    frame 2 decaps + SNATs on device: the same bytes both times
    up = _pppoe_data()
    first = roundtrip([up])
    fwd = roundtrip([up])
    assert len(fwd) == 1 and first == fwd
    d = packets.decode(fwd[0])
    assert d.ethertype == 0x0800  # PPPoE framing stripped on device
    assert d.src_ip == ip_to_u32("203.0.113.1")
    out["pppoe"] = fwd
    return out


@needs_native
class TestWireServingMemoryRung:
    """The acceptance twin: the four scenarios over the memory rung,
    byte-exact across BOTH pump implementations (identical stacks,
    identical traffic, frozen clocks — any wire-visible divergence
    between the pumps is a bug)."""

    def test_four_scenarios_byte_exact_across_pumps(self):
        results = {}
        for path in ("scalar", "vector"):
            engine, server, nat, qos = _serving_stack()
            ring = NativeRing(nframes=256, frame_size=2048, depth=64)
            kern = xsk.SimKernelRings(ring, headroom=256, ring_size=128)
            pump = xsk.WirePump(ring, kern, path=path)
            results[path] = _drive_wire_scenarios(engine, ring, kern, pump)
            assert pump.last_path == path
            assert pump.pump_stats["rx_submit_fail"] == 0
            assert pump.pump_stats["tx_overflow"] == 0
            ring.close()
        assert results["scalar"] == results["vector"], (
            "far-end bytes diverge between pump implementations")


# ---------------------------------------------------------------------------
# wire serving: the live AF_XDP copy-mode rung on veth (slow tier)
# ---------------------------------------------------------------------------

def _veth_ok() -> bool:
    import subprocess

    r = subprocess.run(["ip", "link", "add", "bngwp0", "type", "veth",
                        "peer", "name", "bngwp1"], capture_output=True)
    if r.returncode != 0:
        return False
    subprocess.run(["ip", "link", "del", "bngwp0"], capture_output=True)
    return True


def _live_rung_possible() -> bool:
    from bng_tpu.runtime import xdp_redirect

    return (xsk.probe() != "unavailable" and xsk.probe() != xsk.MODE_MEMORY
            and xdp_redirect.probe() and _veth_ok())


@pytest.mark.slow  # heavy e2e: the 870s tier-1 cap (ISSUE 15 satellite)
@pytest.mark.skipif(not _live_rung_possible(),
                    reason="needs CAP_NET_ADMIN + AF_XDP + CAP_BPF")
class TestWireServingVeth:
    """The four scenarios over the REAL kernel: AF_XDP copy-mode bind
    on a veth pair, frames injected on the far peer with AF_PACKET,
    replies asserted byte-exact against the memory-rung twin's output
    (the twin ran the identical stack, so any difference is the wire)."""

    IF_A, IF_B = "bngwp0", "bngwp1"

    @pytest.fixture
    def veth(self):
        import subprocess

        subprocess.run(["ip", "link", "del", self.IF_A], capture_output=True)
        subprocess.run(["ip", "link", "add", self.IF_A, "type", "veth",
                        "peer", "name", self.IF_B], check=True,
                       capture_output=True)
        for i in (self.IF_A, self.IF_B):
            subprocess.run(["ip", "link", "set", i, "up"], check=True,
                           capture_output=True)
        time.sleep(0.3)
        yield
        subprocess.run(["ip", "link", "del", self.IF_A], capture_output=True)

    @pytest.mark.parametrize("pump_path", ["scalar", "vector"])
    def test_four_scenarios_live(self, veth, pump_path):
        import socket as so

        from bng_tpu.runtime import xdp_redirect

        # reference: the memory-rung twin over an identical stack gives
        # the exact reply bytes the live rung must reproduce
        engine_ref, _, _, _ = _serving_stack()
        ring_ref = NativeRing(nframes=256, frame_size=2048, depth=64)
        kern_ref = xsk.SimKernelRings(ring_ref, headroom=256, ring_size=128)
        expected = _drive_wire_scenarios(
            engine_ref, ring_ref, kern_ref,
            xsk.WirePump(ring_ref, kern_ref, path=pump_path))
        ring_ref.close()

        engine, server, nat, qos = _serving_stack()
        ring = NativeRing(nframes=4096, frame_size=2048, depth=1024)
        att = xsk.open_wire(ring, ifname=self.IF_A, queue=0,
                            pump_path=pump_path)
        assert att.mode == xsk.MODE_COPY, (att.mode, att.detail)
        s = att.xsk
        redir = xdp_redirect.XdpRedirect(self.IF_A, {0: s.fd})
        txs = so.socket(so.AF_PACKET, so.SOCK_RAW)
        txs.bind((self.IF_B, 0))
        rxs = so.socket(so.AF_PACKET, so.SOCK_RAW, so.htons(0x0003))
        rxs.bind((self.IF_B, 0))
        rxs.setblocking(False)
        try:
            s.pump()  # pre-stock the kernel fill ring

            def exchange(frames, want: int, deadline_s=8.0):
                for f in frames:
                    txs.send(f)
                got = []
                deadline = time.time() + deadline_s
                while time.time() < deadline and len(got) < want:
                    s.pump(budget=64)
                    engine.process_ring_pipelined(ring)
                    engine.flush_pipeline(ring)
                    s.pump(budget=64)
                    while True:
                        try:
                            got.append(rxs.recv(4096))
                        except (BlockingIOError, OSError):
                            break
                    time.sleep(0.01)
                return got

            mac = bytes.fromhex("02c0ffee0001")
            # 1. DORA, byte-exact vs the twin
            got = exchange([_dhcp(mac, dhcp_codec.DISCOVER, xid=0x11)], 1)
            assert expected["dora"][0] in got
            offer = dhcp_codec.decode(
                packets.decode(expected["dora"][0]).payload)
            got = exchange([_dhcp(mac, dhcp_codec.REQUEST, xid=0x12,
                                  requested_ip=offer.yiaddr,
                                  server_id=SERVER_IP)], 1)
            assert expected["dora"][1] in got
            tx_before = engine.stats.tx
            got = exchange([_dhcp(mac, dhcp_codec.DISCOVER, xid=0x13)], 1)
            assert expected["dora"][2] in got
            assert engine.stats.tx == tx_before + 1  # on-device OFFER

            # 2. NAT new-flow punt, then device SNAT
            sub_ip = ip_to_u32("10.0.0.55")
            f = packets.udp_packet(bytes.fromhex("02c0ffee0010"),
                                   SERVER_MAC, sub_ip,
                                   ip_to_u32("93.184.216.34"), 40000, 443,
                                   b"nat-payload")
            got = exchange([f], 1, deadline_s=2.0)  # punt: nothing OURS
            assert expected["nat"][0] not in got
            got = exchange([f], 1)
            assert expected["nat"][0] in got

            # 3. QoS: the over-budget frames drop, survivors byte-exact
            exchange([_qos_frame()], 1, deadline_s=2.0)  # punt
            dropped_before = engine.stats.dropped
            got = exchange([_qos_frame() for _ in range(4)],
                           len(expected["qos"]))
            assert engine.stats.dropped > dropped_before
            for surviving in expected["qos"]:
                assert surviving in got

            # 4. PPPoE session data: punt, then decap+SNAT on device
            up = _pppoe_data()
            exchange([up], 1, deadline_s=2.0)
            got = exchange([up], 1)
            assert expected["pppoe"][0] in got

            assert s.pump_stats["rx"] > 0 and s.pump_stats["tx"] > 0
            assert s.wire_pump.last_path == pump_path
        finally:
            txs.close()
            rxs.close()
            redir.close()
            s.close()
            ring.close()
