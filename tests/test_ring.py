"""Native packet ring: ABI layout, SPSC semantics, verdict demux, and the
ring-driven end-to-end DORA loop.

The ABI tests are the test/ebpf/maps_test.go role (reference asserts
unsafe.Sizeof(Go mirror) == C layout, maps_test.go:17-80): here the C
library self-describes bng_desc offsets and the ctypes mirror must match
byte-for-byte, or host<->native frame descriptors would corrupt.

Every behavioral test runs against BOTH backends (NativeRing via the C++
.so built from native/bngring.cpp, and the PyRing stub) — the reference's
_linux.go/_stub.go parity discipline (SURVEY.md §4.6).
"""

import ctypes as C

import numpy as np
import pytest

from bng_tpu.runtime.ring import (
    Desc,
    NativeRing,
    PyRing,
    RingStats,
    load_native,
    wire_pump,
)

native_available = load_native() is not None


@pytest.fixture(params=["native", "py"])
def ring_cls(request):
    if request.param == "native":
        if not native_available:
            pytest.skip("native toolchain unavailable")
        return NativeRing
    return PyRing


class TestABI:
    """Host mirror <-> C layout (maps_test.go:17-80 role)."""

    @pytest.mark.skipif(not native_available, reason="no native lib")
    def test_desc_layout(self):
        lib = load_native()
        assert lib.bng_abi_desc_size() == C.sizeof(Desc)
        assert lib.bng_abi_desc_addr_off() == Desc.addr.offset
        assert lib.bng_abi_desc_len_off() == Desc.len.offset
        assert lib.bng_abi_desc_flags_off() == Desc.flags.offset

    @pytest.mark.skipif(not native_available, reason="no native lib")
    def test_stats_layout_and_version(self):
        lib = load_native()
        assert lib.bng_abi_stats_size() == C.sizeof(RingStats)
        assert lib.bng_abi_version() == 5  # PR 53: fwd_inject


class TestRingBasics:
    def test_push_assemble_roundtrip(self, ring_cls):
        r = ring_cls(nframes=64, frame_size=256, depth=32)
        frames = [bytes([i]) * (20 + i) for i in range(5)]
        for i, f in enumerate(frames):
            assert r.rx_push(f, from_access=(i % 2 == 0))
        assert r.rx_pending() == 5

        out = np.zeros((8, 128), dtype=np.uint8)
        ln = np.zeros((8,), dtype=np.uint32)
        fl = np.zeros((8,), dtype=np.uint32)
        n = r.assemble(out, ln, fl)
        assert n == 5
        for i, f in enumerate(frames):
            assert bytes(out[i, : ln[i]]) == f
            assert (fl[i] & 1) == (1 if i % 2 == 0 else 0)
        r.close()

    def test_verdict_demux(self, ring_cls):
        r = ring_cls(nframes=64, frame_size=256, depth=32)
        for i in range(4):
            r.rx_push(bytes([i]) * 64)
        out = np.zeros((8, 128), dtype=np.uint8)
        ln = np.zeros((8,), dtype=np.uint32)
        fl = np.zeros((8,), dtype=np.uint32)
        n = r.assemble(out, ln, fl)
        assert n == 4

        # lane 0 TX (rewritten), 1 DROP, 2 FWD (rewritten), 3 PASS
        out[0, :4] = (0xAA, 0xBB, 0xCC, 0xDD)
        ln[0] = 4
        out[2, :2] = (0x11, 0x22)
        ln[2] = 2
        verdict = np.array([2, 1, 3, 0], dtype=np.uint8)
        r.complete(verdict, out, ln, n)

        assert r.tx_pending() == 1 and r.fwd_pending() == 1 and r.slow_pending() == 1
        frame, _ = r.tx_pop()
        assert frame == bytes([0xAA, 0xBB, 0xCC, 0xDD])
        frame, _ = r.fwd_pop()
        assert frame == bytes([0x11, 0x22])
        frame, _ = r.slow_pop()
        assert frame == bytes([3]) * 64  # PASS keeps original bytes
        s = r.stats()
        assert s["tx"] == 1 and s["fwd"] == 1 and s["drop"] == 1 and s["slow"] == 1
        r.close()

    def test_frames_recycle(self, ring_cls):
        r = ring_cls(nframes=8, frame_size=128, depth=8)
        out = np.zeros((8, 128), dtype=np.uint8)
        ln = np.zeros((8,), dtype=np.uint32)
        fl = np.zeros((8,), dtype=np.uint32)
        for _round in range(5):  # > nframes total frames: must recycle
            for i in range(4):
                assert r.rx_push(b"x" * 60)
            n = r.assemble(out, ln, fl)
            r.complete(np.full((n,), 1, dtype=np.uint8), out, ln, n)  # DROP all
        assert r.free_frames() == 8

    def test_fill_exhaustion(self, ring_cls):
        r = ring_cls(nframes=8, frame_size=128, depth=16)
        ok = sum(1 for _ in range(12) if r.rx_push(b"y" * 32))
        assert ok == 8  # only nframes fit
        assert r.stats()["fill_empty"] >= 1 or r.free_frames() == 0
        r.close()

    def test_oversize_frame_rejected(self, ring_cls):
        r = ring_cls(nframes=8, frame_size=128, depth=8)
        assert not r.rx_push(b"z" * 500)
        r.close()

    def test_tx_inject(self, ring_cls):
        r = ring_cls(nframes=8, frame_size=128, depth=8)
        assert r.tx_inject(b"reply" * 4)
        frame, fl = r.tx_pop()
        assert frame == b"reply" * 4 and (fl & 1) == 1
        r.close()

    def test_two_inflight_windows_fifo(self, ring_cls):
        """Double buffering: two assemble..complete windows may be open
        (the pipelined engine's contract); a third is refused; complete()
        retires strictly FIFO."""
        r = ring_cls(nframes=8, frame_size=128, depth=8)
        out1 = np.zeros((4, 64), dtype=np.uint8)
        out2 = np.zeros((4, 64), dtype=np.uint8)
        ln1 = np.zeros((4,), dtype=np.uint32)
        ln2 = np.zeros((4,), dtype=np.uint32)
        fl = np.zeros((4,), dtype=np.uint32)

        r.rx_push(b"a" * 32)
        assert r.assemble(out1, ln1, fl) == 1  # window 1
        r.rx_push(b"b" * 32)
        r.rx_push(b"c" * 32)
        assert r.assemble(out2, ln2, fl) == 2  # window 2 (double buffer)
        r.rx_push(b"d" * 32)
        assert r.assemble(out1, ln1, fl) == 0  # third window refused

        # FIFO: the first complete retires window 1 (the 1-frame batch);
        # PASS it so the original bytes prove which batch retired
        r.complete(np.array([0], dtype=np.uint8), out1, ln1, 1)
        frame, _ = r.slow_pop()
        assert frame == b"a" * 32
        r.complete(np.array([0, 0], dtype=np.uint8), out2, ln2, 2)
        assert r.slow_pop()[0] == b"b" * 32
        assert r.slow_pop()[0] == b"c" * 32
        # both windows closed: assemble works again
        assert r.assemble(out1, ln1, fl) == 1
        r.close()


class TestTxRefused:
    def test_full_tx_ring_refuses_and_counts(self, ring_cls):
        """A reply the TX ring has no room for is refused AND counted
        (`stats()["tx_refused"]`): cli.py _drive_scheduler ignores the
        refusal, so the count is the only trace of the lost frame."""
        r = ring_cls(nframes=64, frame_size=256, depth=8)
        assert r.stats()["tx_refused"] == 0
        took = sum(r.tx_inject(bytes([i]) * 40, from_access=True)
                   for i in range(12))
        assert took == 8
        assert r.stats()["tx_refused"] == 4
        assert r.stats()["tx"] == 8
        assert r.tx_pop() is not None           # room again
        assert r.tx_inject(b"z" * 40)
        assert r.stats()["tx_refused"] == 4
        r.close()

    def test_drive_scheduler_counts_what_it_loses(self):
        """S-a, counted and not repaired: one retire of more replies than
        the TX ring is deep loses the rest; `ring.tx_refused` says how
        many."""
        from bng_tpu.cli import BNGApp, BNGConfig
        from bng_tpu.control import packets
        from bng_tpu.utils.net import ip_to_u32

        app = BNGApp(BNGConfig(synthetic_subs=1, batch_size=32,
                               scheduler_enabled=True,
                               dhcpv6_enabled=False, slaac_enabled=False))
        app.config.synthetic_subs = 0  # the ring without its generator
        try:
            ring = app.components["ring"]
            from bng_tpu.runtime.scheduler import Completion

            sched = app.components["scheduler"]
            frame = packets.udp_packet(b"\x02" * 6, b"\x04" * 6,
                                       ip_to_u32("10.0.0.9"),
                                       ip_to_u32("93.184.216.34"), 4000, 443,
                                       b"x" * 18)
            n = ring.depth + 5
            for i in range(n):
                sched.completions.append(
                    Completion(i, "bulk", "fwd", frame, True, 0.0))
            app.drive_once()
            assert ring.stats()["tx_refused"] == 5
            assert ring.tx_pending() == ring.depth
        finally:
            app.close()


class TestWire:
    def test_loopback_pump_flips_direction(self, ring_cls):
        a = ring_cls(nframes=32, frame_size=256, depth=16)
        b = ring_cls(nframes=32, frame_size=256, depth=16)
        a.rx_push(b"ping" * 8, from_access=True)
        out = np.zeros((4, 128), dtype=np.uint8)
        ln = np.zeros((4,), dtype=np.uint32)
        fl = np.zeros((4,), dtype=np.uint32)
        n = a.assemble(out, ln, fl)
        r_verdict = np.array([3], dtype=np.uint8)  # FWD
        a.complete(r_verdict, out, ln, n)
        moved = wire_pump(a, b, budget=8)
        assert moved == 1
        n = b.assemble(out, ln, fl)
        assert n == 1 and (fl[0] & 1) == 0  # arrived on the core side
        a.close()
        b.close()

    def test_pump_does_not_leak_dhcp_ctrl_flag(self, ring_cls):
        """A FWD'd access-side DHCP frame arriving on the core side must
        NOT keep its control bit (code-review r3: a stale bit would smuggle
        network-side frames past the fast lane's direction gate)."""
        from bng_tpu.control import dhcp_codec, packets
        from bng_tpu.runtime.ring import FLAG_DHCP_CTRL

        a = ring_cls(nframes=32, frame_size=1024, depth=16)
        b = ring_cls(nframes=32, frame_size=1024, depth=16)
        mac = bytes.fromhex("02c0ffee0041")
        p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER)
        f = packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                               p.encode().ljust(320, b"\x00"))
        assert a.rx_push(f, from_access=True)
        out = np.zeros((4, 1024), dtype=np.uint8)
        ln = np.zeros((4,), dtype=np.uint32)
        fl = np.zeros((4,), dtype=np.uint32)
        n = a.assemble(out, ln, fl)
        assert fl[0] & FLAG_DHCP_CTRL  # classified on the access side
        a.complete(np.array([3], dtype=np.uint8), out, ln, n)  # FWD
        assert wire_pump(a, b, budget=8) == 1
        n = b.assemble(out, ln, fl)
        assert n == 1 and (fl[0] & FLAG_DHCP_CTRL) == 0
        a.close()
        b.close()


class TestRingEngine:
    """Ring-driven end-to-end: the production I/O loop."""

    def _stack(self, ring):
        from bng_tpu.control.dhcp_server import DHCPServer
        from bng_tpu.control.nat import NATManager
        from bng_tpu.control.pool import Pool, PoolManager
        from bng_tpu.runtime.engine import Engine
        from bng_tpu.runtime.tables import FastPathTables
        from bng_tpu.utils.net import ip_to_u32

        server_mac = bytes.fromhex("02aabbccdd01")
        server_ip = ip_to_u32("10.0.0.1")
        fastpath = FastPathTables(sub_nbuckets=512, vlan_nbuckets=64,
                                  cid_nbuckets=64, max_pools=16)
        fastpath.set_server_config(server_mac, server_ip)
        pools = PoolManager(fastpath)
        pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.0.0.0"),
                            prefix_len=24, gateway=server_ip,
                            dns_primary=ip_to_u32("1.1.1.1"), lease_time=3600))
        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         sessions_nbuckets=256, sub_nat_nbuckets=64)
        server = DHCPServer(server_mac, server_ip, pools,
                            fastpath_tables=fastpath,
                            clock=lambda: 1_753_000_000.0)
        engine = Engine(fastpath, nat, batch_size=8,
                        slow_path=server.handle_frame,
                        clock=lambda: 1_753_000_000.0)
        return engine, server

    def test_ring_dora_slow_then_fast(self, ring_cls):
        from bng_tpu.control import dhcp_codec, packets

        ring = ring_cls(nframes=64, frame_size=1024, depth=32)
        engine, server = self._stack(ring)
        mac = bytes.fromhex("02c0ffee0009")

        def discover():
            p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER)
            p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
            return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                      p.encode().ljust(320, b"\x00"))

        # DISCOVER #1: misses on device -> PASS -> slow path -> OFFER injected
        ring.rx_push(discover(), from_access=True)
        n = engine.process_ring(ring)
        assert n == 1
        assert engine.stats.passed == 1
        got = ring.tx_pop()
        assert got is not None
        offer, _ = got
        parsed = dhcp_codec.decode(packets.decode(offer).payload)
        assert parsed.msg_type == dhcp_codec.OFFER

        # REQUEST via slow path installs the fast-path entry
        req = dhcp_codec.build_request(mac, dhcp_codec.REQUEST)
        req.options.append((dhcp_codec.OPT_REQUESTED_IP, parsed.yiaddr.to_bytes(4, 'big')))
        req.options.append((dhcp_codec.OPT_SERVER_ID,
                            packets.decode(offer).src_ip.to_bytes(4, "big")))
        ring.rx_push(packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                        req.encode().ljust(320, b"\x00")))
        engine.process_ring(ring)
        ack, _ = ring.tx_pop()
        assert dhcp_codec.decode(packets.decode(ack).payload).msg_type == dhcp_codec.ACK

        # DISCOVER #2: answered ON DEVICE (TX verdict, no slow path)
        before_passed = engine.stats.passed
        ring.rx_push(discover(), from_access=True)
        engine.process_ring(ring)
        assert engine.stats.tx == 1
        assert engine.stats.passed == before_passed
        offer2, _ = ring.tx_pop()
        assert dhcp_codec.decode(packets.decode(offer2).payload).msg_type == dhcp_codec.OFFER
        ring.close()


    def test_pipelined_ring_loop_matches_sync(self, ring_cls):
        """Double-buffered dispatch: same verdicts, one-call delay, stats
        identical after flush (SURVEY §7 dispatch design)."""
        from bng_tpu.control import dhcp_codec, packets

        ring = ring_cls(nframes=64, frame_size=1024, depth=32)
        engine, server = self._stack(ring)
        mac = bytes.fromhex("02c0ffee0010")

        def discover(xid):
            p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER, xid=xid)
            p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST,
                              bytes([1, 3, 6, 51, 54])))
            return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                      p.encode().ljust(320, b"\x00"))

        # call 1: dispatches, retires nothing (pipe filling)
        ring.rx_push(discover(1), from_access=True)
        assert engine.process_ring_pipelined(ring) == 0
        assert ring.tx_pop() is None  # verdicts not applied yet

        # call 2: retires batch 1 (slow-path OFFER appears), dispatches #2
        ring.rx_push(discover(2), from_access=True)
        assert engine.process_ring_pipelined(ring) == 1
        offer, _ = ring.tx_pop()
        parsed = dhcp_codec.decode(packets.decode(offer).payload)
        assert parsed.msg_type == dhcp_codec.OFFER

        # flush retires the tail batch
        assert engine.flush_pipeline(ring) == 1
        offer2, _ = ring.tx_pop()
        assert dhcp_codec.decode(
            packets.decode(offer2).payload).msg_type == dhcp_codec.OFFER
        assert engine.flush_pipeline(ring) == 0  # idempotent
        assert engine.stats.passed == 2 and engine.stats.batches == 2

        # empty calls are cheap no-ops
        assert engine.process_ring_pipelined(ring) == 0
        ring.close()



    def test_pipelined_dispatch_failure_fails_closed(self, ring_cls):
        """Dispatch dying mid-pipeline: the previous batch's verdicts
        still apply (FIFO retire first), the new window closes via DROP,
        and the ring stays fully usable (code-review r3 finding)."""
        from bng_tpu.control import dhcp_codec, packets

        ring = ring_cls(nframes=64, frame_size=1024, depth=32)
        engine, server = self._stack(ring)
        mac = bytes.fromhex("02c0ffee0011")

        def discover(xid):
            p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER, xid=xid)
            p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST,
                              bytes([1, 3, 6, 51, 54])))
            return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                      p.encode().ljust(320, b"\x00"))

        ring.rx_push(discover(1), from_access=True)
        assert engine.process_ring_pipelined(ring) == 0  # batch A in flight

        real_dispatch = engine._dispatch_step
        real_dhcp = engine._run_dhcp_batch

        def boom(*a, **k):
            raise RuntimeError("synthetic device error")

        # DHCP batches ride the fast lane; patch BOTH dispatch entry points
        # so the failure covers whichever program the batch routes to
        engine._dispatch_step = boom
        engine._run_dhcp_batch = boom
        ring.rx_push(discover(2), from_access=True)
        import pytest as _pytest

        with _pytest.raises(RuntimeError, match="synthetic"):
            engine.process_ring_pipelined(ring)  # batch B dispatch dies
        engine._dispatch_step = real_dispatch
        engine._run_dhcp_batch = real_dhcp

        # batch A's OFFER still arrived (retired before the fail-close)
        got = ring.tx_pop()
        assert got is not None
        assert dhcp_codec.decode(
            packets.decode(got[0]).payload).msg_type == dhcp_codec.OFFER
        # batch B was dropped fail-closed; no window leaked: ring drives on
        assert engine._inflight is None
        ring.rx_push(discover(3), from_access=True)
        assert engine.process_ring_pipelined(ring) == 0
        assert engine.flush_pipeline() == 1
        assert ring.tx_pop() is not None  # DISCOVER #3 answered
        assert ring.free_frames() > 0
        ring.close()



class TestFillPoolConcurrency:
    """The fill pool is MPMC (Vyukov per-slot sequences): wire, engine and
    slow-path threads all alloc/free frames concurrently (round-1 ADVICE:
    the SPSC cursors corrupted under exactly this pattern). Drive all three
    roles at once and assert frame conservation — a lost or doubled frame
    descriptor fails the accounting."""

    def test_three_thread_stress_conserves_frames(self):
        import threading
        import time

        from bng_tpu.runtime.ring import NativeRing, load_native

        if load_native() is None:
            import pytest

            pytest.skip("no C++ toolchain for the native ring")

        nframes = 256
        ring = NativeRing(nframes=nframes, frame_size=256, depth=64)
        stop = threading.Event()
        errors = []

        def wire():
            f = b"\x02" * 60
            while not stop.is_set():
                ring.rx_push(f, from_access=True)
                ring.tx_pop()
                ring.fwd_pop()

        def engine():
            B, slot = 32, 256
            out = np.zeros((B, slot), dtype=np.uint8)
            ln = np.zeros((B,), dtype=np.uint32)
            fl = np.zeros((B,), dtype=np.uint32)
            rng = np.random.default_rng(0)
            while not stop.is_set():
                n = ring.assemble(out, ln, fl)
                if n == 0:
                    continue
                verdict = rng.integers(0, 4, size=B).astype(np.uint8)
                ring.complete(verdict, out, ln, n)
                ring.tx_inject(b"\x03" * 64)

        def slow():
            while not stop.is_set():
                ring.slow_pop()

        threads = [threading.Thread(target=t, daemon=True)
                   for t in (wire, engine, slow)]
        for t in threads:
            t.start()
        time.sleep(2.0)
        stop.set()
        for t in threads:
            t.join(timeout=5)
            if t.is_alive():
                errors.append(f"{t} wedged")
        assert not errors

        # quiesce: drain every ring, then every frame must be back in fill
        B, slot = 64, 256
        out = np.zeros((B, slot), dtype=np.uint8)
        ln = np.zeros((B,), dtype=np.uint32)
        fl = np.zeros((B,), dtype=np.uint32)
        for _ in range(20):
            n = ring.assemble(out, ln, fl)
            if n:
                ring.complete(np.ones((B,), dtype=np.uint8), out, ln, n)  # DROP
            while ring.tx_pop() is not None:
                pass
            while ring.fwd_pop() is not None:
                pass
            while ring.slow_pop() is not None:
                pass
        assert ring.free_frames() == nframes, (
            f"frame leak/duplication: {ring.free_frames()}/{nframes} free, "
            f"stats={ring.stats()}")
        ring.close()


class TestDHCPClassify:
    """Ring-side control classification (BNG_DESC_F_DHCP_CTRL, bit1):
    IPv4/UDP dst:67 with 0-2 VLAN tags, parity between the C++ and PyRing
    classifiers — enables the engine's DHCP-only fast lane on all-control
    batches."""

    def _dhcp_frame(self, vlans=None):
        from bng_tpu.control import dhcp_codec, packets

        mac = bytes.fromhex("02c0ffee0031")
        p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER)
        f = packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                               p.encode().ljust(320, b"\x00"))
        if vlans:
            # insert 802.1Q/802.1ad tags after the MACs
            tags = b""
            ets = ([0x88A8, 0x8100] if len(vlans) == 2 else [0x8100])
            for et, vid in zip(ets, vlans):
                tags += et.to_bytes(2, "big") + vid.to_bytes(2, "big")
            f = f[:12] + tags + f[12:]
        return f

    def test_classifier_parity_and_tagging(self, ring_cls):
        from bng_tpu.control import packets
        from bng_tpu.runtime.ring import FLAG_DHCP_CTRL, classify_dhcp

        ring = ring_cls(nframes=64, frame_size=1024, depth=32)
        frames = [self._dhcp_frame(), self._dhcp_frame([100]),
                  self._dhcp_frame([100, 200])]
        data = packets.udp_packet(b"\x02" * 6, b"\x04" * 6, 0x0A000002,
                                  0x08080808, 1234, 80, b"x")
        # port 67 but NOT DHCP (no BOOTP/magic): natable transit, not control
        port67 = packets.udp_packet(b"\x02" * 6, b"\x04" * 6, 0x0A000002,
                                    0x08080808, 1234, 67, b"y" * 300)
        # a fragment of a dst-67 flow: no parseable L4
        frag = bytearray(self._dhcp_frame())
        frag[20] = 0x20  # MF flag in the IPv4 frag word
        frag = bytes(frag)
        pushes = frames + [data, port67, frag]
        for f in pushes:
            assert ring.rx_push(f)
        # network-side DHCP must NOT classify (direction gate)
        assert ring.rx_push(self._dhcp_frame(), from_access=False)
        B = 8
        pkt = np.zeros((B, 1024), dtype=np.uint8)
        ln = np.zeros((B,), dtype=np.uint32)
        fl = np.zeros((B,), dtype=np.uint32)
        n = ring.assemble(pkt, ln, fl)
        assert n == 7
        want = [True, True, True, False, False, False, False]
        assert [(x & FLAG_DHCP_CTRL) != 0 for x in fl[:7]] == want
        # python-side classifier agrees bit-for-bit with what the ring set
        for i, f in enumerate(pushes):
            assert classify_dhcp(f) == (fl[i] & FLAG_DHCP_CTRL)
        ring.complete(np.zeros((n,), dtype=np.uint8), pkt, ln, n)

    def test_all_control_batch_takes_fast_lane(self, ring_cls):
        ring = ring_cls(nframes=64, frame_size=1024, depth=32)
        eng_test = TestRingEngine()
        engine, server = eng_test._stack(ring)
        calls = {"dhcp": 0}
        orig = engine._run_dhcp_batch

        def spy(pkt, length, now):
            calls["dhcp"] += 1
            return orig(pkt, length, now)

        engine._run_dhcp_batch = spy
        # all-control batch -> fast lane
        assert ring.rx_push(self._dhcp_frame())
        assert engine.process_ring(ring) == 1
        assert calls["dhcp"] == 1
        # mixed batch -> fused step (spy not called again)
        from bng_tpu.control import packets
        assert ring.rx_push(self._dhcp_frame())
        assert ring.rx_push(packets.udp_packet(
            b"\x02" * 6, b"\x04" * 6, 0x0A000002, 0x08080808, 1234, 80, b"x"))
        assert engine.process_ring(ring) == 2
        assert calls["dhcp"] == 1
        # the slow path answered the DISCOVER both times (server reply TX'd)
        assert engine.stats.passed >= 2


class TestShardSteering:
    """Ring->shard subscriber steering (owner-routing at the host ring,
    the pkg/pool/peer.go:230-368 role): C++/PyRing decision parity, the
    affinity invariant (control plane and ring agree on the owner), the
    per-shard lane-range batch layout, and padding-lane accounting."""

    def _ip_frame(self, src_ip, dst_ip, vlans=None, sport=1234, dport=443):
        from bng_tpu.control import packets

        f = packets.udp_packet(b"\x02\xaa\x00\x00\x00\x07", b"\x04" * 6,
                               src_ip, dst_ip, sport, dport, b"p" * 64,
                               vlans=vlans)
        return f

    def _dhcp_frame(self, mac):
        from bng_tpu.control import dhcp_codec, packets

        p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER)
        return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                  p.encode().ljust(320, b"\x00"))

    def _corpus(self):
        rng = np.random.default_rng(0x51EE)
        frames = []
        for i in range(24):  # IPv4 up/down, 0-2 VLAN tags
            vl = [None, [100], [100, 200]][i % 3]
            frames.append(self._ip_frame(0x0A000000 + i, 0xCB007100 + (i % 4),
                                         vlans=vl))
        for i in range(4):  # DHCP control
            frames.append(self._dhcp_frame(bytes([2, 0xAA, 0, 0, 0, i])))
        frames.append(b"\x02" * 6 + b"\x04" * 6 + b"\x86\xdd" + b"\x00" * 60)
        frames.append(b"\x01\x02\x03")  # shorter than an Ethernet header
        frames.append(bytes(rng.integers(0, 256, size=200, dtype=np.uint8)))
        return frames

    @pytest.mark.skipif(not native_available, reason="no native lib")
    def test_shard_of_native_py_parity(self):
        from bng_tpu.runtime.ring import (FLAG_DHCP_CTRL, FLAG_FROM_ACCESS,
                                          classify_dhcp, shard_of)

        n = 8
        pub = {0xCB007100 + s % n: s for s in range(4)}
        nr = NativeRing(nframes=64, frame_size=2048, depth=32, n_shards=n)
        try:
            for ip, s in pub.items():
                assert nr.steer_pub_ip(ip, s)
            for f in self._corpus():
                for fa in (True, False):
                    fl = FLAG_FROM_ACCESS if fa else 0
                    if fa:
                        fl |= classify_dhcp(f)
                    assert nr.shard_of(f, fl) == shard_of(f, fl, n, pub), (
                        f[:20].hex(), fl)
        finally:
            nr.close()

    def test_steering_spec(self, ring_cls):
        """Upstream = FNV(src IP) % n; downstream = pub-IP owner, else
        FNV(dst IP) % n; DHCP/non-IP = FNV(src MAC) % n."""
        from bng_tpu.runtime.ring import FLAG_DHCP_CTRL, FLAG_FROM_ACCESS
        from bng_tpu.utils.net import fnv1a32

        n = 8
        r = ring_cls(nframes=64, frame_size=2048, depth=32, n_shards=n)
        assert r.steer_pub_ip(0xCB007105, 5)
        assert not r.steer_pub_ip(0xCB007106, n)  # shard out of range
        up = self._ip_frame(0x0A0000FE, 0xCB007105)
        assert (r.shard_of(up, FLAG_FROM_ACCESS)
                == fnv1a32(bytes([10, 0, 0, 0xFE])) % n)
        # downstream to the registered public IP -> owner shard 5
        down = self._ip_frame(0x01020304, 0xCB007105)
        assert r.shard_of(down, 0) == 5
        # downstream to an unregistered IP -> dst-IP hash
        down2 = self._ip_frame(0x01020304, 0x08080808)
        assert r.shard_of(down2, 0) == fnv1a32(bytes([8, 8, 8, 8])) % n
        # DHCP control + non-IPv4: src-MAC hash
        mac = bytes([2, 0xAA, 0, 0, 0, 9])
        dh = self._dhcp_frame(mac)
        assert (r.shard_of(dh, FLAG_FROM_ACCESS | FLAG_DHCP_CTRL)
                == fnv1a32(mac) % n)
        v6 = b"\x02" * 6 + mac + b"\x86\xdd" + b"\x00" * 60
        assert r.shard_of(v6, FLAG_FROM_ACCESS) == fnv1a32(mac) % n
        r.close()

    def test_assemble_sharded_lane_ranges_and_padding(self, ring_cls):
        """Shard i's frames land at rows i*b..; padding rows are zeroed and
        complete() recycles only real frames."""
        from bng_tpu.utils.net import fnv1a32

        n, b, slot = 4, 4, 256
        r = ring_cls(nframes=64, frame_size=512, depth=16, n_shards=n)
        # craft src IPs that steer to shards 1 and 3
        by_shard = {}
        ip = 0x0A000001
        while len(by_shard) < 2 or any(len(v) < 2 for v in by_shard.values()):
            s = fnv1a32(ip.to_bytes(4, "big")) % n
            if s in (1, 3):
                by_shard.setdefault(s, []).append(ip)
            ip += 1
            if len(by_shard.get(1, [])) >= 2 and len(by_shard.get(3, [])) >= 2:
                break
        frames = {s: [self._ip_frame(i, 0x08080808) for i in ips[:2]]
                  for s, ips in by_shard.items()}
        for s in (1, 3):
            for f in frames[s]:
                assert r.rx_push(f, from_access=True)
        out = np.full((n * b, slot), 0xEE, dtype=np.uint8)  # stale bytes
        ln = np.full((n * b,), 99, dtype=np.uint32)
        fl = np.full((n * b,), 99, dtype=np.uint32)
        got = r.assemble_sharded(out, ln, fl)
        assert got == 4
        for s in (1, 3):
            for k, f in enumerate(frames[s]):
                row = s * b + k
                assert ln[row] == len(f)
                assert bytes(out[row, : len(f)]) == f
        # padding rows: len 0, flags 0, bytes zeroed (no stale 0xEE)
        for row in (0, 1, 2 * b, 1 * b + 2, 3 * b + 3):
            assert ln[row] == 0 and fl[row] == 0
            assert not out[row].any()
        # complete with n = total rows; every verdict PASS
        r.complete(np.zeros((n * b,), dtype=np.uint8), out, ln, n * b)
        assert r.slow_pending() == 4  # only the real frames
        drained = 0
        while r.slow_pop() is not None:
            drained += 1
        assert drained == 4
        assert r.free_frames() == 64
        r.close()

    def test_assemble_sharded_overflow_stays_queued(self, ring_cls):
        from bng_tpu.utils.net import fnv1a32

        n, b = 2, 1
        r = ring_cls(nframes=64, frame_size=512, depth=16, n_shards=n)
        ip = 0x0A000001
        while fnv1a32(ip.to_bytes(4, "big")) % n != 1:
            ip += 1
        f = self._ip_frame(ip, 0x08080808)
        for _ in range(3):
            assert r.rx_push(f, from_access=True)
        out = np.zeros((n * b, 256), dtype=np.uint8)
        ln = np.zeros((n * b,), dtype=np.uint32)
        fl = np.zeros((n * b,), dtype=np.uint32)
        assert r.assemble_sharded(out, ln, fl) == 1  # region is 1 row
        assert r.shard_rx_pending(1) == 2  # the rest stay queued, in order
        r.complete(np.zeros((n * b,), dtype=np.uint8), out, ln, n * b)
        assert r.assemble_sharded(out, ln, fl) == 1
        r.complete(np.zeros((n * b,), dtype=np.uint8), out, ln, n * b)
        assert r.shard_rx_pending(1) == 1
        r.close()

    def test_assemble_sharded_empty_opens_no_window(self, ring_cls):
        r = ring_cls(nframes=64, frame_size=512, depth=16, n_shards=2)
        out = np.zeros((4, 256), dtype=np.uint8)
        ln = np.zeros((4,), dtype=np.uint32)
        fl = np.zeros((4,), dtype=np.uint32)
        assert r.assemble_sharded(out, ln, fl) == 0
        with pytest.raises(RuntimeError):
            r.complete(np.zeros((4,), dtype=np.uint8), out, ln, 4)
        r.close()
