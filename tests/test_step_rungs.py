"""The fused step's compile-shape ladder (PR 39): a dispatched step runs
at the narrowest rung that holds its window, not at `--batch-size`.

Whichever rung carries a window, the same frames give the same verdicts,
bytes out, stats and tables after; two windows of one rung share one
compiled program; the scheduler's bulk lane packs to the rung; and after
`bng run`'s start-up hook no window builds a program. Tiny tables, CPU;
one engine geometry a stage, so the file compiles a rung of it once.
"""

import time
from contextlib import contextmanager
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bng_tpu.control import dhcp_codec, packets
from bng_tpu.control.nat import NATManager
from bng_tpu.control.pool import Pool, PoolManager
from bng_tpu.control.pppoe import codec as pppoe_codec
from bng_tpu.ops.antispoof import AST_ALLOWED, MODE_STRICT
from bng_tpu.ops.dhcp import ST_HIT
from bng_tpu.ops.pppoe import PPP_IPV4, PST_DECAP, PST_ENCAP
from bng_tpu.ops.qos import QST_PKTS_DROPPED
from bng_tpu.ops.v6 import V6ST_FWD_DOWN, V6ST_FWD_UP
from bng_tpu.runtime import engine as engine_mod
from bng_tpu.runtime.engine import (AntispoofTables, Engine, QoSTables,
                                    step_rung, step_rungs)
from bng_tpu.runtime.scheduler import SchedulerConfig, TieredScheduler
from bng_tpu.runtime.tables import (FastPathTables, PPPoEFastPathTables,
                                    V6FastPathTables)
from bng_tpu.telemetry import spans
from bng_tpu.utils.net import ip_to_u32

SERVER_MAC = bytes.fromhex("02aabbccdd01")
ROUTER_MAC = bytes.fromhex("029999999999")
SERVER_IP = ip_to_u32("10.0.0.1")
PEER = ip_to_u32("93.184.216.34")
T0 = 1_753_000_000
SUBS = 12
BATCH = 256  # rungs 128 and 256
SLOT = 512
STAGES = ("plain", "pppoe", "v6")

# every program JAX builds from here on, as benchmark/run.py counts them
BUILT = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, dur, **kw: BUILT.append(dur)
    if name.endswith("backend_compile_duration") else None)

@contextmanager
def timed(name):
    """How long a compile-bound case took, printed with `-s` / `-rA`."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        print(f"test_step_rungs seconds: {name} "
              f"{time.perf_counter() - t0:.2f}")


@contextmanager
def whole_batch_only():
    """The parent's rule: one rung, the configured batch."""
    was = engine_mod.STEP_RUNGS_MAX
    engine_mod.STEP_RUNGS_MAX = 1
    step_rungs.cache_clear()
    try:
        yield
    finally:
        engine_mod.STEP_RUNGS_MAX = was
        step_rungs.cache_clear()


def _mac(i: int) -> bytes:
    return (0x02D9 << 32 | i).to_bytes(6, "big")


def _ip(i: int) -> int:
    return ip_to_u32("10.0.0.10") + i


def _v6(i: int) -> bytes:
    return bytes.fromhex("20010db8000100000000000000000000")[:14] \
        + (0x100 + i).to_bytes(2, "big")


V6_PEER = bytes.fromhex("20010db8ffff00000000000000000042")


def _stack(stage: str, batch: int = BATCH):
    """One engine of the stage's geometry: every subscriber a DHCP row, a
    QoS row, a strict binding, a NAT block and one flow; subscriber 5's
    bucket two frames deep; 8 and 9 PPPoE sessions (stage `pppoe`); 6 to 9
    hold a /128 (stage `v6`)."""
    fastpath = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64,
                              cid_nbuckets=64, max_pools=16)
    fastpath.set_server_config(SERVER_MAC, SERVER_IP)
    PoolManager(fastpath).add_pool(Pool(
        pool_id=1, network=ip_to_u32("10.0.0.0"), prefix_len=24,
        gateway=SERVER_IP, dns_primary=ip_to_u32("1.1.1.1"), lease_time=3600))
    nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                     sessions_nbuckets=256, sub_nat_nbuckets=64)
    qos = QoSTables(nbuckets=256)
    spoof = AntispoofTables(nbuckets=256)
    spoof.set_config(MODE_STRICT, log_violations=True)
    flows = []
    for i in range(SUBS):
        fastpath.add_subscriber(_mac(i), pool_id=1, ip=_ip(i),
                                lease_expiry=T0 + 86400)
        burst = 500 if i == 5 else 1 << 20
        qos.set_subscriber(_ip(i), down_bps=80_000, up_bps=80_000,
                           down_burst=burst, up_burst=burst)
        spoof.add_binding(_mac(i), _ip(i), MODE_STRICT)
        assert nat.allocate_nat(_ip(i), T0) is not None
        flows.append(nat.handle_new_flow(_ip(i), PEER, 40000 + i, 443, 17,
                                         64, T0))
    pppoe = v6 = None
    if stage == "pppoe":
        pppoe = PPPoEFastPathTables(nbuckets=64, stash=8,
                                    server_mac=SERVER_MAC)
        for i in (8, 9):
            pppoe.session_up(SimpleNamespace(
                session_id=0x40 + i, client_mac=_mac(i), assigned_ip=_ip(i)))
    if stage == "v6":
        v6 = V6FastPathTables(spoof, nbuckets=64)
        for i in (6, 7, 8, 9):
            v6.bind(_mac(i), _v6(i))
    engine = Engine(fastpath, nat, qos, spoof, pppoe=pppoe, v6=v6,
                    batch_size=batch, pkt_slot=SLOT, clock=lambda: float(T0))
    return engine, flows


def _dhcp(i: int, kind, xid: int) -> bytes:
    extra = ({"requested_ip": _ip(i), "server_id": SERVER_IP}
             if kind == dhcp_codec.REQUEST else {})
    p = dhcp_codec.build_request(_mac(i), kind, xid=xid, **extra)
    p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
    return packets.udp_packet(_mac(i), b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                              p.encode().ljust(320, b"\x00"))


def _up(i: int, payload: bytes = b"up" * 20, src_ip: int | None = None) -> bytes:
    return packets.udp_packet(_mac(i), SERVER_MAC,
                              _ip(i) if src_ip is None else src_ip, PEER,
                              40000 + i, 443, payload)


def _down(flow, payload: bytes = b"dn" * 20) -> bytes:
    nat_ip, nat_port = flow
    return packets.udp_packet(ROUTER_MAC, SERVER_MAC, PEER, nat_ip, 443,
                              nat_port, payload)


def _session(i: int) -> bytes:
    ppp = pppoe_codec.ppp_frame(PPP_IPV4, _up(i)[14:])
    body = pppoe_codec.PPPoEPacket(code=0, session_id=0x40 + i,
                                   payload=ppp).encode()
    return pppoe_codec.eth_frame(SERVER_MAC, _mac(i),
                                 pppoe_codec.ETH_PPPOE_SESSION, body)


def _window(stage: str, flows, k: int = 0):
    """One mixed window: DHCP (a DISCOVER and a renewing REQUEST), SNAT,
    the matching DNAT, a policed flow (subscriber 5: four frames into a
    bucket of two), a spoofed source (6's MAC under 7's address), and the
    stage's own lanes. [(frame, from_access)]."""
    win = [(_dhcp(0, dhcp_codec.DISCOVER, 0x3900 + k), True),
           (_dhcp(1, dhcp_codec.REQUEST, 0x3a00 + k), True)]
    win += [(_up(i), True) for i in (2, 3, 4)]
    win += [(_down(flows[i]), False) for i in (2, 3, 4)]
    win += [(_up(5, b"p" * 200), True) for _ in range(4)]
    win += [(_up(6, src_ip=_ip(7)), True)]
    if stage == "pppoe":
        win += [(_session(8), True), (_session(9), True),
                (_down(flows[8]), False), (_down(flows[9]), False)]
    if stage == "v6":
        win += [(packets.udp6_packet(_mac(6), SERVER_MAC, _v6(6), V6_PEER,
                                     5000, 443, b"six" * 9), True),
                (packets.udp6_packet(ROUTER_MAC, SERVER_MAC, V6_PEER, _v6(7),
                                     443, 5001, b"six" * 11), False),
                (packets.udp6_packet(_mac(8), SERVER_MAC, _v6(9), V6_PEER,
                                     5002, 443, b"not mine"), True)]
    return win


def _serve(stage: str, windows: int = 2, batch: int = BATCH):
    """The stage's windows through `Engine.process`; everything a rung
    could change: what came out lane by lane, every stats block, every
    leaf of the device's tables."""
    engine, flows = _stack(stage, batch)
    lanes = []
    with spans.armed() as tr:
        for k in range(windows):
            win = _window(stage, flows, k)
            out = engine.process([f for f, _ in win],
                                 from_access=[fa for _, fa in win],
                                 now=T0 + 0.5 * k)
            lanes.append(out)
    return {
        "lanes": lanes,
        "stats": {k: np.asarray(getattr(engine.stats, k)).copy()
                  for k in ("dhcp", "nat", "qos", "spoof", "pppoe", "v6")},
        "verdicts": (engine.stats.tx, engine.stats.fwd, engine.stats.dropped,
                     engine.stats.passed),
        "tables": [(jax.tree_util.keystr(kp), np.asarray(x)) for kp, x in
                   jax.tree_util.tree_flatten_with_path(engine.tables)[0]],
        "step_lanes": tr.sums()["step_lanes"],
        "n": len(_window(stage, flows)),
    }


# -- (a) the rung rule ------------------------------------------------------

def test_rung_set_is_bounded_monotone_and_covering():
    assert step_rungs(8192) == (128, 1024, 8192)
    assert step_rungs(2048) == (128, 256, 2048)
    assert step_rungs(1024) == (128, 1024)
    assert step_rungs(256) == (128, 256)
    # a batch at or under the floor has one rung: itself
    for B in (1, 4, 8, 16, 64, 127, 128):
        assert step_rungs(B) == (B,)
    for B in (129, 200, 256, 1000, 4096, 8192, 65536):
        rungs = step_rungs(B)
        assert 1 <= len(rungs) <= engine_mod.STEP_RUNGS_MAX
        assert rungs[-1] == B and rungs[0] >= engine_mod.STEP_RUNG_FLOOR
        assert list(rungs) == sorted(set(rungs))
        taken = {step_rung(n, B) for n in range(0, B + 1, 7)} | {
            step_rung(n, B) for n in (1, B // 8, B // 8 + 1, B - 1, B)}
        assert taken <= set(rungs)
        prev = 0
        for n in range(0, B + 1, 3):  # monotone + covering
            b = step_rung(n, B)
            assert n <= b and b >= prev
            prev = b
    # a window over the next rung down runs the configured batch's program
    assert step_rung(1025, 8192) == 8192 and step_rung(1024, 8192) == 1024
    assert step_rung(129, 8192) == 1024 and step_rung(128, 8192) == 128
    assert step_rung(0, 8192) == 128
    with whole_batch_only():
        assert step_rungs(8192) == (8192,) and step_rung(3, 8192) == 8192
    assert step_rungs(8192) == (128, 1024, 8192)


# -- (b) the same frames, whichever rung carries them -------------------------

@pytest.mark.parametrize("stage", STAGES)
def test_a_window_leaves_the_same_state_through_its_rung_and_through_B(stage):
    with timed(f"rungs[{stage}]"):
        got = _serve(stage)  # 16-19 frames: the 128 rung of 256
        with whole_batch_only():
            want = _serve(stage)  # the configured batch, as the parent
    n = got["n"]
    assert got["step_lanes"] == 2 * step_rung(n, BATCH) == 2 * 128
    assert want["step_lanes"] == 2 * BATCH

    # the window is the one the claim needs: device replies, NAT both
    # ways, the policer and antispoof at work, and the stage's own lanes
    tx, fwd, dropped, _passed = want["verdicts"]
    assert tx == 4 and fwd >= 12 and dropped >= 2
    assert want["stats"]["dhcp"][ST_HIT] == 4
    assert want["stats"]["qos"][QST_PKTS_DROPPED] > 0
    assert want["stats"]["spoof"].sum() > 0 and want["stats"]["nat"].sum() > 0
    if stage == "pppoe":
        assert want["stats"]["pppoe"][PST_DECAP] == 4
        assert want["stats"]["pppoe"][PST_ENCAP] == 4
    if stage == "v6":
        assert want["stats"]["v6"][V6ST_FWD_UP] == 2
        assert want["stats"]["v6"][V6ST_FWD_DOWN] == 2

    assert got["lanes"] == want["lanes"]  # verdict and bytes, lane by lane
    assert got["verdicts"] == want["verdicts"]
    # one counter is a count of lanes, live or not, in the kernel as it
    # stands (antispoof's ALLOWED is every lane it did not drop, padding
    # too): it reads lower by exactly the inert lanes the rung left out
    allowed = want["stats"]["spoof"][AST_ALLOWED] - 2 * (BATCH - 128)
    want["stats"]["spoof"][AST_ALLOWED] = allowed
    for name in want["stats"]:
        assert (got["stats"][name] == want["stats"][name]).all(), name
    assert len(got["tables"]) == len(want["tables"])
    for (name, a), (_name, b) in zip(got["tables"], want["tables"]):
        assert a.shape == b.shape and (a == b).all(), name


# -- (c) one rung, one program ------------------------------------------------

def test_two_window_lengths_in_one_rung_share_one_program():
    with timed("one_program_a_rung"):
        engine, flows = _stack("plain")
        win = _window("plain", flows)
        jit = engine._step

        def serve(n):
            frames = [win[i % len(win)] for i in range(n)]
            engine.process([f for f, _ in frames],
                           from_access=[fa for _, fa in frames], now=T0)
            return jit._cache_size()

        before = jit._cache_size()
        first = serve(5)
        assert first <= before + 1
        assert serve(1) == serve(17) == serve(128) == first
        over = serve(129)  # the next rung: at most one more program
        assert first <= over <= first + 1
        assert serve(200) == serve(256) == serve(3) == over
        with pytest.raises(ValueError, match="exceeds batch size"):
            serve(257)


# -- (d) the scheduler's bulk lane --------------------------------------------

def test_the_bulk_lane_dispatches_at_the_rung_and_completes_every_frame():
    with timed("bulk_lane"):
        engine, flows = _stack("plain")
        sched = TieredScheduler(engine, SchedulerConfig(
            express_batch=8, express_aot=False, bulk_batch=BATCH,
            bulk_max_wait_us=0.0))
        shapes = []
        real = engine.dispatch_scheduled_bulk

        def spy(pkt, length, fa, *a, **k):
            shapes.append((pkt.shape, length.shape, fa.shape))
            return real(pkt, length, fa, *a, **k)

        engine.dispatch_scheduled_bulk = spy
        data = [w for w in _window("plain", flows) if len(w[0]) < 300]
        with spans.armed() as tr:
            for size in (len(data), 150):
                for j in range(size):
                    frame, fa = data[j % len(data)]
                    sched.submit(frame, from_access=fa, tag=j, lane="bulk")
                sched.flush(float(T0))
                done = sched.drain_completions()
                assert sorted(c.tag for c in done) == list(range(size))
                assert all(c.verdict in ("tx", "fwd", "drop", "slow")
                           for c in done)
        assert shapes == [((128, SLOT), (128,), (128,)),
                          ((256, SLOT), (256,), (256,))]
        assert tr.sums()["step_lanes"] == 128 + 256
        # occupancy keeps its meaning: frames over the configured batch
        assert sched.bulk.stats.occupancy_sum == pytest.approx(
            (len(data) + 150) / BATCH)
        assert sched.bulk.stats.batches == 2
        sched.close()


# -- (e) start-up builds every rung a loop can reach --------------------------

@pytest.mark.parametrize("loop", ["engine", "scheduler"])
def test_after_the_start_up_hook_no_window_builds_a_program(loop):
    from bng_tpu.cli import BNGApp, BNGConfig

    with timed(f"start_up[{loop}]"):
        app = BNGApp(BNGConfig(
            batch_size=BATCH, synthetic_subs=4, max_subscribers=256,
            max_nat_sessions=512, max_nat_subscribers=128,
            scheduler_enabled=loop == "scheduler", sched_express_batch=8,
            dhcpv6_enabled=False, slaac_enabled=False,
            walled_garden_enabled=False, metrics_enabled=False))
        try:
            app.config.synthetic_subs = 0  # the ring is built; we push
            ring, engine = app.components["ring"], app.components["engine"]
            assert hasattr(ring, "rx_pop") == (loop == "scheduler")
            before = [np.asarray(x).copy() for x in
                      jax.tree_util.tree_leaves(engine.tables)]
            batches = engine.stats.batches
            app.drive_once()  # no frame yet: the hook alone
            assert app._rungs_built_for is engine
            assert engine._step._cache_size() >= 2
            # an inert window changes no table and counts as no batch
            assert engine.stats.batches == batches
            for a, b in zip(before, jax.tree_util.tree_leaves(engine.tables)):
                assert (a == np.asarray(b)).all()
            built = len(BUILT)
            frame = _up(3)
            for size in (140, 5, 256 if loop == "scheduler" else 200, 1):
                for _ in range(size):
                    assert ring.rx_push(frame, from_access=True)
                for _ in range(40):
                    app.drive_once()
                    if not ring.rx_pending() and engine._inflight is None \
                            and loop == "engine":
                        break
                    time.sleep(0.003)
            assert engine.stats.batches >= batches + 4
            seen = (engine.stats.tx + engine.stats.fwd + engine.stats.dropped
                    + engine.stats.passed)
            assert seen == 140 + 5 + (256 if loop == "scheduler" else 200) + 1
            assert len(BUILT) == built, "a window built a program"
        finally:
            app.close()
