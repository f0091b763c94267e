"""The first packet of a new NAT flow is forwarded (PR 53), on all three
loops: the engine's ring loop (both rings), the scheduler's bulk lane, the
mesh's ring loop on four virtual devices.

The source translates the packet that missed and lets it go
(bpf/nat44.c:686-801); until PR 53 this tree created the session and consumed
the frame. Held here, on seeded data against `benchmark/kits/churn.py Plain`,
which allocates the mapping itself by the source's rule and imports nothing
of `bng_tpu`:

- packet 1 of an admitted flow leaves on the forward side byte for byte as
  the reference says, by the mapping its later packets get;
- a second packet that races the apply punts too, gets the same mapping and
  leaves behind the first; a third goes straight through the chip;
- the reply over the session made is translated back;
- a flow whose block is full is refused: its frame is a counted drop;
- nothing is forwarded twice and nothing loops: a frame that punts again on
  its second pass is counted and dropped, and the hold queue is bounded;
- `passed` counts a first packet once, the ring's `rx` does not see the
  second pass, and pushed = popped + counted drops.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import app as applib  # noqa: E402
from benchmark.lib import gen  # noqa: E402
from bng_tpu.control.nat import NATManager  # noqa: E402
from bng_tpu.ops.hashing import SEED1, SEED2, hash_words, hash_words_int  # noqa: E402
from bng_tpu.ops.table import nbuckets_for  # noqa: E402
from bng_tpu.parallel.sharded import ShardedCluster  # noqa: E402
from bng_tpu.runtime import ring as ringmod  # noqa: E402
from bng_tpu.runtime.engine import AntispoofTables, Engine, QoSTables  # noqa: E402
from bng_tpu.runtime.ring import NativeRing, PyRing  # noqa: E402
from bng_tpu.runtime.scheduler import SchedulerConfig, TieredScheduler  # noqa: E402
from bng_tpu.runtime.tables import FastPathTables  # noqa: E402
from bng_tpu.telemetry import spans as tele  # noqa: E402
from bng_tpu.utils.net import ip_to_u32, parse_mac  # noqa: E402

NOW = 1_753_000_000
SERVER_MAC = np.frombuffer(parse_mac("02:aa:bb:cc:dd:01"), np.uint8)
ROUTER_MAC = np.frombuffer(bytes.fromhex("02ee00000001"), np.uint8)
PUB = [ip_to_u32("198.18.0.0") + i for i in range(8)]
SUB_BASE = ip_to_u32("10.16.0.0")
REMOTE = ip_to_u32("93.184.0.7")
SUBS, FLOWS_PER, PER_BLOCK, SLOT, B = 48, 2, 4, 512, 16
native_available = ringmod.load_native() is not None
kit = applib.load_kit({"kit": "churn"})


def macs(i) -> np.ndarray:
    return np.asarray(i, np.uint64) + np.uint64(0x02AA00000000)


def provisioned():
    ips = (SUB_BASE + np.arange(SUBS)).astype(np.uint32)
    k = np.arange(SUBS * FLOWS_PER)
    j, f = k // FLOWS_PER, k % FLOWS_PER
    return ips, (ips[j], np.full(len(k), REMOTE, np.uint32),
                 (40000 + f).astype(np.uint32), np.full(len(k), 443, np.uint32),
                 np.where(f % 2 == 0, 17, 6).astype(np.uint32))


def up_frame(src, dst, sport, dport, proto, fid) -> bytes:
    return gen.row_bytes(gen.data_frames(
        gen.mac_cols(macs([src - SUB_BASE])), SERVER_MAC, [src], [dst],
        [sport], [dport], np.array([proto]), [fid]))[0]


def down_frame(dst, dport, nat_ip, nat_port, proto, fid) -> bytes:
    return gen.row_bytes(gen.data_frames(
        ROUTER_MAC, SERVER_MAC, [dst], [nat_ip], [dport], [nat_port],
        np.array([proto]), [fid]))[0]


class Loop:
    """One of the three loops over the same deployment: 48 subscribers, a
    four-port block each, two provisioned flows in it."""

    def __init__(self, kind: str, ring_cls=None):
        self.kind = kind
        ips, flows = provisioned()
        nat_kw = dict(ports_per_subscriber=PER_BLOCK)
        if kind == "cluster":
            self.cl = cl = ShardedCluster(
                4, public_ips=PUB, batch_per_shard=B, sub_nbuckets=256,
                vlan_nbuckets=64, cid_nbuckets=64, qos_nbuckets=1024,
                spoof_nbuckets=1024, nat_sessions_nbuckets=nbuckets_for(1024),
                nat_sub_nbuckets=1024, garden_enabled=False)
            for nat in cl.nat:
                nat.ports_per_subscriber = PER_BLOCK
            for s in range(4):
                m = cl.affinity_shards(ips) == s
                cl.qos[s].bulk_set_subscribers(ips[m], 10**9, 10**9)
            assert int(cl.bulk_allocate_nat(ips, NOW).sum()) == SUBS
            nat_ip, nat_port, ok = cl.bulk_flows(*flows, pkt_len=64, now=NOW)
            cl.sync_tables()
            self.ring = cl.make_ring(nframes=1024, frame_size=SLOT, depth=64)
            self.newflows = cl.newflows
            self.nats = cl.nat
        else:
            nat = NATManager(public_ips=PUB, sessions_nbuckets=1024,
                             sub_nat_nbuckets=256, **nat_kw)
            assert nat.bulk_allocate_nat(ips, NOW) == SUBS
            nat_ip, nat_port, ok = nat.bulk_flows(*flows, pkt_len=64, now=NOW)
            qos = QoSTables(nbuckets=1024)
            qos.bulk_set_subscribers(ips, 10**9, 10**9)
            fp = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64,
                                cid_nbuckets=64, max_pools=16)
            self.eng = eng = Engine(fp, nat, qos, AntispoofTables(nbuckets=1024),
                                    batch_size=B, pkt_slot=SLOT,
                                    clock=lambda: float(NOW))
            eng.resync_tables()
            self.newflows = eng.newflows
            self.nats = [nat]
            if kind == "scheduler":
                self.sched = TieredScheduler(
                    eng, SchedulerConfig(bulk_batch=B, bulk_max_wait_us=0.0,
                                         express_aot=False),
                    clock=lambda: float(NOW))
            else:
                self.ring = ring_cls(nframes=1024, frame_size=SLOT, depth=64)
        assert bool(ok.all())
        self.plain = kit.Plain(*flows, nat_ip, nat_port,
                               ports_per_block=PER_BLOCK)
        self.pushed = 0
        self.out: list[bytes] = []

    def push(self, frame: bytes, from_access: bool = True) -> None:
        self.pushed += 1
        if self.kind == "scheduler":
            assert self.sched.submit(frame, from_access=from_access)
        else:
            assert self.ring.rx_push(frame, from_access=from_access)

    def beat(self, n: int = 1) -> None:
        for _ in range(n):
            if self.kind == "scheduler":
                self.sched.poll()
                self.sched.flush() if not len(self.sched.bulk) else None
                self.out += [c.frame for c in self.sched.drain_completions()
                             if c.verdict == "fwd"]
                continue
            if self.kind == "cluster":
                self.cl.process_ring_pipelined(self.ring, NOW, 0,
                                               pkt_slot=SLOT)
            else:
                self.eng.process_ring_pipelined(self.ring)
            while (got := self.ring.fwd_pop()) is not None:
                self.out.append(got[0])

    def dropped(self) -> int:
        st = self.newflows.stats
        return (st.dropped() if self.kind == "cluster"
                else self.eng.stats.dropped)


def loops():
    out = [pytest.param(("engine", PyRing), id="engine-pyring"),
           pytest.param(("scheduler", None), id="scheduler"),
           pytest.param(("cluster", None), id="cluster-4",
                        marks=pytest.mark.sharded)]
    if native_available:
        out.insert(1, pytest.param(("engine", NativeRing),
                                   id="engine-native"))
    return out


@pytest.fixture(scope="module", params=loops())
def loop(request):
    kind, ring_cls = request.param
    if kind == "cluster":
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs four (virtual) devices")
    return Loop(kind, ring_cls)


def test_packet_one_leaves_translated_and_a_racing_second_follows(loop):
    """Packet 1, a second packet one beat behind it (the session is not on
    the chip yet), a third after both have left, then the reply."""
    src = SUB_BASE + 5
    flow = (src, ip_to_u32("93.184.9.9"), 50123, 443, 17)
    want = loop.plain.open(*flow)
    _row, pub, start, end = loop.plain.block_of(src)
    assert want == (pub, start + FLOWS_PER)  # the next port of its block
    p1, p2, p3 = (up_frame(*flow, fid) for fid in (1, 2, 3))
    st, before = loop.newflows.stats, len(loop.out)
    base = (st.admitted, st.requeued)
    loop.push(p1)
    loop.beat()
    loop.push(p2)
    loop.beat(8)
    got = loop.out[before:]
    assert len(got) == 2, "each forwarded once, none twice"
    # in order, byte for byte the reference's outside the rewritten endpoint
    assert loop.plain.holds(p1, got[0], True), got[0].hex()
    assert loop.plain.holds(p2, got[1], True), got[1].hex()
    assert got[0][26:30] == want[0].to_bytes(4, "big")
    assert got[0][34:36] == got[1][34:36] == want[1].to_bytes(2, "big")
    assert st.admitted - base[0] in (1, 2)  # 2 where the second raced
    assert st.requeued - base[1] == st.admitted - base[0]
    assert (st.again, st.refused, st.hold_full, st.lost) == (0, 0, 0, 0)
    # the session is the host's too, and the third packet needs no host
    nat = next(n for n in loop.nats if src in n.blocks)
    assert nat.handle_new_flow(*flow, 64, NOW) == want
    admitted = st.admitted
    loop.push(p3)
    loop.beat(4)
    assert len(loop.out) == before + 3 and st.admitted == admitted
    assert loop.plain.holds(p3, loop.out[-1], True)
    # the reply to the external endpoint comes back to the internal one
    reply = down_frame(flow[1], flow[3], *want, flow[4], 4)
    loop.push(reply, from_access=False)
    loop.beat(4)
    assert len(loop.out) == before + 4
    assert loop.plain.holds(reply, loop.out[-1], False)
    assert len(loop.newflows) == 0  # nothing waits


def test_a_flow_whose_block_is_full_is_a_counted_drop(loop):
    src = SUB_BASE + 9
    st, before = loop.newflows.stats, len(loop.out)
    nat = next(n for n in loop.nats if src in n.blocks)
    # the block holds four ports, one of them a provisioned UDP endpoint's
    # (the other's is in use at TCP alone): three more UDP endpoints fit,
    # the last of them by wrapping onto the TCP endpoint's port
    _row, pub, start, _end = loop.plain.block_of(src)
    for n in range(3):
        flow = (src, ip_to_u32("93.184.9.9"), 51000 + n, 443, 17)
        assert loop.plain.open(*flow) == (pub, start + (2, 3, 1)[n])
        loop.push(up_frame(*flow, 10 + n))
    loop.beat(8)
    assert len(loop.out) == before + 3
    assert all(loop.plain.holds(up_frame(src, ip_to_u32("93.184.9.9"),
                                         51000 + n, 443, 17, 10 + n),
                                loop.out[before + n], True) for n in range(3))
    refused0, drops0, ex0 = st.refused, loop.dropped(), nat.exhausted["port"]
    full = (src, ip_to_u32("93.184.9.9"), 51003, 443, 17)
    assert loop.plain.open(*full) is None  # the reference refuses it too
    loop.push(up_frame(*full, 13))
    loop.beat(6)
    assert len(loop.out) == before + 3  # nothing left for it
    assert st.refused == refused0 + 1 and loop.dropped() == drops0 + 1
    assert nat.exhausted["port"] == ex0 + 1
    assert len(loop.newflows) == 0


def test_a_frame_that_punts_again_is_counted_and_does_not_loop(loop,
                                                               monkeypatch):
    """A create that reports a mapping and writes no session (a fault: the
    apply runs ahead of the step that carries the frame): the frame punts
    again on its second pass, is counted, dropped, and does not go round."""
    monkeypatch.setattr(loop.newflows, "handle_new_flows",
                        lambda src, *rest: [(PUB[0], 1024)] * len(src))
    st, before = loop.newflows.stats, len(loop.out)
    again0, drops0, req0 = st.again, loop.dropped(), st.requeued
    loop.push(up_frame(SUB_BASE + 20, ip_to_u32("93.184.9.9"), 52000, 443, 6,
                       20))
    loop.beat(10)
    assert len(loop.out) == before
    assert st.again == again0 + 1 and st.requeued == req0 + 1
    assert loop.dropped() == drops0 + 1 and len(loop.newflows) == 0


def test_the_counts_close(loop):
    """After the three tests above on this loop: every frame pushed was
    popped once or is a counted drop; a first packet is `passed` once, and
    the ring accepted each frame once."""
    assert loop.pushed == len(loop.out) + loop.dropped()
    st = loop.newflows.stats
    if loop.kind == "cluster":
        return
    es = loop.eng.stats
    punts = st.admitted + st.refused  # every punt was served once
    assert es.passed == punts
    assert es.fwd == len(loop.out) and es.dropped == st.dropped()
    if loop.kind == "engine":
        assert loop.ring.stats()["rx"] == loop.pushed
        assert loop.ring.stats()["slow"] == punts


# -- one create a retire (PR 54) ------------------------------------------------

def _errors(loop) -> int:
    return (loop.cl.stats["slow_errors"] if loop.kind == "cluster"
            else loop.eng.stats.slow_errors)


def _counting(loop, monkeypatch, spoil=None) -> list:
    """Count the loop's create calls (the flows of each); `spoil(answers)`
    edits a call's answers before the loop sees them."""
    calls, real = [], loop.newflows.handle_new_flows

    def create(*cols):
        calls.append(len(cols[0]))
        got = real(*cols)
        if spoil is not None:
            spoil(got)
        return got

    monkeypatch.setattr(loop.newflows, "handle_new_flows", create)
    return calls


def _window_of(loop, first_sub: int, k: int, port: int) -> list:
    """k first packets from k subscribers, pushed into one window."""
    flows = [(SUB_BASE + first_sub + i, ip_to_u32("93.184.9.9"), port, 443, 17)
             for i in range(k)]
    for i, flow in enumerate(flows):
        assert loop.plain.open(*flow) is not None
        loop.push(up_frame(*flow, 200 + i))
    return flows


def _fids(frames) -> list[int]:
    return [int.from_bytes(f[-4:], "big") for f in frames]


@pytest.mark.parametrize("armed", [True, False], ids=["armed", "disarmed"])
def test_a_windows_punts_are_one_create_and_one_lap(loop, monkeypatch, armed):
    """Four punted lanes of one window (the ring loops hold a quarter of a
    16-lane window): one `handle_new_flows` over the four, one `punt` lap,
    `newflow_creates` one a manager that opened a flow (one on a chip, one an owner shard on the mesh); disarmed, the
    same frames leave and nothing is counted."""
    calls = _counting(loop, monkeypatch)
    st, before = loop.newflows.stats, len(loop.out)
    admitted0 = st.admitted
    tr = tele.arm(tele.Tracer(keep_events=1 << 12)) if armed else None
    try:
        flows = _window_of(loop, 24 if armed else 30, 4, 55000)
        loop.beat(8)
    finally:
        tele.disarm()
    assert calls == [4] and st.admitted == admitted0 + 4
    got = loop.out[before:]
    assert sorted(_fids(got)) == list(range(200, 204))
    if loop.kind != "cluster":  # one queue: in the order they came
        assert _fids(got) == list(range(200, 204))
    sums = (tr or tele.Tracer()).sums()
    if not armed:
        assert (sums["newflow_creates"], sums["newflow_admitted"],
                sums["stage_ns"]["punt"]) == (0, 0, 0)
        return
    owners = ({loop.cl.affinity_shard_ip(f[0]) for f in flows}
              if loop.kind == "cluster" else {0})
    assert sums["newflow_creates"] == len(owners)
    assert (sums["newflow_admitted"], sums["newflow_singles"]) == (4, 0)
    assert sum(1 for e in tr.events if e[0] == tele.PUNT) == 1
    assert sums["stage_ns"]["punt"] > 0
    if loop.kind == "engine":  # the ring loop's retire: a child of `reply`
        assert sums["stage_ns"]["punt"] <= sums["stage_ns"]["reply"]


def test_a_window_without_a_punt_makes_no_call(loop, monkeypatch):
    def never(*a, **kw):
        raise AssertionError("no lane punted: nothing to serve")

    monkeypatch.setattr(loop.newflows, "punt_many", never)
    calls = _counting(loop, monkeypatch)
    before = len(loop.out)
    tr = tele.arm(tele.Tracer(keep_events=1 << 12))
    try:
        for i in range(6):  # provisioned flows: the chip answers alone
            loop.push(up_frame(SUB_BASE + i, REMOTE, 40000, 443, 17, 300 + i))
        loop.beat(6)
    finally:
        tele.disarm()
    assert len(loop.out) == before + 6 and not calls
    assert not any(e[0] == tele.PUNT for e in tr.events)
    assert tr.sums()["newflow_creates"] == 0


def test_one_bad_flow_in_a_batch_costs_that_frame_alone(loop, monkeypatch):
    """The create raises for the middle flow of five (a table with no room
    for its row): it is reported with its lane and dropped, the four around
    it leave translated in order, and the next window is served."""
    def spoil(answers):
        if len(answers) == 5:
            answers[2] = RuntimeError("table 'nat_sessions' full")

    calls = _counting(loop, monkeypatch, spoil)
    st, before, err0 = loop.newflows.stats, len(loop.out), _errors(loop)
    drops0, admitted0 = loop.dropped(), st.admitted
    _window_of(loop, 36, 5, 56000)
    loop.beat(8)
    got = loop.out[before:]
    assert calls == [5] and _errors(loop) == err0 + 1
    assert st.admitted == admitted0 + 4 and len(loop.newflows) == 0
    if loop.kind == "cluster":  # the window's lanes go by shard
        assert len(got) == 4 and set(_fids(got)) < set(range(200, 205))
    else:
        assert _fids(got) == [200, 201, 203, 204]
        assert loop.dropped() == drops0 + 1
    _window_of(loop, 42, 2, 56000)  # the drain was not cut short
    loop.beat(8)
    assert calls == [5, 2] and len(loop.out) == before + 6


def test_a_create_that_raises_whole_drops_its_batch_and_no_more(loop,
                                                                monkeypatch):
    def boom(*cols):
        raise ValueError("not one flow's fault")

    real = loop.newflows.handle_new_flows
    monkeypatch.setattr(loop.newflows, "handle_new_flows", boom)
    before, err0, drops0 = len(loop.out), _errors(loop), loop.dropped()
    loop.push(up_frame(SUB_BASE + 45, ip_to_u32("93.184.9.9"), 57000, 443, 17,
                       400))
    loop.push(up_frame(SUB_BASE + 46, ip_to_u32("93.184.9.9"), 57000, 443, 17,
                       401))
    loop.beat(6)
    assert len(loop.out) == before and _errors(loop) == err0 + 2
    if loop.kind != "cluster":
        assert loop.dropped() == drops0 + 2
    monkeypatch.setattr(loop.newflows, "handle_new_flows", real)
    flow = (SUB_BASE + 47, ip_to_u32("93.184.9.9"), 57000, 443, 17)
    loop.plain.open(*flow)
    loop.push(up_frame(*flow, 402))
    loop.beat(6)
    assert _fids(loop.out[before:]) == [402]


@pytest.mark.parametrize("ring_cls", [PyRing] + ([NativeRing]
                                                 if native_available else []))
def test_the_hold_queue_is_bounded_and_counts_what_it_refuses(ring_cls):
    lp = Loop("engine", ring_cls)
    bound = lp.newflows.bound
    assert bound == B // 4
    flows = [(SUB_BASE + i, ip_to_u32("93.184.9.9"), 53000, 443, 17)
             for i in range(bound + 3)]
    for i, flow in enumerate(flows):  # one window of B: all punt at once
        assert lp.plain.open(*flow) is not None
        lp.push(up_frame(*flow, 100 + i))
    lp.beat(8)
    st = lp.newflows.stats
    assert st.admitted == bound + 3 and st.hold_full == 3
    assert st.hold_high == bound and len(lp.out) == bound
    assert lp.eng.stats.dropped == 3 and lp.pushed == len(lp.out) + 3
    # the frames held left in the order they came
    assert [int.from_bytes(f[-4:], "big") for f in lp.out] == list(
        range(100, 100 + bound))


def test_the_tracer_hears_the_punts_and_the_lap():
    lp = Loop("engine", PyRing)
    tr = tele.arm(tele.Tracer())
    try:
        flow = (SUB_BASE + 1, ip_to_u32("93.184.9.9"), 54000, 443, 17)
        lp.plain.open(*flow)
        lp.push(up_frame(*flow, 1))
        lp.beat(6)
    finally:
        tele.disarm()
    sums = tr.sums()
    assert (sums["newflow_admitted"], sums["newflow_requeued"]) == (1, 1)
    assert sums["newflow_hold_high"] == 1 and sums["newflow_again"] == 0
    assert 0 < sums["stage_ns"]["punt"] <= sums["stage_ns"]["reply"]
    assert sums["drain_built"] >= 2  # sessions and reverse rows, applied
    assert tele.STAGE_NAMES[tele.PUNT] == "punt"
    assert tele.STAGE_NAMES[-1] == "total"
    zero = tele.Tracer().sums()
    assert zero["stage_ns"]["punt"] == 0 and zero["newflow_admitted"] == 0


def test_one_keys_hash_in_plain_ints_is_the_arrays_hash():
    """`HostTable._buckets` hashes one key in Python ints (a punt hashes a
    flow some seven times): bit for bit `hash_words` of the same words."""
    rng = np.random.default_rng(53)
    for k in (1, 2, 4, 8):
        for n in range(300):
            key = rng.integers(0, 1 << 32, k, dtype=np.uint64).astype(np.uint32)
            if n % 5 == 0:
                key[n % k] = 0xFFFFFFFF
            for seed in (SEED1, SEED2):
                want = int(hash_words([key[i:i + 1] for i in range(k)],
                                      seed)[0])
                assert hash_words_int(key.tolist(), seed) == want


@pytest.mark.parametrize("ring_cls", [PyRing] + ([NativeRing]
                                                 if native_available else []))
def test_fwd_inject_queues_on_the_forward_side(ring_cls):
    r = ring_cls(nframes=8, frame_size=128, depth=4)
    assert r.fwd_inject(b"held" * 5, 1) and r.fwd_pending() == 1
    assert r.tx_pending() == 0 and r.stats()["fwd"] == 1
    assert r.fwd_pop() == (b"held" * 5, 1)
    assert not r.fwd_inject(b"x" * 500, 1)  # oversize
    for _ in range(4):
        assert r.fwd_inject(b"y" * 40, 0)
    assert not r.fwd_inject(b"y" * 40, 0)  # the ring is full
    assert r.free_frames() == 4
    r.close()
