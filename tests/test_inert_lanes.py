"""The pipelined ring loop's staging invariant: a lane beyond the
assembled count is inert.

`Engine.process_ring_pipelined` reuses two staging buffers, and
`assemble` (both rings) leaves the rows beyond its count as the buffer's
last window left them. A short window after a long one into the same
buffer must leave the device exactly where `Engine.process_ring`, which
starts every call from zeros, leaves it: the DHCP stats, NAT's session
counters, the QoS buckets, and every reply's bytes. Seeded random tables
and frames, tiny sizes, CPU.

Since PR 39 a step runs at the rung of its window (engine.py step_rung):
shape `two-rungs` has a batch of 256 (rungs 128 and 256) and windows that
cross the boundary both ways, long then short into one buffer and short
then long into the other. The stale rows between the window's end and the
rung's are the inert ones; those beyond the rung never reach the chip.
"""

import jax
import numpy as np
import pytest

from bng_tpu.control import dhcp_codec, packets
from bng_tpu.control.nat import NATManager
from bng_tpu.control.pool import Pool, PoolManager
from bng_tpu.ops.antispoof import MODE_STRICT
from bng_tpu.ops.dhcp import ST_HIT
from bng_tpu.runtime import hostpath
from bng_tpu.runtime.engine import (AntispoofTables, Engine, QoSTables,
                                    split_window, step_rung)
from bng_tpu.runtime.ring import NativeRing, PyRing, load_native
from bng_tpu.runtime.tables import FastPathTables
from bng_tpu.telemetry import spans
from bng_tpu.utils.net import ip_to_u32

SERVER_MAC = bytes.fromhex("02aabbccdd01")
SERVER_IP = ip_to_u32("10.0.0.1")
T0 = 1_753_000_000
SUBS = 24
BATCH = 16

RINGS = {
    "py-scalar": lambda **kw: PyRing(host_path="scalar", **kw),
    "py-vector": lambda **kw: PyRing(host_path="vector", **kw),
    "native": lambda **kw: NativeRing(**kw),
}
# windows in frames, by call: the third lands in the first's buffer and
# the fourth in the second's, each far shorter than what it finds there
WINDOWS = (14, 12, 3, 2)
# `two-rungs`: 140 (the 256 rung) then 3 (the 128 rung) into one buffer,
# 12 (128) then 150 (256) into the other
SHAPES = {
    "one-rung": dict(batch=BATCH, windows=WINDOWS, nframes=128, depth=32),
    "two-rungs": dict(batch=256, windows=(140, 12, 3, 150), nframes=1024,
                      depth=256),
}


def _stack(seed, batch=BATCH):
    """One engine over seeded random tables: every subscriber has a DHCP
    row, a QoS row a few frames deep, a strict binding, a NAT block and
    two flows. Returns the engine and what the frames are made from."""
    rng = np.random.default_rng(seed)
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
    macs = [bytes([0x02, *rng.integers(0, 256, 5).tolist()])
            for _ in range(SUBS)]
    ips = ip_to_u32("10.0.0.10") + rng.permutation(200)[:SUBS]
    flows = []
    for mac, ip in zip(macs, (int(x) for x in ips)):
        fastpath.add_subscriber(mac, pool_id=1, ip=ip,
                                lease_expiry=T0 + 86400)
        # buckets of 2-6 frames: the stale lanes' bytes would drain them
        qos.set_subscriber(ip, down_bps=80_000, up_bps=80_000,
                           down_burst=int(rng.integers(600, 1800)),
                           up_burst=int(rng.integers(600, 1800)))
        spoof.add_binding(mac, ip, MODE_STRICT)
        assert nat.allocate_nat(ip, T0) is not None
        for _ in range(2):
            dst = int(ip_to_u32("93.184.0.0") + rng.integers(1, 60000))
            sport, proto = int(rng.integers(20000, 60000)), int(rng.choice([6, 17]))
            nat_ip, nat_port = nat.handle_new_flow(ip, dst, sport, 443, proto,
                                                   64, T0)
            flows.append((mac, ip, dst, sport, proto, nat_ip, nat_port))
    engine = Engine(fastpath, nat, qos, spoof, batch_size=batch,
                    clock=lambda: float(T0))
    return engine, macs, ips, flows


def _dhcp(rng, macs, ips):
    i = int(rng.integers(0, SUBS))
    xid = int(rng.integers(1, 2**31))
    if rng.random() < 0.5:
        p = dhcp_codec.build_request(macs[i], dhcp_codec.DISCOVER, xid=xid)
    else:
        p = dhcp_codec.build_request(macs[i], dhcp_codec.REQUEST, xid=xid,
                                     requested_ip=int(ips[i]),
                                     server_id=SERVER_IP)
    p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
    frame = packets.udp_packet(macs[i], b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                               p.encode().ljust(320, b"\x00"))
    return frame, True


def _data(rng, flows):
    mac, ip, dst, sport, proto, nat_ip, nat_port = flows[
        int(rng.integers(0, len(flows)))]
    payload = bytes(rng.integers(0, 256, int(rng.integers(8, 200)),
                                 dtype=np.uint8))
    make = packets.udp_packet if proto == 17 else packets.tcp_packet
    if rng.random() < 0.5:  # upstream, SNAT
        return make(mac, SERVER_MAC, ip, dst, sport, 443, payload), True
    return make(b"\x02\x99" * 3, SERVER_MAC, dst, nat_ip, 443, nat_port,
                payload), False  # the matching downstream, DNAT


def _windows(path, seed, macs, ips, flows, windows=WINDOWS):
    """The same seeded frames in the same windows for both loops. `mixed`:
    every window DHCP and data, so each rides the fused step. `dhcp`: the
    third window is DHCP alone, and rides the DHCP-only fast lane into the
    buffer a long mixed window used."""
    rng = np.random.default_rng(seed + 1)
    out = []
    for k, size in enumerate(windows):
        if path == "dhcp" and k == 2:
            out.append([_dhcp(rng, macs, ips) for _ in range(size)])
            continue
        n_dhcp = max(1, size // 3)
        win = ([_dhcp(rng, macs, ips) for _ in range(n_dhcp)]
               + [_data(rng, flows) for _ in range(size - n_dhcp)])
        out.append([win[i] for i in rng.permutation(size)])
    return out


def _serve(ring_kind, pipelined, path, seed, shape="one-rung"):
    sh = SHAPES[shape]
    engine, macs, ips, flows = _stack(seed, sh["batch"])
    ring = RINGS[ring_kind](nframes=sh["nframes"], frame_size=1024,
                            depth=sh["depth"])
    fast_lane = []
    real = engine._run_dhcp_batch

    def spy(*a, **k):
        fast_lane.append(1)
        return real(*a, **k)

    engine._run_dhcp_batch = spy
    step = engine.process_ring_pipelined if pipelined else engine.process_ring
    replies = {"tx": [], "fwd": []}

    def pop():
        for name, one in (("tx", ring.tx_pop), ("fwd", ring.fwd_pop)):
            while (got := one()) is not None:
                replies[name].append(got[0])

    try:
        for k, win in enumerate(_windows(path, seed, macs, ips, flows,
                                         sh["windows"])):
            for frame, from_access in win:
                assert ring.rx_push(frame, from_access=from_access)
            step(ring, now=T0 + 0.02 * k)
            pop()
        engine.flush_pipeline()
        pop()
        assert ring.slow_pop() is None  # nothing punted: the tables moved
        # on the device alone, so both loops' updates line up step for step
        state = {
            "stats": {k: np.asarray(getattr(engine.stats, k)).copy()
                      for k in ("dhcp", "nat", "qos", "spoof")},
            "verdicts": (engine.stats.tx, engine.stats.fwd,
                         engine.stats.dropped, engine.stats.passed),
            "tables": [(jax.tree_util.keystr(kp), np.asarray(x)) for kp, x in
                       jax.tree_util.tree_flatten_with_path(engine.tables)[0]],
            "replies": replies,
            "fast_lane": len(fast_lane),
            "batches": engine.stats.batches,
        }
    finally:
        ring.close()
    return state


@pytest.mark.parametrize("path,shape", [("mixed", "one-rung"),
                                        ("dhcp", "one-rung"),
                                        ("mixed", "two-rungs")])
@pytest.mark.parametrize("ring_kind", sorted(RINGS))
def test_short_window_after_long_leaves_process_rings_state(ring_kind, path,
                                                            shape):
    if ring_kind == "native" and load_native() is None:
        pytest.skip("native toolchain unavailable")
    seed = 20280 + sorted(RINGS).index(ring_kind)
    want = _serve(ring_kind, False, path, seed, shape)
    got = _serve(ring_kind, True, path, seed, shape)

    # the scenario is the one the defect needs: four dispatches, device
    # DHCP hits, NAT and QoS at work, the fast lane taken where meant
    assert want["batches"] == got["batches"] == len(WINDOWS)
    assert want["fast_lane"] == got["fast_lane"] == (1 if path == "dhcp" else 0)
    assert want["stats"]["dhcp"][ST_HIT] > 0
    assert want["stats"]["nat"].sum() > 0 and want["stats"]["qos"].sum() > 0
    assert want["verdicts"][0] > 0 and want["verdicts"][1] > 0
    assert want["verdicts"][3] == 0
    assert len(want["replies"]["tx"]) == want["verdicts"][0]

    for name in want["stats"]:
        assert (got["stats"][name] == want["stats"][name]).all(), (
            name, got["stats"][name], want["stats"][name])
    assert got["verdicts"] == want["verdicts"]
    # every leaf of the device's tables: the DHCP rows, NAT's sessions with
    # their packet and byte counters, both QoS bucket tables
    assert len(got["tables"]) == len(want["tables"])
    for (name, a), (_name, b) in zip(got["tables"], want["tables"]):
        assert (a == b).all(), name
    assert got["replies"] == want["replies"]


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("ring_kind", sorted(RINGS))
def test_a_short_windows_block_holds_nothing_beyond_its_frames(
        ring_kind, shape, monkeypatch):
    """Since PR 51 the lengths and the access flags reach the device inside
    the window's one block (hostpath.seal_window, written in place into the
    staging buffer once the rung is known). The invariant is the block's:
    read as the step reads it (engine.py split_window), a window's lanes
    are its frames with their flags, and every lane beyond them has length
    0 and flags 0, in the short window that lands where a long one was
    (and where that one's planes were) too."""
    if ring_kind == "native" and load_native() is None:
        pytest.skip("native toolchain unavailable")
    blocks = []
    real = hostpath.seal_window

    def spy(pkt, length, fa):
        block = real(pkt, length, fa)
        blocks.append((block.copy(), block.ctypes.data == pkt.ctypes.data))
        return block

    monkeypatch.setattr(hostpath, "seal_window", spy)
    sh = SHAPES[shape]
    seed = 20310 + sorted(RINGS).index(ring_kind)
    got = _serve(ring_kind, True, "mixed", seed, shape)
    _engine, macs, ips, flows = _stack(seed, sh["batch"])
    sent = _windows("mixed", seed, macs, ips, flows, sh["windows"])
    assert got["batches"] == len(blocks) == len(sent) == 4
    split = jax.jit(split_window)
    for (block, in_place), win in zip(blocks, sent):
        assert in_place  # the staging buffer's own rows, no copy
        n, b = len(win), step_rung(len(win), sh["batch"])
        pkt, length, fa = (np.asarray(x) for x in split(block))
        assert pkt.shape == (b, block.shape[1]) and n <= b
        for lane, (frame, from_access) in enumerate(win):
            assert length[lane] == len(frame)
            assert bytes(pkt[lane, :len(frame)]) == frame
            assert bool(fa[lane]) == from_access
        assert not length[n:].any() and not fa[n:].any()
    # not vacuous: the third window is shorter than the one its buffer held
    assert len(sent[2]) < len(sent[0])


def test_masked_lanes_is_a_sum_of_every_tracer_and_counts_ghost_lanes():
    zero = spans.Tracer().sums()
    assert zero["masked_lanes"] == 0  # never armed: the key, at zero
    assert "masked_lanes" in spans.trace_sums()
    assert spans._ZERO_SUMS["masked_lanes"] == 0
    with spans.armed() as tr:
        _serve("py-scalar", True, "mixed", 20289)
    # each buffer once: what its long window held beyond its short one
    assert tr.sums()["masked_lanes"] == (
        (WINDOWS[0] - WINDOWS[2]) + (WINDOWS[1] - WINDOWS[3]))
    assert spans.trace_sums()["masked_lanes"] == tr.sums()["masked_lanes"]
    with spans.armed() as tr:
        _serve("py-scalar", False, "mixed", 20289)  # fresh zeros a call
    assert tr.sums()["masked_lanes"] == 0


def test_windows_that_cross_a_rung_are_masked_over_the_whole_buffer():
    """`step_lanes` (PR 39) sums the rung each window took; the mask's
    high-water mark runs over the whole buffer, beyond the rung too: the
    short window's buffer held 140 lanes and is cleared from 3 to 140,
    though only rows 3 to 128 go to the chip."""
    assert spans.Tracer().sums()["step_lanes"] == 0
    assert spans._ZERO_SUMS["step_lanes"] == 0
    windows = SHAPES["two-rungs"]["windows"]
    with spans.armed() as tr:
        got = _serve("py-scalar", True, "mixed", 20289, "two-rungs")
    assert got["batches"] == len(windows)
    assert tr.sums()["step_lanes"] == 256 + 128 + 128 + 256
    assert tr.sums()["masked_lanes"] == windows[0] - windows[2]
    assert spans.trace_sums()["step_lanes"] == tr.sums()["step_lanes"]
    with spans.armed() as tr:
        _serve("py-scalar", True, "mixed", 20289)  # a batch under the floor
    assert tr.sums()["step_lanes"] == len(WINDOWS) * BATCH


def test_drain_tables_adds_up_to_the_tables_drained_and_is_silent_disarmed():
    """`drain_built` / `drain_cached` (PR 35): per drain, the tables that
    held dirty slots and the clean ones, whose batch is already on the
    chip. Together they are the tables that drain went over."""
    zero = spans.Tracer().sums()
    assert zero["drain_built"] == zero["drain_cached"] == 0
    assert {"drain_built", "drain_cached"} <= set(spans.trace_sums())
    engine, macs, ips, _flows = _stack(20291)
    n = len(engine.host_mirror_tables())  # 3 fastpath + 6: no garden, no edge

    def counts(tr):
        s = tr.sums()
        return s["drain_built"], s["drain_cached"]

    with spans.armed() as tr:
        engine._drain_updates()  # the whole set, clean since the upload
        assert counts(tr) == (0, n)
        assert engine.fastpath.touch_lease(macs[0], T0 + 5)
        engine.qos.set_subscriber(int(ips[1]), down_bps=1, up_bps=1)  # both ways
        engine._drain_updates()
        assert counts(tr) == (3, n + n - 3)
        assert engine.pending_dirty() == 0
        engine.antispoof.add_binding(macs[2], int(ips[2]), MODE_STRICT)
        engine._make_bulk_updates()  # every table but the fastpath's three
        assert counts(tr) == (4, 2 * n - 3 + (n - 3) - 1)
        engine._drain_fastpath_updates()  # those three
        assert counts(tr) == (4, 3 * n - 7 + 3)
        engine._empty_updates()  # drains nothing: counts nothing
        assert counts(tr) == (4, 3 * n - 4)
        assert sum(counts(tr)) == n + n + (n - 3) + 3
    # a loop's worth: four windows, a drain a dispatch, every table clean
    with spans.armed() as tr:
        got = _serve("py-scalar", True, "mixed", 20289)
    assert counts(tr) == (0, got["batches"] * n)
    # disarmed, nothing is stamped: the last tracer's sums stand
    assert engine.fastpath.touch_lease(macs[0], T0 + 9)
    engine._drain_updates()
    assert counts(tr) == (0, got["batches"] * n)
    assert spans.trace_sums()["drain_built"] == 0


def test_an_armed_loop_counts_the_crossings_the_code_makes():
    """`upload` / `fetch` (PR 37): every host-to-device call and every read
    of a step's outputs on the engine's loop is a lap with its calls and
    bytes. The literals are the crossings the code makes a step: a PR that
    merges reads lowers them here. Since PR 43 the copy of every output a
    retire reads is started at dispatch: the reads are `prefetch_calls`,
    their laps stay, and no blocking crossing is left on this loop."""
    zero = spans.Tracer().sums()["xfer"]
    assert zero == {"upload_calls": 0, "upload_bytes": 0,
                    "fetch_calls": 0, "fetch_bytes": 0, "prefetch_calls": 0}
    with spans.armed(keep_events=1 << 10) as tr:
        got = _serve("py-scalar", True, "mixed", 20289)
    steps = got["batches"]
    s = tr.sums()
    x = s["xfer"]
    # a step, every table clean: the staged window, ONE block in one call
    # since PR 51 (the packet slots with the lengths' and the access
    # flags' planes in the rows behind them, hostpath.seal_window), and
    # nothing from the drain, not on a new engine's
    # first either (PR 50: its seven dense arrays are in the tables it
    # uploaded, and one crosses again only when the host's bytes differ
    # from what the tables hold, Engine._fresh_dense)
    engine = _stack(20289)[0]
    slot = engine.L  # the staging row, in bytes
    assert x["upload_calls"] == steps == 4
    assert x["upload_bytes"] == steps * slot * hostpath.window_rows(BATCH,
                                                                    slot)
    # a retire: verdict, out_pkt, out_len (inside `device_wait`), the
    # violation and punt flags (inside `reply`), and _fold_stats' four
    # blocks (dhcp, nat, qos, spoof; no garden, PPPoE, edge or v6 here):
    # each one's copy was started when its step was dispatched
    assert x["prefetch_calls"] == (3 + 2 + 4) * steps
    assert x["fetch_calls"] == x["fetch_bytes"] == 0
    by_stage = {}
    for stage, _lane, _t0, _dur in tr.events:
        by_stage[stage] = by_stage.get(stage, 0) + 1
    # one `drain` lap a dispatch (the loop's first), one `upload`, three
    # `fetch` (device_wait's, reply's, _fold_stats')
    assert by_stage[spans.DRAIN] == steps
    assert by_stage[spans.UPLOAD] == steps
    assert by_stage[spans.FETCH] == 3 * steps
    assert s["stage_ns"]["upload"] <= s["stage_ns"]["dispatch"]
    assert sum(s["starved_ns"].values()) == \
        s["beat_starved_ns"] + s["starved_ns"]["outside"]

    # a drain that ships something shows as calls: one dirty cuckoo table
    # is six arrays, one dirty QoS table three, a dense array one
    engine, macs, ips, _flows = _stack(20291)
    # what start-up builds the two apply programs with: the dense arrays a
    # batch carries are placed with it (ops/table.py placed)
    engine._empty_updates(), engine.fastpath.empty_updates()
    with spans.armed() as tr:
        engine._drain_updates()
        assert tr.sums()["xfer"]["upload_calls"] == 0
        assert engine.fastpath.touch_lease(macs[0], T0 + 5)
        engine._drain_updates()
        assert tr.sums()["xfer"]["upload_calls"] == 6
        engine.qos.set_subscriber(int(ips[1]), down_bps=1, up_bps=1)
        engine._drain_updates()
        assert tr.sums()["xfer"]["upload_calls"] == 6 + 3 + 3
        engine.antispoof.set_config(MODE_STRICT, log_violations=False)
        engine._drain_updates()
        assert tr.sums()["xfer"]["upload_calls"] == 12 + 1
        assert tr.sums()["xfer"]["fetch_calls"] == 0
        assert tr.sums()["xfer"]["prefetch_calls"] == 0


class _Out:
    """A stand-in for a step's device output: counts who waits on it."""

    def __init__(self, host):
        self.host = np.asarray(host)
        self.nbytes = self.host.nbytes
        self.waits = 0

    def block_until_ready(self):
        self.waits += 1
        return self

    def __array__(self, dtype=None, copy=None):
        return self.host if dtype is None else self.host.astype(dtype)


def test_disarmed_a_retire_forces_nothing_it_did_not_force_before():
    """Armed, a retire blocks on its first output alone, so that the wait
    and the copies are told apart; disarmed there is no such call: the
    first read waits and copies at once, as it always did."""
    from types import SimpleNamespace

    from bng_tpu.runtime.ring import VERDICT_DROP

    engine, *_ = _stack(20292)
    ring = PyRing(nframes=64, frame_size=1024, depth=8)

    def retire():
        pkt, length, flags = (np.zeros((BATCH, engine.L), np.uint8),
                              np.zeros(BATCH, np.uint32),
                              np.zeros(BATCH, np.uint32))
        for _ in range(3):
            assert ring.rx_push(b"\x02" * 64, from_access=True)
        n = ring.assemble(pkt, length, flags)
        assert n == 3
        res = SimpleNamespace(
            verdict=_Out(np.full(BATCH, VERDICT_DROP, np.uint8)),
            out_pkt=_Out(pkt), out_len=_Out(length),
            spoof_violation=_Out(np.zeros(BATCH, bool)),
            nat_punt=_Out(np.zeros(BATCH, bool)))
        engine._apply_ring_verdicts(ring, res, pkt, length, n, float(T0))
        return res

    try:
        res = retire()
        assert not spans.enabled()
        assert [o.waits for o in vars(res).values()] == [0] * 5
        with spans.armed() as tr:
            res = retire()
        assert res.verdict.waits == 1
        assert sum(o.waits for o in vars(res).values()) == 1
        assert tr.sums()["xfer"]["fetch_calls"] == 3 + 2
        assert tr.sums()["xfer"]["upload_calls"] == 0
    finally:
        ring.close()
