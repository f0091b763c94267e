"""A retired mesh step's outputs are already on the host (PR 44).

`ShardedCluster._start_host_copies` starts, at dispatch, the
device-to-host copy of every leaf of a mesh step's result that its retire
reads (PR 43's route on the one-chip loops, carried over the mesh); the
reads stay where they were. Nothing a program computes or the loop does
changes, so with the helper stubbed to start nothing (the parent's
behaviour) the loop gives the same bytes; the Tracer's `prefetch_calls` /
`fetch_calls` say which path ran. Two shards on the forced host platform,
tiny tables: no number from here is a device metric.

(a) several pipelined beats and `flush_pipeline`, a DHCP-only window among
    them: shipped against stubbed, byte for byte (what `ring.complete` is
    handed, the ring's TX and FWD frames, every stats block, the mirror
    sink's lanes), with and without the garden, with edge taps and a
    mirror sink set and unset
(b) `prefetch_calls` are the leaves the retire reads, `fetch_calls` 0
    there; stubbed, the reads are the parent's crossings; the same for the
    synchronous facades `step`, `dhcp_step`, `process_ring`
(c) no table leaf is ever handed to the Tracer, and the donated tables
    still thread
(d) a dispatch that raises leaves nothing remembered
"""

import functools
import gc

import jax
import numpy as np
import pytest

from bng_tpu.control import dhcp_codec, packets
from bng_tpu.parallel import sharded as sharded_mod
from bng_tpu.parallel.sharded import ShardedCluster
from bng_tpu.telemetry import spans
from bng_tpu.utils.net import ip_to_u32, parse_mac

pytestmark = pytest.mark.sharded

NOW = 1_753_000_000
SERVER_MAC = parse_mac("02:aa:bb:cc:dd:01")
SERVER_IP = ip_to_u32("10.0.0.1")
NAT_IP = ip_to_u32("10.0.0.50")
REMOTE = ip_to_u32("93.184.216.34")
GEOM = dict(batch_per_shard=8, sub_nbuckets=64, vlan_nbuckets=64,
            cid_nbuckets=64, nat_sessions_nbuckets=64, qos_nbuckets=64,
            spoof_nbuckets=64)
EDGE = dict(garden_enabled=False, edge_enabled=True, edge_nbuckets=64)
# kind -> (constructor arguments beyond GEOM, a mirror sink set, the leaves
# a fused window's retire reads: verdict, out_pkt, out_len, the punt and
# violation flags, the dhcp / nat / qos / spoof blocks, then the garden's
# block, the edge stage's block, and the mirror column where a sink reads it)
KINDS = {
    "garden": ({}, False, 10),  # tests/test_sharded_serving.py's geometry
    "plain": (dict(garden_enabled=False), False, 9),
    "edge-sink": (EDGE, True, 11),
    "edge-no-sink": (EDGE, False, 10),
}
DHCP_READS = 4  # is_reply, out_pkt, out_len, the dhcp block
STATS = ("dhcp", "nat", "qos", "spoof", "garden", "pppoe", "edge")
BEATS = 5


def _nothing(self, names, outs):
    """The helper stubbed: no copy is started, as on the parent."""


def _mac(i: int) -> bytes:
    return (0x02D0 << 32 | i).to_bytes(6, "big")


def _discover(mac: bytes, xid: int) -> bytes:
    p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER, xid=xid)
    p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
    return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                              p.encode().ljust(320, b"\x00"))


def _data(mac: bytes, k: int) -> bytes:
    return packets.udp_packet(mac, SERVER_MAC, NAT_IP, REMOTE, 40000, 443,
                              bytes([k]) * 64)


def _cluster(kind: str):
    """A provisioned two-shard cluster of `kind`, its subscribers' MACs
    and the lanes its mirror sink was handed."""
    over, sink, _reads = KINDS[kind]
    cl = ShardedCluster(2, **GEOM, **over)
    cl.set_server_config_all(SERVER_MAC, SERVER_IP)
    cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 24, SERVER_IP, lease_time=3600)
    macs = [_mac(i) for i in range(8)]
    for i, m in enumerate(macs):
        cl.add_subscriber(m, pool_id=1, ip=ip_to_u32(f"10.0.0.{50 + i}"),
                          lease_expiry=NOW + 600)
    cl.allocate_nat(NAT_IP, NOW)
    _owner, flow = cl.handle_new_flow(NAT_IP, REMOTE, 40000, 443, 17, 600, NOW)
    assert flow is not None  # the data frames translate from the first
    cl.set_qos(NAT_IP, down_bps=8_000_000, up_bps=8_000_000,
               down_burst=100_000, up_burst=100_000)
    cl.add_spoof_binding(macs[0], NAT_IP, 1)
    if cl.garden is not None:
        cl.set_gardened(ip_to_u32("10.0.0.51"), True)
    mirrored = []
    if cl.edge is not None:
        cl.arm_tap(NAT_IP, 7)
    if sink:
        cl.mirror_sink = lambda lane, frame, wid: mirrored.append(
            (lane, bytes(frame), wid))
    cl.sync_tables()
    return cl, macs, mirrored


@functools.lru_cache(maxsize=None)
def _serve(kind: str, shipped: bool) -> dict:
    """BEATS pipelined beats and the flush, armed; everything the loop
    hands back or counts. Beat 2 is all DISCOVERs (the DHCP-only lane),
    the others carry a data frame and ride the fused step."""
    was = ShardedCluster._start_host_copies
    if not shipped:
        ShardedCluster._start_host_copies = _nothing
    try:
        cl, macs, mirrored = _cluster(kind)
        ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)
        completes, replies = [], []
        complete = ring.complete

        def spy(verdict, out, out_len, n):
            completes.append((np.asarray(verdict).tobytes(),
                              np.asarray(out).tobytes(),
                              np.asarray(out_len).tobytes()))
            return complete(verdict, out, out_len, n)

        ring.complete = spy

        def pop():
            for one in (ring.tx_pop, ring.fwd_pop):
                while (got := one()) is not None:
                    replies.append((bytes(got[0]), int(got[1])))

        with spans.armed(keep_events=1 << 12) as tr:
            for k in range(BEATS):
                for i, mac in enumerate(macs[:3]):
                    assert ring.rx_push(_discover(mac, 100 + 10 * k + i),
                                        from_access=True)
                if k != 2:
                    assert ring.rx_push(_data(macs[0], k), from_access=True)
                cl.process_ring_pipelined(ring, NOW + k, k * 1000)
                pop()
            assert cl._inflight is not None
            cl.flush_pipeline()
            pop()
            sums = tr.sums()
            left = len(tr._prefetched)
        snap = cl.telemetry.snapshot()
        return {
            "completes": completes,
            "replies": replies,
            "stats": {k: np.asarray(cl.stats[k]).tobytes()
                      for k in STATS if k in cl.stats},
            "slow_errors": cl.stats["slow_errors"],
            "mirrored": mirrored,
            "steps": snap["steps"],
            "per_shard": [{k: v for k, v in s.items() if k != "nat_pool"}
                          for s in snap["per_shard"]],
            "ring": dict(ring.stats()),
            "xfer": sums["xfer"],
            "remembered": left,
        }
    finally:
        ShardedCluster._start_host_copies = was


# -- (a) shipped against stubbed, byte for byte --------------------------------

@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_loop_gives_the_same_bytes_with_the_copies_started_and_without(kind):
    got, want = _serve(kind, True), _serve(kind, False)
    # the windows are the ones the claim needs: BEATS retired in order, a
    # DHCP-only one among them, device OFFERs and translated frames out
    assert len(want["completes"]) == want["steps"] == BEATS
    assert want["slow_errors"] == 0
    tx = [f for f, _fl in want["replies"] if f[12:14] == b"\x08\x00"]
    assert len(tx) == len(want["replies"]) == 3 * BEATS + (BEATS - 1)
    assert sum(s["verdicts"]["tx"] for s in want["per_shard"]) == 3 * BEATS
    assert sum(s["verdicts"]["fwd"] for s in want["per_shard"]) == BEATS - 1
    assert any(want["stats"]["nat"]) and any(want["stats"]["dhcp"])
    assert set(want["stats"]) == {"dhcp", "nat", "qos", "spoof"} | (
        {"garden"} if kind == "garden" else set()) | (
        {"edge"} if kind.startswith("edge") else set())
    if kind == "edge-sink":  # the tapped subscriber's data frames, each once
        assert [w for _l, _f, w in want["mirrored"]] == [7] * (BEATS - 1)
    else:
        assert want["mirrored"] == []
    for key in ("completes", "replies", "stats", "slow_errors", "mirrored",
                "steps", "per_shard", "ring"):
        assert got[key] == want[key], key


# -- (b) which path ran --------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(KINDS))
def test_prefetch_calls_of_the_loop_are_the_leaves_its_retires_read(kind):
    reads = KINDS[kind][2] * (BEATS - 1) + DHCP_READS
    got, stub = _serve(kind, True), _serve(kind, False)
    # every output a retire reads was started at its dispatch: the read
    # crossed nothing, and the Tracer forgot it there
    assert got["xfer"]["prefetch_calls"] == reads
    assert got["xfer"]["fetch_calls"] == got["xfer"]["fetch_bytes"] == 0
    assert got["remembered"] == 0
    # stubbed, the same reads are the parent's crossings
    assert stub["xfer"]["prefetch_calls"] == 0
    assert stub["xfer"]["fetch_calls"] == reads
    assert stub["xfer"]["fetch_bytes"] > 0
    for key in ("upload_calls", "upload_bytes"):
        assert got["xfer"][key] == stub["xfer"][key]


def _window(cl, macs, dhcp_only: bool):
    B = cl.n * cl.b
    pkt = np.zeros((B, 2048), dtype=np.uint8)
    length = np.zeros((B,), dtype=np.uint32)
    frames = [_discover(macs[0], 7)] + ([] if dhcp_only
                                        else [_data(macs[0], 1)])
    for lane, f in enumerate(frames):  # shard 0's region: raw steps steer nothing
        pkt[lane, : len(f)] = np.frombuffer(f, dtype=np.uint8)
        length[lane] = len(f)
    return pkt, length


def _facade(name: str, cl, macs) -> dict:
    if name == "step":
        pkt, length = _window(cl, macs, False)
        out = cl.step(pkt, length, np.ones(len(length), dtype=bool), NOW, 0)
    elif name == "dhcp_step":
        out = cl.dhcp_step(*_window(cl, macs, True), NOW)
    else:
        ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)
        assert ring.rx_push(_discover(macs[0], 7), from_access=True)
        assert ring.rx_push(_data(macs[0], 1), from_access=True)
        assert cl.process_ring(ring, NOW, 0) == 2
        out = {"tx": ring.tx_pop()[0], "fwd": ring.fwd_pop()[0]}
    return {k: np.asarray(v).tobytes() for k, v in out.items()}


# facade -> (leaves whose copy its dispatch starts, leaves it reads back:
# `step` and `dhcp_step` hand `out_pkt` back unread)
FACADES = {"step": (10, 9), "dhcp_step": (DHCP_READS, DHCP_READS - 1),
           "process_ring": (10, 10)}


@pytest.mark.parametrize("name", sorted(FACADES))
def test_a_sync_facade_starts_its_copies_and_returns_what_it_returned(
        name, monkeypatch):
    started, read = FACADES[name]
    cl, macs, _ = _cluster("garden")
    with spans.armed() as tr:
        got = _facade(name, cl, macs)
        x = tr.sums()["xfer"]
    assert (x["prefetch_calls"], x["fetch_calls"]) == (started, 0)
    monkeypatch.setattr(ShardedCluster, "_start_host_copies", _nothing)
    cl, macs, _ = _cluster("garden")
    with spans.armed() as tr:
        want = _facade(name, cl, macs)
        x = tr.sums()["xfer"]
    assert (x["prefetch_calls"], x["fetch_calls"]) == (0, read)
    assert got == want and len(want) >= 2


# -- (c) the tables are never among them ---------------------------------------

def test_no_table_leaf_is_prefetched_and_the_donated_tables_thread(monkeypatch):
    handed, results = [], []
    real = sharded_mod.start_host_copies

    def spy(outs):
        outs = list(outs)
        handed.append(outs)
        real(outs)

    monkeypatch.setattr(sharded_mod, "start_host_copies", spy)
    cl, macs, _ = _cluster("edge-sink")
    for attr in ("_step", "_dhcp_step"):
        def run(*args, _real=getattr(cl, attr)):
            results.append(_real(*args))
            return results[-1]
        setattr(cl, attr, run)
    pkt, length = _window(cl, macs, False)
    fa = np.ones(len(length), dtype=bool)
    cl.step(pkt, length, fa, NOW, 0)
    cl.dhcp_step(*_window(cl, macs, True), NOW)
    out = cl.step(pkt, length, fa, NOW + 1, 1000)  # the tables threaded
    assert out["verdict"].shape == (cl.n * cl.b,)
    assert [len(h) for h in handed] == [11, DHCP_READS, 11]
    for outs, raw in zip(handed, results):
        tables = raw[3] if len(raw) > 5 else raw[0]
        leaves = {id(a) for a in jax.tree.leaves(tables)}
        assert len(leaves) > 10 and not leaves & {id(a) for a in outs}
        # and every other leaf of the result is: nothing a retire reads
        # was left to cross at the read
        rest = [a for a in jax.tree.leaves(raw) if id(a) not in leaves]
        assert {id(a) for a in rest} == {id(a) for a in outs}
    assert all(not a.is_deleted() for a in jax.tree.leaves(cl.tables))


# -- (d) a dispatch that raises ------------------------------------------------

def test_a_dispatch_that_raises_leaves_nothing_remembered():
    cl, macs, _ = _cluster("garden")
    ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)

    def push(k):
        assert ring.rx_push(_discover(macs[0], k), from_access=True)
        assert ring.rx_push(_data(macs[0], k), from_access=True)

    def boom(*a, **k):
        raise RuntimeError("synthetic device error")

    with spans.armed() as tr:
        push(1)
        assert cl.process_ring_pipelined(ring, NOW, 0) == 0
        assert len(tr._prefetched) == 10  # window 1, in flight
        real = cl._step
        cl._step = boom
        push(2)
        with pytest.raises(RuntimeError, match="synthetic"):
            cl.process_ring_pipelined(ring, NOW + 1, 1000)
        cl._step = real
        # window 1 retired first (its reads forgot its outputs), window 2
        # dropped fail-closed and started nothing
        assert cl._inflight is None
        x = tr.sums()["xfer"]
        assert (x["prefetch_calls"], x["fetch_calls"]) == (10, 0)
        gc.collect()
        assert len(tr._prefetched) == 0
        push(3)
        assert cl.process_ring(ring, NOW + 2, 2000) == 2  # and serves on
        assert tr.sums()["xfer"]["prefetch_calls"] == 20
        assert len(tr._prefetched) == 0
