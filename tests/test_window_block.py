"""One crossing a window (PR 51): the staged window is one block.

A dispatched window goes to the chip in one host-to-device call: the
packet slots in rows [0, b) of the staging buffer and, flat over the k
rows behind them, five planes of b bytes (the lengths' four bytes, then
the access flags). `hostpath.seal_window` writes the planes in place,
`engine.split_window` takes the block apart inside the fused step. Held
here:

  - for every rung of `step_rungs(8192)` and every stage geometry
    `runtime/verify.py` builds, at the cells' slot of 1,536 bytes, the
    split inside the step hands `pipeline_step` the `pkt` / `length` /
    `from_access` that were staged, bit for bit, from a block sealed in
    place in the widest rung's staging buffer;
  - a matrix that is not the head of a staging buffer is copied, never
    written behind;
  - each road of the serving loops (`_dispatch_step`,
    `dispatch_scheduled_bulk`, `run_express_aot` with and without a device
    of its own, and the ring loops and `process` above them) makes exactly
    one placement a batch by the Tracer's `xfer.upload_calls`, of the
    staging buffer's own rows.

Tiny tables, CPU. No number from here is a device metric.
"""

import functools

import jax
import numpy as np
import pytest

from bng_tpu.control import packets
from bng_tpu.ops.express import XD_WORDS
from bng_tpu.runtime import engine as eng_mod
from bng_tpu.runtime import hostpath, verify
from bng_tpu.runtime.engine import step_rungs
from bng_tpu.runtime.ring import PyRing
from bng_tpu.telemetry import spans

import test_engine_update_path as up  # the every-stage toy engine, its
# frames and descriptors (tests/ is on the path, as for cellfiles)

RUNGS = step_rungs(verify.REAL_1M.batch)
# the stage geometries verify.py builds, at the cells' batch and slot over
# toy tables (the split sees the block's shape and nothing of a table)
GEOMS = {name: g._replace(
    sub_nbuckets=256, side_nbuckets=64, nat_sessions_nbuckets=256,
    sub_nat_nbuckets=64, max_pools=4,
    **{k: 64 for k in ("pppoe_nbuckets", "v6_nbuckets", "qinq_nbuckets",
                       "route_nbuckets", "tap_nbuckets") if getattr(g, k)})
    for name, g in (("ipoe", verify.REAL_1M), ("pppoe", verify.REAL_1M_PPPOE),
                    ("v6", verify.REAL_1M_V6), ("qinq", verify.REAL_1M_QINQ),
                    ("edge", verify.REAL_1M_EDGE))}


@functools.lru_cache(maxsize=None)
def _pipeline_geom(name):
    return verify._engine(GEOMS[name]).geom


def _staged(seed, B, L, n):
    """A window of `n` frames staged in a `B`-lane buffer: random bytes,
    lengths over the whole of a u32 (every plane carries bits), flags."""
    rng = np.random.default_rng(seed)
    pkt = hostpath.window_buffer(B, L)
    pkt[:n] = rng.integers(0, 256, (n, L), dtype=np.uint8)
    length = np.zeros((B,), np.uint32)
    length[:n] = rng.integers(1, 1 << 32, (n,), dtype=np.uint64)
    fa = np.zeros((B,), bool)
    fa[:n] = rng.integers(0, 2, (n,)).astype(bool)
    return pkt, length, fa


def test_rungs_are_the_cells_and_a_blocks_rows_name_its_lanes():
    assert RUNGS == (128, 1024, 8192)
    assert [hostpath.window_meta_rows(b, 1536) for b in RUNGS] == [1, 4, 27]
    for width in (64, 512, 1536, 2048):
        lanes = list(range(1, 300)) + [1024, 2048, 8191, 8192]
        assert [hostpath.window_lanes(hostpath.window_rows(b, width), width)
                for b in lanes] == lanes
    with pytest.raises(ValueError):
        hostpath.window_lanes(1, 1536)  # a block has a row of meta


@pytest.mark.parametrize("b", RUNGS)
@pytest.mark.parametrize("stages", sorted(GEOMS))
def test_the_split_inside_the_step_returns_what_was_staged(stages, b,
                                                           monkeypatch):
    g = GEOMS[stages]
    B, L = g.batch, g.pkt_slot
    assert (B, L) == (8192, 1536)
    seen = {}

    def pipeline_step(tables, pkt, length, from_access, geom, now_s, now_us):
        seen["geom"] = geom
        return pkt, length, from_access, now_s + now_us

    monkeypatch.setattr(eng_mod, "pipeline_step", pipeline_step)
    # the step as `_pipeline_jit` wraps it, outside its cache: this one
    # calls the spy
    step = eng_mod._pipeline_jit.__wrapped__(_pipeline_geom(stages))
    n = b - b // 3  # lanes n..b are inert, rows beyond b stay home
    pkt, length, fa = _staged(5100 + b, B, L, n)
    want = (pkt[:b].copy(), length[:b].copy(), fa[:b].copy())

    block = hostpath.seal_window(pkt[:b], length[:b], fa[:b])
    # sealed in place: a contiguous prefix of the staging buffer
    assert block.shape == (hostpath.window_rows(b, L), L)
    assert block.ctypes.data == pkt.ctypes.data and block.base is pkt.base
    assert block.flags.c_contiguous

    got_pkt, got_len, got_fa, clock = step((), block, np.uint32(3),
                                           np.uint32(4))
    assert seen["geom"] == _pipeline_geom(stages)
    assert int(clock) == 7
    for got, staged in zip((got_pkt, got_len, got_fa), want):
        got = np.asarray(got)
        assert got.dtype == staged.dtype and got.shape == staged.shape
        assert np.array_equal(got, staged)
    assert not np.asarray(got_len)[n:].any() and not np.asarray(got_fa)[n:].any()


FOREIGN = {
    "plain-matrix": lambda: np.ones((16, 64), np.uint8),
    "not-the-head": lambda: hostpath.window_buffer(32, 64)[4:20],
    "no-room-behind": lambda: hostpath.window_buffer(8, 64).base[:10],
    "another-dtype-behind": lambda: np.ones((32, 16), np.uint32).view(
        np.uint8)[:16],
}


@pytest.mark.parametrize("kind", sorted(FOREIGN))
def test_a_matrix_that_heads_no_staging_buffer_is_copied(kind):
    pkt = FOREIGN[kind]()
    pkt[:] = 7
    whole = pkt.base if pkt.base is not None else pkt
    before = whole.copy()
    b = pkt.shape[0]
    length = np.arange(b, dtype=np.uint32) * 70_001
    fa = np.arange(b) % 3 == 0
    block = hostpath.seal_window(pkt, length, fa)
    assert not np.shares_memory(block, whole)
    assert np.array_equal(whole, before)  # nothing written behind it
    got = jax.jit(eng_mod.split_window)(block)
    for x, y in zip(got, (pkt, length, fa)):
        assert np.array_equal(np.asarray(x), y)


# ---------------------------------------------------------------------------
# one placement a batch, on every road, of the staging buffer's own rows
# ---------------------------------------------------------------------------

@pytest.fixture
def sealed(monkeypatch):
    """Every block sealed from now on: (the block, the matrix it was
    sealed from)."""
    blocks = []
    real = hostpath.seal_window

    def spy(pkt, length, fa):
        block = real(pkt, length, fa)
        blocks.append((block, pkt))
        return block

    monkeypatch.setattr(hostpath, "seal_window", spy)
    return blocks


def _in_place(sealed) -> bool:
    return all(block.ctypes.data == pkt.ctypes.data for block, pkt in sealed)


# a known subscriber's DISCOVER and frames of its one known flow: nothing
# is punted, so no table turns dirty and a drain places nothing of its own
FLOW = packets.tcp_packet(up.MAC, up.SERVER_MAC, up.IP, up.DST, 5555, 443,
                          b"x" * 64)
CLEAN = [up.FRAMES[0], FLOW]
ROADS = ("dispatch_step", "dispatch_bulk", "express_aot",
         "express_aot_own_device", "process", "process_ring",
         "process_ring_pipelined", "scheduler_poll")


@pytest.mark.parametrize("road", ROADS)
def test_a_dispatch_makes_one_placement(road, sealed):
    e = up.make_engine()
    B, L = up.B, up.L
    desc = up.descriptors(up.DHCP_FRAMES)
    device = jax.devices()[1] if road == "express_aot_own_device" else None
    if road.startswith("express_aot"):
        exe = e.compile_express_aot(up.XB, device)
    ring = PyRing(nframes=64, frame_size=1024, depth=16)
    sched = None
    if road == "scheduler_poll":
        from bng_tpu.runtime.scheduler import SchedulerConfig, TieredScheduler

        sched = TieredScheduler(e, SchedulerConfig(
            bulk_batch=B, bulk_depth=2, express_batch=up.XB,
            express_device_index=-1), clock=lambda: float(up.T0))

    def once():
        if road == "dispatch_step":
            pkt, length = e._pack_frames(CLEAN, B)
            e._dispatch_step(pkt, length, np.ones((B,), bool),
                             len(CLEAN), up.NOW_S, up.NOW_US)
        elif road == "dispatch_bulk":
            pkt, length = e._pack_frames(CLEAN, B)
            e.dispatch_scheduled_bulk(pkt, length, np.ones((B,), bool),
                                      float(up.T0),
                                      e.dhcp_replica(jax.numpy.copy))
        elif road.startswith("express_aot"):
            e.run_express_aot(exe, desc, float(up.T0), device)
        elif road == "process":
            e.process(CLEAN, True, float(up.T0))
        elif road == "scheduler_poll":
            # a full batch of data frames: the bulk lane closes it in this
            # poll (a DISCOVER would ride express)
            for _ in range(B):
                sched.submit(FLOW)
            sched.poll()
            sched.flush()
        else:
            for f in CLEAN:
                assert ring.rx_push(f, from_access=True)
            getattr(e, road)(ring, now=float(up.T0))

    try:
        once()  # builds what it builds
        e.flush_pipeline()
        del sealed[:]
        with spans.armed() as tr:
            before = e.stats.batches
            once()
            once()
            e.flush_pipeline()
            x = tr.sums()["xfer"]
    finally:
        ring.close()
    batches = e.stats.batches - before
    assert batches == 2
    assert x["upload_calls"] == batches
    if road.startswith("express_aot"):
        assert sealed == []  # the descriptor crosses alone
        assert x["upload_bytes"] == batches * up.XB * XD_WORDS * 4
    else:
        assert len(sealed) == batches and _in_place(sealed)
        assert x["upload_bytes"] == batches * L * hostpath.window_rows(B, L)
