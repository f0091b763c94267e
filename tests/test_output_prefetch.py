"""A retired step's outputs are already on the host (PR 43).

`Engine._start_host_copies` starts, at dispatch, the device-to-host copy of
every output the retire will read; the retire's `np.asarray` calls stay
where they were and find the bytes there. Nothing a program computes or a
loop does changes, so with the helper stubbed to start nothing (the
parent's behaviour) every loop gives the same bytes; the Tracer's
`prefetch_calls` / `fetch_calls` say which path ran. Tiny tables, CPU: no
number from here is a device metric.

(a) the four engine-loop configurations' tiny stand-ins (W, P, D, Q) and
    the scheduler's, provisioned and fed by their benchmark kits: shipped
    against stubbed, byte for byte
(b) `prefetch_calls` a step is the number of on-device leaves the retire
    reads for the stage set, `fetch_calls` 0 there (the sharded loop's
    are tests/test_sharded_prefetch.py's, PR 44)
(c) the fail-closed branch and `flush_pipeline` retire FIFO with a
    prefetched result in flight
(d) a result that is never retired leaks nothing
(e) the synchronous facades return what they returned
"""

import argparse
import functools
import gc
import os
import sys
import weakref

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import app as applib  # noqa: E402
from bng_tpu import cli  # noqa: E402
from bng_tpu.chaos.faults import FaultPlan, FaultSpec, armed  # noqa: E402
from bng_tpu.control import dhcp_codec  # noqa: E402
from bng_tpu.runtime.engine import Engine, FaultInjectedError  # noqa: E402
from bng_tpu.runtime.ring import PyRing  # noqa: E402
from bng_tpu.telemetry import spans  # noqa: E402

import test_step_rungs as rungs  # noqa: E402  (the engine-level recipe)

SEED = 2**31 + 43
T0 = 1_753_000_000
WIRE = ["--pool-cidr", "10.0.0.0/11", "--batch-size", "256",
        "--synthetic-subs", "1", "--max-subscribers", "4096",
        "--max-nat-sessions", "512", "--max-nat-subscribers", "128"]
PPPOE = ["--pppoe-enabled", "--pppoe-auth", "none"]
SIZES = {"subscribers": 4096, "nat_subscribers": 128,
         "flows_per_nat_subscriber": 2}
# cell -> (the configuration it is the tiny stand-in of, its argv, the
# on-device leaves one fused step's retire reads: verdict, out_pkt,
# out_len, the violation and punt flags, and a stats block a stage: dhcp,
# nat, qos, spoof, garden, then pppoe / v6 / qinq where compiled in)
CELLS = {
    "wire": ("ipoe-cgnat-1M-wire", WIRE, 10),
    "pppoe": ("pppoe-cgnat-1M-wire", WIRE + PPPOE, 11),
    "dualstack": ("dualstack-cgnat-1M-wire", WIRE + ["--ipv6-fastpath"], 11),
    "qinq": ("qinq-pppoe-cgnat-1M-wire", WIRE + PPPOE + ["--qinq-enabled"], 12),
    "sched": ("ipoe-cgnat-1M", WIRE + ["--scheduler-enabled"], 10),
}
BEATS, SIDE = 6, 48  # six windows of 48 frames a side: the 128 rung of 256
STATS = ("dhcp", "nat", "qos", "spoof", "garden", "pppoe", "edge", "v6", "qinq")


def _nothing(self, res):
    """The helper stubbed: no copy is started, as on the parent."""


def _build(cfg, clock):
    parser = argparse.ArgumentParser()
    cli._add_run_flags(parser)
    app = cli.BNGApp(cli._config_from_args(
        parser.parse_args(applib.run_argv(cfg))), clock=clock)
    app.config.synthetic_subs = 0  # the test pushes every frame
    return app


@functools.lru_cache(maxsize=None)
def _serve(cell: str, shipped: bool) -> dict:
    """The cell's seeded windows through `app.drive_once()` on a clock the
    test owns; everything a loop hands back or counts."""
    base, argv, _reads = CELLS[cell]
    cfg = applib.load_named("configs", base)
    cfg.update(name="tiny-" + cell, argv=argv, sizes=dict(SIZES))
    cfg["nat_public_ips"]["count"] = 4
    kit = applib.load_kit(cfg)
    lay = kit.Layout(cfg, SEED)
    now = [float(T0)]
    was = Engine._start_host_copies
    if not shipped:
        Engine._start_host_copies = _nothing
    app = _build(cfg, lambda: now[0])
    try:
        prov = kit.provision(app, lay)
        mix = dict(applib.load_named("traffic", "flood-64B"),
                   pool_frames=2 * BEATS * SIDE, dhcp_share=0.05)
        traffic = kit.Traffic(mix, lay, prov, app, SEED, 0.0)
        ring, engine = app.components["ring"], app.components["engine"]
        verdicts, replies, pushed = [], [], 0
        if applib.shape(app) == "engine":
            complete = ring.complete

            def spy(verdict, out, out_len, n):
                verdicts.append(np.asarray(verdict[:n]).tobytes())
                return complete(verdict, out, out_len, n)

            ring.complete = spy

        def beat():
            now[0] += 0.01
            app.drive_once()
            replies.extend(ring.tx_pop_batch())
            while (got := ring.fwd_pop()) is not None:
                replies.append(got)

        with spans.armed(keep_events=1 << 12) as tr:
            for k in range(BEATS):
                for s in traffic.streams:
                    part = s.frames[k * SIDE:(k + 1) * SIDE]
                    assert part and ring.rx_push_batch(
                        part, from_access=s.from_access) == len(part)
                    pushed += len(part)
                beat()
            quiet = 0
            for _ in range(200):
                beat()
                quiet = quiet + 1 if applib.idle(app) else 0
                if quiet >= 3:
                    break
            assert quiet >= 3
            sums = tr.sums()
            sched = app.components.get("scheduler")
            snap = sched.stats_snapshot() if sched is not None else None
        st = engine.stats
        return {
            "replies": [(bytes(f), int(fl)) for f, fl in replies],
            "verdicts": verdicts,
            "counts": (st.tx, st.fwd, st.dropped, st.passed, st.slow_errors),
            "stats": {k: np.asarray(getattr(st, k)).copy() for k in STATS},
            "ring": dict(ring.stats()),
            "batches": st.batches,
            "pushed": pushed,
            "xfer": sums["xfer"],
            "sched": snap,
        }
    finally:
        Engine._start_host_copies = was
        app.close()


# -- (a) shipped against stubbed, byte for byte --------------------------------

@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_loop_gives_the_same_bytes_with_the_copies_started_and_without(cell):
    got, want = _serve(cell, True), _serve(cell, False)
    pushed = want["pushed"]
    assert 1.8 * BEATS * SIDE < pushed == got["pushed"]
    tx, fwd, dropped, passed, slow_errors = want["counts"]
    # the windows are the ones the claim needs: every frame left with a
    # verdict, device DHCP replies and NAT both ways among them
    assert tx + fwd + dropped + passed == pushed and slow_errors == 0
    assert tx > 0 and fwd > pushed // 2
    assert want["stats"]["dhcp"].sum() > 0 and want["stats"]["nat"].sum() > 0
    if cell in ("pppoe", "qinq"):
        assert want["stats"]["pppoe"].sum() > 0
    if cell == "dualstack":
        assert want["stats"]["v6"].sum() > 0
    if cell == "qinq":
        assert want["stats"]["qinq"].sum() > 0
    assert got["counts"] == want["counts"]
    if cell == "sched":
        # its bulk retire is by readiness, so the order replies of the two
        # lanes leave in is the machine's; each reply is not
        assert sorted(got["replies"]) == sorted(want["replies"])
    else:
        assert got["replies"] == want["replies"]
        assert got["verdicts"] == want["verdicts"] and len(want["verdicts"]) > 1
        assert got["batches"] == want["batches"]
    assert len(want["replies"]) == tx + fwd
    for name in STATS:  # every EngineStats block
        assert got["stats"][name].tobytes() == want["stats"][name].tobytes(), name
    assert got["ring"] == want["ring"] and want["ring"]["rx"] == pushed


# -- (b) which path ran --------------------------------------------------------

@pytest.mark.parametrize("cell", sorted(CELLS))
def test_prefetch_calls_a_step_are_the_leaves_its_retire_reads(cell):
    reads = CELLS[cell][2]
    got, stub = _serve(cell, True), _serve(cell, False)
    if cell != "sched":
        steps = got["batches"]
        assert steps >= BEATS
        # every output a retire reads was started at dispatch: its read
        # crossed nothing; stubbed, the same reads are the parent's crossings
        assert got["xfer"]["prefetch_calls"] == reads * steps
        assert got["xfer"]["fetch_calls"] == got["xfer"]["fetch_bytes"] == 0
        assert stub["xfer"]["prefetch_calls"] == 0
        assert stub["xfer"]["fetch_calls"] == reads * steps
        assert stub["xfer"]["fetch_bytes"] > 0
    else:
        # the bulk lane's fused step through `dispatch_scheduled_bulk`; the
        # express program's forced retire stays two crossings (its verdict
        # block, and its dhcp block in `_fold_stats`)
        bulk = got["sched"]["bulk"]["batches"]
        express = got["sched"]["express"]["batches"]
        assert bulk > 0 and express > 0
        assert got["xfer"]["prefetch_calls"] == reads * bulk
        assert got["xfer"]["fetch_calls"] == 2 * express
        assert stub["xfer"]["prefetch_calls"] == 0
        assert stub["xfer"]["fetch_calls"] == (
            reads * stub["sched"]["bulk"]["batches"]
            + 2 * stub["sched"]["express"]["batches"])
        assert got["sched"]["trace"]["xfer"]["prefetch_calls"] == \
            got["xfer"]["prefetch_calls"]
    if cell != "sched":  # (how the lanes batch is the machine's)
        assert got["xfer"]["upload_calls"] == stub["xfer"]["upload_calls"]
        assert got["xfer"]["upload_bytes"] == stub["xfer"]["upload_bytes"]


def test_the_tracer_counts_what_it_is_handed_and_forgets_it_at_the_read():
    a, b, host = jax.numpy.arange(4), jax.numpy.arange(8), np.arange(4)
    assert spans._ZERO_SUMS["xfer"]["prefetch_calls"] == 0
    spans.prefetched([a])  # disarmed: nothing
    with spans.armed() as tr:
        spans.prefetched([a])
        spans.fetched(spans.t(), a, b, host, None)  # b alone crossed
        assert tr.sums()["xfer"] == {
            "upload_calls": 0, "upload_bytes": 0, "fetch_calls": 1,
            "fetch_bytes": b.nbytes, "prefetch_calls": 1}
        spans.fetched(spans.t(), a)  # a second read of `a` is a crossing
        assert tr.sums()["xfer"]["fetch_calls"] == 2
        assert tr.stage_hist(spans.FETCH).n == 2  # the laps stay
    assert spans.trace_sums()["xfer"]["prefetch_calls"] == 1


# -- (c) FIFO with a prefetched result in flight --------------------------------

def _ring_of(engine, windows):
    ring = PyRing(nframes=256, frame_size=1024, depth=64)
    for win in windows:
        for frame, from_access in win:
            assert ring.rx_push(frame, from_access=from_access)
    return ring


def _pop(ring) -> list:
    out = []
    for one in (ring.tx_pop, ring.fwd_pop):
        while (got := one()) is not None:
            out.append(got[0])
    return out


def _plain_windows(flows, ks):
    return [rungs._window("plain", flows, k) for k in ks]


def test_a_dispatch_that_raises_retires_the_prefetched_window_first():
    engine, flows = rungs._stack("plain")
    first, second = _plain_windows(flows, (0, 1))
    want_engine, _ = rungs._stack("plain")
    want_ring = _ring_of(want_engine, [first])
    ring = PyRing(nframes=256, frame_size=1024, depth=64)
    try:
        want_engine.process_ring(want_ring, now=float(T0))
        want = _pop(want_ring)
        policed = want_ring.stats()["drop"]
        assert len(want) >= 10 and policed > 0

        with spans.armed() as tr:
            for frame, fa in first:
                assert ring.rx_push(frame, from_access=fa)
            assert engine.process_ring_pipelined(ring, now=float(T0)) == 0
            assert engine._inflight is not None
            reads = tr.sums()["xfer"]["prefetch_calls"]
            assert reads == 9  # no garden on this engine: four stats blocks
            for frame, fa in second:
                assert ring.rx_push(frame, from_access=fa)
            plan = FaultPlan(1, [FaultSpec("engine.dispatch", "fail", at_hit=1)])
            with armed(plan, log=False), pytest.raises(FaultInjectedError):
                engine.process_ring_pipelined(ring, now=T0 + 0.5)
            # the older window retired whole and first; the failed one was
            # dropped into its own window; nothing is left in flight
            assert engine._inflight is None
            assert _pop(ring) == want
            assert ring.stats()["drop"] == policed + len(second)
            x = tr.sums()["xfer"]
            assert (x["prefetch_calls"], x["fetch_calls"]) == (reads, 0)
            assert not tr._prefetched and len(tr._free) == tr.OPEN_SLOTS
        assert engine.flush_pipeline() == 0
    finally:
        ring.close()
        want_ring.close()


def test_flush_pipeline_retires_what_is_in_flight_in_order():
    engine, flows = rungs._stack("plain")
    sync_engine, _ = rungs._stack("plain")
    wins = _plain_windows(flows, (0, 1, 2))
    ring, sync_ring = PyRing(nframes=256, frame_size=1024, depth=64), None
    try:
        got, want = [], []
        for k, win in enumerate(wins):
            for frame, fa in win:
                assert ring.rx_push(frame, from_access=fa)
            engine.process_ring_pipelined(ring, now=T0 + 0.5 * k)
            got.append(_pop(ring))
        assert engine._inflight is not None and got[0] == []
        assert engine.flush_pipeline() == len(wins[-1])
        got.append(_pop(ring))
        assert engine._inflight is None and engine.flush_pipeline() == 0
        sync_ring = PyRing(nframes=256, frame_size=1024, depth=64)
        for k, win in enumerate(wins):
            for frame, fa in win:
                assert sync_ring.rx_push(frame, from_access=fa)
            sync_engine.process_ring(sync_ring, now=T0 + 0.5 * k)
            want.append(_pop(sync_ring))
        # window k's replies leave at call k + 1, the last at the flush
        assert got[1:] == want and all(want)
        for name in ("dhcp", "nat", "qos", "spoof"):
            assert (getattr(engine.stats, name)
                    == getattr(sync_engine.stats, name)).all(), name
    finally:
        ring.close()
        if sync_ring is not None:
            sync_ring.close()


# -- (d) a result that is never retired ----------------------------------------

def test_a_step_left_in_flight_leaks_nothing_and_raises_nothing():
    engine, flows = rungs._stack("plain")
    ring = _ring_of(engine, _plain_windows(flows, (0,)))
    with spans.armed() as tr:
        assert engine.process_ring_pipelined(ring, now=float(T0)) == 0
        res = engine._inflight[1]
        outs = [weakref.ref(getattr(res, name)) for name in
                ("verdict", "out_pkt", "dhcp_stats")]
        assert len(tr._prefetched) == 9
        # the engine goes with its window never retired (a closed app)
        del res
        engine._inflight = None
        del engine
        gc.collect()
        assert [r() for r in outs] == [None] * 3
        assert not tr._prefetched  # held weakly: nothing to forget
        x = tr.sums()["xfer"]
        assert (x["prefetch_calls"], x["fetch_calls"], x["fetch_bytes"]) == (9, 0, 0)
    ring.close()


# -- (e) the synchronous facades -----------------------------------------------

def _facade(name: str, engine, flows):
    """One call of a sync facade over a mixed window (`process_dhcp`: the
    window's DHCP frames and one data frame, which falls out as slow)."""
    win = rungs._window("plain", flows)
    if name == "process":
        return engine.process([f for f, _ in win],
                              from_access=[fa for _, fa in win], now=float(T0))
    if name == "process_dhcp":
        frames = [rungs._dhcp(i, dhcp_codec.DISCOVER, 0x4300 + i)
                  for i in range(4)] + [win[2][0]]
        return engine.process_dhcp(frames, now=float(T0))
    ring = _ring_of(engine, [win])
    try:
        n = engine.process_ring(ring, now=float(T0))
        return n, _pop(ring), dict(ring.stats())
    finally:
        ring.close()


@pytest.mark.parametrize("name", ["process", "process_dhcp", "process_ring"])
def test_a_sync_facade_returns_what_it_returned(name, monkeypatch):
    engine, flows = rungs._stack("plain")
    with spans.armed() as tr:
        got = _facade(name, engine, flows)
        x = tr.sums()["xfer"]
    # the copy is started and read in the same call: verdict, out_pkt,
    # out_len and the dhcp block of the DHCP-only program (its flags and
    # other blocks are host arrays), nine leaves of the fused step
    assert x["prefetch_calls"] == (4 if name == "process_dhcp" else 9)
    assert x["fetch_calls"] == 0
    monkeypatch.setattr(Engine, "_start_host_copies", _nothing)
    stub_engine, stub_flows = rungs._stack("plain")
    assert stub_flows == flows
    with spans.armed() as tr:
        want = _facade(name, stub_engine, stub_flows)
        assert tr.sums()["xfer"]["prefetch_calls"] == 0
    assert got == want
    if name == "process":
        assert len(got["tx"]) == 2 and len(got["fwd"]) >= 6 and got["dropped"]
    if name == "process_dhcp":
        assert len(got["tx"]) == 4 and [i for i, _ in got["slow"]] == [4]
    for block in ("dhcp", "nat", "qos", "spoof"):
        assert (getattr(engine.stats, block)
                == getattr(stub_engine.stats, block)).all(), block
