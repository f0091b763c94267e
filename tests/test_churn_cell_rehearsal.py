"""CPU rehearsal of `churn-cgnat-1M-wire.flood-64B-newflows`: the
configuration, its kit and its traffic dropped into a temporary copy of the
benchmark at 4,096 subscribers, 1,024 of them behind NAT, through `run.py`'s
own loop on both one-chip loops (the engine's own over the native ring, as
the cell runs it, and the scheduler's over `PyRing`). A thousand flows are
opened in the window: each first packet leaves translated by the mapping the
plain reference allocated before the run, its second packet and the reply
over its session follow, `check` balances the punts against the declaration
at limit 0, and the read-back finds every session on the host.

**The parent's behaviour is the control.** With the forwarding patched out
(packet 1 consumed, as every tree before PR 53 did) the run does not pass:
it ends in `run.py: warm-up lost N frames`; with a warm-up that holds no
first packet it is not `correct` by `lost_frames` and by nothing else; with
the declaration short by one frame it is not `correct` by `punted_frames`.
No number from here is a device metric.
"""

import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import app as applib  # noqa: E402
from benchmark.lib import layers  # noqa: E402
from bng_tpu.runtime import newflow  # noqa: E402

REAL = "churn-cgnat-1M-wire.flood-64B-newflows"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
SIZES = {"subscribers": 4096, "nat_subscribers": 1024,
         "flows_per_nat_subscriber": 2, "follow_up_gap_frames": 2048}
ARGV = ["--pool-cidr", "10.0.0.0/11", "--batch-size", "256",
        "--synthetic-subs", "1", "--max-subscribers", "4096",
        "--max-nat-sessions", "8192", "--max-nat-subscribers", "2048"]
# loop -> (cell, configuration, the argv that selects it)
LOOPS = {"engine": ("tiny-churn.flood", "tiny-churn", ARGV),
         "scheduler": ("tiny-churn-sched.flood", "tiny-churn-sched",
                       ARGV + ["--scheduler-enabled"])}
# a CPU runs these loops at the chip's own rate (some 50 kpps): 1.5 s of
# three times that, so the pool cannot wrap
POOL = 262144
# The per-layer file ISSUE 54 asked for. PR 54 could not add it to
# `benchmark/layers/`: `tests/benchmark/test_churn_stand_in.py`, which only a
# `benchmark` PR may edit, holds the cell's files to a set of names (PERF.md
# section 7 row 1 has it for that PR). Dropped into the copy here as that PR
# will add it, so that the counter it reads is held to a value by a test.
FLOWS_PER_CREATE = {
    "name": "churn.flows_per_create", "unit": "flows", "better": "higher",
    "source": "program_counter", "layer": "engine (runtime/engine.py)",
    "moves": "served_kpps", "cells": [REAL],
    "read": {"kind": "counter", "path": "engine.trace.newflow_admitted",
             "per": "engine.trace.newflow_creates"},
    "note": "flows admitted over the create batches that opened at least one "
            "flow (NewFlows.punt_many -> NATManager.handle_new_flows, "
            "tele.new_flows(creates=1)): about churn.new_flows_per_step where "
            "a retire's punts go in one batch, 1 on the one-by-one path; on a "
            "program without the counter the divisor is absent and the metric "
            "is left out of the line"}


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def cell_dir(tmp_path_factory):
    top = tmp_path_factory.mktemp("churn")
    bdir = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bdir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    real = {w["name"]: w for w in bench["workloads"]}[REAL]
    base = applib.load_named("configs", real["config"], bdir)
    assert base["kit"] == "churn"
    mix = applib.load_named("traffic", real["traffic"], bdir)
    mix.update(name="tiny-flood-newflows", pool_frames=POOL, dhcp_share=0.05,
               warmup_frames=400)
    _write(os.path.join(bdir, "traffic", "tiny-flood-newflows.json"), mix)
    listed = [m for m in layers.layer_files(bdir) if REAL in m["cells"]]
    assert len(listed) == 9
    listed.append(json.loads(json.dumps(FLOWS_PER_CREATE)))
    for cell, name, argv in LOOPS.values():
        cfg = dict(base, name=name, argv=argv, sizes=dict(SIZES))
        cfg["nat_public_ips"] = dict(base["nat_public_ips"], count=32)
        _write(os.path.join(bdir, "configs", name + ".json"), cfg)
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": "tiny-flood-newflows",
                                   "chips": 1, "why": "test"})
        for m in bench["end_to_end"]:
            if REAL in m.get("workloads", ()):
                m["workloads"].append(cell)
        for m in listed:
            m["cells"].append(cell)
    for m in listed:
        _write(os.path.join(bdir, "layers", m["name"] + ".json"), m)
    _write(os.path.join(top, "BENCHMARK.json"), bench)
    return bdir


def _run(cell_dir, capsys, loop, seed, *extra, seconds="1.5"):
    capsys.readouterr()
    rc = bench_run.main(["--workload", LOOPS[loop][0], "--seed", str(seed),
                         "--seconds", seconds, "--bench-dir", cell_dir,
                         *extra])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    sel = [ln for ln in out if ln.startswith("selectors: ")][0]
    assert sel.endswith("ring=NativeRing loop=engine" if loop == "engine"
                        else "ring=PyRing")
    assert any(ln.startswith("cell: ") and ln.endswith("kit=churn")
               for ln in out)
    return json.loads(out[-1]), out


def _line(out, head):
    return [ln for ln in out if ln.startswith(head)][0]


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_the_cell_rehearses_traced_on_both_one_chip_loops(cell_dir, capsys,
                                                          loop):
    res, out = _run(cell_dir, capsys, loop, 2**31 + 53, "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert all(c["value"] == 0 for c in res["compared"].values())
    told = _line(out, "check declared to the host: ").split()
    assert told[5:7] == ["0", "DHCP,"] and int(told[7]) > 50
    sample = _line(out, "check sample: ")
    assert " 0 first packets" not in sample and " 0 replies" not in sample
    assert "flows opened, each read back" in sample
    assert "in the window 0 " in _line(out, "programs built or loaded: ")
    got = res["metrics"]
    if loop == "engine":  # the cell's own loop: its counters under engine.trace
        assert got["churn.drain_built_per_step"]["value"] > 0
        assert got["churn.new_flows_per_step"]["value"] > 0
        assert got["churn.punt_us_per_flow"]["value"] > 0
        assert got["churn.requeued_again_per_step"]["value"] == 0
        # one create a retire that punted (PR 54): as many flows a batch as
        # a step admits, over the steps that punted at all
        assert (got["churn.flows_per_create"]["value"]
                >= max(got["churn.new_flows_per_step"]["value"], 1.0))
    # some 2% of the data frames served, each declared once
    served = int(_line(out, "window: ").split("pushed ")[1].split(",")[0])
    assert 0.002 * served < int(told[7]) < 0.03 * served


def _consume_packet_one(monkeypatch):
    """The parent's behaviour: the session is created and the frame that
    asked for it is consumed (no hold, no second pass, no counted drop)."""
    def punt_many(self, frames, flags, now, pppoe, *, on_error, hold=None):
        self._open(frames, now, pppoe)
        # "kept": nothing counts them, and nothing sends them round
        return [True] * len(frames)

    monkeypatch.setattr(newflow.NewFlows, "punt_many", punt_many)


def test_with_packet_one_consumed_the_warm_up_loses_frames(cell_dir, capsys,
                                                           monkeypatch):
    """What the parent commit does with this cell's files over it: the
    warm-up's first packets are consumed, its drain finds frames
    outstanding, and the run ends before any window."""
    _consume_packet_one(monkeypatch)
    with pytest.raises(SystemExit, match=r"run.py: warm-up lost \d+ frames"):
        bench_run.main(["--workload", LOOPS["engine"][0], "--seed", "7",
                        "--seconds", "1.5", "--bench-dir", cell_dir])
    capsys.readouterr()


def test_with_packet_one_consumed_the_window_is_not_correct_by_lost_frames(
        cell_dir, capsys, monkeypatch):
    """Were the warm-up to pass (here: a warm-up stream that holds no first
    packet), each consumed frame keeps one of the outstanding places and
    the run is not `correct` by `lost_frames`; the sample then holds no
    first packet, which the kinds say; every other count balances (the
    sessions were created, so the follow-ups are translated and the
    read-back is sound)."""
    import copy

    _consume_packet_one(monkeypatch)
    real_load = applib.load_kit

    def load_kit(config, bench_dir=applib.BENCH_DIR):
        kit = real_load(config, bench_dir)

        class Traffic(kit.Traffic):
            def __init__(self, mix, lay, prov, app, seed, seconds, stream=0):
                if stream == 1:  # the warm-up opens no flow
                    lay = copy.copy(lay)
                    lay.new_flow_share = 0.0
                super().__init__(mix, lay, prov, app, seed, seconds, stream)

        kit.Traffic = Traffic
        return kit

    monkeypatch.setattr(applib, "load_kit", load_kit)
    res, out = _run(cell_dir, capsys, "engine", 8)
    assert res["correct"] is False, out[-14:]
    got = {k: c["value"] for k, c in res["compared"].items()}
    assert got.pop("lost_frames") > 50
    assert got.pop("sample_kinds_missing") == 1
    assert "of the first packets of new flows" in _line(out, "check sample: ")
    assert all(v == 0 for v in got.values()), got


def test_a_declaration_short_by_one_frame_is_not_correct_by_punted_frames(
        cell_dir, capsys, monkeypatch):
    real_check = bench_run.check

    def check(app, kit, traffic, loop, c0, c1, seed):
        first = np.nonzero(traffic.to_host)[0]
        sent = first[traffic.sent_once(4)][0]  # one the ring accepted
        traffic.to_host[sent] = False
        return real_check(app, kit, traffic, loop, c0, c1, seed)

    monkeypatch.setattr(bench_run, "check", check)
    res, out = _run(cell_dir, capsys, "engine", 9)
    assert res["correct"] is False
    got = {k: c["value"] for k, c in res["compared"].items()}
    assert got.pop("punted_frames") == 1
    assert all(v == 0 for v in got.values()), got


@pytest.mark.parametrize("control", bench_run.CONTROLS)
def test_both_controls_fail_the_cell(cell_dir, capsys, control):
    res, out = _run(cell_dir, capsys, "engine", 10, "--control", control)
    assert res["correct"] is False, (control, out[-12:])
    assert res["compared"]["sampled_replies_differing"]["value"] > 0
    assert res["compared"]["lost_frames"]["value"] == 0
    assert res["compared"]["punted_frames"]["value"] == 0


def test_a_pool_that_wraps_is_refused(cell_dir, capsys):
    """A first packet is new once: a stream whose `sent` passed its length
    is a kind no sample holds (`sample_kinds_missing`), whatever else the
    run counted."""
    mix = applib.load_named("traffic", "tiny-flood-newflows", cell_dir)
    _write(os.path.join(cell_dir, "traffic", "tiny-flood-newflows.json"),
           dict(mix, pool_frames=16384))
    try:
        res, out = _run(cell_dir, capsys, "engine", 11)
    finally:
        _write(os.path.join(cell_dir, "traffic", "tiny-flood-newflows.json"),
               mix)
    assert res["correct"] is False
    assert "pool wrapped" in _line(out, "check sample: ")
    assert res["compared"]["sample_kinds_missing"]["value"] >= 1
    assert res["compared"]["lost_frames"]["value"] == 0


def test_the_reference_allocates_by_the_sources_rule():
    """`Plain.open`: the next port of the subscriber's block from where
    provisioning stopped, skipping ports in use at that protocol, wrapping
    once; the same internal endpoint keeps its endpoint; a full block
    refuses. Held against the program's `NATManager` on the same flows."""
    from bng_tpu.control.nat import NATManager

    kit = applib.load_kit({"kit": "churn"})
    pub = [0xC6120000 + i for i in range(2)]
    nat = NATManager(public_ips=pub, ports_per_subscriber=8,
                     port_range=(1024, 1024 + 8 * 3 - 1),
                     sessions_nbuckets=256, sub_nat_nbuckets=64)
    subs = (0x0A100000 + np.arange(5)).astype(np.uint32)
    assert nat.bulk_allocate_nat(subs, 1) == 5
    j, f = np.repeat(np.arange(5), 2), np.tile(np.arange(2), 5)
    flows = (subs[j], np.full(10, 0x5DB80001, np.uint32),
             (40000 + f).astype(np.uint32), np.full(10, 443, np.uint32),
             np.where(f % 2 == 0, 17, 6).astype(np.uint32))
    nat_ip, nat_port, ok = nat.bulk_flows(*flows, pkt_len=64, now=1)
    assert ok.all()
    plain = kit.Plain(*flows, nat_ip, nat_port, ports_per_block=8)
    src = int(subs[3])
    _row, pub_ip, start, end = plain.block_of(src)
    assert (pub_ip, start, end) == (
        nat.blocks[src]["public_ip"], nat.blocks[src]["port_start"],
        nat.blocks[src]["port_end"])
    # six ports left in a block of eight: each new endpoint takes the next
    for n in range(6):
        flow = (src, 0x5DB80002, 41000 + n, 443, 17 if n % 2 else 6)
        want = plain.open(*flow)
        assert want == (pub_ip, start + 2 + n)
        assert nat.handle_new_flow(*flow, 64, 2) == want
        assert plain.open(*flow) == want  # idempotent
    # the same internal endpoint to another destination: the same mapping
    again = (src, 0x5DB80009, 41003, 80, 17)
    assert plain.open(*again) == (pub_ip, start + 5)
    assert nat.handle_new_flow(*again, 64, 2) == (pub_ip, start + 5)
    # the block is full at both protocols' used ports: a new UDP endpoint
    # finds the TCP-only ports (in use at TCP, free at UDP) by wrapping
    wrapped = (src, 0x5DB80002, 42000, 443, 17)
    got = plain.open(*wrapped)
    assert got == nat.handle_new_flow(*wrapped, 64, 2) and got is not None
    assert start <= got[1] <= end
    # ... until none is left at that protocol: refused by both
    refused = None
    for n in range(8):
        flow = (src, 0x5DB80002, 43000 + n, 443, 17)
        a, b = plain.open(*flow), nat.handle_new_flow(*flow, 64, 2)
        assert a == b
        refused = refused or (a is None)
    assert refused and nat.exhausted["port"] > 0
    # the way back is injective over everything opened
    assert len(plain.opened_back) == len(plain.opened_eim)
    assert {(*ext, k[2]) for k, ext in plain.opened_eim.items()} == set(
        plain.opened_back)
    assert plain.open(0x0A1000FF, 1, 2, 3, 17) is None  # no block
