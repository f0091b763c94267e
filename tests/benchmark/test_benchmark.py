"""CPU rehearsal of benchmark/run.py at tiny sizes, and the contract's
shape: names, files found by name, the trace reduction on a recording,
and the `correct` check's controls. No number from here is a device
metric: the runs below report platform cpu."""

import copy
import glob
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import app as applib  # noqa: E402
from benchmark.kits import ipoe  # noqa: E402
from benchmark.lib import gen, layers, trace  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}  # the last: each number `correct` compared, and its limit

TINY_ARGV = {
    "tiny-cgnat": ["--pool-cidr", "10.0.0.0/11", "--batch-size", "256",
                   "--synthetic-subs", "1", "--scheduler-enabled",
                   "--max-subscribers", "4096", "--max-nat-sessions", "512",
                   "--max-nat-subscribers", "128"],
    "tiny-sharded": ["--pool-cidr", "10.0.0.0/11", "--batch-size", "256",
                     "--synthetic-subs", "1", "--shards", "4",
                     "--shard-nbuckets", "1024"],
}
# the loop a box with a NIC runs: the same app without the scheduler
TINY_ARGV["tiny-wire"] = [a for a in TINY_ARGV["tiny-cgnat"]
                          if a != "--scheduler-enabled"]
# 4,096 subscribers, 128 NAT subscribers, every one of them PPPoE; and the
# same 4,096 dual stack, 128 of them behind NAT
TINY_ARGV["tiny-pppoe"] = TINY_ARGV["tiny-wire"] + ["--pppoe-enabled",
                                                    "--pppoe-auth", "none"]
TINY_ARGV["tiny-dualstack"] = TINY_ARGV["tiny-wire"] + ["--ipv6-fastpath"]
# behind S-tag/C-tag pairs, the kit's default making a quarter of the NAT
# subscribers PPPoE; and tiny-sharded's four shards with the two capacities
# that size a shard's NAT tables (tiny_dir gives it 4 public addresses, one
# a shard; the `4` in its cell's name gives it four chips)
TINY_ARGV["tiny-qinq"] = TINY_ARGV["tiny-pppoe"] + ["--qinq-enabled"]
TINY_ARGV["tiny4-nat"] = TINY_ARGV["tiny-sharded"] + [
    "--max-nat-sessions", "512", "--max-nat-subscribers", "128"]
# tiny-wire with the edge stage on: a route row a subscriber over four
# upstreams, warrants on some of the 128 NAT subscribers (the kit's shares)
TINY_ARGV["tiny-multiisp"] = TINY_ARGV["tiny-wire"] + ["--edge-enabled"]
DROPIN_KIT = "ipoe-dot1q"
# a kit whose traffic declares frames for the host (`Traffic.to_host`), on
# the engine's loop and on the scheduler's: dropped-in cell -> the tiny
# configuration it is laid over
STRANGERS_KIT = "ipoe-strangers"
STRANGERS = {"tiny-strangers.flood": "tiny-wire",
             "tiny-strangers-sched.flood": "tiny-cgnat"}
BASE_OF = {"tiny-cgnat": "ipoe-cgnat-1M", "tiny-sharded": "ipoe-sharded4-1M",
           "tiny-wire": "ipoe-cgnat-1M-wire",
           "tiny-pppoe": "pppoe-cgnat-1M-wire",
           "tiny-dualstack": "dualstack-cgnat-1M-wire",
           "tiny-qinq": "qinq-pppoe-cgnat-1M-wire",
           "tiny4-nat": "ipoe-cgnat-sharded4-1M",
           "tiny-multiisp": "multiisp-li-cgnat-1M-wire"}
# tiny cell -> (the cell its layer files name, config, traffic). A layer file
# that names a cell without a stand-in here stops `tiny_dir` with a KeyError:
# a PR that adds a cell adds its stand-in to these three literals
TINY_CELLS = {
    "tiny.flood": ("cgnat-1M.flood-64B", "tiny-cgnat", "tiny-flood"),
    "tiny.renew": ("cgnat-1M.renew-under-load", "tiny-cgnat", "tiny-renew"),
    "tiny4.flood": ("sharded4-1M.flood-64B", "tiny-sharded", "tiny-flood-32"),
    "tiny-wire.flood": ("cgnat-1M-wire.flood-64B", "tiny-wire", "tiny-flood"),
    "tiny-pppoe.flood": ("pppoe-cgnat-1M-wire.flood-64B", "tiny-pppoe",
                         "tiny-flood"),
    "tiny-dualstack.flood": ("dualstack-cgnat-1M-wire.flood-64B",
                             "tiny-dualstack", "tiny-flood"),
    "tiny-qinq.flood": ("qinq-pppoe-cgnat-1M-wire.flood-64B", "tiny-qinq",
                        "tiny-flood"),
    "tiny4-nat.flood": ("cgnat-sharded4-1M.flood-64B", "tiny4-nat",
                        "tiny-flood-32"),
    "tiny-multiisp.flood": ("multiisp-li-cgnat-1M-wire.flood-64B",
                            "tiny-multiisp", "tiny-flood"),
}

# what the engine's own loop reports in each of its five cells (wire, PPPoE,
# dual stack, QinQ, multi-ISP): the `wire.*` spans and sums and the loop's counters; and those
# of them that are 0 in a sound rehearsal (no stale lane short of the pool's
# wrap, no dirty table; the device is seen starved only when a beat finds
# the ring empty)
ENGINE_LOOP = {
    "wire.dispatch_p50_us", "wire.device_p50_us", "wire.device_wait_p50_us",
    "wire.reply_us_per_frame", "wire.ring_us_per_frame",
    "wire.frames_per_step", "wire.device_starved_share",
    "wire.unattributed_share", "wire.masked_lanes_per_step",
    "engine.drain_built_per_step", "engine.drain_cached_per_step"}
ENGINE_LOOP_ZERO_OK = {"wire.device_starved_share",
                       "wire.masked_lanes_per_step",
                       "engine.drain_built_per_step"}

# The loop's generic reads, whatever the file that holds them is called: a
# cell's files are found by what lists the cell and by what each reads, so a
# merge of a kit-prefixed repeat into its original (PR 52) edits no test.
# (tests/cellfiles.py is the original; the benchmark's tests keep to `paths`.)
GENERIC = {
    "gen": dict(kind="bench_span", span="gen", stat="share_of_window"),
    "loop": dict(kind="bench_span", span="drive_once", stat="sum_per_frame"),
    "beat": dict(kind="bench_span", span="beat", stat="p99"),
    "step": dict(kind="trace_program", pick="longest", stat="p50"),
    "tick": dict(kind="counter", path="engine.trace.stage_ns.slow_path"),
}


def listed(cell: str, bench_dir: str = applib.BENCH_DIR) -> dict:
    """{name: file} of the layer files that list `cell`."""
    return {m["name"]: m for m in layers.layer_files(bench_dir)
            if cell in m["cells"]}


def reading(files: dict, **read) -> str:
    """The name of the one file among `files` whose `read` holds `read`."""
    hit = [name for name, m in files.items()
           if all(m["read"].get(k) == v for k, v in read.items())]
    assert len(hit) == 1, (read, hit)
    return hit[0]


def generic(cell: str, *which: str) -> dict:
    """{short: file name} of the generic reads `which` among `cell`'s files."""
    files = listed(cell)
    return {k: reading(files, **GENERIC[k]) for k in which}


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def tiny_dir(tmp_path_factory):
    """A temporary copy of the benchmark with one configuration, one
    traffic mix and one layer metric dropped in as files: nothing of the
    harness's code is touched to pick them up."""
    top = tmp_path_factory.mktemp("bench")
    bdir = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bdir)
    bench = copy.deepcopy(BENCH)
    sizes = {"subscribers": 4096, "nat_subscribers": 128,
             "flows_per_nat_subscriber": 2}
    for name, argv in TINY_ARGV.items():
        cfg = applib.load_named("configs", BASE_OF[name], bdir)
        cfg.update(name=name, argv=argv, sizes=sizes)
        if "nat_public_ips" in cfg:
            cfg["nat_public_ips"]["count"] = 4
        _write(os.path.join(bdir, "configs", name + ".json"), cfg)
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    flood = applib.load_named("traffic", "flood-64B", bdir)
    flood.update(name="tiny-flood", pool_frames=2048, dhcp_share=0.05,
                 warmup_frames=400)
    _write(os.path.join(bdir, "traffic", "tiny-flood.json"), flood)
    # The sharded lookup's exchange holds 2 x lanes / shards keys a
    # destination (ops/table.py exchange_capacity): 1,024 of a 2,048-lane
    # shard at the cell's size, where at most 2,048 frames are outstanding
    # over four shards, and 32 of the 64 lanes here. Holding the frames
    # outstanding to 32 keeps the rehearsal inside it as the cell is, so
    # that no DHCP frame is punted to the host and the guarantee is held.
    flood32 = dict(flood, name="tiny-flood-32",
                   outstanding_cap_of_ring_depth=32 / 1024)
    _write(os.path.join(bdir, "traffic", "tiny-flood-32.json"), flood32)
    renew = applib.load_named("traffic", "renew-under-load", bdir)
    renew.update(name="tiny-renew", dhcp_rate=100, data_rate=1000,
                 warmup_frames=400)
    _write(os.path.join(bdir, "traffic", "tiny-renew.json"), renew)
    stands_for = {v[0]: k for k, v in TINY_CELLS.items()}
    for cell, (_real, cfg, mix) in TINY_CELLS.items():
        bench["workloads"].append({"name": cell, "config": cfg, "traffic": mix,
                                   "chips": 4 if "4" in cell else 1,
                                   "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] += [tiny for real, tiny in stands_for.items()
                               if real in m["workloads"]]
    for path in glob.glob(os.path.join(bdir, "layers", "*.json")):
        m = json.load(open(path))
        m["cells"] += [stands_for[c] for c in list(m["cells"])]
        _write(path, m)
    # the dropped-in kit: a file in kits/, a configuration that names it, a
    # cell on that configuration; it reports what the cell beside it does
    shutil.copy(os.path.join(ROOT, "tests", "benchmark", "dropin", DROPIN_KIT + ".py"),
                os.path.join(bdir, "kits"))
    cfg = applib.load_named("configs", "tiny-wire", bdir)
    cfg.update(name="tiny-dot1q", kit=DROPIN_KIT)
    _write(os.path.join(bdir, "configs", "tiny-dot1q.json"), cfg)
    bench["workloads"].append({"name": "tiny-dot1q.flood", "config": "tiny-dot1q",
                               "traffic": "tiny-flood", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "tiny-wire.flood" in m.get("workloads", []):
            m["workloads"].append("tiny-dot1q.flood")
    # a second dropped-in kit, whose traffic declares frames for the host
    shutil.copy(os.path.join(ROOT, "tests", "benchmark", "dropin",
                             STRANGERS_KIT + ".py"), os.path.join(bdir, "kits"))
    for cell, over in STRANGERS.items():
        cfg = applib.load_named("configs", over, bdir)
        cfg.update(name=cell.removesuffix(".flood"), kit=STRANGERS_KIT)
        _write(os.path.join(bdir, "configs", cfg["name"] + ".json"), cfg)
        bench["workloads"].append({"name": cell, "config": cfg["name"],
                                   "traffic": "tiny-flood", "chips": 1,
                                   "why": "test"})
        {m["name"]: m for m in bench["end_to_end"]}[
            "served_kpps"]["workloads"].append(cell)
    # the dropped-in layer metric: a counter nobody read before
    _write(os.path.join(bdir, "layers", "test.batches.json"), {
        "name": "test.batches", "unit": "batches/s", "better": "higher",
        "source": "program_counter", "layer": "engine (runtime/engine.py)",
        "moves": "served_kpps", "cells": ["tiny.flood"],
        "read": {"kind": "counter", "path": "engine.batches", "per": "second"}})
    _write(os.path.join(top, "BENCHMARK.json"), bench)
    return bdir


def _run(tiny_dir, capsys, cell, *extra, seed=3000000019):
    capsys.readouterr()
    rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "1.5", "--bench-dir", tiny_dir, *extra])
    captured = capsys.readouterr()
    out = captured.out.strip().splitlines()
    _run.err = captured.err.strip().splitlines()  # the run's standard error
    assert rc == 0
    return json.loads(out[-1]), out


# -- rehearsal of every cell's phases --------------------------------------

@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_cell_rehearses_on_cpu(tiny_dir, capsys, cell):
    if "4" in cell:
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs four (virtual) devices")
    res, out = _run(tiny_dir, capsys, cell, "--trace", "0")
    assert set(res) == RESULT_KEYS
    assert res["correct"] is True and res["failed"] == 0, out[-14:]
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" not in res["device"]  # no device metric here
    real = TINY_CELLS[cell][0]
    want = {m["name"] for m in BENCH["end_to_end"]
            if real in m.get("workloads", [real])}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert any(line.startswith("check lost_frames=0 limit=0") for line in out)
    assert list(res)[-1] == "compared"
    assert res["compared"]["lost_frames"] == {"value": 0, "limit": 0}
    assert res["compared"]["sample_kinds_missing"] == {"value": 0, "limit": 0}
    assert all(abs(c["value"]) <= c["limit"] for c in res["compared"].values())
    # the same, as the last lines on standard error
    n = len(res["compared"])
    assert _run.err[-n:] == [f"check {k}={c['value']} limit={c['limit']}"
                             for k, c in res["compared"].items()]
    # held in every cell, the sharded one too: no punt to the host
    assert any(line == "check host_slow_path_dhcp=0 limit=0" for line in out)
    assert any(line.startswith("selectors: ") for line in out)
    assert any(line.startswith("programs built or loaded: ") for line in out)


def test_traced_run_reads_span_counter_and_bench_span_files(tiny_dir, capsys):
    res, _ = _run(tiny_dir, capsys, "tiny.flood", "--trace", "1")
    assert set(res) == RESULT_KEYS
    got = res["metrics"]
    # one metric of each host-side reader kind, and the dropped-in file
    for name in ("ring.us_per_frame", "sched.bulk_occupancy", "gen.share",
                 "loop.us_per_frame", "test.batches"):
        assert got[name]["value"] > 0, name
    assert "fused_step.device_p50_us" not in got  # no device trace on the CPU
    assert "busy_s" not in res["device"]


def test_traced_latency_cell_reads_lane_spans(tiny_dir, capsys):
    res, _ = _run(tiny_dir, capsys, "tiny.renew", "--trace", "1")
    got = res["metrics"]
    for name in ("sched.express_wait_p99_us", "sched.bulk_wait_p99_us",
                 "loop.beat_p99_us", "gen.late_p99_us", "loop.offer_p99_us",
                 "loop.fwd_p99_us"):
        assert got[name]["value"] > 0, name
    assert got["slow.punt_share"]["value"] == 0


def test_traced_wire_cell_reads_the_ring_lane_and_the_engines_tiling(tiny_dir,
                                                                     capsys):
    """The loop without a scheduler: the `wire.*` files read lane `ring`'s
    spans and the Tracer's sums under `engine.trace`; the device trace's
    file finds nothing on the CPU, is left out, and the run says so."""
    res, out = _run(tiny_dir, capsys, "tiny-wire.flood", "--trace", "1")
    assert res["correct"] is True, out[-14:]
    got = res["metrics"]
    wire = {m["name"] for m in layers.layer_files(applib.BENCH_DIR)
            if m["name"].startswith("wire")
            and "cgnat-1M-wire.flood-64B" in m["cells"]
            and not m["read"]["kind"].startswith("trace_")}
    positive = (ENGINE_LOOP - ENGINE_LOOP_ZERO_OK) | {"gen.share",
                                                      "loop.us_per_frame"}
    assert ENGINE_LOOP <= wire | {"engine.drain_built_per_step",
                                  "engine.drain_cached_per_step"}
    for name in wire | positive:  # a file added later may read 0 here
        assert got[name]["value"] >= 0 and (got[name]["value"] > 0
                                            or name not in positive), name
    assert got["wire.unattributed_share"]["value"] < 100.0
    # one window of at most the cap's frames a step, two in flight
    assert 1 <= got["wire.frames_per_step"]["value"] <= 1024
    assert "fused_step.device_p50_us" not in got
    said = [ln for ln in out if ln.startswith("per-layer metrics with nothing")]
    assert said and "fused_step.device_p50_us" in said[0]
    sel = [ln for ln in out if ln.startswith("selectors: ")][0]
    assert sel.endswith("ring=NativeRing loop=engine") and "host_path=" in sel
    assert not any(name.startswith("sched.") for name in got)


# -- `correct` has to be able to fail ---------------------------------------

@pytest.mark.parametrize("cell", ["tiny.flood", "tiny-wire.flood"])
@pytest.mark.parametrize("control", bench_run.CONTROLS)
def test_control_run_is_not_correct(tiny_dir, capsys, control, cell):
    """A reply with one flipped checksum byte, and a reply built from a
    binding one update behind: both have to come out as not correct, on the
    scheduler's loop and on the engine's."""
    res, out = _run(tiny_dir, capsys, cell, "--control", control, seed=11)
    assert res["correct"] is False and res["failed"] > 0
    bad = [ln for ln in out if ln.startswith("check sampled_replies_differing=")]
    assert bad and not bad[0].startswith("check sampled_replies_differing=0 ")


@pytest.mark.parametrize("control", [None, "stale-binding"])
def test_a_dropped_in_kit_serves_its_deployment_and_its_control_fails(
        tiny_dir, capsys, control):
    """A deployment added as files: `kits/ipoe-dot1q.py`, a configuration
    with `"kit": "ipoe-dot1q"`, a cell. The harness imports the kit by the
    name in the configuration: tagged frames in, the tagged reference held
    against what came out, `correct` true; with the kit's stale-binding
    control planted, false."""
    assert not os.path.exists(os.path.join(ROOT, "benchmark", "kits",
                                           DROPIN_KIT + ".py"))
    extra = ["--control", control] if control else []
    res, out = _run(tiny_dir, capsys, "tiny-dot1q.flood", *extra, seed=21)
    assert any(ln.startswith("cell: ") and ln.endswith("kit=" + DROPIN_KIT)
               for ln in out)
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    assert "the access tag back on" in sample and " 0 " not in sample
    if control:
        assert res["correct"] is False and res["failed"] > 0
        assert not any(ln.startswith("check sampled_replies_differing=0 ")
                       for ln in out)
    else:
        assert res["correct"] is True and res["failed"] == 0, out[-14:]
        assert any(ln == "check dhcp_accepted_minus_device_hits=0 limit=0"
                   for ln in out)
        assert set(res["metrics"]) == {"served_kpps", "setup_s"}


@pytest.mark.parametrize("short", [0, 1])
@pytest.mark.parametrize("cell", sorted(STRANGERS))
def test_check_balances_the_hosts_share_against_what_a_kit_declares(
        tiny_dir, capsys, monkeypatch, cell, short):
    """`kits/ipoe-strangers.py`: one DHCP frame in 16 is a DISCOVER from a
    MAC the kit did not provision, and the kit's `Traffic.to_host` says so.
    The responder misses it, the host's `DHCPServer` leases and answers, and
    `check` holds the slow path's count, the program's passes and the
    responder's hits to the declared frames the ring accepted: `correct`.
    With the declaration short by one frame the same run is not: the three
    balances read that frame's pushes, and no other count moves."""
    real_check = bench_run.check
    state = {}

    def check(app, kit, traffic, loop, c0, c1, seed):
        assert traffic.to_host.any() and traffic.is_dhcp[traffic.to_host].all()
        state["declared"] = int(traffic.to_host.sum())
        if short:
            traffic.to_host[np.nonzero(traffic.to_host)[0][0]] = False
        return real_check(app, kit, traffic, loop, c0, c1, seed)

    monkeypatch.setattr(bench_run, "check", check)
    res, out = _run(tiny_dir, capsys, cell, seed=22 + short)
    assert any(ln.startswith("cell: ") and ln.endswith("kit=" + STRANGERS_KIT)
               for ln in out)
    told = [ln for ln in out if ln.startswith("check declared to the host: ")]
    assert told and told[0].endswith(" DHCP, 0 data frames accepted")
    n_told = int(told[0].split()[5])
    assert n_told >= state["declared"] - short > 0  # every one pushed, at least once
    got = {k: c["value"] for k, c in res["compared"].items()}
    balances = ("host_slow_path_dhcp", "punted_frames",
                "dhcp_accepted_minus_device_hits")
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    if short:
        assert res["correct"] is False
        assert got["host_slow_path_dhcp"] > 0
        assert {got[k] for k in balances} == {got["host_slow_path_dhcp"]}
        # the reference holds an undeclared frame to the device's answer,
        # so the sample may differ by that frame's replies and nothing else
        assert 0 <= got.pop("sampled_replies_differing") \
            <= got["host_slow_path_dhcp"]
        assert all(v == 0 for k, v in got.items() if k not in balances)
    else:
        assert res["correct"] is True and res["failed"] == 0, out[-16:]
        assert all(v == 0 for v in got.values())
        assert "strangers' OFFERs among them" in sample
    assert set(res["metrics"]) == {"served_kpps", "setup_s"}


def test_broken_timed_path_is_not_correct(tiny_dir, capsys, monkeypatch):
    """The rest of a run with the timed path broken underneath: the ring
    gives back every DHCP reply with another address in it."""
    real_pop = bench_run.Loop._pop

    def pop(self):
        got = real_pop(self)
        return [(raw[:58 + 2] + bytes([raw[60] ^ 1]) + raw[61:], fl)
                if len(raw) > 300 else (raw, fl) for raw, fl in got]

    monkeypatch.setattr(bench_run.Loop, "_pop", pop)
    res, _ = _run(tiny_dir, capsys, "tiny.flood", seed=12)
    assert res["correct"] is False


def test_lost_frame_is_not_correct(tiny_dir, capsys, monkeypatch):
    """A step that swallows part of what it was given."""
    real_pop = bench_run.Loop._pop
    state = {"swallowed": False}

    def pop(self):
        got = real_pop(self)
        # once, in the measured loop (the warm-up's is built without a seed)
        if got and self.in_window and not state["swallowed"]:
            state["swallowed"] = True
            self.popped -= 1
            return got[1:]
        return got

    real_init = bench_run.Loop.__init__

    def init(self, app, traffic, seed=0, tamper=None):
        real_init(self, app, traffic, seed, tamper)
        self.in_window = bool(seed)

    monkeypatch.setattr(bench_run.Loop, "__init__", init)
    monkeypatch.setattr(bench_run.Loop, "_pop", pop)
    res, out = _run(tiny_dir, capsys, "tiny.flood", seed=13)
    assert res["correct"] is False and res["failed"] > 0
    assert not any(ln.startswith("check lost_frames=0 ") for ln in out)


def test_stalled_loop_holds_what_is_due_and_fails_nothing(tiny_dir, capsys,
                                                          monkeypatch):
    """An open-loop frame that finds no room is held and offered at a later
    beat, timed from when it was due: a loop that stalls, with little room
    in front of it, delays frames and fails none."""
    real_init = bench_run.Loop.__init__

    def init(self, app, traffic, seed=0, tamper=None):
        real_init(self, app, traffic, seed, tamper)
        if not seed:  # the warm-up's loop
            return
        self.cap = 64
        drive, state = app.drive_once, {"beats": 0}

        def stalling():
            state["beats"] += 1
            if state["beats"] == 50:
                bench_run.time.sleep(0.5)
            return drive()

        monkeypatch.setattr(app, "drive_once", stalling)

    monkeypatch.setattr(bench_run.Loop, "__init__", init)
    res, out = _run(tiny_dir, capsys, "tiny.renew", seed=14)
    assert res["correct"] is True and res["failed"] == 0, out[-14:]
    line = [ln for ln in out if ln.startswith("window: ")][0]
    held = int(re.search(r"held at most (\d+) at once", line).group(1))
    assert held > 64 and "never offered 0," in line
    assert res["attempted"] == int(re.search(r"pushed (\d+),", line).group(1))
    assert any(ln == "check frames_never_offered=0 limit=0" for ln in out)
    # what waited is in the latencies: the stall is half a second long
    assert res["metrics"]["fwd_p95_us"]["value"] > 100_000


def test_stale_control_is_planted_in_the_table_that_is_uploaded():
    """The control changes what the device holds, not what the reference
    says: one address in eight of the DHCP table is the one from before."""
    lay = ipoe.Layout({"sizes": {"subscribers": 64, "nat_subscribers": 8,
                                   "flows_per_nat_subscriber": 2}}, 7)
    idx = np.arange(64)
    sound = ipoe.dhcp_table_ips(lay, idx, stale=False)
    stale = ipoe.dhcp_table_ips(lay, idx, stale=True)
    assert (sound == lay.sub_ips(idx)).all()
    assert ((stale != sound) == (idx % 8 == 0)).all()
    assert (lay.sub_ips(idx) == sound).all()  # the layout's own are untouched


@pytest.mark.parametrize("name", sorted(
    os.path.basename(p)[:-5]
    for p in glob.glob(os.path.join(ROOT, "benchmark", "configs", "*.json"))))
def test_every_configuration_holds_the_four_guarantees(name):
    """No configuration may let the slow path answer what the device table
    could: `check` holds `host_slow_path_dhcp` to 0 in every cell, over what
    the kit's traffic declares for the host and nothing a configuration
    says. A deployment's own guarantees follow the four (M's two)."""
    cfg = applib.load_named("configs", name)
    want = applib.load_named("configs", "ipoe-cgnat-1M")["guarantees"]
    assert cfg["guarantees"][:4] == want and len(want) == 4
    assert len(set(cfg["guarantees"])) == len(cfg["guarantees"])
    assert "slow_path_may_answer" not in cfg and "guarantees_edge" not in cfg
    import inspect

    src = inspect.getsource(bench_run.check)
    assert 'hold("host_slow_path_dhcp"' in src and "punts_allowed" not in src


def test_flood_tops_the_ring_up_before_every_beat():
    """However few slots came free, the next beat fills them: the generator
    is a queue that is never empty, up to the cap on frames outstanding."""
    class Ring:
        depth = 8

        def __init__(self):
            self.rx = []

        def rx_push_batch(self, frames, from_access):
            self.rx += frames
            return len(frames)

        def stats(self):
            return {"drop": 0}

    class Stats:
        dropped = 0

    class Engine:
        stats = Stats()

    class App:
        def __init__(self):
            self.components = {"ring": Ring(), "engine": Engine()}

    class Mix:
        flood, n = True, 8
        mix = {"outstanding_cap_of_ring_depth": 1.0}
        streams = [gen.Stream(True, range(4), [b"a"] * 4),
                   gen.Stream(False, range(4, 8), [b"n"] * 4)]

    app = App()
    loop = bench_run.Loop(app, Mix())
    assert loop._push(0.0) == 8 and loop._push(0.0) == 0  # full: no room
    loop.popped += 1  # one reply left
    assert loop._push(0.0) == 1 and loop.outstanding() == 8
    loop.popped += 3
    assert loop._push(0.0) == 3
    assert (loop.push_beats, loop.cap_full, loop.ring_short) == (4, 1, 0)
    assert "refill_min_share_of_cap" not in applib.load_named("traffic",
                                                              "flood-64B")


def test_counter_per_counter_and_per_frame_latency_readers():
    class Plan:
        flood = True

    ctx = layers.Context(plan=Plan(), loop=None, window=2.0, served=10,
                         c0={"a": {"sum": 1.0, "n": 2}},
                         c1={"a": {"sum": 2.5, "n": 5}}, tracer=None,
                         profile=None, setup_s=0.0, n_devices=1)
    read = {"kind": "counter", "path": "a.sum", "per": "a.n"}
    assert layers.read_counter(read, ctx) == pytest.approx(0.5)  # the window's own
    assert layers.read_counter(dict(read, per="second"), ctx) == pytest.approx(0.75)
    assert layers.read_counter(dict(read, per="a.missing"), ctx) is None
    occ = applib.load_named("layers", "sched.bulk_occupancy")["read"]
    assert occ["per"] == "sched.bulk.batches" and "delta" not in occ

    class Loop:
        spans = [(0.0, 0.1, 0.2, 0.3, 1, 1)]

    ctx.loop = Loop()
    ctx._lat = {"dhcp_us": np.arange(1.0, 101.0), "data_us": np.arange(1.0, 201.0),
                "late_us": np.zeros(3)}
    for span, top in (("dhcp", 100), ("data", 200)):
        got = layers.read_bench_span({"span": span, "stat": "p99"}, ctx)
        assert top * 0.98 < got < top


def test_span_reader_returns_nothing_for_a_stage_or_lane_the_program_lacks():
    """The driver lays a PR's layer files over the parent's checkout too,
    whose Tracer may lack the stage or lane a file names: nothing to read,
    the metric is left out of the line, and nothing raises."""
    from bng_tpu.telemetry import spans as tele

    class Tracer:
        events = [(tele.STAGE_NAMES.index("ring"),
                   tele.LANE_NAMES.index("ring"), 0, 4000)]

    ctx = layers.Context(plan=None, loop=None, window=1.0, served=2, c0={},
                         c1={}, tracer=Tracer(), profile=None, setup_s=0.0,
                         n_devices=1)
    read = {"kind": "span", "stage": "ring", "lane": "ring",
            "stat": "sum_per_frame"}
    assert layers.read_span(read, ctx) == pytest.approx(2.0)
    assert layers.read_span(dict(read, stage="a-later-stage"), ctx) is None
    assert layers.read_span(dict(read, lane="a-later-lane"), ctx) is None
    assert layers.read_span(dict(read, lane="bulk"), ctx) is None  # no event


def test_an_app_of_an_unknown_shape_is_refused_by_name():
    """`idle`, `counters` and `selectors` choose among three shapes by what
    the app holds; an app that fits none is an error that says what it
    holds, not a KeyError in whichever function indexed first."""
    class Ring:
        def rx_pending(self):
            return 0

        def stats(self):
            return {}

    class App:
        components = {"ring": Ring(), "pppoe_only_dataplane": object()}

    for read in (applib.shape, applib.idle, applib.counters, applib.selectors):
        with pytest.raises(applib.BenchError, match="pppoe_only_dataplane"):
            read(App())

    class Sched:  # a scheduler in front of a ring without rx_pop is bypassed
        components = {"ring": Ring(), "scheduler": object(), "engine": object()}

    assert applib.shape(Sched()) == "engine"
    with pytest.raises(applib.BenchError, match="no file"):
        applib.load_kit({"name": "x", "kit": "not-there"})


# -- no chip, no result -------------------------------------------------------

def test_refuses_to_run_without_the_chip():
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "cgnat-1M.flood-64B", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300, env=env,
        cwd=ROOT)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_cpu_is_refused_at_the_cells_real_size():
    with pytest.raises(SystemExit):
        bench_run.find_devices(1, 1_000_000)


# -- the contract's shape -----------------------------------------------------

def test_names_units_and_lengths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert "no frame crossed a link" in w["why"]
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in BENCH["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_cell_resolves_its_files_by_name():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        cfg = applib.load_named("configs", w["config"])
        mix = applib.load_named("traffic", w["traffic"])
        entry = configs[w["config"]]
        assert entry["file"] == f"benchmark/configs/{w['config']}.json"
        assert cfg["name"] == w["config"] and mix["name"] == w["traffic"]
        assert cfg["chips"] == w["chips"]
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        assert cfg["guarantees"] and mix["kind"] in ("flood", "fixed_rate")
    assert {c["name"] for c in BENCH["configs"]} == {
        w["config"] for w in BENCH["workloads"]}
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_layer_files_and_benchmark_json_agree():
    cells = {w["name"] for w in BENCH["workloads"]}
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in BENCH["end_to_end"]}
    files = {m["name"]: m for m in layers.layer_files(applib.BENCH_DIR)}
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    # a file may name a cell that is not (yet) in BENCHMARK.json
    assert set(listed) <= set(files)
    for name, m in listed.items():
        f = files[name]
        assert m["workloads"] == [c for c in f["cells"] if c in cells]
        assert m["workloads"], name
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == f[key], (name, key)
        assert f["source"] == layers.SOURCE_OF_KIND[f["read"]["kind"]]
        # `moves` names an end-to-end metric that each of its cells reports
        assert set(m["workloads"]) <= reports[m["moves"]], name
    for cell in cells:
        assert any(cell in m["workloads"] for m in BENCH["per_layer"])
    peaks = json.load(open(os.path.join(applib.HERE, "peaks.json")))
    assert peaks["TPU v5 lite"]["hbm_gbytes_per_s"] == 819


# -- one file, one entry; the count (PR 48) ------------------------------------

PER_LAYER_LIMIT = 128  # the format's
LAYER_FILES = {os.path.basename(p)[:-5]: json.load(open(p)) for p in glob.glob(
    os.path.join(ROOT, "benchmark", "layers", "*.json"))}


# what a file reads: two files that agree in these five are one read twice
READS = {name: json.dumps([m[k] for k in ("read", "unit", "better", "source",
                                          "moves")], sort_keys=True)
         for name, m in LAYER_FILES.items()}


def twins(name: str) -> list[str]:
    """The other files that hold `name`'s read, unit, better, source, moves."""
    return [n for n, r in READS.items() if n != name and r == READS[name]]


def test_per_layer_is_within_the_formats_limit():
    n = len(BENCH["per_layer"])
    waiting = sorted(name for name in LAYER_FILES if twins(name))
    print(f"per_layer holds {n} of {PER_LAYER_LIMIT} entries; files that "
          f"hold another file's read for other cells and wait for a "
          f"`benchmark` PR to merge them: {waiting or 'none'}")
    assert n <= PER_LAYER_LIMIT and n == len(LAYER_FILES)


@pytest.mark.parametrize("name", sorted(LAYER_FILES))
def test_no_cell_reports_this_files_read_under_a_second_name(name):
    """One read, one name a cell (PR 52): no file that shares this file's
    `read`, `unit`, `better`, `source` and `moves` lists a cell this file
    lists. On PR 52's tree no two files share the five at all. A later PR
    that adds a cell may not edit a file that is there, so it brings its
    loop's generic reads under names of its own FOR ITS OWN CELL (that is
    admitted here, and the count's line above names each such file) until a
    `benchmark` PR appends the cell to the originals (benchmark/README.md,
    "The count"); a second name for a read in a cell that already reports
    it is refused."""
    mine = set(LAYER_FILES[name]["cells"])
    shared = {n: sorted(mine & set(LAYER_FILES[n]["cells"]))
              for n in twins(name) if mine & set(LAYER_FILES[n]["cells"])}
    assert not shared, f"{name} is a second name for a read of {shared}"


@pytest.mark.parametrize("name", sorted(LAYER_FILES))
def test_a_layer_files_cells_are_its_entrys_workloads(name):
    """One file, one entry: the same cells in the same order, which is the
    order of `workloads`."""
    m = LAYER_FILES[name]
    entries = {e["name"]: e for e in BENCH["per_layer"]}
    order = [w["name"] for w in BENCH["workloads"]]
    assert m["name"] == name and entries[name]["workloads"] == m["cells"]
    assert m["cells"] == [c for c in order if c in m["cells"]] != []


# -- the generator's frames ---------------------------------------------------

def test_patched_frames_equal_the_codecs():
    from bng_tpu.control import dhcp_codec, packets

    macs = np.array([0x02AA00000005, 0x02AA000FFFFF], np.uint64)
    xids = np.array([0x01000007, 0x7F00FFFF], np.uint32)
    ips = np.array([0x0A100005, 0x0A1FFFFF], np.uint32)
    kinds = np.array([gen.DISCOVER, gen.REQUEST])
    rows = gen.dhcp_frames(macs, kinds, xids, ips, 0x0A000001)
    assert bytes(rows[0]) == gen.dhcp_frame(int(macs[0]), dhcp_codec.DISCOVER,
                                            int(xids[0]))
    assert bytes(rows[1]) == gen.dhcp_frame(
        int(macs[1]), dhcp_codec.REQUEST, int(xids[1]),
        requested_ip=int(ips[1]), server_id=0x0A000001)

    src_mac = np.frombuffer(bytes.fromhex("02aa00000005" "02aa00000006"),
                            np.uint8).reshape(2, 6)
    dst_mac = np.frombuffer(bytes.fromhex("02aabbccdd01"), np.uint8)
    buf = gen.data_frames(src_mac, dst_mac, [0x0A100005, 0x0A100006],
                          [0x5DB80001, 0x5DB80002], [40000, 40001], [443, 443],
                          np.array([17, 6]), [7, 0xFFFFFFFE])
    for row, proto in zip(buf, (17, 6)):
        raw = bytes(row)
        d = packets.decode(raw)
        assert len(raw) == 60 and d.proto == proto and d.ip_checksum_ok
        assert d.l4_checksum != 0 and ipoe.l4_checksum_ok(raw)
        assert (d.src_port, d.dst_port) in ((40000, 443), (40001, 443))
    assert bytes(buf[0][-4:]) == (7).to_bytes(4, "big")
    tcp = packets.tcp_packet(bytes(src_mac[1]), bytes(dst_mac), 0x0A100006,
                             0x5DB80002, 40001, 443, bytes(buf[1][54:]))
    assert bytes(buf[1]) == tcp


def test_every_seed_offers_the_same_amount_in_another_order():
    class App:  # what Traffic reads of the app
        class config:
            server_mac = "02:aa:bb:cc:dd:01"
            server_ip = "10.0.0.1"

    cfg = {"sizes": {"subscribers": 4096, "nat_subscribers": 128,
                     "flows_per_nat_subscriber": 2}}
    mix = applib.load_named("traffic", "renew-under-load")
    prov = {"nat_ip": np.full(256, 0xC6120001, np.uint32),
            "nat_port": np.arange(256, dtype=np.uint32) + 1024}
    big = 2**31 + 11
    a, b = (ipoe.Traffic(mix, ipoe.Layout(cfg, s), prov, App, s, 1.0)
            for s in (5, big))
    assert a.n == b.n and (a.kind == b.kind).sum() > 0
    assert [len(s.frames) for s in a.streams] == [len(s.frames) for s in b.streams]
    assert a.frames != b.frames
    again = ipoe.Traffic(mix, ipoe.Layout(cfg, big), prov, App, big, 1.0)
    assert again.frames == b.frames and (again.due == b.due).all()


# SHA-256 taken on the parent commit (47e83f5, before PR 27 moved this code
# out of lib/app.py and lib/gen.py), by the arithmetic of the test below
PINNED = {
    "tiny-flood": ("9ef3253d77ef08d6d95137f1969b76610d6b6e9c4fa24cd5edf6d0e52f53e673",
                   "6cb9f97aa0abdd703c14e32a642c9e463bc2a2d9cc090d933cb11ca229b85355"),
    "tiny-renew": ("2d5aba4724c72a63ddf653f3c89421e04a80f1a6078be0dabbb7836495befa30",
                   "0d79ef5e7278e7070a2ac9c606382930b4844020cf0891c5193d9dd8d52d29b0"),
}


def test_the_default_kit_builds_the_bytes_it_built_before_the_move(tiny_dir):
    """The default kit is the harness's old code, moved: for a fixed seed
    and the tiny mixes, the frame pool with its offer order, and for 64
    frame ids the reference's reply, `reply_id` of it and `expected_data`,
    are what the parent commit built."""
    import hashlib

    seed = 2027
    cfg = applib.load_named("configs", "tiny-cgnat", tiny_dir)
    assert "kit" not in cfg
    kit = applib.load_kit(cfg, tiny_dir)
    assert kit.__name__ == "benchmark.kits." + applib.DEFAULT_KIT
    lay = kit.Layout(cfg, seed)
    app = applib.build_app(cfg)
    try:
        prov = kit.provision(app, lay)
        for mix_name, (pool, replies) in PINNED.items():
            tr = kit.Traffic(applib.load_named("traffic", mix_name, tiny_dir),
                             lay, prov, app, seed, 1.5)
            h = hashlib.sha256()
            for f in tr.frames:
                h.update(f)
            for st in tr.streams:
                h.update(st.ids.tobytes())
                if st.due is not None:
                    h.update(np.asarray(st.due, np.float64).tobytes())
            assert h.hexdigest() == pool, mix_name
            ref = kit.Reference(app, tr)
            n_d = int(tr.is_dhcp.sum())
            ids = [*range(32), *range(n_d, n_d + 16), *range(tr.n - 16, tr.n)]
            h = hashlib.sha256()
            for i in ids:
                if tr.is_dhcp[i]:
                    sub = int(tr.key[i])
                    want = ref.dhcp.reply(tr.frames[i], lay.mac_base + sub,
                                          int(lay.sub_ips([sub])[0]))
                    assert ref.holds(i, want) and not ref.holds(i, want[:-1])
                    h.update(want)
                    h.update(repr(tr.reply_id(want)).encode())
                else:
                    h.update(repr(tr.expected_data(i, app)).encode())
                    h.update(repr(tr.reply_id(tr.frames[i])).encode())
            assert h.hexdigest() == replies, mix_name
    finally:
        app.close()


# -- the trace reduction, on a small recorded trace ---------------------------

def test_trace_reduction_on_the_recorded_trace():
    path = os.path.join(applib.HERE, "testdata", "trace_small.json")
    data = json.load(open(path))
    want = json.load(open(os.path.join(applib.HERE, "testdata",
                                       "trace_small.expect.json")))
    got = trace.reduce(data, want["n_devices"], want["window_s"])
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["idle_share"] == pytest.approx(want["idle_share"], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    progs = {}
    for name, _t, d in got["programs"]:
        progs.setdefault(name, []).append(d)
    assert {k: len(v) for k, v in progs.items()} == want["program_counts"]
    assert len(got["breakdown"]["device_ops"]) <= 10
    assert got["breakdown"]["idle_gaps"][0][0] == want["longest_gap_label"]
    # a hand-made case: two overlapping ops and one apart, on two devices
    hand = {"planes": {
        "/device:TPU:0": {"XLA Ops": [["a", 0, 4e8], ["b", 2e8, 4e8],
                                      ["all-to-all.1", 8e8, 1e8]],
                          "XLA Modules": [["jit_step", 0, 9e8]]},
        "/device:TPU:1": {"XLA Ops": [["a", 0, 2e8]], "XLA Modules": []},
        "/host:CPU": {"bench": [["bench.drive_once", 5e8, 4e8]]}}}
    got = trace.reduce(hand, 2, 1.0)
    assert got["busy_s"] == pytest.approx((0.7 + 0.2) / 2)
    assert got["idle_share"] == pytest.approx(0.8)  # the worst device
    assert got["collective_share"] == pytest.approx(0.1)
    assert got["breakdown"]["idle_gaps"] == [["bench.drive_once", pytest.approx(0.2)]]
    assert trace.reduce({"planes": {}}, 1, 1.0) is None


# a device with three ops and two gaps; the harness's spans and the
# program's two beats around them, on the trace's timeline (ns)
_OPS = [["a", 0, 1e8], ["b", 5e8, 1e8], ["c", 9e8, 1e8]]
_BENCH = [["bench.drive_once", 0.5e8, 5e8], ["bench.pop", 6e8, 0.1e8],
          ["bench.push", 8.5e8, 0.1e8], ["bench.drive_once", 8.6e8, 2.4e8]]
_CLOCK = 40_000_000_000_000  # the Tracer's clock where the trace reads 0.5e8
_BEATS = [[0.5e8, 5e8, _CLOCK, 7], [8.6e8, 2.4e8, _CLOCK + int(8.1e8), 8]]
_STAGES = ["ring", "dispatch", "device", "slow_path", "reply", "beat", "pack",
           "drain", "upload", "fetch"]


def _lap(stage, start, dur, beat):  # a lap by where it lies on the trace
    return ([_STAGES.index(stage), 0, _CLOCK + int(start - 0.5e8), int(dur)],
            beat)


def _hand(laps, beats=_BEATS):
    data = {"planes": {"/device:TPU:0": {"XLA Ops": _OPS, "XLA Modules": []},
                       "/host:CPU": {"bench": _BENCH, "beats": beats}}}
    log = {"stages": _STAGES, "events": [e for e, _b in laps],
           "beats": [b for _e, b in laps]}
    got = trace.reduce(data, 1, 1.0, log if laps else None)
    assert got["busy_s"] == pytest.approx(0.3)
    assert sum(got["gaps"].values()) == pytest.approx(0.7)
    assert got["breakdown"]["idle_gaps"] == [
        [k, v] for k, v in sorted(got["gaps"].items(), key=lambda kv: -kv[1])]
    return got["gaps"]


# what the ledger keeps of a label as it stands
LABEL = re.compile(r"^[A-Za-z0-9_.\-]{1,64}$")
HAND_MADE = {
    # between two steps the loop runs `reply` and then `dispatch`, with a
    # `drain` lap inside it: the gap is theirs by overlap, the innermost
    # wins, and a fed duration (`device`) or the beat itself claims nothing
    "a gap split over the laps by overlap": (
        [_lap("reply", 1e8, 1e8, 7), _lap("dispatch", 2.5e8, 2e8, 7),
         _lap("drain", 3e8, 0.5e8, 7), _lap("device", 0, 10e8, 7),
         _lap("beat", 0.5e8, 5e8, 7), _lap("pack", 8.7e8, 0.2e8, 8)],
        {"drive_once.reply": 0.1, "drive_once.dispatch": 0.15,
         "drive_once.drain": 0.05, "drive_once.no_lap": 0.12,
         "drive_once.pack": 0.02, "bench.pop": 0.01, "bench.push": 0.01,
         "between beats": 0.24}),
    # the harness calls `app.tick()` between beats: that lap has beat id -1
    # and lands through the nearest anchor
    "a gap between beats under a slow_path lap": (
        [_lap("slow_path", 6.2e8, 2e8, -1)],
        {"between_beats.slow_path": 0.2, "between beats": 0.04,
         "drive_once.no_lap": 0.44, "bench.pop": 0.01, "bench.push": 0.01}),
    # a crossing between host and chip is a lap of its own (PR 37), closed
    # inside `dispatch` or `reply`: the innermost wins, the parent keeps the
    # rest, and the gap's total is what it was
    "an upload inside dispatch and a fetch inside reply": (
        [_lap("reply", 1e8, 1e8, 7), _lap("fetch", 1.2e8, 0.1e8, 7),
         _lap("dispatch", 2e8, 2e8, 7), _lap("upload", 2.5e8, 0.5e8, 7)],
        {"drive_once.reply": 0.09, "drive_once.fetch": 0.01,
         "drive_once.dispatch": 0.15, "drive_once.upload": 0.05,
         "drive_once.no_lap": 0.14, "bench.pop": 0.01, "bench.push": 0.01,
         "between beats": 0.24}),
    # no event log: the harness's own names, as before PR 36
    "no event log": (
        [], {"bench.drive_once": 0.44, "between beats": 0.24,
             "bench.pop": 0.01, "bench.push": 0.01}),
}


@pytest.mark.parametrize("case", sorted(HAND_MADE))
def test_an_idle_gap_is_named_by_the_programs_stage(case):
    laps, want = HAND_MADE[case]
    got = _hand(laps)
    assert got == {k: pytest.approx(v) for k, v in want.items()}
    assert all(LABEL.match(k) or k == "between beats" for k in got)


def test_every_host_lap_is_a_stage_the_program_stamps():
    """`HOST_LAPS` filters the event log by name: a name the Tracer dropped
    (`loop_fill`, `loop_retire`: PR 37) only sits there, and one it gained
    and the list lacks (`upload`, `fetch`) reads as `no_lap`."""
    from bng_tpu.telemetry import spans

    assert set(trace.HOST_LAPS) <= set(spans.STAGE_NAMES)
    assert {"upload", "fetch"} <= set(trace.HOST_LAPS)
    assert len(set(trace.HOST_LAPS)) == len(trace.HOST_LAPS)


def test_a_trace_without_anchors_reads_as_before():
    """An event log and no `bng.beat` in the trace (a program from before
    PR 25, or a profiler the Tracer did not see start): no lap can be put
    on the trace's timeline, and the labels are the harness's own."""
    laps = HAND_MADE["a gap split over the laps by overlap"][0]
    assert _hand(laps, beats=[]) == {
        k: pytest.approx(v) for k, v in HAND_MADE["no event log"][1].items()}


def test_trace_reduction_on_the_recording_with_anchors():
    """A traced chip run of `cgnat-1M-wire.flood-64B`, cut to its first
    programs (`python -m benchmark.lib.trace <dir> <out> <n> programs`), with
    the `bng.beat` anchors and the matching slice of the Tracer's event log:
    the gaps between the programs are named by the engine loop's stages."""
    base = os.path.join(applib.HERE, "testdata", "trace_anchored")
    data, log, want = (json.load(open(base + ext))
                       for ext in (".json", ".events.json", ".expect.json"))
    beats = data["planes"]["/host:CPU"]["beats"]
    assert len(beats) == want["anchors"] and len(log["events"]) == want["laps"]
    got = trace.reduce(data, want["n_devices"], want["window_s"], log)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["idle_share"] == pytest.approx(want["idle_share"], rel=1e-9)
    assert got["gaps"] == {k: pytest.approx(v, rel=1e-9)
                           for k, v in want["gaps"].items()}
    assert all(LABEL.match(k) or k == "between beats" for k in got["gaps"])
    stages = [k for k in got["gaps"]
              if k.startswith("drive_once.") and k != "drive_once.no_lap"]
    assert len(stages) >= 2 and "bench.drive_once" not in got["gaps"]
    # the same recording without its event log: the harness's own names,
    # and the stages' entries sum back to them
    bare = trace.reduce(data, want["n_devices"], want["window_s"])["gaps"]
    assert set(bare) <= {*trace.BENCH_SPANS, "between beats"}
    for old, heads in (("bench.drive_once", ("drive_once.",)),
                       ("between beats", ("between_beats.", "between beats"))):
        assert sum(v for k, v in got["gaps"].items() if k.startswith(heads)) \
            == pytest.approx(bare.get(old, 0.0), rel=1e-9, abs=1e-12)

