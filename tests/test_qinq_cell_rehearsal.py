"""CPU rehearsal of `qinq-pppoe-cgnat-1M-wire.flood-64B`: the configuration
and its kit dropped into a temporary copy of the benchmark at 4,096
subscribers behind a pair each, 1,024 of them behind NAT and 256 of those
PPPoE, through `run.py`'s own loop past the frame pool's wrap. Every
forwarded data frame leaves at another length than it came (upstream 68 or
76 in, 60 out; downstream 60 in, 68 or 76 out), DHCP is answered from the
VLAN tier with the tags back, and each reply is held to the kit's plain
reference. No number from here is a device metric.

Also here: what the cell rests on in the program. The pair table and
`vlan_subscriber_pools` sized as `bng run --qinq-enabled` sizes them take
1,000,000 pairs through the bulk writers; the generator's frames are the
framing the reference strips.
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
import cellfiles  # noqa: E402

REAL = "qinq-pppoe-cgnat-1M-wire.flood-64B"
CELL = "tiny-qinq-1024.flood-4096"
# the cell's layer files are taken from what lists the cell (cellfiles.py),
# by what each reads. Dropped in: reads the rehearsal wants under a name of
# its own
DROPPED = [
    {"name": "tiny.frames_per_step", "unit": "frames", "better": "higher",
     "source": "program_counter", "layer": "engine (runtime/engine.py)",
     "moves": "served_kpps", "cells": [CELL],
     "read": {"kind": "counter", "path": "ring.rx", "per": "engine.batches"}},
    {"name": "tiny.fetch_calls_per_step", "unit": "calls", "better": "lower",
     "source": "program_counter", "layer": "engine (runtime/engine.py)",
     "moves": "served_kpps", "cells": [CELL],
     "read": {"kind": "counter", "path": "engine.trace.xfer.fetch_calls",
              "per": "engine.batches"}},
    {"name": "tiny.prefetch_calls_per_step", "unit": "calls",
     "better": "higher", "source": "program_counter",
     "layer": "engine (runtime/engine.py)", "moves": "served_kpps",
     "cells": [CELL],
     "read": {"kind": "counter", "path": "engine.trace.xfer.prefetch_calls",
              "per": "engine.batches"}},
]
SIZES = {"subscribers": 4096, "nat_subscribers": 1024,
         "flows_per_nat_subscriber": 2, "pppoe_sessions": 256}


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def cell_dir(tmp_path_factory):
    top = tmp_path_factory.mktemp("qinq")
    bdir = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bdir)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = applib.load_named("configs", "qinq-pppoe-cgnat-1M-wire", bdir)
    assert cfg["kit"] == "qinq" and cfg["argv"][-1] == "--qinq-enabled"
    cfg.update(name="tiny-qinq-1024",
               argv=["--pool-cidr", "10.0.0.0/11", "--batch-size", "1024",
                     "--synthetic-subs", "1", "--max-subscribers", "4096",
                     "--max-nat-sessions", "4096", "--max-nat-subscribers",
                     "1024", "--pppoe-enabled", "--pppoe-auth", "none",
                     "--qinq-enabled"],
               sizes=dict(SIZES))
    cfg["nat_public_ips"]["count"] = 20
    _write(os.path.join(bdir, "configs", "tiny-qinq-1024.json"), cfg)
    bench["configs"].append({"name": "tiny-qinq-1024", "source": "test",
                             "file": "benchmark/configs/tiny-qinq-1024.json",
                             "reduced": [], "why": "test"})
    flood = applib.load_named("traffic", "flood-64B", bdir)
    flood.update(name="tiny-flood-4096", pool_frames=4096, dhcp_share=0.05,
                 warmup_frames=400)
    _write(os.path.join(bdir, "traffic", "tiny-flood-4096.json"), flood)
    bench["workloads"].append({"name": CELL, "config": "tiny-qinq-1024",
                               "traffic": "tiny-flood-4096", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "served_kpps":
            m["workloads"].append(CELL)
    assert all(m["moves"] == "served_kpps"
               for m in cellfiles.stand_in(bdir, REAL, CELL))
    for m in DROPPED:
        _write(os.path.join(bdir, "layers", m["name"] + ".json"), m)
    _write(os.path.join(top, "BENCHMARK.json"), bench)
    return bdir


def _run(cell_dir, capsys, seed, *extra):
    capsys.readouterr()
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                         "4", "--bench-dir", cell_dir, *extra])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    window = [ln for ln in out if ln.startswith("window: ")][0]
    pushed = int(window.split("pushed ")[1].split(",")[0])
    assert pushed > 4096 + 2 * 1024  # the pool wrapped, with windows after it
    sel = [ln for ln in out if ln.startswith("selectors: ")][0]
    assert sel.endswith("ring=NativeRing loop=engine")
    assert any(ln.startswith("cell: ") and ln.endswith("kit=qinq")
               for ln in out)
    return json.loads(out[-1]), out


@pytest.mark.parametrize("seed,trace", [(3000000047, "0"), (2**31 + 48, "1")])
def test_the_cell_is_correct_past_the_pools_wrap(cell_dir, capsys, seed, trace):
    res, out = _run(cell_dir, capsys, seed, "--trace", trace)
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert all(c["value"] == 0 for c in res["compared"].values())
    assert "punted_frames" in res["compared"]
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    # DHCP, IPoE and PPPoE data replies are all in the sample
    assert " 0 " not in sample and "none-" not in sample, sample
    assert "IPoE" in sample and "PPPoE" in sample and "tagged" in sample
    got = res["metrics"]
    if trace == "0":
        assert set(got) == {"served_kpps", "setup_s"}
        return
    files = cellfiles.listed(cell_dir, REAL)
    name = {k: cellfiles.reading(files, **read) for k, read in (
        ("gen", cellfiles.GEN_SHARE), ("loop", cellfiles.LOOP_US),
        ("beat", cellfiles.BEAT_P99), ("step", cellfiles.STEP_P50),
        ("tick", cellfiles.TICK_MS),
        *((k, cellfiles.counter(f"engine.trace.qinq_{k}"))
          for k in ("push", "pop", "miss")))}
    for k in ("loop", "gen", "beat", "tick"):
        assert got[name[k]]["value"] > 0, name[k]
    assert got[name["gen"]]["value"] < 100.0
    assert name["step"] not in got  # no device trace on the CPU
    said = [ln for ln in out if ln.startswith("per-layer metrics with nothing")]
    assert said and name["step"] in said[0]
    # the three counters, through `engine.trace` by their layer files: every
    # data frame of a retired window was popped or pushed, none missed
    push, pop, miss = (got[name[k]]["value"]
                       for k in ("push", "pop", "miss"))
    assert push > 0 and pop > 0 and miss == 0
    frames = got["tiny.frames_per_step"]["value"]  # 5% of them DHCP
    assert 0.90 * frames < push + pop < frames <= 1024
    # a retire's reads: P's eleven (verdict, out_pkt, out_len, two flag
    # columns, six stats blocks) and the stage's one block more, each
    # one's copy started at its step's dispatch since PR 43
    assert got["tiny.fetch_calls_per_step"]["value"] == 0
    assert got["tiny.prefetch_calls_per_step"]["value"] == \
        pytest.approx(3 + 2 + 7, abs=0.25)


def test_both_controls_fail_by_the_sample(cell_dir, capsys):
    for control in bench_run.CONTROLS:
        res, out = _run(cell_dir, capsys, 3000000049, "--control", control)
        assert res["correct"] is False and res["failed"] > 0, control
        bad = res["compared"]
        assert bad["sampled_replies_differing"]["value"] > 0, control
        assert all(c["value"] == 0 for k, c in bad.items()
                   if k != "sampled_replies_differing"), (control, bad)


def test_the_generators_frames_are_the_framing_the_reference_strips():
    """The kit patches tags and session framing in as bytes: every length
    is the cell's, and `Plain` strips each upstream frame back to the
    default kit's frame of the same draw."""
    from benchmark.kits import ipoe

    kit = applib.load_kit({"kit": "qinq"})
    cfg = {"sizes": dict(SIZES)}

    class App:
        class config:
            server_mac = "02:aa:bb:cc:dd:01"
            server_ip = "10.0.0.1"

    seed = 2**31 + 7
    lay = kit.Layout(cfg, seed)
    assert kit.Layout({"sizes": {k: v for k, v in SIZES.items()
                                 if k != "pppoe_sessions"}},
                      seed).pppoe_sessions == 256  # the kit's own default
    s, c = lay.pairs(np.arange(lay.subscribers))
    assert len({(a, b) for a, b in zip(s.tolist(), c.tolist())}) == 4096
    assert (s.min(), s.max(), c.min(), c.max()) == (1, 2, 1, 4094)
    s2, c2 = lay.pairs(np.arange(lay.subscribers), moved=True)
    moved = (s2 != s) | (c2 != c)
    assert moved.sum() == 512 and moved[::8].all()
    assert not {(a, b) for a, b in zip(s2[moved].tolist(), c2[moved].tolist())
                } & {(a, b) for a, b in zip(s.tolist(), c.tolist())}
    prov = {"nat_ip": np.full(2048, 0xC6120001, np.uint32),
            "nat_port": np.arange(2048, dtype=np.uint32) + 1024,
            "session_id": np.arange(256, dtype=np.uint32) + 1}
    mix = dict(applib.load_named("traffic", "flood-64B"), pool_frames=2048)
    tr = kit.Traffic(mix, lay, prov, App, seed, 0.0)
    macs = [int(m).to_bytes(6, "big")
            for m in lay.sub_macs(lay.nat_sub_index(np.arange(256)))]
    plain = kit.Plain({}, {}, {k + 1: (macs[k], 0) for k in range(256)},
                      b"\x02\xaa\xbb\xcc\xdd\x01")
    lengths = {"dhcp": set(), "ipoe-up": set(), "pppoe-up": set(),
               "down": set()}
    for i in range(tr.n):
        f, sub = tr.frames[i], tr.sub_of(i)
        if tr.is_dhcp[i]:
            lengths["dhcp"].add(len(f))
            assert sub in set(lay.ipoe_subs().tolist())
        elif tr.kind[i] == ipoe.DOWN:
            lengths["down"].add(len(f))
            assert kit.Plain.untag(f) == ((), f)
            continue
        else:
            pppoe = tr.key[i] // lay.flows_per < lay.pppoe_sessions
            lengths["pppoe-up" if pppoe else "ipoe-up"].add(len(f))
            inner = plain.up(f)
            assert inner is not None and len(inner) == 60
            assert inner[12:14] == b"\x08\x00" and tr.reply_id(inner) == (False, i)
        tags, _body = kit.Plain.untag(f)
        assert tags == (int(s[sub]), int(c[sub]))
    assert lengths == {"dhcp": {370}, "ipoe-up": {68}, "pppoe-up": {76},
                       "down": {60}}
    assert kit.stage_bytes(8192, 1536) == 4 * 8192 * 1536


def test_the_cell_is_in_the_benchmark_as_pr40_put_it():
    """What tests/benchmark/test_qinq_stand_in.py holds of the cell's entries,
    but for its pin that the cell is the LAST name in `served_kpps.workloads`
    (a cell added since is appended after it; tests/conftest.py marks that
    test): the cell, its configuration, and one entry a file that lists it."""
    from benchmark.lib import layers

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[REAL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qinq-pppoe-cgnat-1M-wire", "flood-64B", 1)
    assert "no frame crossed a link" in cell["why"]
    cfg = applib.load_named("configs", cell["config"])
    base = applib.load_named("configs", "pppoe-cgnat-1M-wire")
    assert cfg["kit"] == "qinq" and cfg["reduced"] == ["max_nat_sessions"]
    assert cfg["architecture"] is None and "framing" in cfg
    assert cfg["sizes"] == dict(base["sizes"], qinq_pairs=1_000_000)
    assert cfg["argv"] == base["argv"] + ["--qinq-enabled"]
    assert "QinQ" not in cfg["off"]
    assert cfg["guarantees"] == applib.load_named("configs", "ipoe-cgnat-1M")[
        "guarantees"]
    named = {m["name"] for m in layers.layer_files(applib.BENCH_DIR)
             if REAL in m["cells"]}
    assert named and {m["name"] for m in bench["per_layer"]
                      if REAL in m["workloads"]} == named
    served = {m["name"]: m for m in bench["end_to_end"]}["served_kpps"]
    assert REAL in served["workloads"]


def test_the_tables_take_a_million_pairs_through_the_bulk_writers():
    """Sized as `bng run --qinq-enabled --max-subscribers 1000000` sizes
    them: the pair table, its registry and `vlan_subscriber_pools` hold
    1,000,000 pairs laid out as the configuration lays them, the stash all
    but untouched, each read back."""
    from bng_tpu.control.qinq import VLANPair
    from bng_tpu.ops.qinq import QV_C_TAG, QV_S_TAG
    from bng_tpu.ops.table import WAYS, nbuckets_for
    from bng_tpu.runtime.tables import FastPathTables, QinQFastPathTables

    n = 1_000_000
    nb = nbuckets_for(n)
    i = np.arange(n)
    s, c = (1 + i // 4094).astype(np.uint32), (1 + i % 4094).astype(np.uint32)
    ips = (i + ((10 << 24) | (16 << 16))).astype(np.uint32)
    assert int(s.max()) == 245
    q = QinQFastPathTables(nbuckets=nb)
    q.bulk_bind(ips, s, c)
    assert q.by_ip.count == n and q.by_ip._dirty_all
    assert int(q.by_ip.used[nb * WAYS:].sum()) <= 8  # the stash
    at = np.random.default_rng(7).integers(0, n, 4096)
    rows = q.by_ip.lookup_batch_host(ips[at, None])
    assert (rows[:, QV_S_TAG] == s[at]).all() and (rows[:, QV_C_TAG] == c[at]).all()
    assert q.registry.stats()["double_tagged"] == n
    assert q.registry.get_subscriber(VLANPair(245, 1 + (n - 1) % 4094)) == int(ips[-1])
    assert not q.bind(1, 1, 1)  # subscriber 0's line
    fp = FastPathTables(sub_nbuckets=64, vlan_nbuckets=nb, cid_nbuckets=64,
                        max_pools=4)
    fp.add_vlan_subscribers_bulk(s, c, 1, ips, np.uint32(99))
    assert fp.vlan.count == n
    keys = ((s[at] << np.uint32(16)) | c[at])[:, None]
    assert (fp.vlan.lookup_batch_host(keys)[:, 1] == ips[at]).all()
