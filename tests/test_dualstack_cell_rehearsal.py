"""CPU rehearsal of `dualstack-cgnat-1M-wire.flood-64B`: the configuration
and its kit dropped into a temporary copy of the benchmark at 4,096
dual-stack subscribers, through `run.py`'s own loop past the frame pool's
wrap. Two data frames in five are IPv6, leave byte for byte, and are held
to the kit's plain reference; the others are NAT'd IPv4 and DHCP, held as
the default kit holds them. No number from here is a device metric.

Also here: what the cell rests on in the program. The by-address table
sized as `bng run --ipv6-fastpath` sizes it holds 1,000,000 bindings'
geometry; the bulk writer and the lease-by-lease writer leave the same
rows; the Tracer's three IPv6 counters are what the engine folds.
"""

import ipaddress
import json
import os
import shutil
import struct
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import app as applib  # noqa: E402
import cellfiles  # noqa: E402

REAL = "dualstack-cgnat-1M-wire.flood-64B"
CELL = "tiny-dualstack-1024.flood-4096"
# the cell's layer files are taken from what lists the cell (cellfiles.py),
# by what each reads; only `tiny.frames_per_step`, which the benchmark
# does not have, is dropped in
FRAMES = {"name": "tiny.frames_per_step", "unit": "frames",
          "better": "higher", "source": "program_counter",
          "layer": "engine (runtime/engine.py)", "moves": "served_kpps",
          "cells": [CELL],
          "read": {"kind": "counter", "path": "ring.rx",
                   "per": "engine.batches"}}


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def cell_dir(tmp_path_factory):
    top = tmp_path_factory.mktemp("dualstack")
    bdir = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bdir)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = applib.load_named("configs", "dualstack-cgnat-1M-wire", bdir)
    assert cfg["kit"] == "dualstack" and cfg["argv"][-1] == "--ipv6-fastpath"
    cfg.update(name="tiny-dualstack-1024",
               argv=["--pool-cidr", "10.0.0.0/11", "--batch-size", "1024",
                     "--synthetic-subs", "1", "--max-subscribers", "4096",
                     "--max-nat-sessions", "4096", "--max-nat-subscribers",
                     "1024", "--ipv6-fastpath"],
               sizes={"subscribers": 4096, "nat_subscribers": 1024,
                      "flows_per_nat_subscriber": 2, "v6_bindings": 4096,
                      "v6_data_share_pct": 40})
    cfg["nat_public_ips"]["count"] = 20
    _write(os.path.join(bdir, "configs", "tiny-dualstack-1024.json"), cfg)
    bench["configs"].append({"name": "tiny-dualstack-1024", "source": "test",
                             "file": "benchmark/configs/tiny-dualstack-1024.json",
                             "reduced": [], "why": "test"})
    flood = applib.load_named("traffic", "flood-64B", bdir)
    flood.update(name="tiny-flood-4096", pool_frames=4096, dhcp_share=0.05,
                 warmup_frames=400)
    _write(os.path.join(bdir, "traffic", "tiny-flood-4096.json"), flood)
    bench["workloads"].append({"name": CELL, "config": "tiny-dualstack-1024",
                               "traffic": "tiny-flood-4096", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "served_kpps":
            m["workloads"].append(CELL)
    assert all(m["moves"] == "served_kpps"
               for m in cellfiles.stand_in(bdir, REAL, CELL))
    _write(os.path.join(bdir, "layers", FRAMES["name"] + ".json"), FRAMES)
    _write(os.path.join(top, "BENCHMARK.json"), bench)
    return bdir


def _run(cell_dir, capsys, seed, *extra, wrapped=True):
    capsys.readouterr()
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                         "4", "--bench-dir", cell_dir, *extra])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    window = [ln for ln in out if ln.startswith("window: ")][0]
    pushed = int(window.split("pushed ")[1].split(",")[0])
    if wrapped:  # the pool wrapped, with windows after it
        assert pushed > 4096 + 2 * 1024
    sel = [ln for ln in out if ln.startswith("selectors: ")][0]
    assert sel.endswith("ring=NativeRing loop=engine")
    assert any(ln.startswith("cell: ") and ln.endswith("kit=dualstack")
               for ln in out)
    return json.loads(out[-1]), out


@pytest.mark.parametrize("seed,trace", [(3000000051, "0"), (2**31 + 52, "1")])
def test_the_cell_is_correct_past_the_pools_wrap(cell_dir, capsys, seed, trace):
    res, out = _run(cell_dir, capsys, seed, "--trace", trace)
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert all(c["value"] == 0 for c in res["compared"].values())
    assert "punted_frames" in res["compared"]
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    # DHCP replies, translated IPv4 and forwarded IPv6 are all in the sample
    assert " 0 " not in sample and "(0 " not in sample and "none-" not in sample
    assert "IPv6 byte-for-byte" in sample
    got = res["metrics"]
    if trace == "0":
        assert set(got) == {"served_kpps", "setup_s"}
        return
    files = cellfiles.listed(cell_dir, REAL)
    name = {k: cellfiles.reading(files, **read) for k, read in (
        ("gen", cellfiles.GEN_SHARE), ("loop", cellfiles.LOOP_US),
        ("beat", cellfiles.BEAT_P99), ("step", cellfiles.STEP_P50),
        ("up", cellfiles.UPLOAD_CALLS), ("fetch", cellfiles.FETCH_CALLS),
        ("prefetch", cellfiles.PREFETCH_CALLS),
        *((k, cellfiles.counter(f"engine.trace.v6_{k}"))
          for k in ("fwd", "miss", "ctrl")))}
    for k in ("loop", "gen", "beat"):
        assert got[name[k]]["value"] > 0, name[k]
    assert got[name["gen"]]["value"] < 100.0
    assert name["step"] not in got  # no device trace here
    said = [ln for ln in out if ln.startswith("per-layer metrics with nothing")]
    assert said and name["step"] in said[0]
    # the three counters, through `engine.trace` by their layer files:
    # two in five of a retired window's data frames were forwarded as IPv6
    per_step = {k: got[name[k]]["value"] for k in ("fwd", "miss", "ctrl")}
    frames = got["tiny.frames_per_step"]["value"]  # 5% of them DHCP
    assert per_step["miss"] == 0 and per_step["ctrl"] == 0
    assert 0.36 * 0.95 * frames < per_step["fwd"] < 0.44 * 0.95 * frames
    assert frames <= 1024
    # a step's crossings: the staged window up (one block in one call since
    # PR 51); a retire reads verdict,
    # out_pkt, out_len, the violation and punt flags and six stats blocks
    # (dhcp, nat, qos, spoof, garden, v6): since PR 43 each one's copy was
    # started at its step's dispatch, so the reads cross nothing
    assert got[name["up"]]["value"] == 1
    assert got[name["fetch"]]["value"] == 0
    assert got[name["prefetch"]]["value"] == \
        pytest.approx(3 + 2 + 6, abs=0.25)


def test_both_controls_fail(cell_dir, capsys):
    """`bad-checksum` by the sample alone. `stale-binding` (one subscriber
    in eight renumbered, the device's rows from before): its upstream IPv6
    is a strict violation, counted as a drop, and its downstream IPv6 a miss
    the host has no answer for, a lost frame; nothing that left differs."""
    res, out = _run(cell_dir, capsys, 3000000053, "--control", "bad-checksum")
    assert res["correct"] is False and res["failed"] > 0
    bad = res["compared"]
    assert bad["sampled_replies_differing"]["value"] > 0
    assert all(c["value"] == 0 for k, c in bad.items()
               if k != "sampled_replies_differing"), bad
    res, out = _run(cell_dir, capsys, 3000000053, "--control", "stale-binding",
                    wrapped=False)
    assert res["correct"] is False and res["failed"] > 0
    bad = res["compared"]
    assert bad["counted_drops"]["value"] > 0
    assert bad["lost_frames"]["value"] > 0
    assert bad["punted_frames"]["value"] == bad["lost_frames"]["value"]
    assert bad["sampled_replies_differing"]["value"] == 0
    assert bad["dhcp_accepted_minus_device_hits"]["value"] == 0


def test_the_generators_v6_frames_are_what_the_cell_states():
    """66 bytes UDP and 78 bytes TCP by subscriber, port 443, untagged, no
    extension header, a valid L4 checksum; each downstream frame the reverse
    of an upstream one; two data frames in five; the default kit's draw for
    everything else."""
    from benchmark.kits import ipoe

    kit = applib.load_kit({"kit": "dualstack"})
    cfg = {"sizes": {"subscribers": 4096, "nat_subscribers": 1024,
                     "flows_per_nat_subscriber": 2}}

    class App:
        class config:
            server_mac = "02:aa:bb:cc:dd:01"
            server_ip = "10.0.0.1"

    lay = kit.Layout(cfg, 2**31 + 5)
    assert lay.v6_bindings == 4096 and lay.v6_share == 0.4
    prov = {"nat_ip": np.full(2048, 0xC6120001, np.uint32),
            "nat_port": np.arange(2048, dtype=np.uint32) + 1024}
    mix = dict(applib.load_named("traffic", "flood-64B"), pool_frames=1000)
    tr = kit.Traffic(mix, lay, prov, App, 2**31 + 5, 0.0)
    plain = ipoe.Traffic(mix, lay, prov, App, 2**31 + 5, 0.0)
    ups = np.nonzero(tr.kind == kit.UP6)[0]
    downs = np.nonzero(tr.kind == kit.DOWN6)[0]
    n_data = int((~tr.is_dhcp).sum())
    assert n_data == 980 and len(ups) == len(downs) == 196  # 40% of 490 each
    assert ((tr.kind == plain.kind) | (tr.kind >= kit.UP6)).all()
    v4 = tr.kind < kit.UP6
    assert [tr.frames[i] for i in np.nonzero(v4)[0]] == [
        plain.frames[i] for i in np.nonzero(v4)[0]]
    net = ipaddress.IPv6Network(kit.V6_PREFIX)
    pool_cap = 1 << 20  # AddressPool6.size
    for i, j in zip(ups, downs):
        up, down = tr.frames[i], tr.frames[j]
        sub = int(tr.key[i])
        assert tr.key[j] == sub and 0 <= sub < 4096
        assert len(up) == len(down) == (66 if sub % 2 == 0 else 78)
        assert up[12:14] == down[12:14] == b"\x86\xdd" and up[20] == down[20]
        assert up[20] == (17 if sub % 2 == 0 else 6)
        assert up[6:12] == int(lay.sub_macs([sub])[0]).to_bytes(6, "big")
        src = ipaddress.IPv6Address(up[22:38])
        assert src in net and int(src) - int(net.network_address) == sub + 1
        assert sub + 1 < pool_cap
        assert ipaddress.IPv6Address(up[38:54]) in ipaddress.IPv6Network(
            "2001:db8:ffff::/48")
        assert (down[22:38], down[38:54]) == (up[38:54], up[22:38])
        assert struct.unpack("!HH", up[54:58]) == (40000, 443)
        assert struct.unpack("!HH", down[54:58]) == (443, 40000)
        assert struct.unpack("!H", up[18:20])[0] == len(up) - 54
        assert tr.reply_id(up) == (False, i) and tr.reply_id(down) == (False, j)
        for f in (up, down):  # the checksum over the IPv6 pseudo-header
            seg = f[54:]
            pseudo = f[22:54] + struct.pack("!IHBB", len(seg), 0, 0, f[20])
            words = struct.unpack(f"!{(len(pseudo) + len(seg)) // 2}H",
                                  pseudo + seg)
            total = sum(words)
            while total >> 16:
                total = (total & 0xFFFF) + (total >> 16)
            assert total == 0xFFFF
    assert kit.stage_bytes(8192, 1536) == 8192 * (64 + 288)


def test_the_bulk_writer_and_the_lease_writer_leave_the_same_rows():
    """1,000,000 bindings' geometry (`_sized(max_subscribers)`): 524,288
    buckets of four 8-word ways; the bulk pass at 20,000 subscribers writes
    what 20,000 leases would, and every address is found with its v4."""
    from bng_tpu.ops import antispoof as A
    from bng_tpu.ops.table import WAYS, nbuckets_for
    from bng_tpu.runtime.engine import AntispoofTables
    from bng_tpu.runtime.tables import V6FastPathTables

    kit = applib.load_kit({"kit": "dualstack"})
    lay = kit.Layout({"sizes": {"subscribers": 20000, "nat_subscribers": 64,
                                "flows_per_nat_subscriber": 2}}, 9)
    assert nbuckets_for(1_000_000) == 524_288
    idx = np.arange(lay.subscribers)
    macs, ips, words = lay.sub_macs(idx), lay.sub_ips(idx), lay.sub_v6(idx)
    bulk = V6FastPathTables(AntispoofTables(nbuckets=nbuckets_for(20000)),
                            nbuckets=nbuckets_for(20000))
    bulk.bulk_bind(macs, ips, words, A.MODE_STRICT)
    assert bulk.by_addr.count == bulk.antispoof.bindings.count == 20000
    assert int(bulk.by_addr.used[bulk.by_addr.nbuckets * WAYS:].sum()) == 0
    one = V6FastPathTables(AntispoofTables(nbuckets=nbuckets_for(20000)),
                           nbuckets=nbuckets_for(20000))
    addrs = kit.words_bytes(words)
    for k in range(0, 20000, 97):
        one.antispoof.add_binding(int(macs[k]), int(ips[k]), A.MODE_STRICT)
        one.bind(int(macs[k]), addrs[k])
        key = [int(macs[k]) >> 32, int(macs[k]) & 0xFFFFFFFF]
        assert (one.antispoof.bindings.lookup(key)
                == bulk.antispoof.bindings.lookup(key)).all()
        assert (one.by_addr.lookup(words[k])
                == bulk.by_addr.lookup(words[k])).all()
        assert int(bulk.by_addr.lookup(words[k])[0]) == int(ips[k])
    row = bulk.antispoof.bindings.lookup([int(macs[5]) >> 32,
                                          int(macs[5]) & 0xFFFFFFFF])
    assert row[A.AB_VALIDS] == A.VALID_V4 | A.VALID_V6
    assert row[A.AB_MODE] == A.MODE_STRICT


def test_the_tracer_counts_the_v6_lanes_the_engine_folds():
    from bng_tpu.control.nat import NATManager
    from bng_tpu.ops import v6 as V
    from bng_tpu.runtime.engine import AntispoofTables, Engine
    from bng_tpu.runtime.tables import FastPathTables, V6FastPathTables
    from bng_tpu.telemetry import spans

    assert {k: spans._ZERO_SUMS[k] for k in ("v6_fwd", "v6_miss", "v6_ctrl")
            } == {"v6_fwd": 0, "v6_miss": 0, "v6_ctrl": 0}
    spoof = AntispoofTables(nbuckets=64)
    eng = Engine(FastPathTables(sub_nbuckets=64, vlan_nbuckets=64,
                                cid_nbuckets=64, max_pools=4),
                 NATManager(public_ips=[0xCB007101], sessions_nbuckets=256,
                            sub_nat_nbuckets=64),
                 antispoof=spoof, v6=V6FastPathTables(spoof, nbuckets=64),
                 batch_size=4)

    class Res:
        dhcp_stats = nat_stats = qos_stats = spoof_stats = 0
        v6_stats = np.zeros(V.V6_NSTATS, np.uint32)

    Res.v6_stats[[V.V6ST_FWD_UP, V.V6ST_FWD_DOWN, V.V6ST_MISS,
                  V.V6ST_CTRL]] = (5, 3, 2, 7)
    tr = spans.arm(spans.Tracer())
    try:
        eng._fold_stats(Res)
        eng._fold_stats(Res)
    finally:
        spans.disarm()
    got = tr.sums()
    assert (got["v6_fwd"], got["v6_miss"], got["v6_ctrl"]) == (16, 4, 14)
    assert list(eng.stats.v6) == [10, 6, 4, 14]
    eng._fold_stats(Res)  # disarmed: the engine's own stats still move
    assert list(eng.stats.v6) == [15, 9, 6, 21] and tr.sums()["v6_fwd"] == 16
