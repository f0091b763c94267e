"""CPU rehearsal of `pppoe-cgnat-1M-wire.flood-64B`: the configuration and
its kit dropped into a temporary copy of the benchmark at 4,096 subscribers
and 512 PPPoE sessions, through `run.py`'s own loop past the frame pool's
wrap. Every forwarded data frame leaves 8 bytes shorter (upstream: decap,
SNAT) or longer (downstream: DNAT, encap) than it came, and is held to the
codec-built reference. No number from here is a device metric.

Also here: what the cell rests on in the program. `PPPoEFastPathTables`
sized as `bng run --pppoe-enabled` sizes it holds an access concentrator's
65,535 sessions; the Tracer's three PPPoE counters are what the engine
folds.
"""

import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import cellfiles  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import app as applib  # noqa: E402

REAL = "pppoe-cgnat-1M-wire.flood-64B"
CELL = "tiny-pppoe-1024.flood-4096"
# the cell's layer files are taken from what lists the cell (cellfiles.py),
# by what each reads; only `tiny.frames_per_step`, which the benchmark does
# not have, is dropped in
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
    top = tmp_path_factory.mktemp("pppoe")
    bdir = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bdir)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = applib.load_named("configs", "pppoe-cgnat-1M-wire", bdir)
    assert cfg["kit"] == "pppoe"
    cfg.update(name="tiny-pppoe-1024",
               argv=["--pool-cidr", "10.0.0.0/11", "--batch-size", "1024",
                     "--synthetic-subs", "1", "--max-subscribers", "4096",
                     "--max-nat-sessions", "4096", "--max-nat-subscribers",
                     "1024", "--pppoe-enabled", "--pppoe-auth", "none"],
               sizes={"subscribers": 4096, "nat_subscribers": 1024,
                      "flows_per_nat_subscriber": 2, "pppoe_sessions": 512})
    cfg["nat_public_ips"]["count"] = 20
    _write(os.path.join(bdir, "configs", "tiny-pppoe-1024.json"), cfg)
    bench["configs"].append({"name": "tiny-pppoe-1024", "source": "test",
                             "file": "benchmark/configs/tiny-pppoe-1024.json",
                             "reduced": [], "why": "test"})
    flood = applib.load_named("traffic", "flood-64B", bdir)
    flood.update(name="tiny-flood-4096", pool_frames=4096, dhcp_share=0.05,
                 warmup_frames=400)
    _write(os.path.join(bdir, "traffic", "tiny-flood-4096.json"), flood)
    bench["workloads"].append({"name": CELL, "config": "tiny-pppoe-1024",
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
    assert any(ln.startswith("cell: ") and ln.endswith("kit=pppoe")
               for ln in out)
    return json.loads(out[-1]), out


@pytest.mark.parametrize("seed,trace", [(3000000041, "0"), (2**31 + 42, "1")])
def test_the_cell_is_correct_past_the_pools_wrap(cell_dir, capsys, seed, trace):
    res, out = _run(cell_dir, capsys, seed, "--trace", trace)
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert all(c["value"] == 0 for c in res["compared"].values())
    assert "punted_frames" in res["compared"]
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    # DHCP, upstream and downstream replies are all in the sample
    assert " 0 " not in sample and "none-" not in sample, sample
    assert "upstream, decapsulated" in sample and "downstream, framing" in sample
    got = res["metrics"]
    if trace == "0":
        assert set(got) == {"served_kpps", "setup_s"}
        return
    files = cellfiles.listed(cell_dir, REAL)
    name = {k: cellfiles.reading(files, **read) for k, read in (
        ("gen", cellfiles.GEN_SHARE), ("loop", cellfiles.LOOP_US),
        ("beat", cellfiles.BEAT_P99), ("step", cellfiles.STEP_P50),
        ("tick", cellfiles.TICK_MS), ("up", cellfiles.UPLOAD_CALLS),
        ("fetch", cellfiles.FETCH_CALLS),
        ("prefetch", cellfiles.PREFETCH_CALLS),
        *((k, cellfiles.counter(f"engine.trace.pppoe_{k}"))
          for k in ("decap", "encap", "miss")))}
    for k in ("loop", "gen", "beat"):
        assert got[name[k]]["value"] > 0, name[k]
    assert got[name["gen"]]["value"] < 100.0
    assert name["step"] not in got  # no device trace on the CPU
    said = [ln for ln in out if ln.startswith("per-layer metrics with nothing")]
    assert said and name["step"] in said[0]
    # the three counters, through `engine.trace` by their layer files:
    # every data frame of a retired window was decapsulated or encapsulated
    per_step = {k: got[name[k]]["value"] for k in ("decap", "encap", "miss")}
    assert per_step["decap"] > 0 and per_step["encap"] > 0
    assert per_step["miss"] == 0
    frames = got["tiny.frames_per_step"]["value"]  # 5% of them DHCP
    assert 0.90 * frames < per_step["decap"] + per_step["encap"] < frames <= 1024
    # the once-a-second walk over the sessions, in stage `slow_path`
    assert got[name["tick"]]["value"] > 0
    # a step's crossings: the staged window up (one block in one call since
    # PR 51); a retire reads verdict,
    # out_pkt, out_len, the violation and punt flags and six stats blocks
    # (dhcp, nat, qos, spoof, garden, pppoe): since PR 43 each one's copy
    # was started at its step's dispatch, so the reads cross nothing
    assert got[name["up"]]["value"] == 1
    assert got[name["fetch"]]["value"] == 0
    assert got[name["prefetch"]]["value"] == \
        pytest.approx(3 + 2 + 6, abs=0.25)


def test_both_controls_fail_by_the_sample(cell_dir, capsys):
    for control in bench_run.CONTROLS:
        res, out = _run(cell_dir, capsys, 3000000043, "--control", control)
        assert res["correct"] is False and res["failed"] > 0, control
        bad = res["compared"]
        assert bad["sampled_replies_differing"]["value"] > 0, control
        assert all(c["value"] == 0 for k, c in bad.items()
                   if k != "sampled_replies_differing"), (control, bad)


def test_the_generators_session_frame_is_the_codecs():
    """The kit patches the framing in as bytes; the codec builds the same."""
    from benchmark.kits import ipoe
    from bng_tpu.control.pppoe import codec

    kit = applib.load_kit({"kit": "pppoe"})
    cfg = {"sizes": {"subscribers": 4096, "nat_subscribers": 1024,
                     "flows_per_nat_subscriber": 2, "pppoe_sessions": 512}}

    class App:
        class config:
            server_mac = "02:aa:bb:cc:dd:01"
            server_ip = "10.0.0.1"

    lay = kit.Layout(cfg, 2**31 + 5)
    assert len(lay.ipoe_subs()) == 4096 - 512
    assert not set(lay.ipoe_subs()) & set(lay.pppoe_subs())
    prov = {"nat_ip": np.full(2048, 0xC6120001, np.uint32),
            "nat_port": np.arange(2048, dtype=np.uint32) + 1024,
            "session_id": np.arange(512, dtype=np.uint32) + 1}
    mix = dict(applib.load_named("traffic", "flood-64B"), pool_frames=512)
    tr = kit.Traffic(mix, lay, prov, App, 2**31 + 5, 0.0)
    plain = ipoe.Traffic(mix, lay, prov, App, 2**31 + 5, 0.0)
    n_dhcp = int(tr.is_dhcp.sum())
    assert n_dhcp == 10 and set(tr.key[:n_dhcp]) <= set(lay.ipoe_subs())
    assert tr.key[n_dhcp:].max() < lay.pppoe_flows
    ups = np.nonzero(tr.kind == ipoe.UP)[0]
    downs = np.nonzero(tr.kind == ipoe.DOWN)[0]
    assert len(ups) == len(downs) == 251
    for i in ups:
        f = tr.frames[i]
        dst, src, et, payload = codec.parse_eth(f)
        pkt = codec.PPPoEPacket.decode(payload)
        proto, ip = codec.parse_ppp(pkt.payload)
        sub = int(lay.nat_sub_index(tr.key[i] // lay.flows_per))
        assert len(f) == 68 and (et, proto, pkt.code) == (0x8864, 0x0021, 0)
        assert pkt.session_id == tr.key[i] // lay.flows_per + 1
        assert src == int(lay.sub_macs([sub])[0]).to_bytes(6, "big")
        assert f == codec.eth_frame(dst, src, 0x8864, codec.PPPoEPacket(
            code=0, session_id=pkt.session_id,
            payload=codec.ppp_frame(0x0021, ip)).encode())
        assert tr.reply_id(f) == (False, i)
    assert all(len(tr.frames[i]) == 60 for i in downs)
    # the same draw as the default kit's over the narrowed ranges
    assert (tr.kind == plain.kind).all() and kit.stage_bytes(8192, 1536) == 4 * 8192 * 1536


def test_the_session_tables_hold_an_access_concentrators_65535_sessions():
    """Sized as `bng run --pppoe-enabled` sizes them (cli.py, from
    `PPPoEServerConfig.max_sessions`): both tables take every session
    with the stash untouched; the default size the parent built could
    not (16,384 slots and a stash of 64)."""
    from bng_tpu.control.pppoe.server import PPPoEServerConfig
    from bng_tpu.ops.table import WAYS, nbuckets_for
    from bng_tpu.runtime.tables import PPPoEFastPathTables

    n = PPPoEServerConfig.max_sessions
    assert n == 0xFFFF
    fp = PPPoEFastPathTables(nbuckets=nbuckets_for(n))
    sid = np.arange(1, n + 1, dtype=np.uint32)
    mac = np.uint64(0x02AA12300000) + np.arange(n, dtype=np.uint64) * np.uint64(4)
    ip = np.uint32(0x0A100000) + np.arange(n, dtype=np.uint32) * np.uint32(4)
    fp.sessions_up_bulk(sid, mac, ip)
    for table, keys in ((fp.by_sid, sid), (fp.by_ip, ip)):
        assert table.count == n
        assert int(table.used[table.nbuckets * WAYS:].sum()) == 0  # the stash
        rows = table.lookup_batch_host(keys[:, None])
        assert (rows[:, 0] == sid).all() and (rows[:, 3] == ip).all()
    assert PPPoEFastPathTables().by_sid.nbuckets * WAYS < n


def test_the_tracer_counts_the_pppoe_lanes_the_engine_folds():
    from bng_tpu.control.nat import NATManager
    from bng_tpu.ops import pppoe as P
    from bng_tpu.runtime.engine import Engine
    from bng_tpu.runtime.tables import FastPathTables, PPPoEFastPathTables
    from bng_tpu.telemetry import spans

    assert {k: spans._ZERO_SUMS[k] for k in
            ("pppoe_decap", "pppoe_encap", "pppoe_miss")} == {
        "pppoe_decap": 0, "pppoe_encap": 0, "pppoe_miss": 0}
    eng = Engine(FastPathTables(sub_nbuckets=64, vlan_nbuckets=64,
                                cid_nbuckets=64, max_pools=4),
                 NATManager(public_ips=[0xCB007101], sessions_nbuckets=256,
                            sub_nat_nbuckets=64),
                 pppoe=PPPoEFastPathTables(nbuckets=64, stash=8), batch_size=4)

    class Res:
        dhcp_stats = nat_stats = qos_stats = spoof_stats = 0
        pppoe_stats = np.zeros(P.PPPOE_NSTATS, np.uint32)

    Res.pppoe_stats[[P.PST_DECAP, P.PST_ENCAP, P.PST_CTRL_PUNT, P.PST_BAD,
                     P.PST_MISS]] = (5, 3, 7, 11, 2)
    tr = spans.arm(spans.Tracer())
    try:
        eng._fold_stats(Res)
        eng._fold_stats(Res)
    finally:
        spans.disarm()
    got = tr.sums()
    assert (got["pppoe_decap"], got["pppoe_encap"], got["pppoe_miss"]) == (10, 6, 4)
    assert list(eng.stats.pppoe) == [10, 6, 14, 22, 4]
    eng._fold_stats(Res)  # disarmed: the engine's own stats still move
    assert spans.trace_sums()["pppoe_decap"] == 10 and eng.stats.pppoe[0] == 15
