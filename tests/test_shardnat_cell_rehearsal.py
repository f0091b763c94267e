"""CPU rehearsal of `cgnat-sharded4-1M.flood-64B`: the configuration and its
kit dropped into a temporary copy of the benchmark at 4,096 subscribers,
every one of them behind NAT (8,192 flows), over four shards of the CPU
mesh with 80 public addresses dealt 20 a shard, through `run.py`'s own loop
past the frame pool's wrap. Every data frame is held to the kit's plain
reference (`Plain`: un-sharded, nothing of `bng_tpu`), byte for byte
outside the rewritten endpoint with both checksums verified; both controls
fail; and a program whose shards cannot hold their subscribers fails at
once with the kit's message. No number from here is a device metric.
"""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import app as applib  # noqa: E402
import cellfiles  # noqa: E402

pytestmark = pytest.mark.sharded

REAL = "cgnat-sharded4-1M.flood-64B"
CELL = "tiny4-nat-4096.flood-2048"
STARVED = "tiny4-nat-starved.flood-2048"
# the cell's layer files are taken from what lists the cell (cellfiles.py),
# by what each reads: a merge of a `shardnat.*` repeat into the `sharded.*`
# file it repeats renames what the cell reports and edits nothing here
SHARDED = "sharded.trace."
READS = {
    "step": cellfiles.STEP_P50,
    "collective": dict(kind="trace_device", stat="collective_share"),
    "nat_fwd": cellfiles.counter(SHARDED + "nat_fwd"),
    "nat_punt": cellfiles.counter(SHARDED + "nat_punt"),
    "steer_miss": cellfiles.counter("ring.steer_pub_miss"),
    "imbalance": cellfiles.counter("sharded.per_shard.*.frames"),
    "loop": cellfiles.LOOP_US, "gen": cellfiles.GEN_SHARE,
    "device_wait": cellfiles.counter(SHARDED + "stage_ns.device_wait"),
    "dispatch": cellfiles.counter(SHARDED + "stage_ns.dispatch"),
    "drain_built": cellfiles.counter(SHARDED + "drain_built"),
    "starved": cellfiles.counter(SHARDED + "beat_starved_ns")}
NO_DEVICE = ("step", "collective")
# dropped in: what the `sharded.*` files read in S (their `cells` may not
# be edited), read here to hold "no new read from the chips"
DROPPED = [
    {"name": "tiny.frames_per_step", "unit": "frames", "better": "higher",
     "source": "program_counter",
     "layer": "sharded engine (parallel/sharded.py)", "moves": "served_kpps",
     "cells": [CELL],
     "read": {"kind": "counter", "path": "ring.rx",
              "per": "sharded.trace.batches"}},
    {"name": "tiny.fetch_calls_per_step", "unit": "calls",
     "better": "lower", "source": "program_counter",
     "layer": "sharded engine (parallel/sharded.py)", "moves": "served_kpps",
     "cells": [CELL],
     "read": {"kind": "counter", "path": "sharded.trace.xfer.fetch_calls",
              "per": "sharded.trace.batches"}},
    {"name": "tiny.prefetch_calls_per_step", "unit": "calls",
     "better": "higher", "source": "program_counter",
     "layer": "sharded engine (parallel/sharded.py)", "moves": "served_kpps",
     "cells": [CELL],
     "read": {"kind": "counter", "path": "sharded.trace.xfer.prefetch_calls",
              "per": "sharded.trace.batches"}},
    {"name": "tiny.steer_hit_per_s", "unit": "frames/s",
     "better": "higher", "source": "program_counter",
     "layer": "ring (runtime/ring.py)", "moves": "served_kpps",
     "cells": [CELL],
     "read": {"kind": "counter", "path": "ring.steer_pub_hit",
              "per": "second"}},
]
SIZES = {"subscribers": 4096, "nat_subscribers": 4096,
         "flows_per_nat_subscriber": 2}
ARGV = ["--pool-cidr", "10.0.0.0/11", "--batch-size", "1024",
        "--synthetic-subs", "1", "--shards", "4", "--shard-nbuckets", "1024",
        "--max-nat-sessions", "8192", "--max-nat-subscribers", "4096"]


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def cell_dir(tmp_path_factory):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    top = tmp_path_factory.mktemp("shardnat")
    bdir = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bdir)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = applib.load_named("configs", "ipoe-cgnat-sharded4-1M", bdir)
    assert cfg["kit"] == "shardnat" and cfg["reduced"] == []
    flood = applib.load_named("traffic", "flood-64B", bdir)
    # 256 lanes a shard: the sharded lookup's exchange holds 128 keys a
    # destination (ops/table.py exchange_capacity), so 128 outstanding
    flood.update(name="tiny-flood-2048", pool_frames=2048, dhcp_share=0.05,
                 warmup_frames=400, outstanding_cap_of_ring_depth=128 / 1024)
    _write(os.path.join(bdir, "traffic", "tiny-flood-2048.json"), flood)
    for name, cell, count in (("tiny4-nat-4096", CELL, 80),
                              ("tiny4-nat-starved", STARVED, 4)):
        c = dict(cfg, name=name, argv=ARGV, sizes=dict(SIZES),
                 nat_public_ips=dict(cfg["nat_public_ips"], count=count))
        _write(os.path.join(bdir, "configs", name + ".json"), c)
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": "tiny-flood-2048", "chips": 4,
                                   "why": "test"})
        for m in bench["end_to_end"]:
            if m["name"] == "served_kpps":
                m["workloads"].append(cell)
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
    assert pushed > 2048 + 2 * 128  # the pool wrapped, with windows after it
    sel = [ln for ln in out if ln.startswith("selectors: ")][0]
    assert sel.endswith("ring=NativeRing sharded")
    assert any(ln.startswith("cell: ") and ln.endswith("kit=shardnat")
               for ln in out)
    return json.loads(out[-1]), out


@pytest.mark.parametrize("seed,trace", [(3000000071, "0"), (2**31 + 72, "1")])
def test_the_cell_is_correct_past_the_pools_wrap(cell_dir, capsys, seed, trace):
    res, out = _run(cell_dir, capsys, seed, "--trace", trace)
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert all(c["value"] == 0 for c in res["compared"].values())
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    assert " 0 " not in sample and "both checksums verified" in sample
    # provisioning said what each shard holds and what the reference checked
    said = [ln for ln in out if ln.startswith("shards: ")][0]
    assert said.count("20 addresses") == 4 and "of 1260 blocks" in said
    assert "'flows': 8192" in said and "'owners': 4" in said
    took = [ln for ln in out if ln.startswith("provisioned: ")][0]
    assert all(step in took for step in ("nat_blocks", "nat_flows",
                                         "guarantee", "upload"))
    got = res["metrics"]
    if trace == "0":
        assert set(got) == {"served_kpps", "setup_s"}
        return
    files = cellfiles.listed(cell_dir, REAL)
    name = {k: cellfiles.reading(files, **read) for k, read in READS.items()}
    assert {name[k] for k in READS if k not in NO_DEVICE} <= set(got)
    left = [ln for ln in out if ln.startswith("per-layer metrics with nothing")]
    assert left and all(name[k] in left[0] for k in NO_DEVICE)
    # every data frame of a retired step was translated on its own shard:
    # none punted, none steered by the hash, no table built in a drain
    frames = got["tiny.frames_per_step"]["value"]
    fwd = got[name["nat_fwd"]]["value"]
    assert 0.90 * frames < fwd < frames <= 1024  # 5% of them DHCP
    assert got[name["nat_punt"]]["value"] == 0
    assert got[name["steer_miss"]]["value"] == 0
    assert got["tiny.steer_hit_per_s"]["value"] > 0
    assert got[name["drain_built"]]["value"] == 0
    assert 1.0 <= got[name["imbalance"]]["value"] < 1.5
    for k in ("loop", "gen", "device_wait", "dispatch"):
        assert got[name[k]]["value"] > 0, name[k]
    # the two stamps ride the blocks a retire reads already: ten reads a
    # fused step as in S (a DHCP-only window's four pull the mean down),
    # every one's copy started at its dispatch (PR 44), none a crossing
    assert 9.0 < got["tiny.prefetch_calls_per_step"]["value"] <= 10.0
    assert got["tiny.fetch_calls_per_step"]["value"] == 0


def test_both_controls_fail_by_the_sample(cell_dir, capsys):
    for control in bench_run.CONTROLS:
        res, out = _run(cell_dir, capsys, 3000000073, "--control", control)
        assert res["correct"] is False and res["failed"] > 0, control
        bad = res["compared"]
        assert bad["sampled_replies_differing"]["value"] > 0, control
        assert all(c["value"] == 0 for k, c in bad.items()
                   if k != "sampled_replies_differing"), (control, bad)


def test_a_pool_too_small_for_a_shard_fails_at_once(cell_dir, capsys):
    """What the cell does on a program that gives a shard one address (the
    parent of PR 42): the kit's message, before anything is inserted."""
    with pytest.raises(applib.BenchError,
                       match=r"shard \d owns 1 public address\(es\), 63 port "
                             r"blocks of 1024, and 1024 NAT subscribers"):
        bench_run.main(["--workload", STARVED, "--seed", "5", "--seconds",
                        "1", "--bench-dir", cell_dir])
    out = capsys.readouterr().out
    assert "provisioned:" not in out and "build: bng run" in out


def test_every_file_of_the_cell_is_listed_with_its_cells():
    """Every file that lists the cell has an entry of its name with the same
    list, in the same order, and every entry that lists it a file; each of
    the reads the rehearsal holds is served by exactly one of them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]
              if REAL in m["workloads"]}
    files = {m["name"]: m for m in cellfiles.listed(applib.BENCH_DIR, REAL)}
    assert set(listed) == set(files) and len(files) >= len(READS)
    assert all(m["workloads"] == files[n]["cells"]
               and m["moves"] == files[n]["moves"] == "served_kpps"
               for n, m in listed.items())
    names = [cellfiles.reading(list(files.values()), **read)
             for read in READS.values()]
    assert len(set(names)) == len(READS)
