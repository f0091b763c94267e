"""CPU rehearsal of `cgnat-1M-wire.flood-64B` across the wrap of the
flood's frame pool, where the pipelined ring loop's windows turn short.

The benchmark's own rehearsal (tests/benchmark, `tiny-wire.flood`) runs
1.5 s over a pool of 2,048 frames in windows of 256 lanes, and the defect
PR 27 met on the chip (PERF.md section 6, PR 28) never showed there. This
one runs the tiny wire app with `--batch-size 2048`, so a window takes
the ring's whole depth, over a pool of 8,192 frames: the one short push
at each wrap leaves a short window in a staging buffer a full window
used, and before the engine kept those lanes inert the device counted
them again (`dhcp_accepted_minus_device_hits` -887 and -694 for seeds 5
and 6 on the parent). Since PR 39 the windows cross rungs of the step's
ladder too (2,048: 128 / 256 / 2,048): a full window of 1,024 runs the
2,048 program, the short ones after a wrap the narrower rungs, whose
stale rows between the window's end and the rung's are the inert ones and
those beyond the rung never reach the chip. No number from here is a
device metric.
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
from bng_tpu.runtime import hostpath  # noqa: E402
from bng_tpu.runtime.engine import step_rungs  # noqa: E402

CELL = "tiny-wire-2048.flood-8192"
# the engine loop's counter files (PR 36's, and PR 37's crossings): the cell
# is appended to the real files' `cells` in the copy, read with no edit to
# the harness
COUNTERS = ("wire.masked_lanes_per_step", "engine.drain_built_per_step",
            "engine.drain_cached_per_step", "wire.frames_per_step",
            "wire.ring_us_per_frame", "wire.upload_calls_per_step",
            "wire.fetch_calls_per_step", "wire.upload_kb_per_step",
            "wire.fetch_kb_per_step", "wire.drain_us_per_step",
            "wire.lanes_per_step", "wire.prefetch_calls_per_step")


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def wrap_dir(tmp_path_factory):
    top = tmp_path_factory.mktemp("wrap")
    bdir = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bdir)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = applib.load_named("configs", "ipoe-cgnat-1M-wire", bdir)
    cfg.update(name="tiny-wire-2048",
               argv=["--pool-cidr", "10.0.0.0/11", "--batch-size", "2048",
                     "--synthetic-subs", "1", "--max-subscribers", "4096",
                     "--max-nat-sessions", "512", "--max-nat-subscribers",
                     "128"],
               sizes={"subscribers": 4096, "nat_subscribers": 128,
                      "flows_per_nat_subscriber": 2})
    cfg["nat_public_ips"]["count"] = 4
    _write(os.path.join(bdir, "configs", "tiny-wire-2048.json"), cfg)
    bench["configs"].append({"name": "tiny-wire-2048", "source": "test",
                             "file": "benchmark/configs/tiny-wire-2048.json",
                             "reduced": [], "why": "test"})
    flood = applib.load_named("traffic", "flood-64B", bdir)
    flood.update(name="tiny-flood-8192", pool_frames=8192, dhcp_share=0.05,
                 warmup_frames=400)
    _write(os.path.join(bdir, "traffic", "tiny-flood-8192.json"), flood)
    bench["workloads"].append({"name": CELL, "config": "tiny-wire-2048",
                               "traffic": "tiny-flood-8192", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "served_kpps":
            m["workloads"].append(CELL)
    for name in COUNTERS:
        m = applib.load_named("layers", name, bdir)
        m["cells"].append(CELL)
        _write(os.path.join(bdir, "layers", name + ".json"), m)
    _write(os.path.join(top, "BENCHMARK.json"), bench)
    return bdir


def _run(wrap_dir, capsys, seed, *extra):
    capsys.readouterr()
    # 5 s: at the 5,000 frames/s a loaded test machine reaches, twice what
    # it takes to cross the pool's end and serve two windows beyond it
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                         "5", "--bench-dir", wrap_dir, *extra])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    pushed = int([ln for ln in out if ln.startswith("window: ")][0]
                 .split("pushed ")[1].split(",")[0])
    assert pushed > 8192 + 2048  # the pool wrapped, with windows after it
    sel = [ln for ln in out if ln.startswith("selectors: ")][0]
    assert sel.endswith("ring=NativeRing loop=engine")
    return json.loads(out[-1]), out


@pytest.mark.parametrize("seed,trace", [(5, "0"), (6, "1")])
def test_the_hit_balance_holds_across_the_pools_wrap(wrap_dir, capsys, seed,
                                                     trace):
    """Untraced as the driver times it, and traced: there `masked_lanes`
    is read through `engine.trace` by its layer file, above 0 once a short
    window lands in a buffer a full one used, PR 35's drain counters
    beside it, and PR 37's crossings a step."""
    res, out = _run(wrap_dir, capsys, seed, "--trace", trace)
    assert "check dhcp_accepted_minus_device_hits=0 limit=0" in out
    assert res["correct"] is True and res["failed"] == 0, out[-14:]
    assert all(c["value"] == 0 for c in res["compared"].values())
    if trace == "1":
        got = res["metrics"]
        assert got["wire.masked_lanes_per_step"]["unit"] == "lanes"
        # far under a window a step: a buffer's stale lanes are cleared
        # once, not every time the short window comes round
        assert 0 < got["wire.masked_lanes_per_step"]["value"] < \
            got["wire.frames_per_step"]["value"]
        assert got["wire.ring_us_per_frame"]["value"] > 0
        # the tables are clean in the window (no slow-path DHCP, no punt):
        # every step's drain is answered from the chip, table for table
        # (3 fastpath, 3 NAT, 2 QoS, antispoof, the garden's)
        assert got["engine.drain_built_per_step"]["value"] == 0
        assert got["engine.drain_cached_per_step"]["value"] == 10
        # so a step uploads its staged window and nothing else (one block:
        # packet slots, lengths, access flags), and a retire reads ten arrays:
        # verdict, out_pkt, out_len; violation and punt flags; five stats
        # blocks (dhcp, nat, qos, spoof, garden)
        assert got["wire.upload_calls_per_step"]["value"] == 1
        # the windows crossed rungs: full ones (1,024 frames) took the 2,048
        # program, short ones a narrower rung, so the mean is under 2,048
        # and over the frames it carried; what goes up and comes back is
        # the rung's rows and no others
        lanes = got["wire.lanes_per_step"]["value"]
        assert got["wire.lanes_per_step"]["unit"] == "lanes"
        assert got["wire.frames_per_step"]["value"] < lanes < 2048
        # (since PR 51 the rung's rows and the one to seven behind them
        # that hold its lengths and access flags: one block a window)
        meta = [hostpath.window_meta_rows(b, 1536) for b in step_rungs(2048)]
        assert meta == [1, 1, 7]
        rows = got["wire.upload_kb_per_step"]["value"] * 1024 / 1536
        assert lanes + min(meta) < rows < lanes + max(meta)
        # since PR 43 the copy of each of the ten is started when its step
        # is dispatched: none is a blocking crossing at the retire
        assert got["wire.prefetch_calls_per_step"]["value"] == \
            pytest.approx(3 + 2 + 5, abs=0.25)
        assert got["wire.fetch_calls_per_step"]["value"] == 0
        assert got["wire.fetch_kb_per_step"]["value"] == 0
        assert got["wire.drain_us_per_step"]["value"] > 0


def test_the_stale_binding_control_still_fails_and_by_the_sample_alone(
        wrap_dir, capsys):
    res, out = _run(wrap_dir, capsys, 5, "--control", "stale-binding")
    assert res["correct"] is False and res["failed"] > 0
    assert res["compared"]["sampled_replies_differing"]["value"] > 0
    assert res["compared"]["dhcp_accepted_minus_device_hits"]["value"] == 0
