"""CPU rehearsal of `multiisp-li-cgnat-1M-wire.flood-64B`: the configuration
and its kit dropped into a temporary copy of the benchmark at 4,096
subscribers with a route row each over four upstreams, 1,024 of them behind
NAT and 256 of those under a warrant (64 of them filtered), through
`run.py`'s own loop past the frame pool's wrap. Every upstream data frame
leaves translated AND for the gateway the plain reference elects, every
frame a warrant takes is at the sink as it was pushed, and the stage's
counters read what the traffic pushed. No number from here is a device
metric.

The cell is in `BENCHMARK.json` with one per-layer entry of its own
(`edge.mirror_us_per_step`, entry 128 of the format's 128). The layer files
are taken from what lists the cell (`layers.layer_files`,
`REAL in m["cells"]`), not by name: a `benchmark` PR that merges or renames
them, or lets the loop's generic files list the cell, edits nothing here.
"""

import ast
import inspect
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import app as applib  # noqa: E402
from benchmark.lib import layers  # noqa: E402

REAL = "multiisp-li-cgnat-1M-wire.flood-64B"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELL = "tiny-multiisp-1024.flood-4096"
ENGINE = "engine (runtime/engine.py)"


def counter(name, path, unit="lanes", better="higher"):
    return {"name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": ENGINE,
            "moves": "served_kpps", "cells": [CELL],
            "read": {"kind": "counter", "path": path, "per": "engine.batches"}}


# dropped in: the stage's four counts and the loop's reads, which no file of
# the benchmark reads in this cell yet (`per_layer` is full: PERF.md §7 row 1)
DROPPED = [
    counter("t.mirrored_per_step", "engine.trace.edge_mirrored"),
    counter("t.filtered_per_step", "engine.trace.edge_filtered"),
    counter("t.rewrites_per_step", "engine.trace.edge_rewrites"),
    counter("t.route_miss_per_step", "engine.trace.edge_route_miss",
            better="lower"),
    counter("t.frames_per_step", "ring.rx", "frames"),
    counter("t.fetch_calls_per_step", "engine.trace.xfer.fetch_calls",
            "calls", "lower"),
    counter("t.prefetch_calls_per_step", "engine.trace.xfer.prefetch_calls",
            "calls"),
]
SIZES = {"subscribers": 4096, "nat_subscribers": 1024,
         "flows_per_nat_subscriber": 2}


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def cell_dir(tmp_path_factory):
    top = tmp_path_factory.mktemp("multiisp")
    bdir = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bdir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    real = {w["name"]: w for w in bench["workloads"]}[REAL]
    cfg = applib.load_named("configs", real["config"], bdir)
    assert cfg["kit"] == "multiisp" and cfg["argv"][-1] == "--edge-enabled"
    cfg.update(name="tiny-multiisp-1024",
               argv=["--pool-cidr", "10.0.0.0/11", "--batch-size", "1024",
                     "--synthetic-subs", "1", "--max-subscribers", "4096",
                     "--max-nat-sessions", "4096", "--max-nat-subscribers",
                     "1024", "--edge-enabled"],
               sizes=dict(SIZES))
    cfg["nat_public_ips"]["count"] = 20
    _write(os.path.join(bdir, "configs", "tiny-multiisp-1024.json"), cfg)
    bench["configs"].append({"name": "tiny-multiisp-1024", "source": "test",
                             "file": "benchmark/configs/tiny-multiisp-1024.json",
                             "reduced": [], "why": "test"})
    flood = applib.load_named("traffic", real["traffic"], bdir)
    flood.update(name="tiny-flood-4096", pool_frames=4096, dhcp_share=0.05,
                 warmup_frames=400)
    _write(os.path.join(bdir, "traffic", "tiny-flood-4096.json"), flood)
    bench["workloads"].append({"name": CELL, "config": "tiny-multiisp-1024",
                               "traffic": "tiny-flood-4096", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if REAL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    # every file that lists the cell lists its stand-in, whatever its name
    listed = [m for m in layers.layer_files(bdir) if REAL in m["cells"]]
    assert listed and all(m["moves"] == "served_kpps" for m in listed)
    for m in listed:
        m["cells"].append(CELL)
        _write(os.path.join(bdir, "layers", m["name"] + ".json"), m)
    for m in DROPPED:
        _write(os.path.join(bdir, "layers", m["name"] + ".json"), m)
    # a kit whose sink loses every seventh record, as a file beside the kit
    with open(os.path.join(bdir, "kits", "multiisp.py")) as f:
        src = f.read()
    keep = "        self.cc.append((rec.warrant_id, bytes(rec.payload)))\n"
    assert src.count(keep) == 1
    with open(os.path.join(bdir, "kits", "multiisp-lossy.py"), "w") as f:
        f.write(src.replace(keep, "        self.iri -= 1\n"
                            "        if self.iri % 7:\n    " + keep))
    lossy = dict(cfg, name="tiny-multiisp-lossy", kit="multiisp-lossy")
    _write(os.path.join(bdir, "configs", "tiny-multiisp-lossy.json"), lossy)
    bench["workloads"].append({"name": "lossy.flood", "chips": 1,
                               "config": "tiny-multiisp-lossy",
                               "traffic": "tiny-flood-4096", "why": "test"})
    for m in bench["end_to_end"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("lossy.flood")
    _write(os.path.join(top, "BENCHMARK.json"), bench)
    return bdir


def _run(cell_dir, capsys, seed, *extra, cell=CELL, seconds="4"):
    capsys.readouterr()
    rc = bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                         seconds, "--bench-dir", cell_dir, *extra])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    sel = [ln for ln in out if ln.startswith("selectors: ")][0]
    assert sel.endswith("ring=NativeRing loop=engine")
    assert any(ln.startswith("cell: ") and "kit=multiisp" in ln for ln in out)
    return json.loads(out[-1]), out


def _pushed(out) -> int:
    window = [ln for ln in out if ln.startswith("window: ")][0]
    return int(window.split("pushed ")[1].split(",")[0])


@pytest.mark.parametrize("seed,trace", [(3000000049, "0"), (2**31 + 50, "1")])
def test_the_cell_is_correct_past_the_pools_wrap(cell_dir, capsys, seed, trace):
    res, out = _run(cell_dir, capsys, seed, "--trace", trace)
    assert _pushed(out) > 4096 + 2 * 1024  # the pool wrapped, windows after
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert all(c["value"] == 0 for c in res["compared"].values())
    assert "punted_frames" in res["compared"]
    built = [ln for ln in out if ln.startswith("programs built or loaded")][0]
    assert "in the window 0 " in built
    prov = [ln for ln in out if ln.startswith("provisioned: ")][0]
    assert "'routes'" in prov and "'warrants'" in prov
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    assert " 0 " not in sample and "next hop's MAC" in sample, sample
    assert "intercept sink: " in sample and "under 2" in sample  # of 256
    assert "and no other" in sample
    got = res["metrics"]
    if trace == "0":
        assert set(got) == {"served_kpps", "setup_s"}
        return
    named = [m["name"] for m in layers.layer_files(cell_dir)
             if REAL in m["cells"] and m["read"]["kind"] == "counter"]
    assert named and all(got[name]["value"] > 0 for name in named), named
    # the four counts a step, through `engine.trace`: a route probe on every
    # upstream data lane (none misses: every subscriber is bound), a tap
    # match on a quarter of the data lanes both ways
    frames = got["t.frames_per_step"]["value"]  # 5% of them DHCP
    rewrites, misses = (got[f"t.{k}_per_step"]["value"]
                        for k in ("rewrites", "route_miss"))
    mirrored, filtered = (got[f"t.{k}_per_step"]["value"]
                          for k in ("mirrored", "filtered"))
    assert misses == 0 and 0.40 * frames < rewrites < 0.55 * frames <= 1024
    assert 0.10 * frames < mirrored + filtered < 0.40 * frames
    assert 0 < filtered < mirrored
    # a retire's reads: W's ten (verdict, out_pkt, out_len, two flag columns,
    # five stats blocks), the stage's block and the mirror column, each
    # one's copy started at its step's dispatch: none blocks
    assert got["t.fetch_calls_per_step"]["value"] == 0
    assert got["t.prefetch_calls_per_step"]["value"] == \
        pytest.approx(3 + 2 + 6 + 1, abs=0.25)


def test_stale_binding_fails_by_the_sample_and_the_sink_stands(cell_dir, capsys):
    res, out = _run(cell_dir, capsys, 3000000051, "--control", "stale-binding")
    assert res["correct"] is False and res["failed"] > 0
    bad = res["compared"]
    assert bad["sampled_replies_differing"]["value"] > 0
    assert all(c["value"] == 0 for k, c in bad.items()
               if k != "sampled_replies_differing"), bad
    first = [ln for ln in out if ln.startswith("check first differing")][0]
    assert "dhcp=False" in first  # an upstream data frame, by its MAC
    # three upstream frames in four of the residential nine in ten differ:
    # a third to a half of the sampled data frames (half of them upstream)
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    data = int(sample.split(" DHCP replies byte-for-byte, ")[1].split(" ")[0])
    assert 0.25 * data < bad["sampled_replies_differing"]["value"] < 0.5 * data
    assert "intercept sink: " in sample and "and no other" in sample


def test_bad_checksum_fails_by_the_sample(cell_dir, capsys):
    res, _out = _run(cell_dir, capsys, 3000000052, "--control", "bad-checksum")
    assert res["correct"] is False and res["failed"] > 0
    bad = res["compared"]
    assert bad["sampled_replies_differing"]["value"] > 0
    assert all(c["value"] == 0 for k, c in bad.items()
               if k != "sampled_replies_differing"), bad


def test_a_sink_that_lost_frames_fails_by_the_counts(cell_dir, capsys):
    """Every reply is right and the sink holds six frames in seven: the
    sample finds nothing, the kinds do (PERF.md §2: by the counts)."""
    res, out = _run(cell_dir, capsys, 3000000053, cell="lossy.flood",
                    seconds="2")
    assert res["correct"] is False
    bad = res["compared"]
    assert bad["sample_kinds_missing"] == {"value": 1, "limit": 0}
    assert all(c["value"] == 0 for k, c in bad.items()
               if k != "sample_kinds_missing"), bad
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    assert "AS THE TRAFFIC PUSHED IT" in sample and "records differ" in sample
    said = sample.split("frames at the sink ")[1].split(")")[0]  # n (pushed: m
    got, want = (int(x) for x in said.replace("(pushed: ", "").split())
    assert 0 < got < want and abs(got - want * 6 / 7) < 0.01 * want
    assert "pump delivered " + str(want) in sample  # the pump handed all over


# -- the cell as the benchmark will list it -------------------------------------

def test_the_configuration_is_ws_with_the_stage_on():
    cfg = applib.load_named("configs", "multiisp-li-cgnat-1M-wire")
    base = applib.load_named("configs", "ipoe-cgnat-1M-wire")
    assert cfg["kit"] == "multiisp" and cfg["reduced"] == ["max_nat_sessions"]
    assert cfg["architecture"] is None and cfg["chips"] == 1
    assert cfg["argv"] == base["argv"] + ["--edge-enabled"]
    assert cfg["nat_public_ips"] == base["nat_public_ips"]
    assert cfg["sizes"] == dict(base["sizes"], route_rows=1_000_000,
                                upstreams=4, warrants=1024,
                                filtered_warrants=64)
    assert cfg["off"] == [x for x in base["off"] if x != "edge taps"]
    # six in all: the four every configuration's `guarantees` is held to
    # (tests/benchmark/test_benchmark.py) first; the stage's two after them,
    # under their own key while that test holds the list to four
    assert cfg["guarantees"][:4] == base["guarantees"]
    edge = cfg["guarantees"][4:] + cfg.get("guarantees_edge", [])
    assert "next hop" in edge[0] and "intercept sink once" in edge[1]
    entry = {c["name"]: c for c in BENCH["configs"]}[cfg["name"]]
    cell = {w["name"]: w for w in BENCH["workloads"]}[REAL]
    assert (entry["name"], entry["source"], entry["reduced"]) == (
        cfg["name"], cfg["source"], cfg["reduced"])
    assert entry["file"] == f"benchmark/configs/{cfg['name']}.json"
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        REAL, cfg["name"], "flood-64B", 1)
    served = {m["name"]: m for m in BENCH["end_to_end"]}["served_kpps"]
    assert REAL in served["workloads"]
    assert [m["name"] for m in BENCH["per_layer"] if REAL in m["workloads"]]


def test_a_file_dropped_in_is_refused_by_the_limit_alone_and_only_when_full(
        tmp_path):
    """The cell's own entry is the format's 128th (PR 49). What
    tests/benchmark/test_trace_layers.py::test_a_counter_file_dropped_in_is_
    admitted_with_no_test_edited holds (tests/conftest.py marks it while the
    format is full) is held here but for the room: one more counter file in
    a copy of the benchmark passes every format test as it stands, and while
    `per_layer` is full `test_per_layer_is_within_the_formats_limit` alone
    refuses the copy. The `benchmark` PR that merges a repeated entry gives
    the room back (PERF.md section 7 row 1), and then nothing refuses it."""
    n = len(BENCH["per_layer"])
    assert n == len(layers.layer_files(os.path.join(ROOT, "benchmark"))) <= 128
    for part in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(ROOT, part), tmp_path / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    extra = counter("t.dropped_per_step", "engine.trace.edge_rewrites")
    extra["cells"] = [REAL]
    _write(tmp_path / "benchmark" / "layers" / (extra["name"] + ".json"),
           extra)
    entry = {k: v for k, v in extra.items() if k not in ("cells", "read")}
    bench = json.loads(json.dumps(BENCH))
    bench["per_layer"].append(dict(entry, workloads=[REAL]))
    _write(tmp_path / "BENCHMARK.json", bench)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PYTEST_", "COV_"))}
    env.update(PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    tests = [("test_benchmark.py", t) for t in (
        "test_names_units_and_lengths",
        "test_every_cell_resolves_its_files_by_name",
        "test_layer_files_and_benchmark_json_agree",
        "test_per_layer_is_within_the_formats_limit",
        "test_a_layer_files_cells_are_its_entrys_workloads",
        "test_every_configuration_holds_the_four_guarantees")]
    tests.append(("test_trace_layers.py", "test_the_new_files_are_data_and_"
                  "run_on_a_program_without_the_spans"))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly",
         *(os.path.join("tests", "benchmark", f) + "::" + t
           for f, t in tests)],
        capture_output=True, text=True, timeout=600, env=env, cwd=tmp_path)
    configs = len(os.listdir(tmp_path / "benchmark" / "configs"))
    full = n + 1 > 128
    if full:
        assert "1 failed" in out.stdout, out.stdout[-3000:] + out.stderr[-2000:]
        assert "FAILED tests/benchmark/test_benchmark.py::test_per_layer_is_" \
            "within_the_formats_limit" in out.stdout
    else:
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    passed = 5 - full + n + 1 + configs
    assert f"{passed} passed" in out.stdout, out.stdout[-2000:]


# -- the kit, without the app -------------------------------------------------

def kit():
    return applib.load_kit({"kit": "multiisp"})


def test_the_reference_holds_nothing_of_the_programs_election():
    """The kit imports the program nowhere at its top, nothing of
    `bng_tpu/edge/compile.py` anywhere, and `Plain` and the hash nothing at
    all: the election is written out again."""
    from benchmark.kits import multiisp

    tree = ast.parse(inspect.getsource(multiisp))
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = [a.name for n in top if isinstance(n, ast.Import) for a in n.names]
    names += [n.module for n in top if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.split(".")[0] == "bng_tpu"]
    every = [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert "bng_tpu.edge.compile" not in every and "bng_tpu.edge" not in every
    assert not [n for n in every if n.startswith("bng_tpu.runtime")]
    for name in ("Plain", "fnv1a32"):
        node = next(n for n in tree.body if getattr(n, "name", "") == name)
        assert not [n for n in ast.walk(node)
                    if isinstance(n, (ast.Import, ast.ImportFrom))]
        assert "bng_tpu" not in ast.unparse(node)
    # and the hash is the one the program's cluster steering uses
    from bng_tpu.utils.net import fnv1a32

    rng = np.random.default_rng(9)
    for _ in range(200):
        data = bytes(rng.integers(0, 256, 4, dtype=np.uint8))
        assert multiisp.fnv1a32(data) == fnv1a32(data)


def test_the_layout_is_the_configurations_and_the_seeds():
    k = kit()
    cfg = applib.load_named("configs", "multiisp-li-cgnat-1M-wire")
    tiny = k.Layout({"sizes": dict(SIZES)}, 7)
    assert (tiny.route_rows, tiny.n_upstreams, tiny.n_warrants,
            tiny.n_filtered) == (4096, 4, 256, 64)  # the kit's own defaults
    lay = k.Layout(cfg, 2**31 + 7)
    assert (lay.route_rows, lay.n_upstreams, lay.n_warrants,
            lay.n_filtered) == (1_000_000, 4, 1024, 64)
    share = np.bincount(lay.klass, minlength=3) / lay.subscribers
    assert np.allclose(share, k.CLASS_SHARE, atol=0.002)
    assert len(lay.warrants) == 1024  # 1,024 subscribers, each once
    assert sum(f for _w, f in lay.warrants.values()) == 64
    nat_ips = set(lay.sub_ips(lay.nat_sub_index(
        np.arange(lay.nat_subscribers))).tolist())
    assert set(lay.warrants) <= nat_ips  # every target sends traffic
    again = k.Layout(cfg, 2**31 + 7)
    assert again.warrants == lay.warrants and (again.klass == lay.klass).all()
    other = k.Layout(cfg, 2**31 + 8)
    assert other.warrants != lay.warrants
    with pytest.raises(applib.BenchError, match="every one of the"):
        k.Layout({"sizes": dict(SIZES, route_rows=100)}, 7)
    assert k.stage_bytes(1024, 1536) == 1024 * (2 * 288 + 10) + 1024
    assert k.stage_bytes(8192, 1536) == k.stage_bytes(8192, 64)


def test_plain_elects_by_class_weight_and_what_is_up():
    k = kit()
    ups = [(f"isp{i}", 101 + i, w, bytes([2, 0xEE, 0, 0, 1, i]))
           for i, w in enumerate((1, 1, 2, 1))]
    plain = k.Plain(ups, {"business": (101, 102), "nobody": (7,)}, {})
    ips = (np.arange(4000) + (10 << 24) + (16 << 16)).tolist()
    by = {klass: [plain.next_hop(ip, klass) for ip in ips]
          for klass in ("residential", "business", "nobody")}
    assert set(by["nobody"]) == {None}
    assert {m[5] for m in by["business"]} == {0, 1}
    share = np.bincount([m[5] for m in by["residential"]]) / 4000
    assert np.allclose(share, [0.2, 0.2, 0.4, 0.2], atol=0.03)  # by weight
    down = k.Plain(ups, {}, {}, down=("isp2",))
    assert {down.next_hop(ip, "residential")[5] for ip in ips} == {0, 1, 3}
    assert k.Plain(ups, {}, {}, down=[u[0] for u in ups]).next_hop(
        ips[0], "residential") is None


def test_plain_takes_of_a_frame_what_the_device_and_the_manager_take():
    """`Plain.tap` against `edge/ops.py tap_match` (the device's rule) and
    `InterceptManager._passes_filters` (the manager's), lane by lane."""
    import jax.numpy as jnp

    from bng_tpu.control.intercept import InterceptManager, Warrant
    from bng_tpu.edge import EdgeTables
    from bng_tpu.edge.ops import tap_match

    k = kit()
    rng = np.random.default_rng(13)
    subs = [(10 << 24) + 100 + i for i in range(6)]
    warrants = {subs[0]: ("w-a", False), subs[1]: ("w-b", True),
                subs[2]: ("w-c", True)}
    plain = k.Plain([], {}, warrants)
    edge = EdgeTables(tap_nbuckets=64, route_nbuckets=64)
    for wid, (ip, (_name, filtered)) in enumerate(warrants.items(), 1):
        edge.arm_tap(ip, wid, [(k.FILTER_PORT, k.FILTER_PROTO, 0)]
                     if filtered else ())
    n = 256
    ip = rng.choice(subs, n)
    proto = rng.choice([6, 17], n)
    sport = rng.choice([443, 40000, 40001], n)
    dport = rng.choice([443, 80, 5000], n)
    res = tap_match(jnp.asarray(ip, jnp.uint32), jnp.asarray(sport, jnp.uint32),
                    jnp.asarray(dport, jnp.uint32),
                    jnp.asarray(proto, jnp.uint32),
                    jnp.zeros(n, jnp.uint32), jnp.ones(n, bool),
                    edge.tap.device_state(), jnp.asarray(edge.tap_filters),
                    jnp.asarray(edge.tap_config), edge.tap_geom)
    mirror = np.asarray(res.mirror)
    for i in range(n):
        wid, device, sink = plain.tap(int(ip[i]), int(proto[i]),
                                      int(sport[i]), int(dport[i]))
        assert bool(mirror[i]) == device, i
        if wid is None:
            continue
        w = Warrant(id=wid, liid="x",
                    filter_protocols=[k.FILTER_PROTO] if warrants[int(ip[i])][1]
                    else [],
                    filter_dest_ports=[k.FILTER_PORT] if warrants[int(ip[i])][1]
                    else [])
        passes = InterceptManager._passes_filters(
            w, int(sport[i]), int(dport[i]), int(proto[i]), "1.1.1.1")
        assert sink == (device and passes), i
        assert not sink or device  # the manager sees what the device passed
    assert int(np.asarray(res.stats)[0]) == int((mirror != 0).sum())
