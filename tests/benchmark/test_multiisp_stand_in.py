"""The cell `multiisp-li-cgnat-1M-wire.flood-64B` in test_benchmark.py's own
rehearsal directory, as the stand-in `tiny-multiisp.flood` (tests/conftest.py
adds it to the three literals): its configuration, its kit and its layer
file are found by name, at 4,096 subscribers with a route row each over four
upstreams, 128 of them behind NAT and 32 of those under a warrant.
tests/test_edge_cell_rehearsal.py is the longer rehearsal, past the pool's
wrap, with both controls and a sink that loses frames. No number from here
is a device metric."""

from test_benchmark import BENCH, TINY_CELLS, _run, tiny_dir  # noqa: F401

from benchmark.lib import app as applib
from benchmark.lib import layers

REAL = "multiisp-li-cgnat-1M-wire.flood-64B"
W = "cgnat-1M-wire.flood-64B"
LAP = "edge.mirror_us_per_step"


def test_the_cell_and_its_files_are_in_the_benchmark_by_name():
    cell = {w["name"]: w for w in BENCH["workloads"]}[REAL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "multiisp-li-cgnat-1M-wire", "flood-64B", 1)
    assert cell["why"].endswith("no frame crossed a link")
    entry = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    cfg = applib.load_named("configs", cell["config"])
    base = applib.load_named("configs", "ipoe-cgnat-1M-wire")
    assert cfg["kit"] == "multiisp" and cfg["architecture"] is None
    assert cfg["chips"] == 1 and cfg["reduced"] == entry["reduced"] == [
        "max_nat_sessions"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert "routing/manager.go:521-573" in cfg["source"]
    assert cfg["argv"] == base["argv"] + ["--edge-enabled"]
    assert cfg["nat_public_ips"] == base["nat_public_ips"]
    assert cfg["sizes"] == dict(base["sizes"], route_rows=1_000_000,
                                upstreams=4, warrants=1024,
                                filtered_warrants=64)
    assert cfg["off"] == [x for x in base["off"] if x != "edge taps"]
    assert len(cfg["guarantees"] + cfg.get("guarantees_edge", [])) == 6
    # one file lists the cell, and one entry (a later PR may add more)
    named = {m["name"] for m in layers.layer_files(applib.BENCH_DIR)
             if REAL in m["cells"]}
    assert LAP in named
    assert {m["name"] for m in BENCH["per_layer"]
            if REAL in m["workloads"]} == named
    served = {m["name"]: m for m in BENCH["end_to_end"]}["served_kpps"]
    assert REAL in served["workloads"]
    setup = {m["name"]: m for m in BENCH["end_to_end"]}["setup_s"]
    assert "workloads" not in setup  # every cell reports it
    # the stage reads its lanes' table rows and writes the mirror column:
    # nothing of a frame's slot
    kit = applib.load_kit(cfg)
    assert kit.stage_bytes(8192, 1536) == kit.stage_bytes(8192, 64) > 0


def test_the_stand_in_rehearses_traced(tiny_dir, capsys):  # noqa: F811
    assert TINY_CELLS["tiny-multiisp.flood"][0] == REAL
    res, out = _run(tiny_dir, capsys, "tiny-multiisp.flood", "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert any(ln.startswith("cell: ") and "kit=multiisp" in ln for ln in out)
    assert all(c["value"] == 0 for c in res["compared"].values())
    prov = [ln for ln in out if ln.startswith("provisioned: ")][0]
    assert "'routes'" in prov and "'warrants'" in prov
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    assert "next hop's MAC" in sample and "intercept sink: " in sample
    got = res["metrics"]
    want = {m["name"] for m in layers.layer_files(tiny_dir)
            if REAL in m["cells"]}
    assert LAP in want <= set(got)
    assert got[LAP]["value"] > 0 and got[LAP]["unit"] == "us"


def test_the_lap_is_left_out_where_the_stage_is_off(tiny_dir, capsys):  # noqa: F811
    """W's stand-in runs the same loop with no edge stage: a program without
    the `mirror` lap gives the file nothing to read, and the line leaves the
    metric out (as the parent commit's does in every cell)."""
    res, _out = _run(tiny_dir, capsys, "tiny-wire.flood", "--trace", "1")
    assert res["correct"] is True
    assert TINY_CELLS["tiny-wire.flood"][0] == W
    assert LAP not in res["metrics"]


def test_both_controls_fail_the_stand_in(tiny_dir, capsys):  # noqa: F811
    for control in ("stale-binding", "bad-checksum"):
        res, out = _run(tiny_dir, capsys, "tiny-multiisp.flood", "--trace",
                        "0", "--control", control)
        assert res["correct"] is False, (control, out[-12:])
        assert res["compared"]["sampled_replies_differing"]["value"] > 0
        assert res["compared"]["lost_frames"]["value"] == 0
