"""The cell `multiisp-li-cgnat-1M-wire.flood-64B` in test_benchmark.py's own
rehearsal directory, as the stand-in `tiny-multiisp.flood`: its
configuration and its kit are found by name, its layer files by what lists
the cell, at 4,096 subscribers with a route row each over four
upstreams, 128 of them behind NAT and 32 of those under a warrant.
tests/test_edge_cell_rehearsal.py is the longer rehearsal, past the pool's
wrap, with both controls and a sink that loses frames. No number from here
is a device metric."""

from test_benchmark import (BENCH, ENGINE_LOOP, TINY_CELLS, _run,  # noqa: F401
                            generic, listed, tiny_dir)

from benchmark.lib import app as applib

REAL = "multiisp-li-cgnat-1M-wire.flood-64B"
W = "cgnat-1M-wire.flood-64B"
LAP = "edge.mirror_us_per_step"
# three of the stage's four counts (stamps PR 49, files PR 52): above 0
# where the stage ran. The fourth, route misses a step, is 0 where every
# subscriber holds a route row, and so are three of the loop's counters in a
# sound run: tests/test_edge_cell_rehearsal.py holds every `counter` file
# that lists the cell above 0, so the cell is not listed in W's three and the
# fourth count has no file yet (PERF.md section 7 row 1)
COUNTS = {"edge.rewrites_per_step", "edge.mirrored_per_step",
          "edge.filtered_per_step"}
ZERO_IN_A_SOUND_RUN = {"engine.drain_built_per_step",
                       "wire.fetch_calls_per_step", "wire.fetch_kb_per_step"}


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
    assert len(cfg["guarantees"]) == 6 and "guarantees_edge" not in cfg
    # since PR 52 the cell reports what its loop reports in W, the stage's
    # lap and three of its four counts (a later PR may add more)
    named = set(listed(REAL))
    loop = set(generic(REAL, "step", "loop", "gen", "beat").values())
    assert {LAP} | COUNTS | (ENGINE_LOOP - ZERO_IN_A_SOUND_RUN) | loop <= named
    assert {n for n in listed(W) if n.startswith(("wire.", "engine."))} \
        - named == ZERO_IN_A_SOUND_RUN
    assert not any(m["read"].get("path", "").endswith("edge_route_miss")
                   for m in listed(REAL).values())
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
    step = generic(REAL, "step")["step"]  # no device trace on the CPU
    assert set(got) == set(listed(REAL, tiny_dir)) - {step}
    assert got[LAP]["value"] > 0 and got[LAP]["unit"] == "us"
    # the tiny set arms warrants on NAT subscribers, some with a filter row
    assert all(got[name]["value"] > 0 for name in COUNTS), got


def test_the_lap_is_left_out_where_the_stage_is_off(tiny_dir, capsys):  # noqa: F811
    """W's stand-in runs the same loop with no edge stage: a program without
    the `mirror` lap gives the file nothing to read, and the line leaves the
    metric out (as the parent commit's does in every cell)."""
    res, _out = _run(tiny_dir, capsys, "tiny-wire.flood", "--trace", "1")
    assert res["correct"] is True
    assert TINY_CELLS["tiny-wire.flood"][0] == W
    assert not ({LAP} | COUNTS) & set(res["metrics"])


def test_both_controls_fail_the_stand_in(tiny_dir, capsys):  # noqa: F811
    for control in ("stale-binding", "bad-checksum"):
        res, out = _run(tiny_dir, capsys, "tiny-multiisp.flood", "--trace",
                        "0", "--control", control)
        assert res["correct"] is False, (control, out[-12:])
        assert res["compared"]["sampled_replies_differing"]["value"] > 0
        assert res["compared"]["lost_frames"]["value"] == 0
