"""The cell `pppoe-cgnat-1M-wire.flood-64B` in test_benchmark.py's own
rehearsal directory, as the stand-in `tiny-pppoe.flood`: its configuration
and its kit are found by name, its layer files by what lists the cell and by
what each reads (`test_benchmark.generic`), at 4,096 subscribers of whom
all 128 NAT subscribers are PPPoE. tests/test_pppoe_cell_rehearsal.py is the longer
rehearsal, past the pool's wrap and with both controls. No number from
here is a device metric."""

from test_benchmark import (BENCH, ENGINE_LOOP, ENGINE_LOOP_ZERO_OK,  # noqa: F401
                            TINY_CELLS, _run, generic, listed, tiny_dir)

from benchmark.lib import app as applib

REAL = "pppoe-cgnat-1M-wire.flood-64B"
# the loop's generic reads (PR 32 brought them under the cell's prefix; since
# PR 52 the cell is listed in the files that held them first)
LOOP = generic(REAL, "step", "loop", "gen", "beat", "tick")
# since PR 36 the engine's loop reports here what it reports in the wire
# cell, and the counters of the stage beside it (no unknown session
# in a sound run: 0)
FILES = set(LOOP.values()) | ENGINE_LOOP | {
    "pppoe.decap_per_step", "pppoe.encap_per_step", "pppoe.miss_per_step"}
ZERO_OK = ENGINE_LOOP_ZERO_OK | {"pppoe.miss_per_step"}


def test_the_cell_and_its_files_are_in_the_benchmark_by_name():
    cell = {w["name"]: w for w in BENCH["workloads"]}[REAL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "pppoe-cgnat-1M-wire", "flood-64B", 1)
    cfg = applib.load_named("configs", cell["config"])
    assert cfg["kit"] == "pppoe" and cfg["reduced"] == ["max_nat_sessions"]
    assert cfg["sizes"]["pppoe_sessions"] == 0xFFFF and "framing" in cfg
    assert cfg["argv"] == applib.load_named("configs", "ipoe-cgnat-1M-wire")[
        "argv"] + ["--pppoe-enabled", "--pppoe-auth", "none"]
    named = set(listed(REAL))
    assert FILES <= named  # a later PR may add a file that lists the cell
    assert {m["name"] for m in BENCH["per_layer"]
            if REAL in m["workloads"]} == named
    served = {m["name"]: m for m in BENCH["end_to_end"]}["served_kpps"]
    assert REAL in served["workloads"]
    assert hasattr(applib.load_kit(cfg), "stage_bytes")


def test_the_stand_in_rehearses_traced(tiny_dir, capsys):  # noqa: F811
    assert TINY_CELLS["tiny-pppoe.flood"][0] == REAL
    res, out = _run(tiny_dir, capsys, "tiny-pppoe.flood", "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert any(ln.startswith("cell: ") and ln.endswith("kit=pppoe")
               for ln in out)
    got = res["metrics"]
    assert FILES - {LOOP["step"]} <= set(got)
    assert all(got[name]["value"] > 0 for name in FILES - ZERO_OK
               if name in got)
    assert all(got[name]["value"] >= 0 for name in ZERO_OK)
    said = [ln for ln in out if ln.startswith("per-layer metrics with nothing")]
    assert said and LOOP["step"] in said[0]
