"""The cell `pppoe-cgnat-1M-wire.flood-64B` in test_benchmark.py's own
rehearsal directory, through the stand-in conftest.py gives it (the
fixture's literals lack the cell): its configuration, its kit and its four
layer files are found by name, at 4,096 subscribers of whom all 128 NAT
subscribers are PPPoE. tests/test_pppoe_cell_rehearsal.py is the longer
rehearsal, past the pool's wrap and with both controls. No number from
here is a device metric."""

from test_benchmark import BENCH, TINY_CELLS, _run, tiny_dir  # noqa: F401

from benchmark.lib import app as applib
from benchmark.lib import layers

REAL = "pppoe-cgnat-1M-wire.flood-64B"
FILES = {"pppoe_step.device_p50_us", "pppoe.loop_us_per_frame",
         "pppoe.gen_share", "pppoe.beat_p99_us"}


def test_the_cell_and_its_files_are_in_the_benchmark_by_name():
    cell = {w["name"]: w for w in BENCH["workloads"]}[REAL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "pppoe-cgnat-1M-wire", "flood-64B", 1)
    cfg = applib.load_named("configs", cell["config"])
    assert cfg["kit"] == "pppoe" and cfg["reduced"] == ["max_nat_sessions"]
    assert cfg["sizes"]["pppoe_sessions"] == 0xFFFF and "framing" in cfg
    assert cfg["argv"] == applib.load_named("configs", "ipoe-cgnat-1M-wire")[
        "argv"] + ["--pppoe-enabled", "--pppoe-auth", "none"]
    named = {m["name"] for m in layers.layer_files(applib.BENCH_DIR)
             if REAL in m["cells"]}
    assert named == FILES
    # kinds the pinned counts of span / counter / wire* files let in
    assert all(m["read"]["kind"] in ("bench_span", "trace_program")
               for m in layers.layer_files(applib.BENCH_DIR)
               if m["name"] in FILES)
    assert {m["name"] for m in BENCH["per_layer"]
            if REAL in m["workloads"]} == FILES
    served = {m["name"]: m for m in BENCH["end_to_end"]}["served_kpps"]
    assert served["workloads"][-1] == REAL
    assert hasattr(applib.load_kit(cfg), "stage_bytes")


def test_the_stand_in_rehearses_traced(tiny_dir, capsys):  # noqa: F811
    assert TINY_CELLS["tiny-pppoe.flood"][0] == REAL
    res, out = _run(tiny_dir, capsys, "tiny-pppoe.flood", "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert any(ln.startswith("cell: ") and ln.endswith("kit=pppoe")
               for ln in out)
    got = res["metrics"]
    assert set(got) == FILES - {"pppoe_step.device_p50_us"}
    assert all(m["value"] > 0 for m in got.values())
    said = [ln for ln in out if ln.startswith("per-layer metrics with nothing")]
    assert said and "pppoe_step.device_p50_us" in said[0]
