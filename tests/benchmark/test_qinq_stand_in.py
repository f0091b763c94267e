"""The cell `qinq-pppoe-cgnat-1M-wire.flood-64B` in test_benchmark.py's own
rehearsal directory, as the stand-in `tiny-qinq.flood`: its configuration
and its kit are found by name, its layer files by what lists the cell and by
what each reads (`test_benchmark.generic`), at 4,096 subscribers behind a
pair each, 128 of them behind NAT and 32 of those PPPoE.
tests/test_qinq_cell_rehearsal.py is the longer rehearsal, past the pool's
wrap and with both controls. No number from here is a device metric."""

from test_benchmark import (BENCH, ENGINE_LOOP, ENGINE_LOOP_ZERO_OK,  # noqa: F401
                            TINY_CELLS, _run, generic, listed, tiny_dir)

from benchmark.lib import app as applib

REAL = "qinq-pppoe-cgnat-1M-wire.flood-64B"
# the stage's three counters, the cell's alone; the loop's generic reads
# (PR 40 brought them under the cell's prefix; since PR 52 the cell is listed
# in the files that held them first); and, since PR 48, what the engine's
# loop reports in W, P and D (Q runs the same loop and the same stamps)
LOOP = generic(REAL, "step", "loop", "gen", "beat", "tick")
STEP = LOOP["step"]
FILES = {"qinq.push_per_step", "qinq.pop_per_step", "qinq.miss_per_step",
         *LOOP.values()} | ENGINE_LOOP
# every subscriber holds a pair
ZERO_OK = ENGINE_LOOP_ZERO_OK | {"qinq.miss_per_step"}


def test_the_cell_and_its_files_are_in_the_benchmark_by_name():
    cell = {w["name"]: w for w in BENCH["workloads"]}[REAL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qinq-pppoe-cgnat-1M-wire", "flood-64B", 1)
    assert "no frame crossed a link" in cell["why"]
    cfg = applib.load_named("configs", cell["config"])
    assert cfg["kit"] == "qinq" and cfg["reduced"] == ["max_nat_sessions"]
    assert cfg["architecture"] is None and "framing" in cfg
    assert cfg["sizes"] == dict(
        applib.load_named("configs", "pppoe-cgnat-1M-wire")["sizes"],
        qinq_pairs=1_000_000)
    assert cfg["argv"] == applib.load_named("configs", "pppoe-cgnat-1M-wire")[
        "argv"] + ["--qinq-enabled"]
    assert "QinQ" not in cfg["off"]
    assert cfg["guarantees"] == applib.load_named("configs", "ipoe-cgnat-1M")[
        "guarantees"]
    named = set(listed(REAL))
    assert FILES <= named  # a later PR may add a file that lists the cell
    assert {m["name"] for m in BENCH["per_layer"]
            if REAL in m["workloads"]} == named
    served = {m["name"]: m for m in BENCH["end_to_end"]}["served_kpps"]
    assert REAL in served["workloads"]  # a later cell is appended after it
    assert applib.load_kit(cfg).stage_bytes(8192, 1536) == 4 * 8192 * 1536


def test_the_plain_reference_holds_nothing_of_the_program():
    """`Plain` is `struct` and plain Python: the kit's module imports the
    program nowhere at its top, and the class nowhere at all."""
    import ast
    import inspect

    from benchmark.kits import qinq

    tree = ast.parse(inspect.getsource(qinq))
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = [a.name for n in top if isinstance(n, ast.Import) for a in n.names]
    names += [n.module for n in top if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.split(".")[0] == "bng_tpu"]
    plain = next(n for n in tree.body
                 if isinstance(n, ast.ClassDef) and n.name == "Plain")
    assert not [n for n in ast.walk(plain)
                if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert "bng_tpu" not in ast.unparse(plain)


def test_the_stand_in_rehearses_traced(tiny_dir, capsys):  # noqa: F811
    assert TINY_CELLS["tiny-qinq.flood"][0] == REAL
    res, out = _run(tiny_dir, capsys, "tiny-qinq.flood", "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert any(ln.startswith("cell: ") and ln.endswith("kit=qinq")
               for ln in out)
    assert res["compared"]["sample_kinds_missing"] == {"value": 0, "limit": 0}
    got = res["metrics"]
    assert FILES - {STEP} <= set(got)
    assert all(got[name]["value"] > 0 for name in FILES - ZERO_OK
               if name in got)
    assert got["qinq.miss_per_step"]["value"] == 0
    said = [ln for ln in out if ln.startswith("per-layer metrics with nothing")]
    assert said and STEP in said[0]


def test_both_controls_fail_the_stand_in(tiny_dir, capsys):  # noqa: F811
    for control in ("stale-binding", "bad-checksum"):
        res, out = _run(tiny_dir, capsys, "tiny-qinq.flood", "--trace", "0",
                        "--control", control)
        assert res["correct"] is False, (control, out[-12:])
        assert res["compared"]["sampled_replies_differing"]["value"] > 0
        assert res["compared"]["lost_frames"]["value"] == 0
