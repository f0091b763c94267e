"""The cell `dualstack-cgnat-1M-wire.flood-64B` in test_benchmark.py's own
rehearsal directory, as the stand-in `tiny-dualstack.flood`: its
configuration and its kit are found by name, its layer files by what lists
the cell and by what each reads (`test_benchmark.generic`), at 4,096
dual-stack subscribers of whom 128 are behind NAT.
tests/test_dualstack_cell_rehearsal.py is the longer rehearsal, past the
pool's wrap and with both controls. No number from here is a device
metric, and no position in a `workloads` list is pinned."""

from test_benchmark import (BENCH, ENGINE_LOOP, ENGINE_LOOP_ZERO_OK,  # noqa: F401
                            TINY_CELLS, _run, generic, listed, tiny_dir)

from benchmark.lib import app as applib

REAL = "dualstack-cgnat-1M-wire.flood-64B"
# the loop's generic reads (PR 34 brought them under the cell's prefix; since
# PR 52 the cell is listed in the files that held them first)
LOOP = generic(REAL, "step", "loop", "gen", "beat")
# the stage's own counters, the cell's alone (no v6 miss, no v6 control frame
# in a sound run: 0); since PR 36 the engine's loop reports here what it
# reports in the wire cell
OWN = {"dualstack.v6_fwd_per_step", "dualstack.v6_miss_per_step",
       "dualstack.v6_ctrl_per_step"}
FILES = OWN | ENGINE_LOOP | set(LOOP.values())
ZERO_OK = ENGINE_LOOP_ZERO_OK | {"dualstack.v6_miss_per_step",
                                 "dualstack.v6_ctrl_per_step"}


def test_the_cell_and_its_files_are_in_the_benchmark_by_name():
    cell = {w["name"]: w for w in BENCH["workloads"]}[REAL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dualstack-cgnat-1M-wire", "flood-64B", 1)
    assert len(cell["why"]) <= 200 and "no frame crossed a link" in cell["why"]
    cfg = applib.load_named("configs", cell["config"])
    assert cfg["kit"] == "dualstack" and cfg["reduced"] == ["max_nat_sessions"]
    assert cfg["architecture"] is None and cfg["chips"] == 1
    assert cfg["sizes"] == {
        "subscribers": 1_000_000, "nat_subscribers": 250_000,
        "flows_per_nat_subscriber": 4, "v6_bindings": 1_000_000,
        "v6_data_share_pct": 40}
    assert cfg["argv"] == applib.load_named("configs", "ipoe-cgnat-1M-wire")[
        "argv"] + ["--ipv6-fastpath"]
    assert all(k in cfg for k in ("source", "deployment", "assumed", "off",
                                  "forwarding", "resident"))
    entry = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"]
    named = set(listed(REAL))
    assert FILES <= named  # a later PR may add a file that lists the cell
    assert {m["name"] for m in BENCH["per_layer"]
            if REAL in m["workloads"]} == named
    assert all(m["moves"] == "served_kpps" and (m["workloads"] == [REAL]
                                                or m["name"] not in OWN)
               for m in BENCH["per_layer"] if m["name"] in named)
    served = {m["name"]: m for m in BENCH["end_to_end"]}["served_kpps"]
    assert REAL in served["workloads"]
    kit = applib.load_kit(cfg)
    assert hasattr(kit, "stage_bytes") and hasattr(kit, "Plain")


def test_the_plain_reference_imports_nothing_of_the_device_code():
    import ast

    kit = applib.load_kit({"kit": "dualstack"})
    with open(kit.__file__) as f:
        top = ast.parse(f.read())
    # at import the kit takes nothing of the program (its provisioning
    # imports the bulk writers where it calls them, as every kit does)
    names = {a.name for n in top.body if isinstance(n, ast.Import)
             for a in n.names} | {n.module for n in top.body
                                  if isinstance(n, ast.ImportFrom)}
    assert names and not any(n.startswith("bng_tpu") for n in names), names
    plain = next(n for n in top.body
                 if isinstance(n, ast.ClassDef) and n.name == "Plain")
    assert not [n for n in ast.walk(plain)
                if isinstance(n, (ast.Import, ast.ImportFrom))]
    used = {n.value.id for n in ast.walk(plain)
            if isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name)}
    assert used <= {"self", "struct", "ipaddress", "src", "dst"}, used


def test_the_stand_in_rehearses_traced(tiny_dir, capsys):  # noqa: F811
    assert TINY_CELLS["tiny-dualstack.flood"][0] == REAL
    res, out = _run(tiny_dir, capsys, "tiny-dualstack.flood", "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert any(ln.startswith("cell: ") and ln.endswith("kit=dualstack")
               for ln in out)
    got = res["metrics"]
    assert FILES - {LOOP["step"]} <= set(got)
    assert all(got[name]["value"] > 0 for name in FILES - ZERO_OK
               if name in got)
    assert all(got[name]["value"] >= 0 for name in ZERO_OK)
    said = [ln for ln in out if ln.startswith("per-layer metrics with nothing")]
    assert said and LOOP["step"] in said[0]
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    assert "IPv6 byte-for-byte" in sample and "none-" not in sample
