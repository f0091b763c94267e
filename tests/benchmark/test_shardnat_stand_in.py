"""The cell `cgnat-sharded4-1M.flood-64B` in test_benchmark.py's own
rehearsal directory, as the stand-in `tiny4-nat.flood` (the `4` in its name
gives it four chips there): its configuration and its kit are found by
name, its layer files by what lists the cell and by what each reads
(`test_benchmark.generic`), at 4,096 subscribers of whom 128 are behind NAT,
one public address a shard.
tests/test_shardnat_cell_rehearsal.py is the longer rehearsal (every
subscriber behind NAT, 20 addresses a shard, past the pool's wrap, both
controls, the starved pool). No number from here is a device metric."""

import pytest
from test_benchmark import (BENCH, TINY_CELLS, _run, generic,  # noqa: F401
                            listed, reading, tiny_dir)

from benchmark.lib import app as applib

REAL = "cgnat-sharded4-1M.flood-64B"
OLD = "sharded4-1M.flood-64B"
# the cell's own three; the loop's generic reads; and the mesh loop's seven
# that PR 42 brought under the cell's prefix (since PR 52 the cell is listed
# in S's files, which held those reads first)
OWN = {"shardnat.nat_fwd_per_step", "shardnat.nat_punt_per_step",
       "shardnat.steer_miss_per_s"}
LOOP = generic(REAL, "step", "loop", "gen")
MESH = {k: reading(listed(REAL), **read) for k, read in {
    "collective": dict(kind="trace_device", stat="collective_share"),
    "imbalance": dict(kind="counter", path="sharded.per_shard.*.frames"),
    "wait": dict(kind="counter", path="sharded.trace.stage_ns.device_wait"),
    "dispatch": dict(kind="counter", path="sharded.trace.stage_ns.dispatch"),
    "built": dict(kind="counter", path="sharded.trace.drain_built"),
    "starved": dict(kind="counter", path="sharded.trace.beat_starved_ns"),
}.items()}
FILES = OWN | set(LOOP.values()) | set(MESH.values())
NO_DEVICE = {LOOP["step"], MESH["collective"]}
ZERO_OK = {"shardnat.nat_punt_per_step", "shardnat.steer_miss_per_s",
           MESH["built"], MESH["starved"]}


@pytest.fixture(autouse=True)
def four_devices():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")


def test_the_cell_and_its_files_are_in_the_benchmark_by_name():
    cell = {w["name"]: w for w in BENCH["workloads"]}[REAL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ipoe-cgnat-sharded4-1M", "flood-64B", 4)
    assert cell["why"].endswith("no frame crossed a link")
    cfg = applib.load_named("configs", cell["config"])
    old = applib.load_named("configs", "ipoe-sharded4-1M")
    assert cfg["kit"] == "shardnat" and cfg["architecture"] is None
    assert (cfg["chips"], cfg["shards"]) == (4, 4)
    assert cfg["reduced"] == [] and cfg["reduced_why"] == {}
    assert cfg["argv"] == old["argv"] + ["--max-nat-sessions", "4000000",
                                         "--max-nat-subscribers", "1000000"]
    assert cfg["nat_public_ips"] == {"base": "198.18.0.0", "count": 16000}
    assert cfg["sizes"] == {"subscribers": 1_000_000,
                            "nat_subscribers": 1_000_000,
                            "flows_per_nat_subscriber": 4}  # 4M sessions
    assert len(cfg["source"]) <= 200 and "nat44.c:38-40" in cfg["source"]
    assert set(old["off"]) < set(cfg["off"])
    assert cfg["guarantees"] == applib.load_named("configs", "ipoe-cgnat-1M")[
        "guarantees"]
    assert "one owner a public address" in cfg["sharding"]
    named = set(listed(REAL))
    # twelve, as before the merge; a later PR may add a file that lists the cell
    assert FILES <= named and len(FILES) == 12
    assert {m["name"] for m in BENCH["per_layer"]
            if REAL in m["workloads"]} == named
    served = {m["name"]: m for m in BENCH["end_to_end"]}["served_kpps"]
    at = served["workloads"].index(OLD)
    assert served["workloads"][at + 1] == REAL  # beside the other 4-chip cell
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert four == [OLD, REAL]


def test_the_plain_reference_holds_nothing_of_the_program():
    """`Plain` is `struct`, plain Python and numpy: the kit's module imports
    the program nowhere at its top, and the class nowhere at all."""
    import ast
    import inspect

    from benchmark.kits import shardnat

    tree = ast.parse(inspect.getsource(shardnat))
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = [a.name for n in top if isinstance(n, ast.Import) for a in n.names]
    names += [n.module for n in top if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.split(".")[0] == "bng_tpu"]
    plain = next(n for n in tree.body
                 if isinstance(n, ast.ClassDef) and n.name == "Plain")
    assert not [n for n in ast.walk(plain)
                if isinstance(n, (ast.Import, ast.ImportFrom))]
    src = ast.unparse(plain)
    assert "bng_tpu" not in src and "shard" not in src.lower()
    assert not hasattr(shardnat, "stage_bytes")  # no device stage is added


def test_the_stand_in_rehearses_traced(tiny_dir, capsys):  # noqa: F811
    assert TINY_CELLS["tiny4-nat.flood"][0] == REAL
    res, out = _run(tiny_dir, capsys, "tiny4-nat.flood", "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert res["device"]["count"] == 4
    assert any(ln.startswith("cell: ") and ln.endswith("kit=shardnat")
               for ln in out)
    assert res["compared"]["sample_kinds_missing"] == {"value": 0, "limit": 0}
    got = res["metrics"]
    assert FILES - NO_DEVICE <= set(got)
    assert all(got[name]["value"] > 0 for name in FILES - NO_DEVICE - ZERO_OK)
    for name in ("shardnat.nat_punt_per_step", "shardnat.steer_miss_per_s",
                 MESH["built"]):
        assert got[name]["value"] == 0, name
    said = [ln for ln in out if ln.startswith("per-layer metrics with nothing")]
    assert said and all(name in said[0] for name in NO_DEVICE)
    # what the other four-chip cell reports beside these is not this cell's
    assert set(got) == set(listed(REAL)) - NO_DEVICE
    others = set(listed(OLD)) - set(listed(REAL))
    assert others and not others & set(got)


def test_both_controls_fail_the_stand_in(tiny_dir, capsys):  # noqa: F811
    for control in ("stale-binding", "bad-checksum"):
        res, out = _run(tiny_dir, capsys, "tiny4-nat.flood", "--trace", "0",
                        "--control", control)
        assert res["correct"] is False, (control, out[-12:])
        assert res["compared"]["sampled_replies_differing"]["value"] > 0
        assert res["compared"]["lost_frames"]["value"] == 0
