"""CPU rehearsal of the layer files that read the Tracer's tiling, device
occupancy and counters (PR 25, PR 27's `wire.*` on the engine's own loop,
and every `span` / `counter` file added since): such a file is admitted by
what it is, not by a count, and in a `--trace 1` run of each cell every
such file that lists the cell returns a number. The values are a CPU's:
no number from here is a device metric."""

import glob
import json
import os

import pytest
from test_benchmark import ROOT, TINY_CELLS, _run, tiny_dir  # noqa: F401

from benchmark.lib import layers

ACCEPTED = {  # the per-layer metrics PR 24's benchmark had
    "engine.dispatch_p50_us", "engine.reply_us_per_frame",
    "express_step.device_p50_us", "fused_step.device_p50_us",
    "gen.late_p99_us", "gen.share", "loop.beat_p99_us", "loop.fwd_p99_us",
    "loop.offer_p99_us", "loop.us_per_frame", "ring.us_per_frame",
    "sched.bulk_occupancy", "sched.bulk_wait_p99_us",
    "sched.express_wait_p99_us", "slow.punt_share", "sharded.imbalance",
    "sharded.collective_share", "sharded_step.device_p50_us"}
NEW = {}  # what was added since, read from the program's spans and counters
for _path in glob.glob(os.path.join(ROOT, "benchmark", "layers", "*.json")):
    _m = json.load(open(_path))
    if _m["name"] not in ACCEPTED and _m["read"]["kind"] in ("span", "counter"):
        NEW[_m["name"]] = _m
# by what they read, these are above 0 wherever the loop moved a frame
POSITIVE = {
    "sched.bulk_device_p50_us", "loop.pack_us_per_frame",
    "loop.tx_us_per_frame", "sched.drain_us_per_frame",
    "sched.express_device_wait_p50_us", "sched.express_sojourn_p99_us",
    "sched.bulk_sojourn_p99_us", "sched.drain_p99_us",
    "sharded.ring_us_per_frame", "sharded.pack_us_per_frame",
    "sharded.reply_us_per_frame", "sharded.dispatch_us_per_step",
    "sharded.device_wait_us_per_step", "sharded.drain_us_per_step",
    "sharded.tx_us_per_frame",
    "wire.ring_us_per_frame", "wire.dispatch_p50_us", "wire.device_p50_us",
    "wire.device_wait_p50_us", "wire.reply_us_per_frame",
    "wire.frames_per_step",
    "pppoe.decap_per_step", "pppoe.encap_per_step",
    "dualstack.v6_fwd_per_step", "engine.drain_cached_per_step",
    "sched.drain_cached_per_step"}


def test_the_new_files_are_data_and_run_on_a_program_without_the_spans():
    """The rule that admits a `span` or `counter` file, whatever their
    number: its `source` is its reader's, it is a `.json` under benchmark/,
    and on a program without the Tracer's `trace` subtree its reader returns
    nothing. The driver lays these files over the parent's checkout too: a
    span file may name any stage or lane, the reader returns nothing for one
    the program lacks (test_benchmark.py), and a counter path that is
    missing reads as nothing. The tails a file may read are the next test's,
    a number in every cell it lists the last one's."""
    assert NEW
    for m in NEW.values():
        assert m["source"] == layers.SOURCE_OF_KIND[m["read"]["kind"]], m["name"]

    class Plan:
        flood = True

    ctx = layers.Context(plan=Plan(), loop=None, window=2.0, served=10,
                         c0={"sched": {}, "sharded": {}, "ring": {}, "engine": {}},
                         c1={"sched": {}, "sharded": {}, "ring": {}, "engine": {}},
                         tracer=None, profile=None, setup_s=0.0, n_devices=1)
    for m in NEW.values():
        assert layers.READERS[m["read"]["kind"]](m["read"], ctx) is None
    added = [p for p in glob.glob(os.path.join(ROOT, "benchmark", "**", "*"),
                                  recursive=True)
             if os.path.isfile(p) and "__pycache__" not in p
             and os.path.basename(p)[:-5] in NEW]
    assert len(added) == len(NEW) and all(p.endswith(".json") for p in added)


def test_a_snapshot_serves_the_tails_a_file_reads_and_no_others():
    """`Tracer.sums()` computes a p99 only for the (lane, stage) pairs a
    layer file reads under `trace.p99_us`; a new file that wants another
    adds it to `Tracer.P99_SERVED`."""
    from bng_tpu.telemetry import spans

    read = {tuple(m["read"]["path"].split(".")[-2:]) for m in NEW.values()
            if ".trace.p99_us." in m["read"].get("path", "")}
    served = {("all" if lane is None else spans.LANE_NAMES[lane],
               spans.STAGE_NAMES[stage])
              for lane, stage in spans.Tracer.P99_SERVED}
    assert read == served
    p99 = spans.Tracer().sums()["p99_us"]
    assert {(lane, stage) for lane, d in p99.items() for stage in d} == served
    # every `starved_ns` / `stage_ns` key a file names is a stage (or
    # `outside`), so a file cannot read a key the snapshot never has
    for m in NEW.values():
        parts = m["read"].get("path", "").split(".")
        if len(parts) == 4 and parts[2] in ("starved_ns", "stage_ns"):
            assert parts[3] in spans.STAGE_NAMES + ("outside",), m["name"]


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_every_new_layer_file_returns_a_number_in_its_cell(tiny_dir, capsys,  # noqa: F811
                                                           cell):
    if "4" in cell:
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs four (virtual) devices")
    real = TINY_CELLS[cell][0]
    res, out = _run(tiny_dir, capsys, cell, "--trace", "1")
    assert res["correct"] is True, out[-14:]
    want = {name for name, m in NEW.items() if real in m["cells"]}
    assert want
    got = res["metrics"]
    assert want <= set(got), sorted(want - set(got))
    for name in want:
        value = got[name]["value"]
        assert isinstance(value, float) and value >= 0, (name, value)
        if name in POSITIVE:
            assert value > 0, name
    for name in want:  # a share is one
        if got[name]["unit"] == "%":
            assert got[name]["value"] <= 100.0, (name, got[name])
    # the trap: c0 is taken after arm(), c1 after disarm() and the drain;
    # the `trace` subtree is in both, so every counter file above read it
    # (the four-chip NAT cell lists no such share: its loop's is S's file)
    shares = [n for n in want if "unattributed_share" in n]
    assert shares or real == "cgnat-sharded4-1M.flood-64B"
    assert all(got[n]["value"] < 100.0 for n in shares)


@pytest.mark.parametrize("path, refused", [("ring.tx", 0), ("ring.rx", 2)],
                         ids=["a read no file holds", "a repeat"])
def test_a_counter_file_dropped_in_is_admitted_with_no_test_edited(
        tmp_path, path, refused):
    """The rule at work: a copy of the benchmark with its tests, one more
    counter file in `layers/` and its entry in `BENCHMARK.json`, and the
    tests that admit such a file, as they stand, pass over the copy: the
    count is within the format's limit, every file's `cells` are its
    entry's `workloads`, and its cell reports no read twice. A file that
    is a second name for a read of its cell (`ring.rx` per `engine.batches`
    is `wire.frames_per_step`, which lists the cell) is refused by that last
    test alone, in the twin's case and in the original's. What
    the file reads in its cell is the parametrised rehearsal's to show
    (tests/test_dualstack_cell_rehearsal.py drops the same kind of file
    into a copy and reads a number from it)."""
    import shutil
    import subprocess
    import sys

    for part in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(ROOT, part), tmp_path / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cell = "pppoe-cgnat-1M-wire.flood-64B"
    extra = {"name": "pppoe.ring_frames_per_step", "unit": "frames",
             "better": "higher", "source": "program_counter",
             "layer": "engine (runtime/engine.py)", "moves": "served_kpps",
             "cells": [cell],
             "read": {"kind": "counter", "path": path,
                      "per": "engine.batches"}}
    assert extra["name"] not in NEW
    with open(tmp_path / "benchmark" / "layers" / (extra["name"] + ".json"),
              "w") as f:
        json.dump(extra, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {k: v for k, v in extra.items() if k not in ("cells", "read")}
    bench["per_layer"].append(dict(entry, workloads=[cell]))
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    here = os.path.join("tests", "benchmark")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PYTEST_", "COV_"))}
    env.update(PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly",
         os.path.join(here, "test_trace_layers.py") + "::test_the_new_files_"
         "are_data_and_run_on_a_program_without_the_spans",
         os.path.join(here, "test_trace_layers.py") + "::test_a_snapshot_"
         "serves_the_tails_a_file_reads_and_no_others",
         os.path.join(here, "test_benchmark.py") + "::test_layer_files_and_"
         "benchmark_json_agree",
         os.path.join(here, "test_benchmark.py") + "::test_names_units_and_"
         "lengths",
         os.path.join(here, "test_benchmark.py") + "::test_per_layer_is_"
         "within_the_formats_limit",
         os.path.join(here, "test_benchmark.py") + "::test_a_layer_files_"
         "cells_are_its_entrys_workloads",
         os.path.join(here, "test_benchmark.py") + "::test_no_cell_reports_"
         "this_files_read_under_a_second_name"],
        capture_output=True, text=True, timeout=600, env=env, cwd=tmp_path)
    files = len(layers.layer_files(os.path.join(ROOT, "benchmark"))) + 1
    assert f"{5 + 2 * files - refused} passed" in out.stdout, \
        out.stdout[-3000:] + out.stderr[-2000:]
    if not refused:
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
        return
    assert f"{refused} failed" in out.stdout, out.stdout[-3000:]
    for name in (extra["name"], "wire.frames_per_step"):
        assert ("FAILED tests/benchmark/test_benchmark.py::test_no_cell_reports_"
                f"this_files_read_under_a_second_name[{name}]") in out.stdout
