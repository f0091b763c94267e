"""CPU rehearsal of the layer files that read the Tracer's tiling, device
occupancy and counters (PR 25, and PR 27's `wire.*` on the engine's own
loop): in a `--trace 1` run of each cell, every such file that lists the
cell returns a number. The values are a CPU's:
no number from here is a device metric."""

import glob
import json
import os

import pytest
from test_benchmark import ROOT, TINY_CELLS, _run, tiny_dir  # noqa: F401

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
    "wire.frames_per_step"}


def test_the_new_files_are_data_and_run_on_a_program_without_the_spans():
    """The driver lays these files over the parent's checkout too, whose
    `STAGE_NAMES` and `LANE_NAMES` lacked what PR 25 added, and until PR 27
    `read_span` raised on a name it did not find. So PR 25's `span` files
    name only stages and lanes its parent had, and everything new went
    through `counter`, which returns nothing where the path is missing;
    PR 27's `wire.*` span files name lane `ring`, which PR 25's parent had
    too. Since PR 27 a span file may name any stage: the reader returns
    nothing for one the program lacks (test_benchmark.py)."""
    parent_stages = {"ring", "admit", "lane_wait", "dispatch", "loop_fill",
                     "loop_wait", "loop_retire", "device", "device_wait",
                     "fleet", "worker", "slow_path", "reply", "ops",
                     "wire_rx", "wire_tx", "total"}
    parent_lanes = {"engine", "express", "bulk", "ring", "bench"}
    assert len(NEW) == 29 + 8  # PR 25's, and PR 27's wire.* less the device trace's
    for m in NEW.values():
        read = m["read"]
        assert m["source"] == {"span": "program_span",
                               "counter": "program_counter"}[read["kind"]]
        if read["kind"] == "span":
            assert read["stage"] in parent_stages, m["name"]
            assert read.get("lane", "bulk") in parent_lanes, m["name"]
    # without the program's `trace` subtree a counter file reads nothing
    from benchmark.lib import layers

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
    assert added and all(p.endswith(".json") for p in added)


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
    shares = [n for n in want if "unattributed_share" in n]
    assert shares and all(got[n]["value"] < 100.0 for n in shares)
