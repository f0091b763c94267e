"""The cell `churn-cgnat-1M-wire.flood-64B-newflows` in test_benchmark.py's
own rehearsal directory, as the stand-in `tiny-churn.flood` (tests/conftest.py
adds it to the three literals): its configuration, its kit, its traffic file
and its nine layer files are found by name, at 4,096 subscribers, 1,024 of
them behind NAT, most of which open a flow in the window. Its traffic is the
cell's own file with a pool a CPU run cannot wrap and the gap a tiny pool
allows (a pool of new flows may not cycle: a first packet is new once).
tests/test_churn_cell_rehearsal.py is the longer rehearsal, over both
one-chip loops, with the parent's behaviour as the control. No number from
here is a device metric."""

import json
import os

import pytest
from test_benchmark import BENCH, TINY_CELLS, _run, tiny_dir  # noqa: F401

from benchmark.lib import app as applib
from benchmark.lib import layers

REAL = "churn-cgnat-1M-wire.flood-64B-newflows"
W = "cgnat-1M-wire.flood-64B"
GENERIC = {"churn.gen_share", "churn.loop_us_per_frame", "churn.beat_p99_us",
           "churn_step.device_p50_us"}
OWN = {"churn.new_flows_per_step", "churn.punt_us_per_flow",
       "churn.requeued_again_per_step", "churn.drain_built_per_step",
       "churn.apply_device_p50_us"}
DEVICE = {"churn_step.device_p50_us", "churn.apply_device_p50_us"}
# a frame that punts again on its second pass is a fault, not a race
ZERO_IN_A_SOUND_RUN = {"churn.requeued_again_per_step"}
# a CPU runs the tiny loop at some 50 kpps, the chip's own rate: the pool
# holds 1.5 s of three times that; 1,024 NAT subscribers open its flows
POOL, GAP, NAT_SUBS, PUBLIC_IPS = 262144, 2048, 1024, 32


@pytest.fixture(scope="module")
def churn_dir(tiny_dir):  # noqa: F811
    """`tiny_dir` with the stand-in's traffic file and the gap in its
    configuration's sizes (tiny_dir replaces `sizes` whole, and the kit's
    own keys fall back to their defaults)."""
    mix = applib.load_named("traffic", "flood-64B-newflows", tiny_dir)
    mix.update(name="tiny-flood-newflows", pool_frames=POOL, dhcp_share=0.05,
               warmup_frames=400)
    with open(os.path.join(tiny_dir, "traffic", mix["name"] + ".json"),
              "w") as f:
        json.dump(mix, f)
    cfg = applib.load_named("configs", "tiny-churn", tiny_dir)
    assert "new_flow_share_pct" not in cfg["sizes"]
    cfg["sizes"] = dict(cfg["sizes"], nat_subscribers=NAT_SUBS,
                        follow_up_gap_frames=GAP)
    cfg["nat_public_ips"]["count"] = PUBLIC_IPS  # 63 blocks an address
    with open(os.path.join(tiny_dir, "configs", "tiny-churn.json"), "w") as f:
        json.dump(cfg, f)
    return tiny_dir


def test_the_cell_and_its_files_are_in_the_benchmark_by_name():
    cell = {w["name"]: w for w in BENCH["workloads"]}[REAL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "churn-cgnat-1M-wire", "flood-64B-newflows", 1)
    assert cell["why"].endswith("no frame crossed a link")
    entry = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    # the format's limit on a line, which refused this PR's first hand-in
    for line in (cell["why"], entry["why"], entry["source"]):
        assert 1 <= len(line) <= 200 and line.isprintable(), line
    cfg = applib.load_named("configs", cell["config"])
    base = applib.load_named("configs", "ipoe-cgnat-1M-wire")
    assert cfg["kit"] == "churn" and cfg["architecture"] is None
    assert cfg["chips"] == 1 and cfg["reduced"] == entry["reduced"] == [
        "max_nat_sessions"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert "bpf/nat44.c:565-802" in cfg["source"]
    # W's program to the letter: the same argv, pool and cut
    assert cfg["argv"] == base["argv"]
    assert cfg["nat_public_ips"] == base["nat_public_ips"]
    assert cfg["off"] == base["off"]
    assert cfg["sizes"] == dict(base["sizes"], new_flow_share_pct=2,
                                follow_up_gap_frames=32768)
    assert cfg["guarantees"][:4] == base["guarantees"]
    assert len(cfg["guarantees"]) == 7 == len(set(cfg["guarantees"]))
    mix = applib.load_named("traffic", cell["traffic"])
    flood = applib.load_named("traffic", "flood-64B")
    assert mix["pool_frames"] == 20 * flood["pool_frames"] == 2_621_440
    for key in ("kind", "dhcp_share", "renewal_ratio", "frame_bytes",
                "outstanding_cap_of_ring_depth", "warmup_frames",
                "warmup_dhcp_share"):
        assert mix[key] == flood[key], key
    named = {m["name"] for m in layers.layer_files(applib.BENCH_DIR)
             if REAL in m["cells"]}
    assert named == GENERIC | OWN
    assert [m["name"] for m in BENCH["per_layer"]
            if REAL in m["workloads"]] == [
        m["name"] for m in BENCH["per_layer"]][-9:]
    assert {m["name"] for m in BENCH["per_layer"][-9:]} == named
    assert all(m["workloads"] == [REAL] and m["moves"] == "served_kpps"
               for m in BENCH["per_layer"][-9:])
    served = {m["name"]: m for m in BENCH["end_to_end"]}["served_kpps"]
    assert served["workloads"][-1] == REAL or REAL in served["workloads"]
    assert BENCH["workloads"][-1]["name"] == REAL or REAL in [
        w["name"] for w in BENCH["workloads"]]
    setup = {m["name"]: m for m in BENCH["end_to_end"]}["setup_s"]
    assert "workloads" not in setup  # every cell reports it
    # an apply writes its batch's rows: nothing of a frame's slot
    kit = applib.load_kit(cfg)
    assert kit.stage_bytes(8192, 1536) == kit.stage_bytes(128, 64) > 0


def test_the_plain_reference_holds_nothing_of_the_program():
    """`Plain` allocates by `struct`, plain Python and numpy: the kit's
    module imports the program nowhere at its top, and the class (and the
    class it extends, kits/shardnat.py's) nowhere at all."""
    import ast
    import inspect

    from benchmark.kits import churn, shardnat

    for mod in (churn, shardnat):
        tree = ast.parse(inspect.getsource(mod))
        top = [n for n in tree.body
               if isinstance(n, (ast.Import, ast.ImportFrom))]
        names = [a.name for n in top if isinstance(n, ast.Import)
                 for a in n.names]
        names += [n.module for n in top if isinstance(n, ast.ImportFrom)]
        assert not [n for n in names if n.split(".")[0] == "bng_tpu"]
        plain = next(n for n in tree.body
                     if isinstance(n, ast.ClassDef) and n.name == "Plain")
        assert not [n for n in ast.walk(plain)
                    if isinstance(n, (ast.Import, ast.ImportFrom))]
        assert "bng_tpu" not in ast.unparse(plain)


def test_the_stand_in_rehearses_traced(churn_dir, capsys):
    assert TINY_CELLS["tiny-churn.flood"][0] == REAL
    res, out = _run(churn_dir, capsys, "tiny-churn.flood", "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0, out[-16:]
    assert any(ln.startswith("cell: ") and ln.endswith("kit=churn")
               for ln in out)
    assert all(c["value"] == 0 for c in res["compared"].values())
    told = [ln for ln in out if ln.startswith("check declared to the host: ")]
    assert told and told[0].split()[5:7] == ["0", "DHCP,"]
    assert int(told[0].split()[7]) > 0  # data frames declared, and accepted
    sample = [ln for ln in out if ln.startswith("check sample: ")][0]
    assert "first packets of new flows" in sample
    assert "flows opened, each read back" in sample
    assert " 0 first packets" not in sample and " 0 replies" not in sample
    late = [ln for ln in out if ln.startswith("programs built or loaded: ")]
    assert late and "in the window 0 " in late[0]
    got = res["metrics"]
    # every file that lists the cell reports, but the device trace's two
    assert set(got) == (GENERIC | OWN) - DEVICE
    for name in (OWN - DEVICE) - ZERO_IN_A_SOUND_RUN:
        assert got[name]["value"] > 0, name  # every `counter` file above 0
    for name in ZERO_IN_A_SOUND_RUN:
        assert got[name]["value"] == 0, name
    assert got["churn.punt_us_per_flow"]["unit"] == "us"
    said = [ln for ln in out if ln.startswith("per-layer metrics with nothing")]
    assert said and all(name in said[0] for name in DEVICE)


def test_the_counters_are_left_out_where_no_flow_is_opened(churn_dir, capsys):
    """W's stand-in runs the same program under flood-64B: no punt, no dirty
    beat, so the new counters read 0 there, `engine.drain_built_per_step`
    stays 0.0, and none of the cell's files is in W's line."""
    res, _out = _run(churn_dir, capsys, "tiny-wire.flood", "--trace", "1")
    assert res["correct"] is True
    assert TINY_CELLS["tiny-wire.flood"][0] == W
    assert not (GENERIC | OWN) & set(res["metrics"])
    assert res["metrics"]["engine.drain_built_per_step"]["value"] == 0.0
    from bng_tpu.telemetry import spans

    sums = spans.trace_sums()  # the window's tracer, frozen at disarm
    assert sums["batches"] > 0 and sums["stage_ns"]["punt"] == 0
    assert not any(v for k, v in sums.items() if k.startswith("newflow_"))


def test_both_controls_fail_the_stand_in(churn_dir, capsys):
    for control in ("stale-binding", "bad-checksum"):
        res, out = _run(churn_dir, capsys, "tiny-churn.flood", "--trace", "0",
                        "--control", control)
        assert res["correct"] is False, (control, out[-12:])
        assert res["compared"]["sampled_replies_differing"]["value"] > 0
        assert res["compared"]["lost_frames"]["value"] == 0
