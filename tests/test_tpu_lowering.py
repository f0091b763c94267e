"""Described-chip compiles: every program of the served path, at the
geometry `chip_smoke.py` serves, through the TPU compiler installed
here — for a v5e that is described, not attached.

A program can pass its whole CPU suite and still be refused by the
chip's compiler. These compiles cost no chip time and guard every later
PR. Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports every
test file. Keep all described-chip tests in THIS file.
"""

import re

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, SingleDeviceSharding

from bng_tpu.runtime import verify
from bng_tpu.runtime.verify import (REAL_1M, REAL_1M_EDGE, REAL_1M_PPPOE,
                                    REAL_1M_QINQ, REAL_1M_V6,
                                    compile_for)

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
            + m.generated_code_size_in_bytes)


def _whiles(compiled) -> list[str]:
    """The `while` instructions of a compiled program, one line each."""
    return [line.strip() for line in compiled.as_text().splitlines()
            if " while(" in line]


@pytest.fixture(scope="module")
def fused_step(one_chip):
    """B=8192 over the 1M/1M table set, donated: one compile (27 s) for
    every test of the fused step."""
    return compile_for(verify.build_pipeline(REAL_1M), one_chip)


def test_fused_step_fits_one_chip(fused_step):
    m = fused_step.memory_analysis()
    # the tables are donated: nearly every argument byte is aliased
    assert m.alias_size_in_bytes > 0.9 * m.argument_size_in_bytes
    assert _device_bytes(fused_step) < V5E_HBM_BYTES


def test_fused_step_has_no_while(fused_step):
    """A part-row window scattered into a table compiles to a serial
    `while` over the lanes, a single column to two table-sized relayout
    loops (ops/nat44.py nat44_update_sessions): 34 ms of a 94 ms step on
    a v5e until PR 29, and the step's only loops. Every table write is a
    whole-row scatter, which the chip does natively."""
    assert _whiles(fused_step) == []


def _n_leaves(tree) -> int:
    return len(jax.tree_util.tree_leaves(tree))


def test_fused_step_takes_its_tables_and_three_more(fused_step):
    """Since PR 51 a window is ONE block (hostpath.seal_window: packet
    slots, and the lengths' and access flags' planes in the rows behind
    them): the lowered signature holds the tables' 34 leaves, the block
    and the two clock words, where it held 34 + 5. Taking the block apart
    on the device (engine.py split_window) brings no loop (the test above)
    and no move of a table (the relayout tests below stand as they were)."""
    from bng_tpu.runtime import hostpath

    (tables, window, now_s, now_us), _kw = fused_step.args_info
    assert _n_leaves(tables) == 34
    assert _n_leaves(fused_step.args_info) == 34 + 3
    B, L = REAL_1M.batch, REAL_1M.pkt_slot
    assert window.shape == (hostpath.window_rows(B, L), L) == (B + 27, L)
    assert window.dtype == np.uint8
    assert now_s.shape == now_us.shape == ()
    # the block is read, never written: no output aliases it
    assert not window.donated and all(
        x.donated for x in jax.tree_util.tree_leaves(tables))


@pytest.mark.parametrize("lanes", [1024, 128])
def test_fused_step_compiles_at_every_rung_of_its_ladder(one_chip, lanes):
    """A window shorter than `--batch-size` runs at the narrowest rung that
    holds it (engine.py step_rungs: 128 / 1,024 / 8,192 at the cells'
    size). The narrower programs are the same step traced at fewer lanes:
    they fit, and a smaller batch brings back no loop."""
    from bng_tpu.runtime.engine import step_rungs

    assert step_rungs(REAL_1M.batch) == (128, 1024, REAL_1M.batch)
    compiled = compile_for(verify.build_pipeline(REAL_1M, lanes=lanes),
                           one_chip)
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    assert _whiles(compiled) == []


def test_fused_step_with_the_pppoe_stage_fits_and_has_no_while(one_chip):
    """`bng run --pppoe-enabled` at the 1M geometry, the two session
    tables sized for 65,535 sessions: the step whose decap and encap move
    the whole [8192, 1536] slot by 8 bytes, as selects over static
    shifts. It fits, and the byte moves bring no loop."""
    from bng_tpu.control.pppoe.server import PPPoEServerConfig
    from bng_tpu.ops.table import nbuckets_for

    assert REAL_1M_PPPOE.pppoe_nbuckets == nbuckets_for(
        PPPoEServerConfig.max_sessions)  # as cli.py sizes the tables
    compiled = compile_for(verify.build_pipeline(REAL_1M_PPPOE), one_chip)
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    assert _whiles(compiled) == []


def test_fused_step_with_the_v6_stage_fits_and_has_no_while(one_chip):
    """`bng run --ipv6-fastpath` at the 1M geometry, the by-address table
    sized for 1,000,000 IA_NA bindings: the step with the destination
    window, one more table probe and QoS keyed for both families. It fits,
    brings no loop, and moves no table between physical forms."""
    from bng_tpu.ops.table import nbuckets_for

    assert REAL_1M_V6.v6_nbuckets == nbuckets_for(1_000_000)  # as cli.py sizes it
    compiled = compile_for(verify.build_pipeline(REAL_1M_V6), one_chip)
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    assert _whiles(compiled) == []
    assert _table_relayouts(compiled, f"{(1 << 19) // 4},128") == []


@pytest.mark.parametrize("lanes", [1024, None], ids=["rung-1024", "full"])
def test_fused_step_with_the_qinq_stage_fits_and_has_no_while(one_chip, lanes):
    """`bng run --pppoe-enabled --qinq-enabled` at the 1M geometry, the pair
    table sized for 1,000,000 subscribers: the step that pops the tags off
    the whole slot and pushes a pair onto it after the PPPoE encap, as
    selects over static shifts, with one more table probe. It fits, and the
    byte moves bring no loop. (What the pair table's probe costs in whole-
    table copies is the chip's to say: PERF.md section 5.)"""
    from bng_tpu.ops.table import nbuckets_for

    assert REAL_1M_QINQ.qinq_nbuckets == nbuckets_for(1_000_000)  # as cli.py
    assert REAL_1M_QINQ.pppoe_nbuckets == REAL_1M_PPPOE.pppoe_nbuckets
    compiled = compile_for(verify.build_pipeline(REAL_1M_QINQ, lanes=lanes),
                           one_chip)
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    assert _whiles(compiled) == []
    assert _table_relayouts(compiled, f"{(1 << 19) // 4},128") == []


@pytest.mark.parametrize("lanes", [1024, None], ids=["rung-1024", "full"])
def test_fused_step_with_the_edge_stage_fits_and_has_no_while(one_chip, lanes):
    """`bng run --edge-enabled` at the 1M geometry, the route table sized
    for 1,000,000 subscribers and the tap table for its default 4,096
    warrants (two geometries): the step with two more probes, the [B, 64]
    filter scan under its `cond` and the six-byte MAC stamp. It fits, the
    stamp brings no loop, and the route table stays in one physical form."""
    from bng_tpu.ops.table import nbuckets_for

    assert REAL_1M_EDGE.route_nbuckets == nbuckets_for(1_000_000)  # as cli.py
    assert REAL_1M_EDGE.tap_nbuckets == nbuckets_for(4096)
    compiled = compile_for(verify.build_pipeline(REAL_1M_EDGE, lanes=lanes),
                           one_chip)
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    assert _whiles(compiled) == []
    assert _table_relayouts(compiled, f"{(1 << 19) // 4},128") == []


@pytest.mark.parametrize("build", [
    verify.build_dhcp_express,  # engine.py _dhcp_jit, B=64
    verify.build_express_aot,  # engine.py _express_jit, B=64
], ids=["dhcp_express", "express_aot"])
def test_express_programs_compile(one_chip, build):
    compiled = compile_for(build(REAL_1M), one_chip)
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    # the chain's leaves (three tables of three, pools, server), and the
    # window or descriptor rows with the clock word (and the DHCP-only
    # program's lengths): since PR 51 the express dispatch places the
    # descriptor alone, the clock word crosses inside the call
    (chain, *rest), _kw = compiled.args_info
    assert _n_leaves(chain) == 11
    assert len(rest) == (3 if build is verify.build_dhcp_express else 2)
    # PR 50's finding: no step applies an update batch any more, and each
    # still moves the chain's widest table (the circuit-id probe rows,
    # 134 MB) from the form the device holds it in, {0,1}, to the {1,0} its
    # 64-word row gathers read: once, 625 us of an 830 us express program.
    # The parent's trace named that copy after `ops/table.py:152` because
    # it copied the scatter's result; the scatter itself runs in place in
    # {0,1}. The cure is the table's shape (ROADMAP S6), not the batch.
    assert len(_table_relayouts(compiled, CID_KROWS)) == 1


# the circuit-id table's probe rows at 1M subscribers: [nbuckets, 4 ways of
# way_stride(8 key words) = 16]
CID_KROWS = f"{1 << 19},64"


def test_fused_step_relayouts_the_cid_rows_once_for_its_gathers(fused_step):
    """The same for the fused step: the one copy is the gathers', and with
    no update batch in the program nothing else of scope `updates` is left
    (PERF.md section 6 PR 50)."""
    moves = _table_relayouts(fused_step, CID_KROWS)
    assert len(moves) == 1 and "{1,0" in moves[0].split(" copy(")[0]
    assert "scatter" not in moves[0]


@pytest.mark.parametrize("build", [
    verify.build_apply_fastpath,  # engine.py _apply_fastpath_jit
    verify.build_apply_updates,  # engine.py _apply_updates_jit
], ids=["apply_fastpath", "apply_updates"])
def test_apply_programs_compile(one_chip, build):
    """The two packet-free programs a DIRTY drain goes through ahead of
    its step: row scatters in the form the device holds each table in, in
    place (donated), and no whole-table move: what a dirty beat adds on
    the device is small."""
    compiled = compile_for(build(REAL_1M), one_chip)
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    assert _whiles(compiled) == []
    if build is verify.build_apply_fastpath:
        assert _table_relayouts(compiled, CID_KROWS) == []


def test_table_probe_compiles(one_chip):
    compile_for(verify.build_table(REAL_1M), one_chip)


def _table_relayouts(compiled, shape: str) -> list[str]:
    """The `copy` / `reshape` / `transpose` instructions of a compiled
    program whose result is a u32 array of `shape`: a table moved between
    physical forms. (`copy-start` / `copy-done`, the compiler's moves
    between memory spaces, keep the form and are not matched.)"""
    text = compiled.as_text()
    assert f"u32[{shape}]" in text, f"no u32[{shape}] in the program"
    pat = re.compile(r"= u32\[" + re.escape(shape)
                     + r"\]\{[^}]*\} (copy|reshape|transpose)\(")
    return [line.strip()[:160] for line in text.splitlines() if pat.search(line)]


def test_fused_step_keeps_the_qos_tables_in_one_form(fused_step):
    """Held as [nbuckets*4, 8] the two QoS tables were copied between three
    tiled forms every step: eight whole-table ops, 8.7 ms of a 27.0 ms
    step on a v5e until PR 33. Held [nbuckets/4, 128] (ops/qtable.py) the
    chip's compiler has no other form to move them to."""
    assert _table_relayouts(fused_step, f"{(1 << 19) // 4},128") == []

@pytest.fixture(scope="module")
def sharded_step(topo):
    """1M subscribers hash-sharded four ways over a 2x2 v5e host: the
    compiled mesh step, and one shard's session rows `S`."""
    from bng_tpu.parallel.sharded import AXIS

    mesh = Mesh(np.array(topo.devices), (AXIS,))
    per_shard = REAL_1M._replace(
        batch=REAL_1M.batch // 4, sub_nbuckets=1 << 17,
        side_nbuckets=1 << 17, nat_sessions_nbuckets=1 << 17,
        sub_nat_nbuckets=1 << 15)
    fn, args = verify.build_sharded(mesh, per_shard)
    return compile_for((fn, args)), args[0].nat.sessions.vals.shape[1]


def test_sharded_step_compiles_for_four_chips(sharded_step):
    compiled, _ = sharded_step
    # memory_analysis of a mesh program is per device
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    # the hash-sharded DHCP lookup exchanges keys/results over ICI
    assert "all-to-all" in compiled.as_text()


def test_sharded_step_keeps_the_qos_tables_in_one_form(sharded_step):
    compiled, _ = sharded_step
    assert _table_relayouts(compiled, f"1,{(1 << 17) // 4},128") == []


def test_sharded_step_loops_over_no_session_table(sharded_step):
    """No `while` of the mesh step carries the session table, in its own
    shape or in a relayout's (`[S,16]`, `[1,16,S]`, flat): the accounting
    pass writes whole rows there too."""
    compiled, S = sharded_step
    shapes = (f"{S},16]", f"16,{S}]", f"[{16 * S}]")
    over_table = [w for w in _whiles(compiled)
                  if any(shape in w for shape in shapes)]
    assert over_table == []


def test_sharded_step_compiles_with_a_deployments_nat_a_shard(topo):
    """`ipoe-cgnat-sharded4-1M` (PR 42): the same four-way mesh step with a
    shard's NAT tables at a one-chip deployment's size, 1,000,000 sessions
    and 250,000 port blocks a chip as `bng run --shards 4
    --max-nat-sessions 4000000 --max-nat-subscribers 1000000` sizes them.
    It fits, still exchanges only the DHCP lookup, and no `while` carries
    the larger session table either."""
    from bng_tpu import cli
    from bng_tpu.parallel.sharded import AXIS

    mesh = Mesh(np.array(topo.devices), (AXIS,))
    per_shard = REAL_1M._replace(
        batch=REAL_1M.batch // 4, sub_nbuckets=1 << 17,
        side_nbuckets=1 << 17,
        nat_sessions_nbuckets=cli._shard_sized(4_000_000, 4, 0),
        sub_nat_nbuckets=cli._shard_sized(1_000_000, 4, 0))
    assert (per_shard.nat_sessions_nbuckets, per_shard.sub_nat_nbuckets) == (
        1 << 19, 1 << 17)
    fn, args = verify.build_sharded(mesh, per_shard)
    compiled = compile_for((fn, args))
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    assert "all-to-all" in compiled.as_text()
    S = args[0].nat.sessions.vals.shape[1]
    shapes = (f"{S},16]", f"16,{S}]", f"[{16 * S}]")
    assert [w for w in _whiles(compiled)
            if any(shape in w for shape in shapes)] == []


@pytest.mark.slow  # ~47s of CPU compiles
def test_gate_harness_compiles_on_any_backend():
    """The checks must compile on the attached backend, so harness API
    drift is caught by the plain CPU suite."""
    results = verify.verify_tpu_lowering(verbose=False)
    failures = [(n, e) for n, e in results if e is not None]
    assert not failures, "gate harness failures:\n" + "\n".join(
        f"--- {n} ---\n{e}" for n, e in failures)
