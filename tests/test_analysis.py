"""bngcheck analyzer tests: every pass must flag its planted violation
and stay silent on the clean corpus (ISSUE 6 acceptance).

Layout per pass: a miniature project tree is written under tmp_path
(mirroring the real repo-relative paths, because pass scoping and fact
extraction key on them), the pass runs on that tree, and the findings
are asserted by code. The clean-corpus tests run the full analyzer over
THIS repo and require zero non-baselined findings — the same gate
`make verify-static` enforces.

No jax import anywhere here: the static half is pure stdlib, and these
tests prove it stays that way (test_no_jax_import).
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bng_tpu.analysis import baseline as baseline_mod
from bng_tpu.analysis import run_analysis
from bng_tpu.analysis.core import Finding, Project, run_passes
from bng_tpu.analysis.passes import ALL_PASSES, all_codes, build

pytestmark = pytest.mark.analysis

REPO = Path(__file__).resolve().parents[1]


def write_tree(root: Path, files: dict[str, str]) -> Path:
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return root


def run_on(root: Path, select: set[str]) -> list[Finding]:
    project = Project.load(root, [root])
    return run_passes(project, build(select)).findings


def codes_of(findings) -> set[str]:
    return {f.code for f in findings}


# ---------------------------------------------------------------------------
# facts the registry fixtures share (miniature registries)
# ---------------------------------------------------------------------------

MINI_SPANS = """\
(RING, ADMIT, DISPATCH, TOTAL) = range(4)
STAGE_NAMES = ("ring", "admit", "dispatch", "total")
(LANE_ENGINE, LANE_BENCH) = range(2)
LANE_NAMES = ("engine", "bench")

_ACTIVE = None


def t():
    if _ACTIVE is None:
        return None
    return _ACTIVE.clock()


def lap(stage, t0, tok=None):
    if _ACTIVE is None or t0 is None:
        return
    _ACTIVE.lap(stage, t0, tok)
"""

MINI_FAULTS = """\
POINT_KINDS = {
    "engine.dispatch": ("fail", "delay"),
    "ckpt.write": ("truncate",),
}

_ACTIVE = None


def fault_point(name):
    if _ACTIVE is None:
        return None
    return _ACTIVE.check(name)
"""

MINI_METRICS = """\
class Registry:
    def counter(self, name, help_text, labels=()):
        return name


def declare(r):
    a = r.counter("bng_good_total", "fine")
    return a
"""

MINI_RECORDER = """\
TRIG_LATENCY = "latency_excursion"
TRIG_WORKER = "worker_death"
"""

MINI_CKPT = """\
def snapshot(meta, fastpath):
    meta["components"]["fastpath"] = {}
    return meta


def restore_into(ckpt, fastpath):
    targets = {"fastpath": fastpath}
    return targets
"""


# ---------------------------------------------------------------------------
# hotpath pass (BNG001/BNG002/BNG003)
# ---------------------------------------------------------------------------

class TestHotPathPass:
    def test_dispatch_scope_force_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/runtime/engine.py": """\
import numpy as np


class Engine:
    def _dispatch_step(self, pkt):
        res = self._step(pkt)
        v = np.asarray(res.verdict)       # BNG001: force in dispatch
        n = int(res.out_len)              # BNG001: scalar force on taint
        if res.verdict:                   # BNG001: truthiness on taint
            pass
        return res
"""})
        found = run_on(tmp_path, {"hotpath"})
        assert [f.code for f in found].count("BNG001") == 3
        details = {f.detail for f in found}
        assert "np.asarray" in details and "truthiness" in details

    def test_retire_scope_force_not_flagged(self, tmp_path):
        # same forces in a retire-side function: NOT dispatch-scoped
        write_tree(tmp_path, {"bng_tpu/runtime/engine.py": """\
import numpy as np


class Engine:
    def _apply_ring_verdicts(self, res):
        vv = np.asarray(res.verdict)
        return int(res.out_len)
"""})
        assert run_on(tmp_path, {"hotpath"}) == []

    def test_batch_scope_loop_flagged(self, tmp_path):
        # BNG004: per-frame loops in batch-native serving functions
        write_tree(tmp_path, {"bng_tpu/runtime/ring.py": """\
class PyRing:
    def _assemble_vec(self, out, out_len, out_flags):
        for i, f in enumerate(self._pending):   # BNG004: per-frame
            out[i] = f
        return len(self._pending)

    def _complete_vec(self, verdict, out, out_len, n):
        i = 0
        while i < n:                            # BNG004: per-frame
            i += 1
"""})
        found = run_on(tmp_path, {"hotpath"})
        assert [f.code for f in found].count("BNG004") == 2
        details = {f.detail for f in found}
        assert "for:(i, f)" in details and "while" in details

    def test_batch_scope_const_range_not_flagged(self, tmp_path):
        # bounded vectorized iteration (the 2-tag VLAN walk / 64-step
        # TLV scan shape) and comprehensions are the batch-native idiom
        write_tree(tmp_path, {"bng_tpu/runtime/hostpath.py": """\
def classify_dhcp_batch(buf, lens):
    et = buf[:, 12]
    for _ in range(2):
        et = et + 1
    rows = [r for r in (1, 2, 3)]
    return et
"""})
        assert run_on(tmp_path, {"hotpath"}) == []

    def test_batch_scope_other_function_not_flagged(self, tmp_path):
        # a per-frame loop OUTSIDE the batch scope (retire-side helper)
        write_tree(tmp_path, {"bng_tpu/runtime/ring.py": """\
class PyRing:
    def _retire_helper(self, batch):
        for f in batch:
            yield f
"""})
        assert run_on(tmp_path, {"hotpath"}) == []

    def test_hook_missing_guard_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/telemetry/spans.py": """\
_ACTIVE = None


def stamp(stage):
    _ACTIVE.stamp(stage)          # BNG003: no disarmed guard
"""})
        found = run_on(tmp_path, {"hotpath"})
        assert codes_of(found) == {"BNG003"}

    def test_hook_alloc_before_guard_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/chaos/faults.py": """\
_ACTIVE = None


def fault_point(name):
    meta = {"point": name}        # BNG002: allocates while disarmed
    if _ACTIVE is None:
        return None
    return _ACTIVE.check(name, meta)
"""})
        found = run_on(tmp_path, {"hotpath"})
        assert codes_of(found) == {"BNG002"}

    def test_alloc_in_guard_return_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/telemetry/spans.py": """\
_ACTIVE = None


def drain():
    if _ACTIVE is None:
        return []                 # BNG002: allocates per disarmed call
    return _ACTIVE.drain()
"""})
        assert codes_of(run_on(tmp_path, {"hotpath"})) == {"BNG002"}

    def test_guard_first_hook_clean(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/telemetry/spans.py": MINI_SPANS})
        assert run_on(tmp_path, {"hotpath"}) == []


# ---------------------------------------------------------------------------
# jit discipline (BNG010/BNG011/BNG012)
# ---------------------------------------------------------------------------

class TestJitDisciplinePass:
    def test_uncached_jit_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/ops/thing.py": """\
import jax


def make_step(geom):
    def step(x):
        return x
    return jax.jit(step)          # BNG010: no lru_cache on the factory
"""})
        assert "BNG010" in codes_of(run_on(tmp_path, {"jit-discipline"}))

    def test_cached_factory_clean(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/ops/thing.py": """\
import functools

import jax


@functools.lru_cache(maxsize=8)
def make_step(geom):
    def step(tables, upd, x):
        tables = apply_update(tables, upd)
        return tables, x
    return jax.jit(step, donate_argnums=(0,))
"""})
        assert run_on(tmp_path, {"jit-discipline"}) == []

    def test_missing_donate_on_table_step_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/ops/thing.py": """\
import functools

import jax


@functools.lru_cache(maxsize=8)
def make_step(geom):
    def step(tables, upd, x):
        tables = apply_fastpath_updates(tables, upd)
        return tables, x
    return jax.jit(step)          # BNG011: table step, no donation
"""})
        found = run_on(tmp_path, {"jit-discipline"})
        assert codes_of(found) == {"BNG011"}

    def test_missing_donate_on_express_entry_flagged(self, tmp_path):
        # ISSUE 13: the AOT-compiled express entry threads the dhcp
        # chain AND the descriptor batch (verdict block aliases it) —
        # a jitted step running the express probe program must donate
        # even when a refactor drops the in-step update apply
        write_tree(tmp_path, {"bng_tpu/runtime/thing.py": """\
import functools

import jax


@functools.lru_cache(maxsize=8)
def make_express(geom):
    def step(tables, desc, now_s):
        res = express_verdicts(tables, desc, geom, now_s)
        return tables, res.block
    return jax.jit(step)          # BNG011: express entry, no donation
"""})
        found = run_on(tmp_path, {"jit-discipline"})
        assert codes_of(found) == {"BNG011"}

    def test_donated_express_entry_clean(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/runtime/thing.py": """\
import functools

import jax


@functools.lru_cache(maxsize=8)
def make_express(geom):
    def step(tables, upd, desc, now_s):
        tables = apply_fastpath_updates(tables, upd)
        res = express_verdicts(tables, desc, geom, now_s)
        return tables, res.block
    return jax.jit(step, donate_argnums=(0, 2))
"""})
        assert run_on(tmp_path, {"jit-discipline"}) == []

    @pytest.mark.parametrize("entry", ["pipeline_step", "dhcp_fastpath"])
    @pytest.mark.parametrize("donate,want", [("", {"BNG011"}),
                                             (", donate_argnums=(0,)", set())])
    def test_step_without_an_update_argument_still_donates(
            self, tmp_path, entry, donate, want):
        # PR 50: no one-chip step applies a delta, and each still threads
        # the tables it is given (counters, tokens; the chain unchanged)
        write_tree(tmp_path, {"bng_tpu/runtime/thing.py": f"""\
import functools

import jax


@functools.lru_cache(maxsize=8)
def make_step(geom):
    def step(tables, pkt, length, now_s):
        return {entry}(tables, pkt, length, geom, now_s)
    return jax.jit(step{donate})
"""})
        assert codes_of(run_on(tmp_path, {"jit-discipline"})) == want

    @pytest.mark.parametrize("fn", ["_apply_all_updates",
                                    "apply_fastpath_updates"])
    @pytest.mark.parametrize("donate,want", [("", {"BNG011"}),
                                             (", donate_argnums=(0,)", set())])
    def test_apply_function_jitted_as_it_stands_donates(
            self, tmp_path, fn, donate, want):
        # the engine's two packet-free programs: the apply function itself
        # under one module-level jit (no factory: BNG010 has no say)
        write_tree(tmp_path, {"bng_tpu/runtime/thing.py": f"""\
import jax

from somewhere import {fn}

_apply_jit = jax.jit({fn}{donate})
"""})
        assert codes_of(run_on(tmp_path, {"jit-discipline"})) == want

    def test_bare_scalar_at_express_exe_call_flagged(self, tmp_path):
        # the AOT executable call site obeys the same fixed-width
        # scalar discipline as the jitted steps
        write_tree(tmp_path, {"bng_tpu/runtime/thing.py": """\
class Engine:
    def go(self, express_exe, tables, upd, desc, now):
        return self.express_exe(tables, upd, desc, int(now))  # BNG012
"""})
        found = run_on(tmp_path, {"jit-discipline"})
        assert [f.code for f in found] == ["BNG012"]

    def test_bare_scalar_at_step_call_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/runtime/thing.py": """\
class Engine:
    def go(self, pkt, now):
        return self._step(pkt, int(now), now * 1e6)  # BNG012 x2
"""})
        found = run_on(tmp_path, {"jit-discipline"})
        assert [f.code for f in found] == ["BNG012", "BNG012"]

    def test_unhashable_static_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/ops/thing.py": """\
import jax


def f(x, opts):
    return x


g = jax.jit(f, static_argnums=[1])   # BNG012: literal list
"""})
        assert "BNG012" in codes_of(run_on(tmp_path, {"jit-discipline"}))

    def test_bare_jit_decorator_in_function_flagged(self, tmp_path):
        # `@jax.jit` with no parentheses is an ast.Attribute, not a
        # Call — it must still be a BNG010 site inside an uncached body
        write_tree(tmp_path, {"bng_tpu/ops/thing.py": """\
import jax


def bench_config(geom):
    @jax.jit
    def step(x):
        return x
    return step(geom)
"""})
        found = run_on(tmp_path, {"jit-discipline"})
        assert codes_of(found) == {"BNG010"}
        assert found[0].detail == "jit-in-bench_config"

    def test_bare_jit_decorator_on_table_step_flagged(self, tmp_path):
        # the bare form cannot carry donate_argnums at all: a
        # table-applying body is BNG011 even at module level
        write_tree(tmp_path, {"bng_tpu/ops/thing.py": """\
import jax


@jax.jit
def step(tables, upd):
    return apply_fastpath_updates(tables, upd)
"""})
        found = run_on(tmp_path, {"jit-discipline"})
        assert codes_of(found) == {"BNG011"}

    def test_bare_jit_decorator_module_level_clean(self, tmp_path):
        # module-level bare @jax.jit on a non-table body: constructed
        # once at import, nothing to donate — clean
        write_tree(tmp_path, {"bng_tpu/ops/thing.py": """\
import jax


@jax.jit
def step(x):
    return x * 2
"""})
        assert run_on(tmp_path, {"jit-discipline"}) == []


# ---------------------------------------------------------------------------
# handler audit (BNG020/BNG021)
# ---------------------------------------------------------------------------

class TestHandlerAuditPass:
    def test_pass_only_broad_handler_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/control/foo.py": """\
def f(x):
    try:
        return x()
    except Exception:
        pass
"""})
        assert codes_of(run_on(tmp_path, {"handler-audit"})) == {"BNG020"}

    def test_silent_broad_handler_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/runtime/foo.py": """\
def f(x):
    ok = True
    try:
        x()
    except Exception:
        ok = False
    return ok
"""})
        assert codes_of(run_on(tmp_path, {"handler-audit"})) == {"BNG021"}

    def test_logging_counting_raising_handlers_clean(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/control/foo.py": """\
def f(x, log, stats):
    try:
        x()
    except Exception as e:
        log.warning("failed", error=str(e))
    try:
        x()
    except Exception:
        stats.errors += 1
    try:
        x()
    except Exception:
        raise
"""})
        assert run_on(tmp_path, {"handler-audit"}) == []

    def test_narrow_handler_not_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/control/foo.py": """\
def f(x):
    try:
        x()
    except ValueError:
        pass
"""})
        assert run_on(tmp_path, {"handler-audit"}) == []

    def test_outside_scope_not_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/utils/foo.py": """\
def f(x):
    try:
        x()
    except Exception:
        pass
"""})
        assert run_on(tmp_path, {"handler-audit"}) == []


# ---------------------------------------------------------------------------
# registry consistency (BNG030-BNG035)
# ---------------------------------------------------------------------------

REGISTRY_FACTS = {
    "bng_tpu/telemetry/spans.py": MINI_SPANS,
    "bng_tpu/chaos/faults.py": MINI_FAULTS,
    "bng_tpu/control/metrics.py": MINI_METRICS,
    "bng_tpu/telemetry/recorder.py": MINI_RECORDER,
    "bng_tpu/runtime/checkpoint.py": MINI_CKPT,
}


class TestRegistryPass:
    def test_unknown_stage_flagged(self, tmp_path):
        write_tree(tmp_path, {**REGISTRY_FACTS,
                              "bng_tpu/runtime/user.py": """\
from bng_tpu.telemetry import spans as tele


def f(t0):
    tele.lap(tele.BOGUS_STAGE, t0)
    tele.lap("dispatch", t0)
"""})
        found = [f for f in run_on(tmp_path, {"registry"})
                 if f.code == "BNG030"]
        assert {f.detail for f in found} == {"BOGUS_STAGE", "dispatch"}

    def test_unregistered_fault_point_flagged(self, tmp_path):
        write_tree(tmp_path, {**REGISTRY_FACTS,
                              "bng_tpu/control/user.py": """\
from bng_tpu.chaos.faults import fault_point


def f():
    fault_point("engine.dispatch")   # registered: clean
    fault_point("nope.unregistered")  # BNG031
"""})
        found = [f for f in run_on(tmp_path, {"registry"})
                 if f.code == "BNG031"]
        assert [f.detail for f in found] == ["nope.unregistered"]

    def test_unprefixed_and_stray_metric_flagged(self, tmp_path):
        write_tree(tmp_path, {**REGISTRY_FACTS,
                              "bng_tpu/control/metrics.py": MINI_METRICS
                              + """

def bad(r):
    return r.counter("foo_total", "no prefix")  # BNG032
""",
                              "bng_tpu/runtime/stray.py": """\
def f(r):
    return r.counter("bng_stray_total", "x")  # BNG035: not metrics.py
"""})
        found = run_on(tmp_path, {"registry"})
        assert {f.code for f in found} == {"BNG032", "BNG035"}

    def test_checkpoint_asymmetry_flagged(self, tmp_path):
        write_tree(tmp_path, {**REGISTRY_FACTS,
                              "bng_tpu/runtime/checkpoint.py": """\
def snapshot(meta, fastpath, nat):
    meta["components"]["fastpath"] = {}
    meta["components"]["nat"] = {}
    meta["components"]["orphan"] = {}       # save-only -> BNG033
    return meta


def restore_into(ckpt, fastpath, nat):
    comps = dict(ckpt)
    targets = {"fastpath": fastpath, "nat": nat}
    if "fastpath" in comps:
        pass
    return targets
"""})
        found = [f for f in run_on(tmp_path, {"registry"})
                 if f.code == "BNG033"]
        assert [f.detail for f in found] == ["save-only:orphan"]

    def test_unknown_trigger_reason_flagged(self, tmp_path):
        write_tree(tmp_path, {**REGISTRY_FACTS,
                              "bng_tpu/control/user.py": """\
from bng_tpu.telemetry import spans as tele


def f():
    tele.trigger("worker_death", "fine")
    tele.trigger("spooky_reason", "BNG034")
"""})
        found = [f for f in run_on(tmp_path, {"registry"})
                 if f.code == "BNG034"]
        assert [f.detail for f in found] == ["spooky_reason"]

    def test_missing_fact_source_is_loud(self, tmp_path):
        # no fact source anywhere in the tree: EVERY vocabulary-backed
        # check must say so, not silently check nothing
        write_tree(tmp_path, {"bng_tpu/runtime/user.py": "x = 1\n"})
        found = run_on(tmp_path, {"registry"})
        assert {f.code for f in found} == {"BNG990"}
        assert {f.detail for f in found} == {
            "stages", "fault-points", "trigger-reasons",
            "checkpoint-components"}


# ---------------------------------------------------------------------------
# single-writer (BNG040/BNG041)
# ---------------------------------------------------------------------------

class TestSingleWriterPass:
    def test_mutator_outside_allowlist_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/telemetry/rogue.py": """\
def f(engine, mac):
    engine.fastpath.add_subscriber(mac, pool_id=1, ip=1, lease_expiry=9)
"""})
        found = run_on(tmp_path, {"single-writer"})
        assert codes_of(found) == {"BNG040"}

    def test_tables_rebind_outside_engine_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/telemetry/rogue.py": """\
def f(engine, new):
    engine.tables = new
"""})
        found = run_on(tmp_path, {"single-writer"})
        assert codes_of(found) == {"BNG041"}

    def test_allowlisted_writer_clean(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/control/dhcp_server.py": """\
def f(tables, mac):
    tables.fastpath.add_subscriber(mac, pool_id=1, ip=1, lease_expiry=9)
"""})
        assert run_on(tmp_path, {"single-writer"}) == []

    def test_unrelated_insert_not_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/telemetry/fine.py": """\
def f(some_list, q):
    some_list.insert(0, q)      # not a table receiver
"""})
        assert run_on(tmp_path, {"single-writer"}) == []

    def test_fabric_membership_mutator_outside_allowlist_flagged(
            self, tmp_path):
        # ISSUE 19: the failure-detector views are single-writer state;
        # a rogue module watching/resetting slots desyncs verdicts from
        # the coordinator's HA ladder
        write_tree(tmp_path, {"bng_tpu/telemetry/rogue.py": """\
def f(coord, iid, now):
    coord.fabric_detector.watch(iid, now=now)
    coord.fabric_detector.reset(iid, now=now)
    coord.fabric_transport.reset_peer(iid)
"""})
        found = run_on(tmp_path, {"single-writer"})
        assert codes_of(found) == {"BNG040"}
        assert len(found) == 3

    def test_fabric_mutators_from_coordinator_clean(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/cluster/coordinator.py": """\
def f(self, iid, now):
    self.fabric_detector.watch(iid, now=now)
    self.fabric_transport.reset_peer(iid)
"""})
        assert run_on(tmp_path, {"single-writer"}) == []

    def test_generic_reset_receiver_not_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/telemetry/fine.py": """\
def f(histogram, sock):
    histogram.counters.reset()   # not a fabric receiver
    sock.reset_peer("x")         # bare name: no receiver chain match
"""})
        assert run_on(tmp_path, {"single-writer"}) == []

    def test_handoff_cursor_mutator_outside_allowlist_flagged(
            self, tmp_path):
        # ISSUE 20: the handoff receiver's ACK cursor / chunk map is
        # single-writer state — a rogue module feeding chunks or
        # manifests past the manager could half-hydrate a member
        # without the digest gate
        write_tree(tmp_path, {"bng_tpu/telemetry/rogue.py": """\
def f(member, src, body):
    member.handoff.receiver.set_manifest(src, body)
    member.handoff.receiver.accept_chunk(src, body)
"""})
        found = run_on(tmp_path, {"single-writer"})
        assert codes_of(found) == {"BNG040"}
        assert len(found) == 2

    def test_handoff_mutators_from_protocol_clean(self, tmp_path):
        write_tree(tmp_path,
                   {"bng_tpu/cluster/handoff/protocol.py": """\
def f(self, msg):
    self.receiver.set_manifest(msg.src, msg.body)
    self.receiver.accept_chunk(msg.src, msg.body)
"""})
        assert run_on(tmp_path, {"single-writer"}) == []


# ---------------------------------------------------------------------------
# fencing (BNG050)
# ---------------------------------------------------------------------------

class TestFencingPass:
    def test_unfenced_async_timing_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/utils/timing.py": """\
import time


def bench(engine, pkt):
    t1 = time.perf_counter()
    engine._dispatch_step(pkt)
    return time.perf_counter() - t1   # BNG050: measures enqueue only
"""})
        found = run_on(tmp_path, {"fencing"})
        assert codes_of(found) == {"BNG050"}

    def test_fenced_timing_clean(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/utils/timing.py": """\
import time

import jax


def bench(engine, pkt):
    t1 = time.perf_counter()
    res = engine._dispatch_step(pkt)
    jax.block_until_ready(res.verdict)
    return time.perf_counter() - t1
"""})
        assert run_on(tmp_path, {"fencing"}) == []

    def test_sync_surface_not_flagged(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/utils/timing.py": """\
import time


def bench(engine, frames):
    t1 = time.perf_counter()
    engine.process(frames)      # sync surface forces its own outputs
    return time.perf_counter() - t1
"""})
        assert run_on(tmp_path, {"fencing"}) == []


# ---------------------------------------------------------------------------
# concurrency pass (BNG060-BNG064) — ISSUE 9
# ---------------------------------------------------------------------------
#
# Each fixture tree carries a mini cli.py (the loop-roots fact: BNGApp
# tick/drive_once) plus a control/ module spawning its own thread, so
# the pass sees two contexts. The clean twin of every planted tree must
# stay silent — that asymmetry IS the test.

CONC_CLI = """\
class BNGApp:
    def __init__(self):
        self.w = Widget()

    def tick(self):
        self.w.poke()
"""

WIDGET_HEAD = """\
import threading


class Widget:
    def __init__(self):
        self._lock = threading.Lock()
        self.flag = 0
        self._t = None

    def start(self):
        self._t = threading.Thread(target=self._spin)
        self._t.start()

    def stop(self):
        self._t.join()

"""


def conc_tree(widget_tail: str) -> dict:
    return {"bng_tpu/cli.py": CONC_CLI,
            "bng_tpu/control/widget.py": WIDGET_HEAD + widget_tail}


class TestConcurrencyPass:
    def test_cross_context_unlocked_mutation_flagged(self, tmp_path):
        # flag written by the widget thread AND the loop, no lock
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        self.flag = 1

    def poke(self):
        self.flag = 2
"""))
        found = run_on(tmp_path, {"concurrency"})
        assert [f.code for f in found] == ["BNG060"]
        assert found[0].detail == "Widget.flag"

    def test_common_lock_clean(self, tmp_path):
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        with self._lock:
            self.flag = 1

    def poke(self):
        with self._lock:
            self.flag = 2
"""))
        assert run_on(tmp_path, {"concurrency"}) == []

    def test_constructor_writes_not_shared(self, tmp_path):
        # __init__ writes precede publication: the widget thread writing
        # what the constructor also wrote is not a race
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        with self._lock:
            self.flag = 1

    def poke(self):
        return self.flag
"""))
        assert run_on(tmp_path, {"concurrency"}) == []

    def test_check_then_act_without_writers_lock_flagged(self, tmp_path):
        # writers agree on _lock; the loop tests the flag OUTSIDE it
        # then writes under it — the stale-decision shape (PR 7)
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        with self._lock:
            self.flag = 1

    def poke(self):
        if not self.flag:
            with self._lock:
                self.flag = 2
"""))
        found = run_on(tmp_path, {"concurrency"})
        assert [f.code for f in found] == ["BNG062"]
        assert found[0].detail == "Widget.flag"

    def test_check_then_act_inside_lock_clean(self, tmp_path):
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        with self._lock:
            self.flag = 1

    def poke(self):
        with self._lock:
            if not self.flag:
                self.flag = 2
"""))
        assert run_on(tmp_path, {"concurrency"}) == []

    def test_bare_acquire_flagged_try_finally_clean(self, tmp_path):
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        with self._lock:
            self.flag = 1

    def poke(self):
        self._lock.acquire()
        self.flag = 2
        self._lock.release()

    def poke_safe(self):
        self._lock.acquire()
        try:
            self.flag = 3
        finally:
            self._lock.release()
"""))
        found = [f for f in run_on(tmp_path, {"concurrency"})
                 if f.code == "BNG061"]
        assert len(found) == 1
        assert found[0].scope == "Widget.poke"

    def test_blocking_under_loop_lock_flagged(self, tmp_path):
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        with self._lock:
            self.flag = 1

    def poke(self):
        import time
        with self._lock:
            time.sleep(0.1)
            self.flag = 2
"""))
        found = [f for f in run_on(tmp_path, {"concurrency"})
                 if f.code == "BNG063"]
        assert len(found) == 1 and "sleep" in found[0].detail

    def test_blocking_outside_lock_clean(self, tmp_path):
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        with self._lock:
            self.flag = 1

    def poke(self):
        import time
        time.sleep(0.1)
        with self._lock:
            self.flag = 2
"""))
        assert [f for f in run_on(tmp_path, {"concurrency"})
                if f.code == "BNG063"] == []

    def test_string_join_not_blocking(self, tmp_path):
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        with self._lock:
            self.flag = 1

    def poke(self):
        with self._lock:
            self.flag = 2
        return ",".join(str(x) for x in (1, 2))
"""))
        assert [f for f in run_on(tmp_path, {"concurrency"})
                if f.code == "BNG063"] == []

    def test_orphan_thread_flagged_stop_path_clean(self, tmp_path):
        write_tree(tmp_path, {
            "bng_tpu/cli.py": CONC_CLI.replace("Widget", "Orphan"),
            "bng_tpu/control/orphan.py": """\
import threading


class Orphan:
    def poke(self):
        pass

    def launch(self):
        threading.Thread(target=self._spin, daemon=True).start()

    def _spin(self):
        pass
"""})
        found = [f for f in run_on(tmp_path, {"concurrency"})
                 if f.code == "BNG064"]
        assert len(found) == 1 and found[0].scope == "Orphan.launch"
        # the stop-path twin (the same tree's Widget head has stop+join)
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        pass

    def poke(self):
        pass
"""))
        clean = [f for f in run_on(tmp_path, {"concurrency"})
                 if f.code == "BNG064"
                 and "widget" in f.path]
        assert clean == []

    def test_unresolvable_thread_target_is_loud(self, tmp_path):
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        pass

    def poke(self):
        pass

    def weird(self, pick):
        threading.Thread(target=pick()).start()
"""))
        found = [f for f in run_on(tmp_path, {"concurrency"})
                 if f.code == "BNG990"]
        assert any(f.detail.startswith("thread-target:") for f in found)

    def test_missing_loop_roots_is_loud(self, tmp_path):
        # no cli.py/BNGApp anywhere: the pass must say the loop context
        # is unclassifiable, not silently check nothing
        write_tree(tmp_path, {"bng_tpu/control/solo.py": "X = 1\n"})
        found = run_on(tmp_path, {"concurrency"})
        assert any(f.code == "BNG990" and f.detail == "loop-roots"
                   for f in found)

    def test_same_named_classes_in_different_modules_dont_merge(
            self, tmp_path):
        # two `Handler` classes in different control/ modules, each
        # writing the same attr from a different context: their site
        # lists must stay separate (same-file class identity), or the
        # disjoint contexts would fabricate a cross-context BNG060
        handler = '''\
import threading


class Handler:
    def serve(self):
        threading.Thread(target=self._run).start()

    def stop(self):
        pass

    def _run(self):
        self.busy = 1
'''
        write_tree(tmp_path, {
            "bng_tpu/cli.py": "class BNGApp:\n    def tick(self):\n"
                              "        pass\n",
            "bng_tpu/control/alpha.py": handler,
            "bng_tpu/control/beta.py": handler,
        })
        assert [f for f in run_on(tmp_path, {"concurrency"})
                if f.code == "BNG060"] == []

    def test_worker_context_excluded_from_races(self, tmp_path):
        # a multiprocessing target shares no memory with the loop:
        # loop+worker mutation of the same attr is NOT a BNG060
        write_tree(tmp_path, {
            "bng_tpu/cli.py": CONC_CLI,
            "bng_tpu/control/widget.py": """\
import multiprocessing


class Widget:
    def __init__(self):
        self.flag = 0

    def launch(self):
        multiprocessing.Process(target=self._grind).start()

    def _grind(self):
        self.flag = 1

    def poke(self):
        self.flag = 2
"""})
        assert [f for f in run_on(tmp_path, {"concurrency"})
                if f.code == "BNG060"] == []


class TestConcurrencyFacts:
    def test_contexts_json_section(self, tmp_path):
        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        with self._lock:
            self.flag = 1

    def poke(self):
        with self._lock:
            self.flag = 2
"""))
        out = subprocess.run(
            [sys.executable, "-m", "bng_tpu.analysis", "--root",
             str(tmp_path), str(tmp_path), "--no-baseline", "--json",
             "--select", "concurrency"],
            cwd=REPO, capture_output=True, text=True)
        doc = json.loads(out.stdout)
        ctx = doc["contexts"]
        fns = ctx["functions"]
        spin = fns["bng_tpu/control/widget.py::Widget._spin"]
        assert spin["contexts"] == ["thread:widget"]
        poke = fns["bng_tpu/control/widget.py::Widget.poke"]
        assert poke["contexts"] == ["loop"]
        assert any(e["context"] == "thread:widget" for e in ctx["entries"])
        assert ctx["unresolved_entry_points"] == []

    def test_repo_classification_matches_known_anchors(self, repo_report):
        """The real repo's classification must agree with the hand-known
        architecture: ops handlers are ctl, the fleet gather is
        loop-held-_ctl, the SSE delta apply is ha-sync."""
        from bng_tpu.analysis import facts
        from bng_tpu.analysis.core import Project as P

        project = P.load(REPO)
        model = facts.build_concurrency_model(project)
        rep = model.contexts_report()
        fns = rep["functions"]
        sub = fns["bng_tpu/control/opsctl.py::OpsController.submit"]
        assert "ctl" in sub["contexts"]
        gather = fns["bng_tpu/control/fleet.py::SlowPathFleet._gather"]
        assert gather["contexts"] == ["loop"]
        assert "_ctl" in gather["locks_held"]
        onchange = fns["bng_tpu/control/ha.py::StandbySyncer._on_change"]
        assert "ha-sync" in onchange["contexts"]
        run_p = fns["bng_tpu/control/opsctl.py::OpsController.run_pending"]
        assert "loop" in run_p["contexts"]

    def test_extraction_cache_hit_and_invalidation(self, tmp_path):
        import os

        from bng_tpu.analysis import facts
        from bng_tpu.analysis.core import Project as P

        write_tree(tmp_path, conc_tree("""\
    def _spin(self):
        self.flag = 1

    def poke(self):
        self.flag = 2
"""))
        m1 = facts.build_concurrency_model(P.load(tmp_path, [tmp_path]))
        assert m1.cache_hit is False
        assert (tmp_path / facts.CACHE_NAME).exists()
        m2 = facts.build_concurrency_model(P.load(tmp_path, [tmp_path]))
        assert m2.cache_hit is True
        # an edited file must not serve a stale summary: fix the race,
        # bump mtime past the cached key, re-run -> finding disappears
        w = tmp_path / "bng_tpu/control/widget.py"
        w.write_text(w.read_text().replace(
            "        self.flag = 2",
            "        with self._lock:\n            self.flag = 2").replace(
            "    def _spin(self):\n        self.flag = 1",
            "    def _spin(self):\n        with self._lock:\n"
            "            self.flag = 1"))
        st = w.stat()
        os.utime(w, ns=(st.st_atime_ns, st.st_mtime_ns + 10_000_000))
        found = run_on(tmp_path, {"concurrency"})
        assert [f for f in found if f.code == "BNG060"] == []

    def test_narrowed_scan_preserves_other_cache_entries(self, tmp_path):
        # a path-narrowed run must not evict the full tree's cached
        # summaries — the next full run should still warm-hit
        import json as _json

        from bng_tpu.analysis import facts
        from bng_tpu.analysis.core import Project as P

        write_tree(tmp_path, conc_tree('''\
    def _spin(self):
        self.flag = 1

    def poke(self):
        self.flag = 2
'''))
        facts.build_concurrency_model(P.load(tmp_path, [tmp_path]))
        full = set(_json.loads(
            (tmp_path / facts.CACHE_NAME).read_text())["files"])
        assert len(full) == 2
        narrow = P.load(tmp_path,
                        [tmp_path / "bng_tpu" / "control" / "widget.py"])
        facts.build_concurrency_model(narrow)
        kept = set(_json.loads(
            (tmp_path / facts.CACHE_NAME).read_text())["files"])
        assert kept == full
        m = facts.build_concurrency_model(P.load(tmp_path, [tmp_path]))
        assert m.cache_hit is True

    def test_selective_update_preserves_concurrency_entries(self, tmp_path):
        """--select handler-audit --update-baseline must not wipe a
        justified BNG06x entry (and vice versa) — the scope rule covers
        the new pass's codes."""
        write_tree(tmp_path, {"bng_tpu/control/foo.py": "x = 1\n"})
        bl = tmp_path / "bl.json"
        baseline_mod.write([
            Finding(code="BNG063", path="bng_tpu/control/fleet.py", line=7,
                    message="m", scope="SlowPathFleet._gather",
                    detail="recv@SlowPathFleet._gather"),
        ], bl)
        d = json.loads(bl.read_text())
        d["findings"][0]["justification"] = "the fan-in IS the batch"
        bl.write_text(json.dumps(d))
        out = subprocess.run(
            [sys.executable, "-m", "bng_tpu.analysis", "--root",
             str(tmp_path), str(tmp_path), "--baseline", str(bl),
             "--select", "handler-audit", "--update-baseline"],
            cwd=REPO, capture_output=True, text=True)
        assert out.returncode == 0, out.stdout + out.stderr
        kept = json.loads(bl.read_text())["findings"]
        assert [(e["code"], e["justification"]) for e in kept] == [
            ("BNG063", "the fan-in IS the batch")]
        # a concurrency-selected update on a tree missing fleet.py also
        # keeps it: the entry's file is outside the scanned set
        out = subprocess.run(
            [sys.executable, "-m", "bng_tpu.analysis", "--root",
             str(tmp_path), str(tmp_path), "--baseline", str(bl),
             "--select", "concurrency", "--update-baseline"],
            cwd=REPO, capture_output=True, text=True)
        assert out.returncode == 0, out.stdout + out.stderr
        kept = json.loads(bl.read_text())["findings"]
        assert ("BNG063", "the fan-in IS the batch") in [
            (e["code"], e["justification"]) for e in kept]


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------

class TestBaseline:
    def _finding(self, line=10):
        return Finding(code="BNG020", path="bng_tpu/control/x.py",
                       line=line, message="m", scope="F.g", detail="d")

    def test_roundtrip_and_line_independence(self, tmp_path):
        bl = tmp_path / "baseline.json"
        baseline_mod.write([self._finding(line=10)], bl)
        loaded = baseline_mod.load(bl)
        # the same finding at a DIFFERENT line still matches
        new, accepted, stale = baseline_mod.split(
            [self._finding(line=99)], loaded)
        assert new == [] and len(accepted) == 1 and stale == []

    def test_stale_entries_reported(self, tmp_path):
        bl = tmp_path / "baseline.json"
        baseline_mod.write([self._finding()], bl)
        new, accepted, stale = baseline_mod.split([], baseline_mod.load(bl))
        assert len(stale) == 1

    def test_update_preserves_justification(self, tmp_path):
        bl = tmp_path / "baseline.json"
        baseline_mod.write([self._finding()], bl)
        d = json.loads(bl.read_text())
        d["findings"][0]["justification"] = "because reasons"
        bl.write_text(json.dumps(d))
        old = baseline_mod.load(bl)
        baseline_mod.write([self._finding(line=42)], bl, old=old)
        assert (json.loads(bl.read_text())["findings"][0]["justification"]
                == "because reasons")

    def test_repo_baseline_fully_justified(self):
        """Every checked-in baseline entry carries a real justification
        (the satellite requirement: one-line tag each, no TODOs)."""
        d = json.loads((REPO / "bng_tpu/analysis/baseline.json").read_text())
        for e in d["findings"]:
            assert e["justification"] and "TODO" not in e["justification"], e


class TestNarrowGatherPass:
    """BNG014 (ISSUE 11): <8-word table/value rows — the PERF_NOTES §2
    gather-serialization shape — are machine-checked, not folklore."""

    TABLE_STUB = "WAYS = 4\n\n\nclass HostTable:\n    pass\n"

    def test_narrow_val_words_literal_flagged(self, tmp_path):
        write_tree(tmp_path, {
            "bng_tpu/ops/table.py": self.TABLE_STUB,
            "bng_tpu/control/newmap.py": """\
from bng_tpu.ops.table import HostTable


class Manager:
    def __init__(self):
        self.fwd = HostTable(1024, 4, val_words=16, name="wide_ok")
        self.rev = HostTable(1024, key_words=4, val_words=4,
                             name="narrow_rev")
"""})
        found = run_on(tmp_path, {"gather"})
        assert codes_of(found) == {"BNG014"}
        assert len(found) == 1
        assert "narrow_rev" in found[0].detail

    def test_narrow_val_words_via_constant_flagged(self, tmp_path):
        """Widths resolve through module-level constants anywhere in
        the scan set — the repo's *_WORDS convention."""
        write_tree(tmp_path, {
            "bng_tpu/ops/table.py": self.TABLE_STUB,
            "bng_tpu/ops/widths.py": "SHORT_WORDS = 6\nLONG_WORDS = 8\n",
            "bng_tpu/control/newmap.py": """\
from bng_tpu.ops.table import HostTable
from bng_tpu.ops.widths import LONG_WORDS, SHORT_WORDS

t_ok = HostTable(64, 1, LONG_WORDS, name="padded")
t_bad = HostTable(64, 1, SHORT_WORDS, name="short")
"""})
        found = run_on(tmp_path, {"gather"})
        assert len(found) == 1 and found[0].code == "BNG014"
        assert "short" in found[0].detail

    def test_conflicting_constant_names_resolve_same_file_first(
            self, tmp_path):
        """A cross-module name collision must not silently mis-resolve a
        width (the PR-9 collision lesson): the defining file's own value
        wins, and a name with CONFLICTING foreign definitions is
        unresolved — never first-scan-order-wins."""
        write_tree(tmp_path, {
            "bng_tpu/ops/table.py": self.TABLE_STUB,
            # scan order puts this wide same-named constant FIRST
            "bng_tpu/control/a_wide.py": "ROW_WORDS = 8\n",
            "bng_tpu/control/narrowmap.py": """\
from bng_tpu.ops.table import HostTable

ROW_WORDS = 4

t = HostTable(64, 1, ROW_WORDS, name="shadowed_narrow")
"""})
        found = run_on(tmp_path, {"gather"})
        assert codes_of(found) == {"BNG014"}
        assert "shadowed_narrow" in found[0].detail
        # ambiguous foreign-only reference -> unresolved, not flagged
        write_tree(tmp_path, {
            "bng_tpu/control/narrowmap.py": """\
from bng_tpu.ops.table import HostTable
from bng_tpu.control.b_conflict import OTHER_WORDS

t = HostTable(64, 1, OTHER_WORDS, name="ambiguous")
""",
            "bng_tpu/control/b_conflict.py": "OTHER_WORDS = 4\n",
            "bng_tpu/control/c_conflict.py": "OTHER_WORDS = 8\n"})
        assert run_on(tmp_path, {"gather"}) == []

    def test_wide_tables_clean(self, tmp_path):
        write_tree(tmp_path, {
            "bng_tpu/ops/table.py": self.TABLE_STUB,
            "bng_tpu/control/newmap.py": """\
from bng_tpu.ops.table import HostTable

t = HostTable(64, 2, val_words=8, name="fine")
"""})
        assert run_on(tmp_path, {"gather"}) == []

    def test_device_narrow_array_gather_flagged(self, tmp_path):
        """A fresh jnp array with <8-word literal rows gathered by a
        computed index inside ops/ device code."""
        write_tree(tmp_path, {"bng_tpu/ops/newkernel.py": """\
import jax.numpy as jnp


def kernel(slots):
    scratch = jnp.zeros((1024, 4), dtype=jnp.uint32)
    rows = scratch[slots]          # BNG014: 4-word rows, computed index
    head = scratch[0]              # constant index: not a gather
    window = scratch[2:6]          # slice: not a gather
    wide = jnp.zeros((1024, 8), dtype=jnp.uint32)
    ok = wide[slots]               # 8-word rows: fine
    return rows, head, window, ok
"""})
        found = run_on(tmp_path, {"gather"})
        assert codes_of(found) == {"BNG014"}
        assert len(found) == 1 and found[0].detail == "scratch-rows-4"

    def test_host_numpy_masks_not_flagged(self, tmp_path):
        """HostTable.bulk_insert-style numpy boolean masking is host
        code — it never reaches the TPU gather unit."""
        write_tree(tmp_path, {"bng_tpu/ops/hostside.py": """\
import numpy as np


def place(used, idxs):
    unplaced = np.ones((1024,), dtype=bool)
    take = idxs[unplaced[idxs]]
    unplaced[take] = False
    return unplaced
"""})
        assert run_on(tmp_path, {"gather"}) == []

    def test_missing_fact_source_is_loud(self, tmp_path):
        """ops/table.py present but no HostTable construction anywhere:
        the width facts are unextractable -> BNG990, never silence."""
        write_tree(tmp_path, {
            "bng_tpu/ops/table.py": "WAYS = 4\n"})
        found = run_on(tmp_path, {"gather"})
        assert codes_of(found) == {"BNG990"}


# ---------------------------------------------------------------------------
# the clean corpus + CLI (the acceptance gates)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def repo_report():
    t0 = time.perf_counter()
    report = run_analysis(REPO)
    report._elapsed_wall = time.perf_counter() - t0
    return report


class TestCleanCorpus:
    def test_repo_is_clean_against_baseline(self, repo_report):
        bl = baseline_mod.load()
        new, _accepted, stale = baseline_mod.split(repo_report.findings, bl)
        assert new == [], [f.to_dict() for f in new]
        assert stale == [], stale

    def test_full_scan_under_budget(self, repo_report):
        assert repo_report._elapsed_wall < 30.0, (
            f"analyzer took {repo_report._elapsed_wall:.1f}s")
        assert repo_report.files_scanned > 100  # the scan set, not a subset

    def test_every_pass_ran(self, repo_report):
        assert set(repo_report.passes_run) == {p.name for p in ALL_PASSES}

    def test_code_catalog_complete(self):
        codes = all_codes()
        for c in ("BNG001", "BNG002", "BNG003", "BNG010", "BNG011",
                  "BNG012", "BNG014", "BNG020", "BNG021", "BNG030",
                  "BNG031", "BNG032", "BNG033", "BNG034", "BNG035",
                  "BNG040", "BNG041", "BNG050", "BNG060", "BNG061",
                  "BNG062", "BNG063", "BNG064"):
            assert c in codes, c

    def test_no_jax_import(self):
        """`bng check` must not drag in jax (milliseconds, any box)."""
        out = subprocess.run(
            [sys.executable, "-c",
             "import sys; import bng_tpu.analysis.cli; "
             "sys.exit(1 if 'jax' in sys.modules else 0)"],
            cwd=REPO, capture_output=True)
        assert out.returncode == 0, out.stderr.decode()


class TestCLI:
    def test_module_entry_clean_repo_rc0(self):
        out = subprocess.run(
            [sys.executable, "-m", "bng_tpu.analysis"],
            cwd=REPO, capture_output=True, text=True)
        assert out.returncode == 0, out.stdout + out.stderr

    def test_planted_tree_rc1_and_json(self, tmp_path):
        write_tree(tmp_path, {"bng_tpu/control/foo.py": """\
def f(x):
    try:
        x()
    except Exception:
        pass
"""})
        out = subprocess.run(
            [sys.executable, "-m", "bng_tpu.analysis", "--root",
             str(tmp_path), str(tmp_path), "--no-baseline", "--json",
             "--select", "handler-audit"],
            cwd=REPO, capture_output=True, text=True)
        assert out.returncode == 1
        doc = json.loads(out.stdout)
        assert [f["code"] for f in doc["findings"]] == ["BNG020"]

    def test_bng_check_subcommand(self, capsys):
        from bng_tpu import cli as bng_cli

        rc = bng_cli.main(["check", "--codes"])
        assert rc == 0
        assert "BNG001" in capsys.readouterr().out

    def test_select_filter(self):
        out = subprocess.run(
            [sys.executable, "-m", "bng_tpu.analysis", "--select",
             "handler-audit", "--json", "--no-baseline"],
            cwd=REPO, capture_output=True, text=True)
        doc = json.loads(out.stdout)
        assert doc["passes"] == ["handler-audit"]

    def test_selective_update_preserves_other_passes(self, tmp_path):
        # `--select hotpath --update-baseline` must NOT wipe baseline
        # entries belonging to passes that did not run
        write_tree(tmp_path, {"bng_tpu/control/foo.py": "x = 1\n"})
        bl = tmp_path / "bl.json"
        baseline_mod.write([
            # unselected pass's code, scanned file
            Finding(code="BNG020", path="bng_tpu/control/foo.py", line=3,
                    message="m", scope="f", detail="d"),
            # selected pass's code, UNscanned file
            Finding(code="BNG001", path="bng_tpu/runtime/other.py", line=9,
                    message="m", scope="g", detail="e"),
        ], bl)
        d = json.loads(bl.read_text())
        for e in d["findings"]:
            e["justification"] = "hand-written reason"
        bl.write_text(json.dumps(d))
        out = subprocess.run(
            [sys.executable, "-m", "bng_tpu.analysis", "--root",
             str(tmp_path), str(tmp_path), "--baseline", str(bl),
             "--select", "hotpath", "--update-baseline"],
            cwd=REPO, capture_output=True, text=True)
        assert out.returncode == 0, out.stdout + out.stderr
        kept = json.loads(bl.read_text())["findings"]
        assert [(e["code"], e["justification"]) for e in kept] == [
            ("BNG001", "hand-written reason"),
            ("BNG020", "hand-written reason")]

    def test_update_with_no_baseline_rejected(self, tmp_path):
        # --no-baseline discards justifications; combined with
        # --update-baseline it would rewrite the file with TODO tags
        write_tree(tmp_path, {"bng_tpu/control/foo.py": "x = 1\n"})
        out = subprocess.run(
            [sys.executable, "-m", "bng_tpu.analysis", "--root",
             str(tmp_path), str(tmp_path), "--no-baseline",
             "--update-baseline"],
            cwd=REPO, capture_output=True, text=True)
        assert out.returncode == 2
        assert "mutually exclusive" in out.stderr

    # baseline.py's documented contract: "CI should reject a TODO tag"
    # — enforced by the driver, not just promised by the docstring
    _TODO_TREE = {"bng_tpu/control/foo.py": """\
def f(x):
    try:
        x()
    except Exception:
        pass
"""}

    def _check(self, tmp_path, bl, *extra):
        return subprocess.run(
            [sys.executable, "-m", "bng_tpu.analysis", "--root",
             str(tmp_path), str(tmp_path), "--baseline", str(bl),
             "--select", "handler-audit", *extra],
            cwd=REPO, capture_output=True, text=True)

    def test_todo_tagged_baseline_fails_rc1(self, tmp_path):
        """The --update-baseline -> review -> justify flow: a freshly
        stamped entry fails `bng check` (rc=1, named) until a human
        replaces the TODO tag with a reason; then it passes."""
        write_tree(tmp_path, self._TODO_TREE)
        bl = tmp_path / "bl.json"
        out = self._check(tmp_path, bl, "--update-baseline")
        assert out.returncode == 0, out.stdout + out.stderr
        # the new entry is TODO-tagged -> the very next check fails
        out = self._check(tmp_path, bl)
        assert out.returncode == 1
        assert baseline_mod.TODO_TAG in out.stdout
        # a written justification makes the same baseline pass
        d = json.loads(bl.read_text())
        d["findings"][0]["justification"] = "reviewed: fixture swallow"
        bl.write_text(json.dumps(d))
        out = self._check(tmp_path, bl)
        assert out.returncode == 0, out.stdout + out.stderr

    def test_todo_entries_in_json_report(self, tmp_path):
        write_tree(tmp_path, self._TODO_TREE)
        bl = tmp_path / "bl.json"
        assert self._check(tmp_path, bl, "--update-baseline").returncode == 0
        out = self._check(tmp_path, bl, "--json")
        assert out.returncode == 1
        doc = json.loads(out.stdout)
        assert len(doc["todo_baseline_entries"]) == 1
        assert doc["todo_baseline_entries"][0][0] == "BNG020"

    def test_todo_entry_out_of_scope_spares_selective_runs(self, tmp_path):
        """A TODO-tagged entry only fails runs that could re-verify it:
        a --select whose passes can't emit the entry's code, or a path
        scope that doesn't include the entry's file, must stay green —
        the same scope rule --update-baseline uses to preserve
        out-of-scope entries (which a narrow run can't re-stamp either,
        so failing on them would be permanently red)."""
        tree = dict(self._TODO_TREE)
        tree["bng_tpu/control/bar.py"] = "X = 1\n"
        write_tree(tmp_path, tree)
        bl = tmp_path / "bl.json"
        assert self._check(tmp_path, bl, "--update-baseline").returncode == 0
        # same pass, same paths: the debt is in scope -> red
        assert self._check(tmp_path, bl).returncode == 1
        # a pass set that can't emit BNG020 -> out of scope -> green
        out = subprocess.run(
            [sys.executable, "-m", "bng_tpu.analysis", "--root",
             str(tmp_path), str(tmp_path), "--baseline", str(bl),
             "--select", "hotpath"],
            cwd=REPO, capture_output=True, text=True)
        assert out.returncode == 0, out.stdout + out.stderr
        # same pass, but the entry's file is outside the scanned paths
        out = subprocess.run(
            [sys.executable, "-m", "bng_tpu.analysis", "--root",
             str(tmp_path),
             str(tmp_path / "bng_tpu" / "control" / "bar.py"),
             "--baseline", str(bl), "--select", "handler-audit"],
            cwd=REPO, capture_output=True, text=True)
        assert out.returncode == 0, out.stdout + out.stderr
