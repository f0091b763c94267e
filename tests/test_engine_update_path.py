"""The update batch is no argument of any one-chip step program (PR 50).

A drain decides by what the host mirrors show. Nothing dirty: no batch is
built, no call is made, no program scatters. Something dirty: the batch goes
through one of the engine's two packet-free programs (`_apply_fastpath_jit`
for the dhcp chain, `_apply_updates_jit` for every other table) ahead of the
step, on the same tables. A dense config array that changed is `_replace`d
into the tables on the host. Held here:

  - for every owner an engine can hold, a host write followed by a dispatch
    gives tables and outputs bit for bit the parent's (`apply, then the
    pipeline` in one program), on the fused, the DHCP-only and the express
    AOT program;
  - a clean drain makes no apply call, and no step's lowered signature
    holds an update leaf;
  - a dense array changed on the host is in the next step's tables, and no
    program ran to put it there;
  - a "full upload" resync inside a drain threads the new tables into the
    same dispatch;
  - a first dirty drain after start-up builds no program.

One geometry (every stage compiled in) for the whole file: each program is
built once.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bng_tpu.control import dhcp_codec, packets
from bng_tpu.control.nat import NATManager
from bng_tpu.edge.tables import EdgeTables
from bng_tpu.ops.dhcp import dhcp_fastpath
from bng_tpu.ops.express import XD_WORDS, express_verdicts, parse_express
from bng_tpu.ops.parse import PROTO_TCP, parse_batch
from bng_tpu.ops.pipeline import pipeline_step
from bng_tpu.runtime import engine as eng_mod
from bng_tpu.runtime import hostpath
from bng_tpu.runtime.engine import (AntispoofTables, Engine, GardenTables,
                                    QoSTables)
from bng_tpu.runtime.scheduler import SchedulerConfig, TieredScheduler
from bng_tpu.runtime.tables import (FastPathTables, PPPoEFastPathTables,
                                    QinQFastPathTables, V6FastPathTables,
                                    apply_fastpath_updates)
from bng_tpu.utils.net import ip_to_u32

T0 = 1_753_000_000
NOW_S, NOW_US = np.uint32(T0), np.uint32((T0 * 1_000_000) & 0xFFFFFFFF)
B, L, XB = 8, 512, 8  # fused lanes, packet slot, express lanes
SERVER_MAC = bytes.fromhex("02aabbccdd01")
IP = ip_to_u32("10.0.0.10")
MAC = bytes.fromhex("02c0ffee0001")
NEW_IP = ip_to_u32("10.0.0.77")
NEW_MAC = bytes.fromhex("02c0ffee0077")
DST = ip_to_u32("8.8.8.8")

OWNERS = ("fastpath", "nat", "qos_up", "qos_down", "antispoof", "garden",
          "pppoe", "edge", "v6", "qinq")
PROGRAMS = ("fused", "dhcp_only", "express_aot")


def make_engine() -> Engine:
    """An engine with every optional stage compiled in, every table tiny."""
    kw = dict(stash=8, update_slots=8)
    sp = AntispoofTables(nbuckets=64, **kw)
    e = Engine(
        FastPathTables(sub_nbuckets=64, vlan_nbuckets=32, cid_nbuckets=32,
                       max_pools=4, **kw),
        NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                   sessions_nbuckets=64, sub_nat_nbuckets=32, **kw),
        QoSTables(nbuckets=64, **kw), sp,
        garden=GardenTables(nbuckets=32, max_allowed=4, **kw),
        pppoe=PPPoEFastPathTables(nbuckets=32, **kw),
        edge=EdgeTables(tap_nbuckets=32, route_nbuckets=64, max_filters=4,
                        **kw),
        v6=V6FastPathTables(sp, nbuckets=32, **kw),
        qinq=QinQFastPathTables(nbuckets=32, **kw),
        batch_size=B, pkt_slot=L, clock=lambda: float(T0))
    # a served subscriber in every table, then one upload: all clean
    e.fastpath.set_server_config(SERVER_MAC, ip_to_u32("10.0.0.1"))
    e.fastpath.add_pool(1, ip_to_u32("10.0.0.0"), 24, ip_to_u32("10.0.0.1"))
    e.fastpath.add_subscriber(MAC, 1, IP, T0 + 3600)
    e.nat.allocate_nat(IP, T0)
    e.nat.handle_new_flow(IP, DST, 5555, 443, int(PROTO_TCP), 100, T0)
    e.qos.set_subscriber(IP, 10_000_000, 5_000_000)
    e.antispoof.set_config(1, True)
    e.antispoof.add_binding(MAC, IP, 1)
    e.resync_tables()
    # on the CPU backend an upload may alias the host mirror it was made
    # from (ops/table.py device_state), and a later host write would show
    # through before any drain: real buffers, as on the chip
    e.tables = jax.tree.map(jnp.copy, e.tables)
    assert e.pending_dirty() == 0
    return e


class _Sess:
    session_id, client_mac, assigned_ip = 7, NEW_MAC, NEW_IP


# one host write an owner, each into a table of that owner alone
WRITES = {
    "fastpath": lambda e: e.fastpath.add_subscriber(NEW_MAC, 1, NEW_IP,
                                                    T0 + 3600),
    "nat": lambda e: (e.nat.allocate_nat(NEW_IP, T0),
                      e.nat.handle_new_flow(NEW_IP, DST, 6666, 443,
                                            int(PROTO_TCP), 100, T0)),
    "qos_up": lambda e: e.qos.up.insert(NEW_IP, 5_000_000, 781_250, 0),
    "qos_down": lambda e: e.qos.down.insert(NEW_IP, 9_000_000, 1_406_250, 0),
    "antispoof": lambda e: e.antispoof.add_binding(NEW_MAC, NEW_IP, 1),
    "garden": lambda e: e.garden.set_gardened(NEW_IP, True),
    "pppoe": lambda e: e.pppoe.session_up(_Sess()),
    "edge": lambda e: (e.edge.arm_tap(NEW_IP, 3, [(443, 6, 0)]),
                       e.edge.set_route(NEW_IP,
                                        bytes.fromhex("02beef000001"), 2, 1)),
    "v6": lambda e: e.v6.bind(
        NEW_MAC, bytes.fromhex("20010db8000000000000000000000077"), NEW_IP),
    "qinq": lambda e: e.qinq.bind(NEW_IP, 100, 200),
}
# the host mirrors each write dirties, by `host_mirror_tables` name prefix
MIRRORS = {"fastpath": "fastpath/", "nat": "nat/", "qos_up": "qos/up",
           "qos_down": "qos/down", "antispoof": "antispoof/",
           "garden": "garden/", "pppoe": "pppoe/", "edge": "edge/",
           "v6": ("v6/", "antispoof/"),  # the /128 beside the v4 binding
           "qinq": "qinq/"}


def discover(mac: bytes, xid: int) -> bytes:
    p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER, xid=xid)
    p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
    return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                              p.encode().ljust(300, b"\x00"))


def data(mac: bytes, ip: int, sport: int) -> bytes:
    return packets.udp_packet(mac, SERVER_MAC, ip, DST, sport, 443, b"x" * 64)


FRAMES = [discover(MAC, 0x1001), discover(NEW_MAC, 0x1002),
          data(MAC, IP, 5555), data(NEW_MAC, NEW_IP, 6666)]
DHCP_FRAMES = FRAMES[:2]


def window(frames, lanes=B):
    pkt = np.zeros((lanes, L), np.uint8)
    length = np.zeros((lanes,), np.uint32)
    for i, f in enumerate(frames):
        pkt[i, :len(f)] = np.frombuffer(f, np.uint8)
        length[i] = len(f)
    return pkt, length


def descriptors(frames):
    desc = np.zeros((XB, XD_WORDS), np.uint32)
    for i, f in enumerate(frames):
        desc[i] = parse_express(f).words
    return desc


def full_updates(e: Engine) -> tuple:
    """The whole drained update tuple, as the parent's step took it."""
    return (e.fastpath.make_updates(), *e._updates(True)[1:])


def parent_programs(e: Engine):
    """The parent's three programs: the batch applied inside, then the
    stage(s)."""
    geom, dgeom = e.geom, e.fastpath.geom

    def fused(tables, upd, pkt, length, fa):
        tables = eng_mod._apply_all_updates(tables, upd)
        return pipeline_step(tables, pkt, length, fa, geom, NOW_S, NOW_US)

    def dhcp_only(dhcp, upd, pkt, length):
        dhcp = apply_fastpath_updates(dhcp, upd)
        res = dhcp_fastpath(pkt, length, parse_batch(pkt, length), dhcp,
                            dgeom, NOW_S)
        return dhcp, res.is_reply, res.out_pkt, res.out_len, res.stats

    def express(dhcp, upd, desc):
        dhcp = apply_fastpath_updates(dhcp, upd)
        res = express_verdicts(dhcp, desc, dgeom, NOW_S)
        return dhcp, res.block, res.stats

    return fused, dhcp_only, express


_PARENT = {}


def parent(name: str, e: Engine):
    """The parent's program `name`, jitted once for this file's geometry
    (nothing donated: the twin's tables are read again by the asserts)."""
    if not _PARENT:
        for k, fn in zip(PROGRAMS, parent_programs(e)):
            _PARENT[k] = jax.jit(fn)
    return _PARENT[name]


def same(a, b) -> None:
    """Two pytrees, structure and every leaf bit for bit."""
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape, path
        assert np.array_equal(x, y), jax.tree_util.keystr(path)


def dirty_names(e: Engine) -> set:
    return {n for n, t in e.host_mirror_tables().items() if t.dirty_count()}


@pytest.fixture
def spy_apply(monkeypatch):
    """Every call of the two packet-free programs from now on, by name."""
    calls = []
    for name in ("_apply_fastpath_jit", "_apply_updates_jit"):
        def spy(t, u, _name=name, _fn=getattr(eng_mod, name)):
            calls.append(_name)
            return _fn(t, u)

        monkeypatch.setattr(eng_mod, name, spy)
    return calls


@pytest.fixture
def built():
    """One entry for every program JAX builds or loads from now on, as
    `benchmark/run.py` counts the programs built inside its window."""
    events = []

    def listen(name, dur, **kw):
        if name.endswith("backend_compile_duration"):
            events.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    yield events
    # jax 0.9 has a private unregister only; a dead list costs nothing
    events.clear()


# ---------------------------------------------------------------------------
# (1) a host write, then a dispatch: the parent's tables and outputs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("owner", OWNERS)
def test_a_write_then_a_step_is_the_parents_apply_then_pipeline(
        owner, program, spy_apply):
    twin, e = make_engine(), make_engine()
    WRITES[owner](twin), WRITES[owner](e)
    assert dirty_names(e) and all(
        n.startswith(MIRRORS[owner]) for n in dirty_names(e))
    before = jax.tree.map(np.asarray, e.tables)
    chain_only = program != "fused"  # the two express programs drain the
    # fastpath tables alone: another owner's write stays queued for them
    shipped = owner == "fastpath" or not chain_only

    if program == "fused":
        pkt, length = window(FRAMES)
        fa = np.ones((B,), bool)
        want = parent("fused", twin)(twin.tables, full_updates(twin), pkt,
                                     length, fa)
        got = e._dispatch_step(pkt, length, fa, len(FRAMES), NOW_S, NOW_US)
        same(got, want)
        same(e.tables, want.tables)
        tables_want = want.tables
    elif program == "dhcp_only":
        pkt, length = window(DHCP_FRAMES)
        dhcp, is_reply, out_pkt, out_len, stats = parent("dhcp_only", twin)(
            twin.tables.dhcp, twin.fastpath.make_updates(), pkt, length)
        got = e._run_dhcp_batch(pkt, length, float(T0))
        same((got.verdict == eng_mod.VERDICT_TX, got.out_pkt, got.out_len,
              got.dhcp_stats), (is_reply, out_pkt, out_len, stats))
        tables_want = twin.tables._replace(dhcp=dhcp)
    else:
        desc = descriptors(DHCP_FRAMES)
        dhcp, block, stats = parent("express_aot", twin)(
            twin.tables.dhcp, twin.fastpath.make_updates(), desc)
        exe = e.compile_express_aot(XB)  # start-up's: it builds (with one
        del spy_apply[:]  # run) the chain's apply program beside the step
        got = e.run_express_aot(exe, desc, float(T0))
        same((got.block, got.dhcp_stats), (block, stats))
        tables_want = twin.tables._replace(dhcp=dhcp)
    same(e.tables, tables_want)
    if shipped:
        assert e.pending_dirty() == 0
        # not vacuous: the write is on the chip, and one apply program a
        # dirty chain carried it there
        after = jax.tree.map(np.asarray, e.tables)
        assert any(not np.array_equal(x, y) for x, y in
                   zip(jax.tree.leaves(before), jax.tree.leaves(after)))
        assert spy_apply == ["_apply_fastpath_jit" if owner == "fastpath"
                             else "_apply_updates_jit"]
        # the new lease answers in the window that follows its write
        if owner == "fastpath":
            verdict = (np.asarray(got.block)[:2, 0] if program == "express_aot"
                       else np.asarray(got.verdict)[:2])
            assert verdict[0] == verdict[1]  # the old lease and the new
    else:
        assert dirty_names(e) == dirty_names(twin) != set()
        assert spy_apply == []


# ---------------------------------------------------------------------------
# (2) a clean drain makes no call; a step's signature holds no update leaf
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("road", ["fused", "dhcp_only", "express_aot",
                                  "bulk_drain", "bulk_no_drain",
                                  "bulk_prefetched"])
def test_a_clean_drain_makes_no_apply_call(road, spy_apply, monkeypatch):
    e = make_engine()
    made = []
    for name, t in e.host_mirror_tables().items():
        monkeypatch.setattr(t, "make_update", lambda *a, _n=name: made.append(_n))
    pkt, length = window(FRAMES if road in ("fused",) else DHCP_FRAMES)
    fa = np.ones((B,), bool)
    if road == "fused":
        e._dispatch_step(pkt, length, fa, len(FRAMES), NOW_S, NOW_US)
    elif road == "dhcp_only":
        e._run_dhcp_batch(pkt, length, float(T0))
    elif road == "express_aot":
        exe = e.compile_express_aot(XB)  # start-up's: it builds (with one
        del spy_apply[:]  # run) the chain's apply program beside the step
        e.run_express_aot(exe, descriptors(DHCP_FRAMES), float(T0))
    else:
        upd = e.prefetch_bulk_updates() if road == "bulk_prefetched" else None
        assert upd in ((), None)
        e.dispatch_scheduled_bulk(pkt, length, fa, float(T0),
                                  e.dhcp_replica(jnp.copy),
                                  drain=road != "bulk_no_drain", upd=upd)
    assert spy_apply == []
    assert made == []  # and no batch was built


@pytest.mark.parametrize("program", PROGRAMS)
def test_no_step_signature_holds_an_update_leaf(program):
    e = make_engine()
    S = jax.ShapeDtypeStruct
    if program == "fused":
        # the window is one block since PR 51: the tables' leaves, the
        # block and the two clock words
        lowered = e._step.lower(
            e.tables, S((hostpath.window_rows(B, L), L), jnp.uint8),
            S((), jnp.uint32), S((), jnp.uint32))
        tables, rest = e.tables, 3
    elif program == "dhcp_only":
        lowered = e._dhcp_step.lower(e.tables.dhcp, S((B, L), jnp.uint8),
                                     S((B,), jnp.uint32), S((), jnp.uint32))
        tables, rest = e.tables.dhcp, 3
    else:
        lowered = eng_mod._express_jit(e.fastpath.geom).lower(
            e.tables.dhcp, S((XB, XD_WORDS), jnp.uint32), S((), jnp.uint32))
        tables, rest = e.tables.dhcp, 2
    n_in = len(jax.tree.leaves(lowered.in_avals))
    assert n_in == len(jax.tree.leaves(tables)) + rest
    # the parent's signature carried the batch besides: 20 leaves for the
    # chain (three tables of six, pools, server) and, with every stage in,
    # 80 for the rest (six a cuckoo table, three a QoS table, one a dense
    # array)
    n_upd = len(jax.tree.leaves(e.fastpath.empty_updates())) + (
        len(jax.tree.leaves(e._empty_updates())) if program == "fused" else 0)
    assert n_upd == 20 + (80 if program == "fused" else 0)
    import inspect

    step = (e._step if program == "fused" else e._dhcp_step
            if program == "dhcp_only" else eng_mod._express_jit(
                e.fastpath.geom))
    assert "upd" not in inspect.signature(step).parameters


# ---------------------------------------------------------------------------
# (3) a dense array changed on the host: in the tables, and no program ran
# ---------------------------------------------------------------------------

DENSE = {
    "pools": (lambda e: e.fastpath.add_pool(3, ip_to_u32("10.3.0.0"), 24,
                                            ip_to_u32("10.3.0.1")),
              lambda e: e.fastpath.pools, lambda t: t.dhcp.pools),
    "server": (lambda e: e.fastpath.set_server_config(
        SERVER_MAC, ip_to_u32("10.0.0.2")),
        lambda e: e.fastpath.server, lambda t: t.dhcp.server),
    "nat_hairpin": (lambda e: e.nat.add_hairpin_ip(ip_to_u32("203.0.113.9")),
                    lambda e: e.nat.hairpin, lambda t: t.nat.hairpin_ips),
    "nat_alg": (lambda e: e.nat.add_alg_port(21, int(PROTO_TCP)),
                lambda e: e.nat.alg, lambda t: t.nat.alg_ports),
    "nat_config": (lambda e: setattr(e.nat, "ports_per_subscriber", 77),
                   lambda e: e.nat.config_array(), lambda t: t.nat.config),
    "spoof_ranges": (lambda e: e.antispoof.add_allowed_range(
        ip_to_u32("172.16.0.0"), 12),
        lambda e: e.antispoof.ranges, lambda t: t.spoof_ranges),
    "spoof_config": (lambda e: e.antispoof.set_config(2, False),
                     lambda e: e.antispoof.config, lambda t: t.spoof_config),
    "garden_allowed": (lambda e: e.garden.allow_destination(
        ip_to_u32("10.9.9.9"), 80, 6),
        lambda e: e.garden.allowed, lambda t: t.garden_allowed),
    "tap_filters": (lambda e: e.edge.tap_filters.__setitem__((0, 0), 7),
                    lambda e: e.edge.tap_filters, lambda t: t.tap_filters),
    "tap_config": (lambda e: e.edge.tap_config.__setitem__(0, 9),
                   lambda e: e.edge.tap_config, lambda t: t.tap_config),
}


@pytest.mark.parametrize("array", DENSE)
def test_a_dense_array_is_in_the_next_steps_tables_and_no_program_ran(
        array, spy_apply, built):
    write, host, leaf = DENSE[array]
    e = make_engine()
    e._drain_updates()
    leaves = {id(x) for x in jax.tree.leaves(e.tables)}
    write(e)
    assert not np.array_equal(np.asarray(leaf(e.tables)), host(e))
    del built[:]
    e._drain_updates()
    assert np.array_equal(np.asarray(leaf(e.tables)), host(e))
    assert spy_apply == [] and built == []
    # one leaf of the tables is new, every other is the array it was
    assert len({id(x) for x in jax.tree.leaves(e.tables)} - leaves) == 1
    # and the step that follows reads it: its tables come out holding it
    pkt, length = window(FRAMES)
    e._dispatch_step(pkt, length, np.ones((B,), bool), len(FRAMES), NOW_S,
                     NOW_US)
    assert np.array_equal(np.asarray(leaf(e.tables)), host(e))
    # a write in place is seen as well (the compare is on bytes)
    host(e).flat[0] ^= 1
    if array != "nat_config":  # config_array() is built anew a call
        e._drain_updates()
        assert np.array_equal(np.asarray(leaf(e.tables)), host(e))


# ---------------------------------------------------------------------------
# (4) a "full upload" resync inside a drain threads into the same dispatch
# ---------------------------------------------------------------------------

def bulk_build(e: Engine, n: int = 24) -> list[bytes]:
    """More subscribers than the stash holds, in one bulk insert: dirty
    tracking is abandoned and the next drain must answer with a resync."""
    macs = np.arange(n, dtype=np.uint64) + np.uint64(0x02D000000000)
    e.fastpath.add_subscribers_bulk(
        macs, np.full(n, 1), ip_to_u32("10.0.0.100") + np.arange(n),
        np.full(n, T0 + 3600))
    assert e.fastpath.sub._dirty_all
    return [int(m).to_bytes(6, "big") for m in macs[:2]]


@pytest.mark.parametrize("road", ["fused", "dhcp_only", "express_aot", "bulk"])
def test_a_full_upload_resync_threads_into_the_same_dispatch(road, spy_apply):
    e = make_engine()
    macs = bulk_build(e)
    # and a table outside the chain (a fused or a bulk drain meets it)
    e.antispoof.bulk_add_bindings(
        np.arange(24, dtype=np.uint64) + np.uint64(0x02D000000000),
        ip_to_u32("10.0.0.100") + np.arange(24), 1)
    assert e.antispoof.bindings._dirty_all
    frames = [discover(m, 0x2000 + i) for i, m in enumerate(macs)]
    pkt, length = window(frames)
    fa = np.ones((B,), bool)
    if road == "fused":
        res = e._dispatch_step(pkt, length, fa, 2, NOW_S, NOW_US)
        answered = np.asarray(res.verdict)[:2] == eng_mod.VERDICT_TX
    elif road == "dhcp_only":
        res = e._run_dhcp_batch(pkt, length, float(T0))
        answered = np.asarray(res.verdict)[:2] == eng_mod.VERDICT_TX
    elif road == "express_aot":
        exe = e.compile_express_aot(XB)
        del spy_apply[:]
        res = e.run_express_aot(exe, descriptors(frames), float(T0))
        answered = np.asarray(res.block)[:2, 0] == np.asarray(
            e.run_express_aot(exe, descriptors([discover(MAC, 1)] * 2),
                              float(T0)).block)[:2, 0]
    else:
        replica = e.dhcp_replica(jnp.copy)
        res, replica = e.dispatch_scheduled_bulk(pkt, length, fa, float(T0),
                                                 replica)
        # the bulk lane's replica is the scheduler's to rebuild (it watches
        # resync_count); the tables the step threaded are the new upload's
        answered = np.array([True, True])
    assert e.resync_count == 2  # make_engine's own, and this drain's
    assert e.pending_dirty() == 0 and spy_apply == []
    assert answered.all()
    # the dispatch read, donated and rebound the NEW tables: they hold the
    # bulk builds
    key = [int.from_bytes(macs[0], "big") >> 32,
           int.from_bytes(macs[0], "big") & 0xFFFFFFFF]
    assert np.asarray(e.tables.dhcp.sub.vals)[
        e.fastpath.sub._find_slot(key)].any()
    assert np.asarray(e.tables.spoof.vals)[
        e.antispoof.bindings._find_slot(key)].any()


# ---------------------------------------------------------------------------
# (5) a first dirty drain after start-up builds no program
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loop", ["engine", "scheduler", "scheduler_overlap"])
def test_a_first_dirty_drain_after_start_up_builds_no_program(loop, built):
    e = make_engine()
    if loop == "engine":
        e.build_step_rungs(B)
    else:
        sched = TieredScheduler(e, SchedulerConfig(
            express_batch=XB, bulk_batch=B, express_device_index=-1,
            overlap_drain=loop == "scheduler_overlap"),
            clock=lambda: float(T0))
        assert sched._aot_ready
        sched.build_bulk_rungs()
    jax.block_until_ready(e.tables)
    # warm what a first window builds besides (uploads, the verdict select)
    pkt, length = window(FRAMES)
    fa = np.ones((B,), bool)
    if loop == "engine":
        e._dispatch_step(pkt, length, fa, len(FRAMES), NOW_S, NOW_US)
    else:
        sched.process(FRAMES)
    del built[:]
    for owner in OWNERS:
        WRITES[owner](e)
    assert len(dirty_names(e)) >= len(OWNERS)
    if loop == "engine":
        res = e._dispatch_step(pkt, length, fa, len(FRAMES), NOW_S, NOW_US)
        jax.block_until_ready(res.verdict)
    else:
        out = sched.process(FRAMES)
        sched.process(FRAMES)  # the overlap drain's batch rides this one
        assert len(out["tx"]) == 2  # the new lease answered at once
    assert e.pending_dirty() == 0
    assert built == []
