"""The table set's hand-written lists, held to each other (ROADMAP D23).

A device stage's tables are named by hand in some eighteen functions of
four files (`runtime/engine.py`, `runtime/checkpoint.py`, `runtime/ops.py`,
`parallel/sharded.py`): the device pytree, the update tuple and the
programs that apply it (the mesh's step, and since PR 50 the engine's two
packet-free ones: no one-chip step takes the tuple), the host mirrors a drain walks, the checkpoint's
components, the outputs a retire reads, the blue/green twin, the mesh's
stacked tuple. A stage taken out of one of them (or added to all but one)
fails here, per stage set, and the snapshot's format is pinned against a
literal taken from this tree.

Nothing here compiles a program: shapes come from `jax.eval_shape` /
`jax.make_jaxpr`, tables are tiny, and the lru caches that hold the other
test files' compiled programs are bypassed (`_no_program_cache`).
"""

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from bng_tpu.control.nat import NATManager
from bng_tpu.edge.tables import EdgeTables
from bng_tpu.ops.parse import PROTO_TCP
from bng_tpu.ops.pipeline import PipelineResult
from bng_tpu.ops.qtable import QTableState
from bng_tpu.ops.table import TableState
from bng_tpu.parallel import sharded as sh
from bng_tpu.runtime import engine as eng
from bng_tpu.runtime import hostpath
from bng_tpu.runtime import ops
from bng_tpu.runtime.checkpoint import (_resolve_component_meta,
                                        build_checkpoint, decode_checkpoint,
                                        encode_checkpoint,
                                        restore_checkpoint)
from bng_tpu.runtime.engine import (AntispoofTables, Engine, GardenTables,
                                    QoSTables)
from bng_tpu.runtime.tables import (FastPathTables, PPPoEFastPathTables,
                                    QinQFastPathTables, V6FastPathTables)
from bng_tpu.utils.net import ip_to_u32

T0 = 1_753_000_000
IP = ip_to_u32("10.0.0.10")
MAC = bytes.fromhex("02c0ffee0001")

# the engine's stage sets: every optional stage alone, the one pair that
# composes in the benchmark (Q), and all of them
ENGINE_SETS = {
    "none": (),
    "garden": ("garden",),
    "pppoe": ("pppoe",),
    "edge": ("edge",),
    "v6": ("v6",),
    "pppoe+qinq": ("pppoe", "qinq"),
    "all": ("garden", "pppoe", "edge", "v6", "qinq"),
}
# the mesh's (it has no line for v6 or qinq: named blockers of `--shards`)
MESH_SETS = {
    "none": (),
    "garden": ("garden",),
    "pppoe": ("pppoe",),
    "edge": ("edge",),
    "all": ("garden", "pppoe", "edge"),
}
BASE_OWNERS = ("fastpath", "nat", "qos", "antispoof")

engine_sets = pytest.mark.parametrize("stages", ENGINE_SETS.values(),
                                      ids=ENGINE_SETS.keys())
mesh_sets = pytest.mark.parametrize("stages", MESH_SETS.values(),
                                    ids=MESH_SETS.keys())


@pytest.fixture(autouse=True)
def _no_program_cache(monkeypatch):
    """An `Engine` / `ShardedCluster` built here asks for its jitted
    programs and runs none. The factories' lru caches (eight and four
    entries) hold what the other test files of this worker compiled, and
    twelve tiny geometries would push those out: hand out uncached ones."""
    for mod, names in ((eng, ("_pipeline_jit", "_dhcp_jit", "_express_jit")),
                       (sh, ("_sharded_step_jit", "_sharded_dhcp_jit"))):
        for name in names:
            monkeypatch.setattr(mod, name, getattr(mod, name).__wrapped__)


def make_engine(stages) -> Engine:
    """An engine with `stages` compiled in, every table tiny."""
    kw = dict(stash=8, update_slots=8)
    sp = AntispoofTables(nbuckets=64, **kw)
    return Engine(
        FastPathTables(sub_nbuckets=64, vlan_nbuckets=32, cid_nbuckets=32,
                       max_pools=4, **kw),
        NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                   sessions_nbuckets=64, sub_nat_nbuckets=32, **kw),
        QoSTables(nbuckets=64, **kw), sp,
        garden=(GardenTables(nbuckets=32, max_allowed=4, **kw)
                if "garden" in stages else None),
        pppoe=(PPPoEFastPathTables(nbuckets=32, **kw)
               if "pppoe" in stages else None),
        edge=(EdgeTables(tap_nbuckets=32, route_nbuckets=64, max_filters=4,
                         **kw)  # two sizes, as `bng run --edge-enabled` builds
              if "edge" in stages else None),
        v6=(V6FastPathTables(sp, nbuckets=32, **kw)
            if "v6" in stages else None),
        qinq=(QinQFastPathTables(nbuckets=32, **kw)
              if "qinq" in stages else None),
        batch_size=8, pkt_slot=256)


def fill(e: Engine) -> None:
    """At least one row in every table of every owner the engine has, and
    every dense array off its default."""
    e.fastpath.set_server_config(bytes.fromhex("02aabbccdd01"),
                                 ip_to_u32("10.0.0.1"))
    e.fastpath.add_pool(1, ip_to_u32("10.0.0.0"), 24, ip_to_u32("10.0.0.1"))
    e.fastpath.add_subscriber(MAC, 1, IP, T0 + 3600)
    e.fastpath.add_vlan_subscriber(100, 200, 1, IP, T0 + 3600)
    e.fastpath.add_circuit_id_subscriber(b"olt1/1/1", 1, IP, T0 + 3600)
    e.nat.allocate_nat(IP, T0)
    e.nat.handle_new_flow(IP, ip_to_u32("8.8.8.8"), 5555, 443,
                          int(PROTO_TCP), 100, T0)
    e.nat.add_hairpin_ip(ip_to_u32("203.0.113.1"))
    e.nat.add_alg_port(21, int(PROTO_TCP))
    e.qos.set_subscriber(IP, 10_000_000, 5_000_000)
    e.antispoof.set_config(1, True)
    e.antispoof.add_binding(MAC, IP, 1)
    e.antispoof.add_allowed_range(ip_to_u32("10.0.0.0"), 24)
    if e.garden is not None:
        e.garden.set_gardened(IP, True)
        e.garden.allow_destination(ip_to_u32("10.9.9.9"), 80, 6)
    if e.pppoe is not None:
        class Sess:
            session_id, client_mac, assigned_ip = 7, MAC, IP

        e.pppoe.session_up(Sess())
    if e.edge is not None:
        e.edge.arm_tap(IP, 3, [(443, 6, 0)])
        e.edge.set_route(IP, bytes.fromhex("02beef000001"), 2, 1)
    if e.v6 is not None:
        e.v6.bind(MAC, bytes.fromhex("20010db8000000000000000000000010"), IP)
    if e.qinq is not None:
        assert e.qinq.bind(IP, 100, 200)


def owners_of(e: Engine) -> dict:
    """The engine's host owners under the checkpoint's component names."""
    out = {"fastpath": e.fastpath, "nat": e.nat, "qos": e.qos,
           "antispoof": e.antispoof, "garden": e.garden, "pppoe": e.pppoe,
           "edge": e.edge, "v6": e.v6, "qinq": e.qinq}
    return {k: v for k, v in out.items() if v is not None}


def shapes(tree):
    return jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), tree)


def leaf_paths(tree) -> list[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def unread_leaves(fn, tables, upd) -> list[str]:
    """The leaves of `upd` that `fn(tables, upd)` neither computes with nor
    returns, by their path in the tuple."""
    jaxpr = jax.make_jaxpr(fn)(tables, upd).jaxpr
    read = {v for eqn in jaxpr.eqns for v in eqn.invars
            if not isinstance(v, jex_core.Literal)}
    read |= {v for v in jaxpr.outvars if not isinstance(v, jex_core.Literal)}
    paths = leaf_paths(upd)
    upd_vars = jaxpr.invars[len(jax.tree.leaves(tables)):]
    assert len(upd_vars) == len(paths)
    return [p for p, v in zip(paths, upd_vars) if v not in read]


# ---------------------------------------------------------------------------
# (a) the update tuple fits both programs that apply it
# ---------------------------------------------------------------------------

def full_updates(e: Engine, drain: bool) -> tuple:
    """The whole update tuple as the mesh's step unpacks it: the dhcp
    chain's batch in front of the engine's (which holds None there)."""
    fp = e.fastpath.make_updates() if drain else e.fastpath.empty_updates()
    return (fp, *e._updates(drain)[1:])


@engine_sets
@pytest.mark.parametrize("program", ["fused", "apply_only", "apply_fastpath"])
def test_update_tuple_fits_the_program(stages, program):
    """`Engine._updates` and the program that unpacks it agree on what the
    tuple holds: the tables come back as the tree they went in, and no
    entry of the tuple is left unread (an entry the program does not pop
    shifts every tail behind it, or is silently never applied). `fused`:
    the whole tuple over the whole table set, as the mesh's step applies
    it; `apply_only` / `apply_fastpath`: the engine's two packet-free
    programs, each over its chain."""
    e = make_engine(stages)
    drained, empty = e._updates(True), e._updates(False)
    assert shapes(drained) == shapes(empty)
    assert drained[0] is None  # the chain's batch is the other program's
    if program == "fused":
        fn, tables, upd = (eng._apply_all_updates, e.tables,
                           full_updates(e, True))
    elif program == "apply_only":
        fn = eng._apply_updates_jit.__wrapped__
        tables, upd = e.tables._replace(dhcp=None), empty
    else:
        fn = eng._apply_fastpath_jit.__wrapped__
        tables, upd = e.tables.dhcp, e.fastpath.make_updates()
        assert shapes(upd) == shapes(e.fastpath.empty_updates())
    out = jax.eval_shape(fn, tables, upd)
    assert jax.tree.structure(out) == jax.tree.structure(tables)
    assert shapes(out) == shapes(tables)
    assert unread_leaves(fn, tables, upd) == []


# ---------------------------------------------------------------------------
# (b) the mirrors a drain walks are the tables an upload makes
# ---------------------------------------------------------------------------

# every leaf of the device pytree that is no sparse table: applied
# wholesale by every batch (ops/table.py placed) or constant after build
DENSE_LEAVES = {
    "dhcp.pools", "dhcp.server", "nat.hairpin_ips", "nat.alg_ports",
    "nat.config", "spoof_ranges", "spoof_config", "garden_allowed",
    "pppoe_server_mac", "tap_filters", "tap_config",
}


def device_nodes(tables) -> dict:
    """{dotted field: node} of a PipelineTables, `dhcp` and `nat` opened."""
    out = {}
    for name, node in tables._asdict().items():
        if node is None:
            continue
        if name in ("dhcp", "nat"):
            out.update({f"{name}.{k}": v for k, v in node._asdict().items()})
        else:
            out[name] = node
    return out


def spy_uploads(monkeypatch, mirrors: dict) -> list[str]:
    """Record, by mirror name, every `device_state()` made from now on."""
    made = []
    for name, t in mirrors.items():
        def device_state(_name=name, _orig=t.device_state):
            made.append(_name)
            return _orig()

        monkeypatch.setattr(t, "device_state", device_state)
    return made


@engine_sets
def test_host_mirrors_are_the_uploaded_tables(stages, monkeypatch):
    e = make_engine(stages)
    mirrors = e.host_mirror_tables()
    made = spy_uploads(monkeypatch, mirrors)
    nodes = device_nodes(e._device_tables())
    sparse = {k for k, v in nodes.items()
              if isinstance(v, (TableState, QTableState))}
    # one upload a mirror, and no sparse table on the device without one
    assert sorted(made) == sorted(mirrors)
    assert len(sparse) == len(mirrors)
    assert set(nodes) - sparse <= DENSE_LEAVES
    # every owner the set has is behind some mirror, by the name's prefix
    assert {n.split("/")[0] for n in mirrors} == set(owners_of(e))
    assert e.pending_dirty() == 0
    fill(e)
    assert all(t.dirty_count() for t in mirrors.values())


# ---------------------------------------------------------------------------
# (c) a checkpoint carries every component of the set
# ---------------------------------------------------------------------------

def dense_arrays(e: Engine) -> dict:
    out = {"fastpath.pools": e.fastpath.pools,
           "fastpath.server": e.fastpath.server,
           "nat.hairpin": e.nat.hairpin, "nat.alg": e.nat.alg,
           "antispoof.ranges": e.antispoof.ranges,
           "antispoof.config": e.antispoof.config}
    if e.garden is not None:
        out["garden.allowed"] = e.garden.allowed
    if e.pppoe is not None:
        out["pppoe.server_mac"] = e.pppoe.server_mac
    if e.edge is not None:
        out["edge.tap_filters"] = e.edge.tap_filters
        out["edge.tap_config"] = e.edge.tap_config
    return out


@engine_sets
def test_checkpoint_round_trip_restores_every_component(stages):
    src = make_engine(stages)
    fill(src)
    data = encode_checkpoint(build_checkpoint(5, float(T0), engine=src,
                                              node_id="bng0"))
    twin = make_engine(stages)
    rows = restore_checkpoint(decode_checkpoint(data), engine=twin)
    assert {k.split(".")[0] for k in rows} == set(owners_of(src))
    for name, t in src.host_mirror_tables().items():
        assert rows[name.replace("/", ".")] >= 1, name
        got = twin.host_mirror_tables()[name].checkpoint_arrays()
        for k, want in t.checkpoint_arrays().items():
            assert np.array_equal(got[k], want), (name, k)
    for name, want in dense_arrays(src).items():
        assert want.any(), name  # `fill` left none at its default
        assert np.array_equal(dense_arrays(twin)[name], want), name
    # the twin's device tables were uploaded from what was restored
    assert twin.resync_count == 1 and twin.pending_dirty() == 0


# ---------------------------------------------------------------------------
# (d) what a step returns, a retire reads or is known not to
# ---------------------------------------------------------------------------

# outputs no retire reads: the tables thread to the next step (donated),
# the QoS class is the device's own, the mirror column is read only where
# a sink is set (`Engine._start_host_copies`)
NOT_READ_AT_RETIRE = {"tables", "priority", "mirror"}
STAGE_OUTPUTS = {"garden": {"garden_stats"}, "pppoe": {"pppoe_stats"},
                 "edge": {"edge_stats", "mirror"}, "v6": {"v6_stats"},
                 "qinq": {"qinq_stats"}}


@engine_sets
def test_step_outputs_are_read_at_retire_or_listed(stages):
    e = make_engine(stages)
    S = jax.ShapeDtypeStruct
    res = jax.eval_shape(
        e._step, e.tables,
        S((hostpath.window_rows(e.B, e.L), e.L), jnp.uint8),
        S((), jnp.uint32), S((), jnp.uint32))
    returned = {k for k, v in res._asdict().items() if v is not None}
    assert returned - set(Engine._RETIRE_READS) <= NOT_READ_AT_RETIRE
    assert set(Engine._RETIRE_READS) <= set(PipelineResult._fields)
    assert NOT_READ_AT_RETIRE.isdisjoint(Engine._RETIRE_READS)
    optional = set().union(*STAGE_OUTPUTS.values())
    assert returned & optional == {o for s in stages
                                   for o in STAGE_OUTPUTS[s]}
    assert jax.tree.structure(res.tables) == jax.tree.structure(e.tables)


# ---------------------------------------------------------------------------
# (e) the blue/green standby has a twin of every owner
# ---------------------------------------------------------------------------

def owner_geometry(owner) -> dict:
    """Every sparse table and dense array an owner holds, by attribute."""
    out = {}
    for k, v in vars(owner).items():
        if hasattr(v, "checkpoint_geom"):
            out[k] = v.checkpoint_geom()
        elif isinstance(v, np.ndarray):
            out[k] = (v.shape, str(v.dtype))
    out["update_slots"] = owner.update_slots
    return out


D24 = pytest.mark.xfail(
    strict=True, reason="ROADMAP D24: clone_mirrors has no line for `edge`, "
                        "so a blue/green swap drops the tap and route "
                        "tables (and `mirror_sink`)")


@pytest.mark.parametrize("stages", [
    pytest.param(s, id=k, marks=D24 if "edge" in s else ())
    for k, s in ENGINE_SETS.items() if k != "all"])
def test_clone_mirrors_twins_every_owner(stages):
    e = make_engine(stages)
    fill(e)
    twins = ops.clone_mirrors(e)
    assert set(twins) == set(owners_of(e))
    for name, owner in owners_of(e).items():
        assert type(twins[name]) is type(owner)
        assert owner_geometry(twins[name]) == owner_geometry(owner), name
    # empty, and a restore target for a snapshot of the engine as it is
    ck = build_checkpoint(1, float(T0), engine=e)
    assert restore_checkpoint(ck, **twins)


# ---------------------------------------------------------------------------
# (f) the mesh's stacked tuple is the engine's, a shard a leaf
# (h) the mesh's drained mirrors are the tables it uploads
# ---------------------------------------------------------------------------

N_SHARDS = 2


def make_cluster(stages) -> sh.ShardedCluster:
    return sh.ShardedCluster(
        N_SHARDS, batch_per_shard=8, sub_nbuckets=64, vlan_nbuckets=32,
        cid_nbuckets=32, max_pools=4, nat_sessions_nbuckets=64,
        nat_sub_nbuckets=32, qos_nbuckets=64, spoof_nbuckets=32,
        garden_enabled="garden" in stages, pppoe_enabled="pppoe" in stages,
        pppoe_nbuckets=32, edge_enabled="edge" in stages, edge_nbuckets=32)


def shard_engine(cl: sh.ShardedCluster, i: int) -> Engine:
    """An engine over shard `i`'s own owners: the same stages, the same
    geometry by construction."""
    c = cl.shard_components(i)
    return Engine(c["fastpath"], c["nat"], c["qos"], c["antispoof"],
                  garden=c.get("garden"), pppoe=c.get("pppoe"),
                  edge=c.get("edge"), batch_size=cl.b, pkt_slot=256)


@mesh_sets
def test_mesh_update_tuple_is_the_engines_stacked(stages):
    cl = make_cluster(stages)
    assert set(cl.shard_components(0)) == set(BASE_OWNERS) | set(stages)
    stacked = cl._updates()
    one = full_updates(shard_engine(cl, 0), True)
    # the same kinds in the same order, entry by entry ...
    assert jax.tree.structure(stacked) == jax.tree.structure(one)
    # ... and every leaf the engine's with the mesh axis in front
    assert shapes(stacked) == jax.tree.map(
        lambda x: ((N_SHARDS,) + tuple(x.shape), str(x.dtype)), one)


@mesh_sets
def test_mesh_mirrors_are_the_uploaded_tables(stages, monkeypatch):
    cl = make_cluster(stages)
    tabs = cl._host_tables()
    per_shard = len(tabs) // N_SHARDS
    mirrors = {f"{i // per_shard}/{i % per_shard}": t
               for i, t in enumerate(tabs)}
    made = spy_uploads(monkeypatch, mirrors)
    cl.sync_tables()
    nodes = device_nodes(cl.tables)
    sparse = {k for k, v in nodes.items()
              if isinstance(v, (TableState, QTableState))}
    assert sorted(made) == sorted(mirrors)
    assert len(sparse) == per_shard
    assert set(nodes) - sparse <= DENSE_LEAVES
    # a shard's mirrors are an engine's over the same owners, in any order
    one = shard_engine(cl, 0).host_mirror_tables().values()
    assert {id(t) for t in tabs[:per_shard]} == {id(t) for t in one}
    # the fastpath drain's are the first three of each shard
    fp = cl._host_tables(fastpath_only=True)
    assert [id(t) for t in fp[:3]] == [id(t) for t in tabs[:3]]
    assert len(fp) == 3 * N_SHARDS


# ---------------------------------------------------------------------------
# (g) the snapshot's format, component by component
# ---------------------------------------------------------------------------
# Taken from this tree (PR 47) at `make_engine`'s geometry: what a warm
# restart, a blue/green swap and a handoff between boxes read. A change
# here is a change of format: old snapshots need a reader.

def u32(*shape):
    return ("uint32", shape)


def geom_of(nbuckets, key_words, val_words):
    return {"nbuckets": nbuckets, "key_words": key_words,
            "val_words": val_words, "stash": 8}


def table_of(name, nbuckets, key_words, val_words):
    slots = 4 * nbuckets + 8  # four ways a bucket, and the stash
    return {f"{name}.keys": u32(slots, key_words),
            f"{name}.vals": u32(slots, val_words),
            f"{name}.used": u32(slots)}


SNAPSHOT_META = ["seq", "created_at", "node_id", "components"]
SNAPSHOT = {  # in the order the components are written
    "fastpath": {
        "meta": ["geom", "max_pools"],
        "geom": {"sub": geom_of(64, 2, 8), "vlan": geom_of(32, 1, 8),
                 "cid": geom_of(32, 8, 8)},
        "arrays": {**table_of("sub", 64, 2, 8), **table_of("vlan", 32, 1, 8),
                   **table_of("cid", 32, 8, 8),
                   "pools": u32(4, 8), "server": u32(4)}},
    "nat": {
        "meta": ["blocks", "eim", "flags", "free_blocks", "geom",
                 "ip_round_robin", "next_block", "port_range",
                 "ports_per_subscriber", "public_ips", "sub_id_seq"],
        "geom": {"sessions": geom_of(64, 4, 16),
                 "reverse": geom_of(64, 4, 8),
                 "sub_nat": geom_of(32, 1, 8)},
        "arrays": {**table_of("sessions", 64, 4, 16),
                   **table_of("reverse", 64, 4, 8),
                   **table_of("sub_nat", 32, 1, 8),
                   "hairpin": u32(256), "alg": u32(64),
                   # the allocator's books, as long as they are
                   "__payload_json__": ("uint8", None)}},
    "qos": {
        "meta": ["geom"],
        "geom": {"up": {"nbuckets": 64}, "down": {"nbuckets": 64}},
        "arrays": {"up.rows": u32(256, 8), "down.rows": u32(256, 8)}},
    "antispoof": {
        "meta": ["geom"],
        "geom": geom_of(64, 2, 8),
        "arrays": {**table_of("bindings", 64, 2, 8),
                   "ranges": u32(256, 2), "config": u32(2)}},
    "garden": {
        "meta": ["geom"],
        "geom": geom_of(32, 1, 8),
        "arrays": {**table_of("subscribers", 32, 1, 8),
                   "allowed": u32(4, 3)}},
    "pppoe": {
        "meta": ["geom"],
        "geom": {"by_sid": geom_of(32, 1, 8), "by_ip": geom_of(32, 1, 8)},
        "arrays": {**table_of("by_sid", 32, 1, 8),
                   **table_of("by_ip", 32, 1, 8), "server_mac": u32(2)}},
    "edge": {
        "meta": ["geom", "max_filters"],
        "geom": {"tap": geom_of(32, 1, 8), "route": geom_of(64, 1, 8)},
        "arrays": {**table_of("tap", 32, 1, 8), **table_of("route", 64, 1, 8),
                   "tap_filters": u32(4, 4), "tap_config": u32(2)}},
    "v6": {
        "meta": ["geom"],
        "geom": {"by_addr": geom_of(32, 4, 8)},
        "arrays": table_of("by_addr", 32, 4, 8)},
    "qinq": {
        "meta": ["geom"],
        "geom": {"by_ip": geom_of(32, 1, 8)},
        "arrays": table_of("by_ip", 32, 1, 8)},
}


@pytest.fixture
def snapshot():
    """An all-stages engine's checkpoint, through its bytes, with the
    payload-JSON components' meta inflated."""
    e = make_engine(ENGINE_SETS["all"])
    fill(e)
    ck = decode_checkpoint(encode_checkpoint(
        build_checkpoint(5, float(T0), engine=e, node_id="bng0")))
    comps = ck.meta["components"]
    return ck, {c: _resolve_component_meta(ck, comps, c) for c in comps}


def test_snapshot_components_and_their_order(snapshot):
    ck, _ = snapshot
    assert list(ck.meta) == SNAPSHOT_META
    assert list(ck.meta["components"]) == list(SNAPSHOT)
    # no array outside a component's namespace
    assert {k.split("/")[0] for k in ck.arrays} == set(SNAPSHOT)


@pytest.mark.parametrize("component", SNAPSHOT)
def test_snapshot_component_format(snapshot, component):
    ck, metas = snapshot
    want = SNAPSHOT[component]
    assert sorted(metas[component]) == want["meta"]
    assert metas[component]["geom"] == want["geom"]
    got = {k[len(component) + 1:]: (str(v.dtype), tuple(v.shape))
           for k, v in ck.arrays.items() if k.startswith(component + "/")}
    assert set(got) == set(want["arrays"])
    for name, (dtype, shape) in want["arrays"].items():
        assert got[name][0] == dtype, name
        if shape is None:
            assert len(got[name][1]) == 1, name
        else:
            assert got[name][1] == shape, name
