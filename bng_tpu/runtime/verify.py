"""TPU-lowering verification gate — the verifier-harness analog.

The reference refuses to ship an eBPF program the kernel verifier rejects
(cmd/verify-bpf/main.go:58-112, bpf/test-verifier.sh). The TPU analog of
"passes the verifier" is "compiles for the TPU target": a program can
pass its CPU suite and still be refused by the chip's compiler.

Every hot program has a builder here: `build_*(geometry)` returns the
jitted program and its arguments. Two callers share them:

  - `verify_tpu_lowering()` compiles each at the TOY geometry for the
    ATTACHED backend;
  - tests/test_tpu_lowering.py compiles each at the REAL_1M geometry
    for a DESCRIBED v5e (`compile_for`), with no chip attached.

A compile that passes is not a chip run: nothing executes.
"""

from __future__ import annotations

import traceback
from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp


class Geometry(NamedTuple):
    """Batch and table sizes the builders construct (buckets, not
    entries). The defaults are the toy gate; REAL_1M is what
    `chip_smoke.py` serves."""

    batch: int = 256  # fused step lanes
    express_batch: int = 64  # express lane lanes (SchedulerConfig default)
    pkt_slot: int = 512
    sub_nbuckets: int = 1 << 10  # subscriber table
    side_nbuckets: int = 256  # VLAN, circuit-ID, QoS, antispoof, garden
    nat_sessions_nbuckets: int = 1 << 14
    sub_nat_nbuckets: int = 1 << 10
    max_pools: int = 4
    stash: int = 64
    pppoe_nbuckets: int = 0  # PPPoE session tables; 0 = no PPPoE stage
    v6_nbuckets: int = 0  # IPv6 by-address table; 0 = no v6 stage
    qinq_nbuckets: int = 0  # address -> S/C-tag table; 0 = no qinq stage
    route_nbuckets: int = 0  # next-hop table of the edge stage; 0 = no stage
    tap_nbuckets: int = 0  # the edge stage's tap table (a row a warrant)


TOY = Geometry()
# 1M subscribers / 1M NAT flows over 250k NAT subscribers, each table
# at ops.table.nbuckets_for(entries): what BNGApp builds from
# max_subscribers=1_000_000, max_nat_sessions=1_000_000,
# max_nat_subscribers=250_000 (FastPathTables keeps its 256 pools)
REAL_1M = Geometry(batch=8192, pkt_slot=1536, sub_nbuckets=1 << 19,
                   side_nbuckets=1 << 19,
                   nat_sessions_nbuckets=1 << 19, sub_nat_nbuckets=1 << 17,
                   max_pools=256)
# the same with the PPPoE stage compiled in, its two session tables sized
# for an access concentrator's 65,535 sessions (`bng run --pppoe-enabled`)
REAL_1M_PPPOE = REAL_1M._replace(pppoe_nbuckets=1 << 15)
# the same with the IPv6 stage compiled in, its by-address table sized for
# 1,000,000 IA_NA bindings (`bng run --ipv6-fastpath`)
REAL_1M_V6 = REAL_1M._replace(v6_nbuckets=1 << 19)
# PPPoE and the access VLANs together, the pair table sized for 1,000,000
# subscribers (`bng run --pppoe-enabled --qinq-enabled`)
REAL_1M_QINQ = REAL_1M_PPPOE._replace(qinq_nbuckets=1 << 19)
# the edge stage compiled in: a route row a subscriber, and the tap table at
# its default 4,096 warrants (`bng run --edge-enabled`)
REAL_1M_EDGE = REAL_1M._replace(route_nbuckets=1 << 19, tap_nbuckets=1 << 12)


def compile_for(built, sharding=None):
    """Lower + compile one `build_*` result. With `sharding`, the
    arguments become ShapeDtypeStructs placed by it — how a program
    compiles for a described device that can hold no array."""
    fn, args = built
    if sharding is not None:
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=sharding), args)
    return fn.lower(*args).compile()


def _fastpath(g: Geometry):
    from bng_tpu.runtime.tables import FastPathTables
    from bng_tpu.utils.net import ip_to_u32

    fp = FastPathTables(sub_nbuckets=g.sub_nbuckets,
                        vlan_nbuckets=g.side_nbuckets,
                        cid_nbuckets=g.side_nbuckets,
                        max_pools=g.max_pools, stash=g.stash)
    fp.set_server_config(bytes.fromhex("02aabbccdd01"), ip_to_u32("10.0.0.1"))
    return fp


def build_qos(g: Geometry = TOY):
    from bng_tpu.ops.qos import qos_kernel
    from bng_tpu.runtime.engine import QoSTables

    B = g.batch
    qos = QoSTables(nbuckets=g.side_nbuckets)
    for i in range(32):
        qos.set_subscriber((10 << 24) | (i + 2), down_bps=8_000_000, up_bps=8_000_000)
    ips = jnp.asarray(((10 << 24) + 2 + np.arange(B) % 64).astype(np.uint32))
    lens = jnp.full((B,), 900, dtype=jnp.uint32)
    active = jnp.ones((B,), dtype=bool)

    def kernel(t, i, l):
        return qos_kernel(i, l, active, t, qos.geom, jnp.uint32(1)).allowed

    return jax.jit(kernel), (qos.up.device_state(), ips, lens)


def build_table(g: Geometry = TOY):
    """The batched probe (the surface every hot-path kernel funnels
    through) on the subscriber-table shape."""
    from bng_tpu.ops.table import HostTable, device_lookup

    K, V = 2, 8
    t = HostTable(g.sub_nbuckets, K, V, stash=g.stash, name="verify")
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 2**32, size=(g.batch, K), dtype=np.uint32)
    for k in np.unique(keys[:256], axis=0):
        t.insert(k, np.arange(V, dtype=np.uint32))
    nb, stash = t.nbuckets, t.stash

    def look(state, q):
        r = device_lookup(state, q, nb, stash)
        return r.found, r.slot, r.vals

    return jax.jit(look), (t.device_state(), jnp.asarray(keys))


def build_dhcp_express(g: Geometry = TOY):
    """The express-lane OFFER program (donated chain + aliased packet
    batch)."""
    from bng_tpu.runtime.engine import _dhcp_jit

    B = g.express_batch
    fp = _fastpath(g)
    return _dhcp_jit(fp.geom), (
        fp.device_tables(),
        jnp.zeros((B, g.pkt_slot), dtype=jnp.uint8),
        jnp.zeros((B,), dtype=jnp.uint32), jnp.uint32(1))


def build_express_aot(g: Geometry = TOY):
    """The AOT express OFFER program (ISSUE 13): descriptor in, verdict
    block out, tables + descriptor donated. Exactly the lower+compile
    the serving path performs at scheduler init — a program that fails
    HERE would turn every express dispatch into a counted jit-full
    fallback, so the gate refuses it up front."""
    from bng_tpu.ops.express import XD_WORDS
    from bng_tpu.runtime.engine import _express_jit

    fp = _fastpath(g)
    return _express_jit(fp.geom), (
        fp.device_tables(),
        jnp.zeros((g.express_batch, XD_WORDS), dtype=jnp.uint32),
        jnp.uint32(1))


def build_apply_fastpath(g: Geometry = TOY):
    """The dhcp chain's packet-free apply program: what a dirty fastpath
    drain goes through ahead of its step (no step takes an update batch),
    the chain donated."""
    from bng_tpu.runtime.engine import _apply_fastpath_jit

    fp = _fastpath(g)
    return _apply_fastpath_jit, (fp.device_tables(), fp.empty_updates())


def build_apply_updates(g: Geometry = TOY):
    """The same for every table outside the dhcp chain
    (Engine.apply_updates_now): tables without the chain, donated."""
    from bng_tpu.runtime.engine import _apply_updates_jit

    eng = _engine(g)
    return _apply_updates_jit, (eng.tables._replace(dhcp=None),
                                eng._empty_updates())


def _engine(g: Geometry):
    """A single-device Engine over empty tables of geometry `g`, the
    walled-garden gate on (the `bng run` default)."""
    from bng_tpu.control.nat import NATManager
    from bng_tpu.edge import EdgeTables
    from bng_tpu.runtime.engine import (AntispoofTables, Engine, GardenTables,
                                        QoSTables)
    from bng_tpu.runtime.tables import (PPPoEFastPathTables,
                                        QinQFastPathTables, V6FastPathTables)
    from bng_tpu.utils.net import ip_to_u32

    spoof = AntispoofTables(nbuckets=g.side_nbuckets, stash=g.stash)
    return Engine(
        _fastpath(g),
        NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                   sessions_nbuckets=g.nat_sessions_nbuckets,
                   sub_nat_nbuckets=g.sub_nat_nbuckets, stash=g.stash),
        qos=QoSTables(nbuckets=g.side_nbuckets),
        antispoof=spoof,
        garden=GardenTables(nbuckets=g.side_nbuckets, stash=g.stash),
        pppoe=(PPPoEFastPathTables(nbuckets=g.pppoe_nbuckets, stash=g.stash)
               if g.pppoe_nbuckets else None),
        v6=(V6FastPathTables(spoof, nbuckets=g.v6_nbuckets, stash=g.stash)
            if g.v6_nbuckets else None),
        qinq=(QinQFastPathTables(nbuckets=g.qinq_nbuckets, stash=g.stash)
              if g.qinq_nbuckets else None),
        edge=(EdgeTables(route_nbuckets=g.route_nbuckets,
                         tap_nbuckets=g.tap_nbuckets, stash=g.stash)
              if g.route_nbuckets else None),
        batch_size=g.batch, pkt_slot=g.pkt_slot)


def build_pipeline(g: Geometry = TOY, lanes: int | None = None):
    """The fused step as the Engine compiles it: tables (donated), the
    window's one block (hostpath.seal_window: packet slots, lengths and
    access flags) and clock, no update batch. `lanes`: a rung of the
    step's ladder under `g.batch` (engine.py step_rungs), the width a
    shorter window is dispatched at."""
    from bng_tpu.runtime import hostpath

    eng = _engine(g)
    B = lanes or g.batch
    block = hostpath.seal_window(
        hostpath.window_buffer(B, g.pkt_slot),
        np.full((B,), 300, dtype=np.uint32), np.ones((B,), dtype=bool))
    return eng._step, (eng.tables, jnp.asarray(block),
                       jnp.uint32(1), jnp.uint32(1))


def build_sharded(mesh, g: Geometry = TOY):
    """The sharded fused step over `mesh`; `g` is the PER-SHARD
    geometry. The arguments are shapes already (one shard's tables with
    a leading mesh dimension), so this also builds on a described mesh,
    where nothing can be stacked."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bng_tpu.parallel.sharded import AXIS, _sharded_step_jit

    n = mesh.devices.size
    eng = _engine(g)
    split = NamedSharding(mesh, P(AXIS))
    whole = NamedSharding(mesh, P())

    def stacked(a):
        return jax.ShapeDtypeStruct((n,) + np.shape(a), a.dtype,
                                    sharding=split)

    def lanes(*trail, dtype):
        return jax.ShapeDtypeStruct((n * g.batch,) + trail, dtype,
                                    sharding=split)

    now = jax.ShapeDtypeStruct((), jnp.uint32, sharding=whole)
    return _sharded_step_jit(mesh, eng.geom, n), (
        jax.tree.map(stacked, eng.tables),
        # the mesh step still takes its batch: the chain's in front
        jax.tree.map(stacked, (eng.fastpath.empty_updates(),
                               *eng._empty_updates()[1:])),
        lanes(g.pkt_slot, dtype=jnp.uint8), lanes(dtype=jnp.uint32),
        lanes(dtype=jnp.bool_), now, now)


def _check_sharded() -> None:
    """Sharded step over every attached device (n=1 on one chip —
    the 8-way variant is exercised by dryrun_multichip on the CPU mesh)."""
    from bng_tpu.parallel.sharded import ShardedCluster

    n = len(jax.devices())
    cl = ShardedCluster(n_shards=n, batch_per_shard=64)
    pkt = np.zeros((n * 64, 512), dtype=np.uint8)
    ln = np.full((n * 64,), 0, dtype=np.uint32)
    fa = np.ones((n * 64,), dtype=bool)
    cl.step(pkt, ln, fa, 1, 1)
    cl.dhcp_step(pkt, ln, 1)  # the sharded control fast lane too


def _compiles(build: Callable) -> Callable[[], None]:
    def check() -> None:
        compile_for(build())

    return check


# (name, check). Every check also runs on the CPU, so the *harness itself*
# (table constructors, kernel signatures) is exercised by the plain test
# suite — round 3 found the gate broken by NATManager API drift.
CHECKS: list[tuple[str, Callable[[], None]]] = [
    ("qos_kernel", _compiles(build_qos)),
    ("table_lookup", _compiles(build_table)),
    ("dhcp_express", _compiles(build_dhcp_express)),
    # the AOT minimal OFFER program (ISSUE 13) — the architecture the
    # offer_device_only_p99_us gate measures on the express lane
    ("express_aot", _compiles(build_express_aot)),
    ("fused_pipeline_step", _compiles(build_pipeline)),
    ("apply_fastpath", _compiles(build_apply_fastpath)),
    ("apply_updates", _compiles(build_apply_updates)),
    ("sharded_step", _check_sharded),
]


def verify_tpu_lowering(verbose: bool = True) -> list[tuple[str, str | None]]:
    """Compile every hot program for the attached backend.

    Returns [(name, None | error_string)]. Raises nothing; callers decide
    (pytest asserts).
    """
    results: list[tuple[str, str | None]] = []
    for name, check in CHECKS:
        try:
            check()
            results.append((name, None))
            if verbose:
                print(f"  lowering OK   {name}")
        except Exception:
            err = traceback.format_exc(limit=3)
            results.append((name, err))
            if verbose:
                print(f"  lowering FAIL {name}\n{err}")
    return results
