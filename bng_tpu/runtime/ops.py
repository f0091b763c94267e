"""Zero-downtime engine operations: blue/green swap with delta replay.

Changing engine state used to mean restart-with-checkpoint — every
config or recovery action was an outage. This module turns the PR 2-4
machinery (quiesce barrier, versioned snapshot codec, invariant auditor)
into an ONLINE operation:

1. **snapshot** — at the quiesce barrier (scheduler.quiesce() or
   engine.quiesce(); nothing in flight, device-authoritative words
   folded back), build an in-memory checkpoint of every engine-owned
   host mirror and round-trip it through the encode/verify/decode codec
   (`roundtrip_checkpoint`) — the same rejection surface as the disk
   path, so a snapshot that could never restore aborts the swap here.

2. **hydrate** — build geometry-identical CLONE mirrors, restore the
   snapshot into them through the normal all-verified-then-hydrate gate,
   and upload them as the STANDBY engine's device chain. The standby
   shares the live host managers (they are the single-writer authority
   and are not being swapped); only its device pytree comes from the
   snapshot.

3. **delta replay** — host mirrors kept moving while the standby
   hydrated. `replay_delta_since` diffs every sparse host mirror against
   the snapshot arrays, marks exactly the changed slots dirty, and ships
   them to the standby chain through the SAME bounded update drain every
   other table producer uses (single-writer discipline preserved; a
   bulk-sized delta falls back to one resync_tables upload).

4. **audit + flip/rollback** — the cross-authority auditor is the
   steady-state hypothesis (Chaos Engineering, PAPERS.md): the standby
   must prove host==device and every ownership invariant BEFORE it
   serves. On a clean audit the flip is atomic at the barrier: the
   composition root's engine reference and the scheduler's lanes
   re-point in one step (callers hold the app's control lock). On any
   violation — or a chaos `ops.swap` fail, or a snapshot/restore
   reject — the standby is discarded, the ACTIVE engine is re-synced
   (healing any delta the replay already consumed) and keeps serving.

Fault points: `ops.snapshot` (io_error, in roundtrip_checkpoint) and
`ops.swap` (fail, at the flip barrier). Chaos scenario:
`engine_swap_crash_rollback` (chaos/scenarios.py).
"""

from __future__ import annotations

import time

import numpy as np

from bng_tpu.chaos.faults import FaultInjectedError, fault_point
from bng_tpu.runtime.checkpoint import (CheckpointError, build_checkpoint,
                                        restore_checkpoint,
                                        roundtrip_checkpoint)
from bng_tpu.telemetry import spans as tele
from bng_tpu.utils.structlog import get_logger

_log = get_logger("ops.swap")

# bounded drain passes for the delta replay: update_slots per table per
# step, so this covers update_slots * max steps changed rows before the
# resync fallback takes over
MAX_REPLAY_STEPS = 256


def clone_mirrors(engine) -> dict:
    """Fresh, EMPTY host-mirror objects geometry-identical to the
    engine's — the hydration targets for the standby's device chain.
    Only components the engine actually has are cloned (restore rejects
    a component with no target, and rightly so)."""
    from bng_tpu.control.nat import NATManager
    from bng_tpu.runtime.engine import AntispoofTables, GardenTables, QoSTables
    from bng_tpu.runtime.tables import (FastPathTables, PPPoEFastPathTables,
                                        QinQFastPathTables, V6FastPathTables)

    fp = engine.fastpath
    nat = engine.nat
    out = {
        "fastpath": FastPathTables(
            sub_nbuckets=fp.sub.nbuckets, vlan_nbuckets=fp.vlan.nbuckets,
            cid_nbuckets=fp.cid.nbuckets, max_pools=len(fp.pools),
            stash=fp.sub.stash, update_slots=fp.update_slots),
        "nat": NATManager(
            public_ips=list(nat.public_ips),
            ports_per_subscriber=nat.ports_per_subscriber,
            port_range=tuple(nat.port_range), flags=nat.flags,
            sessions_nbuckets=nat.sessions.nbuckets,
            sub_nat_nbuckets=nat.sub_nat.nbuckets,
            stash=nat.sessions.stash, update_slots=nat.update_slots),
        "qos": QoSTables(nbuckets=engine.qos.up.nbuckets,
                         update_slots=engine.qos.update_slots),
        "antispoof": AntispoofTables(
            nbuckets=engine.antispoof.bindings.nbuckets,
            stash=engine.antispoof.bindings.stash,
            update_slots=engine.antispoof.update_slots),
    }
    if engine.garden is not None:
        out["garden"] = GardenTables(
            nbuckets=engine.garden.subscribers.nbuckets,
            stash=engine.garden.subscribers.stash,
            update_slots=engine.garden.update_slots,
            max_allowed=engine.garden.allowed.shape[0])
    if engine.pppoe is not None:
        out["pppoe"] = PPPoEFastPathTables(
            nbuckets=engine.pppoe.by_sid.nbuckets,
            stash=engine.pppoe.by_sid.stash,
            update_slots=engine.pppoe.update_slots)
    if engine.v6 is not None:
        out["v6"] = V6FastPathTables(
            out["antispoof"], nbuckets=engine.v6.by_addr.nbuckets,
            stash=engine.v6.by_addr.stash,
            update_slots=engine.v6.update_slots)
    if engine.qinq is not None:
        out["qinq"] = QinQFastPathTables(
            nbuckets=engine.qinq.by_ip.nbuckets,
            stash=engine.qinq.by_ip.stash,
            update_slots=engine.qinq.update_slots)
    return out


def _changed_slots(table, arrays: dict, name: str) -> np.ndarray:
    """Slot indexes whose host row differs from the snapshot arrays.
    A table absent from the snapshot (shouldn't happen — the snapshot
    came from the same engine) degrades to every occupied slot."""
    if hasattr(table, "keys"):  # HostTable
        snap_k = arrays.get(f"{name}.keys")
        snap_v = arrays.get(f"{name}.vals")
        snap_u = arrays.get(f"{name}.used")
        if snap_k is None or snap_v is None or snap_u is None:
            return np.nonzero(table.used)[0]
        changed = ((table.keys != snap_k).any(axis=1)
                   | (table.vals != snap_v).any(axis=1)
                   | (table.used != snap_u))
        return np.nonzero(changed)[0]
    # HostQTable: one packed row array
    snap_r = arrays.get(f"{name}.rows")
    if snap_r is None:
        return np.nonzero(table.rows.any(axis=1))[0]
    return np.nonzero((table.rows != snap_r).any(axis=1))[0]


def replay_delta_since(engine, arrays: dict,
                       max_steps: int = MAX_REPLAY_STEPS) -> dict:
    """Ship every host-mirror row that changed since `arrays` (a
    checkpoint's array dict) to the engine's device chain through the
    normal bounded update drain. The engine's chain is assumed to be AT
    the snapshot state (adopt_device_tables); after this it is current.

    Returns {"rows": slots re-shipped, "steps": empty drain steps run,
    "resync": whether a bulk-sized delta forced one full upload}.
    """
    rows = 0
    resync = False
    for name, table in engine.host_mirror_tables().items():
        if table._dirty_all:
            resync = True
            continue
        rows += table.mark_dirty(_changed_slots(table, arrays, name))
    if resync:
        # a bulk build happened during hydration: bounded deltas can't
        # express it — one full upload, the same path a cold start takes
        engine.resync_tables()
        return {"rows": rows, "steps": 0, "resync": True}
    steps = 0
    while engine.pending_dirty() > 0 and steps < max_steps:
        # an empty batch runs the full update drain and nothing else —
        # the cheapest way to ship deltas without a second drain path
        engine.process([])
        steps += 1
    if engine.pending_dirty() > 0:
        raise CheckpointError(
            f"delta replay did not converge in {max_steps} steps "
            f"({engine.pending_dirty()} slots still dirty)")
    return {"rows": rows, "steps": steps, "resync": False}


def blue_green_swap(components, *, audit: bool = True, metrics=None,
                    node_id: str = "bluegreen") -> dict:
    """Hydrate a standby engine from an in-memory snapshot, replay the
    delta, audit, and flip — or roll back with the active untouched.

    `components` is the composition root's dict (BNGApp.components or a
    scenario-built equivalent): needs "engine"; uses "scheduler",
    "pools", "dhcp", "fleet" when present. On success
    components["engine"] IS the standby. Callers serialize against the
    dataplane loop (BNGApp holds _ctl); the flip itself is one dict
    store + one scheduler re-point at the quiesce barrier.
    """
    from bng_tpu.runtime.engine import Engine

    eng = components["engine"]
    sched = components.get("scheduler")
    report: dict = {"op": "engine_swap", "outcome": "failed"}
    t_all = time.perf_counter()
    consumed_delta = False
    try:
        # 1. quiesce + in-memory snapshot (codec round-trip verified)
        t0 = tele.t()
        t_q = time.perf_counter()
        deferred = sched.quiesce() if sched is not None else eng.quiesce()
        eng.fold_device_authoritative()
        report["frames_deferred"] = deferred
        ckpt = build_checkpoint(
            0, eng.clock(), fastpath=eng.fastpath, nat=eng.nat, qos=eng.qos,
            antispoof=eng.antispoof, garden=eng.garden, pppoe=eng.pppoe,
            v6=eng.v6, qinq=eng.qinq, node_id=node_id)
        ckpt = roundtrip_checkpoint(ckpt)  # ops.snapshot chaos point
        report["quiesce_s"] = time.perf_counter() - t_q
        tele.lap(tele.OPS, t0)

        # 2. standby hydration: clone mirrors -> verified restore ->
        # device upload; the standby engine shares the LIVE host
        # managers (they stay the single-writer authority) and adopts
        # the snapshot-built device chain in place of its init upload.
        t0 = tele.t()
        t_h = time.perf_counter()
        tmp = clone_mirrors(eng)
        report["restored_rows"] = restore_checkpoint(ckpt, **tmp)
        hydrator = Engine(
            tmp["fastpath"], tmp["nat"], qos=tmp["qos"],
            antispoof=tmp["antispoof"], garden=tmp.get("garden"),
            pppoe=tmp.get("pppoe"), batch_size=eng.B, pkt_slot=eng.L,
            clock=eng.clock, v6=tmp.get("v6"), qinq=tmp.get("qinq"))
        standby = Engine(
            eng.fastpath, eng.nat, qos=eng.qos, antispoof=eng.antispoof,
            garden=eng.garden, pppoe=eng.pppoe, batch_size=eng.B,
            pkt_slot=eng.L, slow_path=eng.slow_path,
            violation_sink=eng.violation_sink, clock=eng.clock,
            device_tables=hydrator.tables, v6=eng.v6, qinq=eng.qinq)
        standby.slow_path_batch = eng.slow_path_batch
        standby.stats = eng.stats  # operational counters never reset
        report["hydrate_s"] = time.perf_counter() - t_h
        tele.lap(tele.OPS, t0)

        # 3. delta replay at the barrier: host mirrors moved while the
        # standby hydrated; ship exactly the changed slots
        t0 = tele.t()
        consumed_delta = True
        delta = replay_delta_since(standby, ckpt.arrays)
        report["delta_rows"] = delta["rows"]
        report["delta_steps"] = delta["steps"]
        report["delta_resync"] = delta["resync"]
        tele.lap(tele.OPS, t0)

        # 4. chaos flip barrier + audit — the steady-state hypothesis
        fp = fault_point("ops.swap")
        if fp is not None and fp.kind == "fail":
            raise FaultInjectedError("chaos: injected crash mid-swap")
        if audit:
            from bng_tpu.chaos.invariants import audit_invariants

            t0 = tele.t()
            audit_rep = audit_invariants(
                engine=standby, pools=components.get("pools"),
                dhcp=components.get("dhcp"), fleet=components.get("fleet"),
                nat=eng.nat, check_roundtrip=False)
            report["audit_ok"] = audit_rep.ok
            report["violations"] = audit_rep.violations_by_kind()
            tele.lap(tele.OPS, t0)
            if not audit_rep.ok:
                raise CheckpointError(
                    f"standby failed the invariant audit: "
                    f"{audit_rep.violations_by_kind()}")

        # 5. the flip: one reference store + scheduler re-point
        t0 = tele.t()
        t_f = time.perf_counter()
        components["engine"] = standby
        if sched is not None:
            sched.adopt_engine(standby)
        report["flip_s"] = time.perf_counter() - t_f
        tele.lap(tele.OPS, t0)
        report["outcome"] = "ok"
    except Exception as e:  # noqa: BLE001 — ANY failure must run the heal
        # rollback: the active engine keeps serving. If the replay/audit
        # already consumed dirty marks into the (now discarded) standby
        # chain, re-sync the ACTIVE chain from the host mirrors — the
        # same full-upload heal a bulk build uses — so no delta is lost.
        # Catching only the expected types would leave the active device
        # chain silently missing those rows on an unexpected one (XLA
        # runtime errors are plain RuntimeError).
        report["outcome"] = "rolled_back" if consumed_delta else "failed"
        report["error"] = f"{type(e).__name__}: {e}"[:300]
        _log.error("engine swap did not flip", outcome=report["outcome"],
                   error=report["error"], healed=consumed_delta)
        if consumed_delta:
            eng.resync_tables()
    report["duration_s"] = time.perf_counter() - t_all
    if metrics is not None:
        metrics.record_transition(report)
    return report


def sharded_blue_green_swap(components, *, audit: bool = True, metrics=None,
                            node_id: str = "bluegreen",
                            clock=time.time) -> dict:
    """Blue/green swap for the ICI-sharded serving path (ISSUE 12):
    hydrate a STANDBY ShardedCluster from an in-memory sharded snapshot
    and flip the composition root's cluster reference — or discard the
    standby with the active cluster untouched.

    Differences from the engine swap that make this one simpler, not
    weaker: callers hold the app's control lock for the whole
    transition (the sharded drive loop cannot run concurrently), so the
    host authorities cannot move between snapshot and flip — no delta
    replay pass is needed; and the standby is built from a geometry
    clone sharing the live mesh, so its jit caches hit the compiled
    programs instead of recompiling. The same failure surfaces stay
    armed: the snapshot round-trips through the versioned codec
    (`ops.snapshot` io_error), the restore runs the full
    all-verified-then-hydrate gate, the cross-authority sharded audit
    must pass BEFORE the flip, and the `ops.swap` chaos point crashes
    at the flip barrier — any failure leaves the ACTIVE cluster
    serving (it was never mutated)."""
    from bng_tpu.runtime.checkpoint import (build_sharded_checkpoint,
                                            restore_sharded_checkpoint)

    cl = components["cluster"]
    report: dict = {"op": "sharded_swap", "outcome": "failed",
                    "shards": cl.n}
    t_all = time.perf_counter()
    try:
        # 1. quiesce + in-memory snapshot, codec round-trip verified
        t0 = tele.t()
        t_q = time.perf_counter()
        report["frames_deferred"] = cl.quiesce()
        # the DHCP lease book is NOT part of the snapshot: the live
        # server keeps the host authority across the flip (engine-swap
        # discipline — only the device-backed shard state swaps)
        ckpt = build_sharded_checkpoint(cl, 0, clock(), node_id=node_id)
        ckpt = roundtrip_checkpoint(ckpt)  # ops.snapshot chaos point
        report["quiesce_s"] = time.perf_counter() - t_q
        tele.lap(tele.OPS, t0)

        # 2. standby hydration: geometry clone + verified restore + one
        # full device upload (inside restore_sharded_checkpoint)
        t0 = tele.t()
        t_h = time.perf_counter()
        standby = cl.clone_empty()
        report["restored_rows"] = restore_sharded_checkpoint(
            ckpt, standby, now=int(clock()))
        report["hydrate_s"] = time.perf_counter() - t_h
        tele.lap(tele.OPS, t0)

        # 3. chaos flip barrier + the sharded cross-authority audit —
        # the standby must prove the partition invariants BEFORE serving
        fp = fault_point("ops.swap")
        if fp is not None and fp.kind == "fail":
            raise FaultInjectedError("chaos: injected crash mid-swap")
        if audit:
            from bng_tpu.chaos.invariants import audit_invariants

            t0 = tele.t()
            audit_rep = audit_invariants(
                cluster=standby, pools=components.get("pools"),
                dhcp=components.get("dhcp"), check_roundtrip=False)
            report["audit_ok"] = audit_rep.ok
            report["violations"] = audit_rep.violations_by_kind()
            tele.lap(tele.OPS, t0)
            if not audit_rep.ok:
                raise CheckpointError(
                    f"standby cluster failed the invariant audit: "
                    f"{audit_rep.violations_by_kind()}")

        # 4. the flip: one reference store (the drive loop reads
        # components["cluster"] every beat)
        t0 = tele.t()
        t_f = time.perf_counter()
        components["cluster"] = standby
        report["flip_s"] = time.perf_counter() - t_f
        tele.lap(tele.OPS, t0)
        report["outcome"] = "ok"
    except Exception as e:  # noqa: BLE001 — ANY failure keeps the active
        # the active cluster was never mutated (the snapshot reads, the
        # standby owns every write): discard the standby and keep serving
        report["outcome"] = "failed"
        report["error"] = f"{type(e).__name__}: {e}"[:300]
        _log.error("sharded swap did not flip", error=report["error"])
    report["duration_s"] = time.perf_counter() - t_all
    if metrics is not None:
        metrics.record_transition(report)
    return report
