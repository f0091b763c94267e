"""Latency-tiered dataplane scheduler: express DHCP + depth-pipelined bulk.

The round-5 verdict's architectural gap: the engine ran one monolithic
fused step, so a DHCP OFFER queued behind a 512-frame NAT44+QoS batch and
every benchmark blocked per step — conflating the host's per-call sync
cost with device time. The reference
BNG never sees this shape because per-packet XDP has no batches; an
inference server solves it with iteration-level scheduling and latency
classes (Orca-style continuous batching). This module is that scheduler
for the TPU re-host:

- **express lane** — frames classifying as genuine access-side DHCP
  (ring.classify_dhcp, the dhcp_fastpath.c parity classifier) run the
  pre-compiled DHCP-only program at a small fixed batch with
  deadline-based close: dispatch when full OR when the oldest frame has
  waited max_wait_us. The lane owns the authoritative device DHCP chain
  and, when >1 device is attached, its OWN device — so an express
  dispatch has neither a data dependency nor an execution-stream
  dependency on in-flight bulk work (XLA runs one FIFO stream per
  device; a same-device express dispatch would still queue behind an
  enqueued bulk step no matter how it is interleaved).

- **bulk lane** — everything else runs the fused NAT44+QoS+antispoof
  pipeline at large batch with depth-N async pipelining: dispatches
  enter a completion ring as futures and `block_until_ready` happens
  only when the ring overflows its depth (>= 2), never per step. The
  bulk program consumes a READ REPLICA of the dhcp tables (refreshed on
  a cadence), which is what breaks the data dependency: a bulk dispatch
  never rebinds the dhcp leaves the express program consumes.

The scheduler also owns the cadence of the engine's bounded table-update
drain: the express lane drains the fastpath delta before every dispatch
(an OFFER must see the newest lease), while the bulk-owned tables are
drained only every `drain_every` bulk dispatches. No step program takes an
update batch: a drain that finds nothing dirty makes no call and moves
nothing, and one that finds something goes through the engine's
packet-free program ahead of the step (Engine.apply_updates_now).

Single-process, poll-driven: `submit()` frames, `poll()` each beat (the
CLI run loop), or use `process()` — the batch-synchronous facade the
loadtest harness drives.
"""

from __future__ import annotations

import functools
import os
from collections import deque
from dataclasses import dataclass
from typing import Callable, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from bng_tpu.control.dhcp_codec import ACK, DISCOVER, OFFER, ExpressTemplateCache
from bng_tpu.ops.dhcp import (PV_DNS1, PV_DNS2, PV_GATEWAY, PV_PREFIX,
                              SC_IP, SC_MAC_HI, SC_MAC_LO)
from bng_tpu.ops.express import (VB_LEASE_T, VB_POOL, VB_VERDICT, VB_YIADDR,
                                 XD_WORDS, parse_express)
from bng_tpu.ops.pipeline import VERDICT_DROP, VERDICT_FWD, VERDICT_TX
from bng_tpu.telemetry import spans as tele
from bng_tpu.telemetry.recorder import (TRIG_EXPRESS_AOT_MISS,
                                        TRIG_EXPRESS_FALLBACK)
from bng_tpu.runtime import hostpath
from bng_tpu.runtime.engine import _ExpressAotResult, step_rung
from bng_tpu.runtime.lanes import (CLOSE_DEADLINE, CLOSE_FLUSH, CompletionRing,
                                   InflightEntry, Lane, LaneConfig, LANE_BULK,
                                   LANE_EXPRESS)
from bng_tpu.runtime.newflow import SECOND_PASS
from bng_tpu.runtime.ring import classify_dhcp
from bng_tpu.utils.net import prefix_to_mask
from bng_tpu.utils.structlog import get_logger


@dataclass
class SchedulerConfig:
    """Knobs for the two lanes + drain/replica cadences."""

    express_batch: int = 64
    express_max_wait_us: float = 200.0
    # depth-k pipelining on the fast lane: up to `express_depth` express
    # dispatches stay in flight inside one poll, so host-side retire
    # work (template patch-in, completions) overlaps device execution
    express_depth: int = 2
    # AOT express OFFER path (ISSUE 13): descriptors extracted at
    # admission, the minimal express program compiled ahead of time for
    # this lane's batch geometry, replies patched into preassembled
    # wire templates at retire. False = the jit full-program path
    # (also reachable via BNG_EXPRESS_AOT=0).
    express_aot: bool = True
    bulk_batch: int | None = None  # None = engine.B
    bulk_max_wait_us: float = 2000.0
    bulk_depth: int = 2  # completion-ring depth (>=2: never block per step)
    drain_every: int = 1  # bulk host-update drain cadence (1 = every step)
    # overlap-drain (VERDICT r5 item 3): build + upload the NEXT drain's
    # bounded scatter right after dispatching step N, so it overlaps with
    # step N's device execution instead of sitting on the batch-close ->
    # dispatch critical path of step N+1
    overlap_drain: bool = True
    dhcp_refresh_every: int = 16  # bulk dhcp-replica refresh cadence
    express_max_queue: int = 1 << 14
    bulk_max_queue: int = 1 << 16
    # express device isolation: None = auto (second attached device when
    # one exists, else share). An int pins jax.devices()[i]; -1 forces
    # same-device mode (single-chip: interleave-only isolation).
    express_device_index: int | None = None


class Completion(NamedTuple):
    """One frame's terminal outcome, delivered at retire time."""

    tag: object
    lane: str
    verdict: str  # "tx" | "fwd" | "drop" | "slow"
    frame: bytes | None  # device output (tx/fwd) or slow-path reply
    from_access: bool
    latency_s: float  # submit -> retire (queue wait + device + demux)


class TieredScheduler:
    """Owns the steady-state device loop over an Engine's two programs."""

    is_scheduler = True  # duck-type marker (loadtest harness routing)

    def __init__(self, engine, cfg: SchedulerConfig | None = None,
                 metrics=None, clock: Callable[[], float] | None = None):
        self.engine = engine
        self.cfg = cfg or SchedulerConfig()
        self.metrics = metrics
        self.clock = clock or engine.clock
        bulk_batch = self.cfg.bulk_batch or engine.B
        self.express = Lane(LaneConfig(
            LANE_EXPRESS, self.cfg.express_batch,
            self.cfg.express_max_wait_us, self.cfg.express_depth,
            self.cfg.express_max_queue), self.clock)
        self.bulk = Lane(LaneConfig(
            LANE_BULK, bulk_batch, self.cfg.bulk_max_wait_us,
            self.cfg.bulk_depth, self.cfg.bulk_max_queue), self.clock)
        self._express_ring = CompletionRing(self.cfg.express_depth)
        self._bulk_ring = CompletionRing(self.cfg.bulk_depth)
        self.completions: deque[Completion] = deque()
        self.completions_dropped = 0
        self.oversize_dropped = 0
        # always-on integers, no clock: express batches dispatched while
        # a bulk step was in flight on their device (their wait is the
        # `device_wait` lap's; no clean `device` sample exists), and bulk
        # retires that blocked because the completion ring overflowed
        self.express_behind_bulk = 0
        self.bulk_blocked_retires = 0
        self._seq = 0
        # bulk-lane dhcp read replica (lazy; refreshed on cadence/resync)
        self._bulk_dhcp = None
        self._replica_resync = -1
        self._bulk_seq = 0
        self._drains_applied = 0
        self._drains_prefetched = 0
        # overlap-drain: the update batch built for the NEXT drain-due
        # bulk step (engine.prefetch_bulk_updates). The scheduler owns
        # it — _flush_prefetched() is the no-more-traffic safety net.
        self._prefetched_upd = None
        self._replica_refreshes = 0
        self._express_dev = self._pick_express_device()
        self._bulk_dev = jax.devices()[0]
        # AOT express path: compile the minimal program for THIS lane's
        # fixed batch geometry at init (never on the dispatch path). A
        # compile failure downgrades to the jit-full path loudly and
        # permanently — every subsequent express dispatch counts as an
        # AOT miss, so a silent downgrade is impossible.
        self._log = get_logger("scheduler")
        self.express_aot_misses = 0
        self.express_aot_dispatches = 0
        self.express_jit_dispatches = 0
        self._aot_enabled = (self.cfg.express_aot
                             and os.environ.get("BNG_EXPRESS_AOT") != "0")
        # express rung-fallback accounting: reason -> count, folded into
        # bng_express_fallback_total by control/metrics.py. Populated by
        # _note_fallback and the dispatch-time geometry-miss path — any
        # express serving rung below the one configured shows up here.
        self.express_fallbacks: dict[str, int] = {}
        self.express_loop = "aot"  # read by benchmark/lib/app.py selectors()
        # _aot_ready gates the per-frame admission parse only: after a
        # permanent compile failure no executable will ever consume a
        # descriptor, so submit() must not keep paying parse_express on
        # the latency-critical path. Dispatch-side miss accounting keys
        # on _aot_enabled alone — the degraded state stays loud.
        self._aot_ready = False
        self._express_templates = ExpressTemplateCache()
        # host-path snapshot (ISSUE 14): vector = cycling descriptor
        # staging buffers (no per-dispatch np.zeros) + batched template
        # patch-in at the AOT express retire
        self._vec = hostpath.resolved_host_path() == "vector"
        # express_depth dispatches may be in flight plus one staging;
        # run_express_aot copies the staged rows to the device
        self._desc_bufs = [
            np.zeros((self.cfg.express_batch, XD_WORDS), dtype=np.uint32)
            for _ in range(self.cfg.express_depth + 2)]
        self._desc_i = 0
        self._ensure_engine_staging()
        if self._aot_enabled:
            self._compile_express_aot()

    def _ensure_engine_staging(self) -> None:
        """Declare this scheduler's worst-case in-flight dispatch count
        to the engine's frame staging pool (vector host path): both
        lanes stage through it, the depths are configurable, and
        express_batch == bulk_batch would even share one B-keyed buffer
        ring — the pool must cycle past every dispatch that could still
        be reading a staged buffer."""
        pool = getattr(self.engine, "_stage_pool", None)
        if pool is not None:
            pool.ensure_depth(self.cfg.express_depth
                              + self.cfg.bulk_depth + 2)

    def _compile_express_aot(self) -> None:
        # reset FIRST: an adopt-time recompile failure (new engine
        # geometry that refuses to lower) must drop readiness from the
        # previous engine's success, or submit() keeps paying the
        # per-frame descriptor parse for a program that no longer exists
        self._aot_ready = False
        try:
            self.engine.compile_express_aot(self.express.cfg.batch,
                                            self._express_dev)
            self._aot_ready = True
        except Exception as e:  # noqa: BLE001 — downgrade, never brick
            # count it and flight-record it, so a cluster serving every
            # OFFER through the jit-full rung is visible in metrics, not
            # just in one scrollback line
            self._note_fallback(
                "compile_failed",
                f"express AOT compile failed, jit-full will serve: "
                f"{type(e).__name__}: {e}")

    def _note_fallback(self, reason: str, detail: str) -> None:
        """One express rung-fallback event: counted (per reason, for
        bng_express_fallback_total), flight-recorded (evidence
        survives the process) and logged. Serving continues on the lower rung either way;
        this exists so it can never do so silently."""
        self.express_fallbacks[reason] = (
            self.express_fallbacks.get(reason, 0) + 1)
        tele.trigger(TRIG_EXPRESS_FALLBACK,
                     f"express fallback ({reason}): {detail}")
        self._log.warning("express fallback", reason=reason, detail=detail)

    def _pick_express_device(self):
        idx = self.cfg.express_device_index
        devs = jax.devices()
        if idx is None:
            return devs[1] if len(devs) > 1 else None
        if idx < 0:
            return None
        return devs[idx]

    # -- ingress ---------------------------------------------------------

    def classify(self, frame: bytes, from_access: bool) -> str:
        """DHCP discover/request from the access side -> express;
        everything else -> bulk (the ring classifier, bit-for-bit the
        dhcp_fastpath.c attach condition)."""
        if from_access and classify_dhcp(frame):
            return LANE_EXPRESS
        return LANE_BULK

    def submit(self, frame: bytes, from_access: bool = True,
               now: float | None = None, tag: object = None,
               lane: str | None = None) -> str | None:
        """Classify + enqueue one frame. Returns the lane name, or None
        when the frame is dropped (lane over its backpressure bound, or
        frame larger than the engine's packet slot). Callers that already
        classified (the ring stamps FLAG_DHCP_CTRL at rx_push) pass
        `lane` to skip the second Python header parse."""
        now = now if now is not None else self.clock()
        if tag is None:
            tag = self._seq
        self._seq += 1
        if len(frame) > self.engine.L:
            # rings admit frames up to their frame_size, which can exceed
            # the engine slot; _pack_frames refuses to truncate silently,
            # so the drop (counted) happens here, not as a dispatch crash
            self.oversize_dropped += 1
            return None
        lane_name = lane or self.classify(frame, from_access)
        if lane_name == LANE_EXPRESS:
            # admission→dispatch bypass (ISSUE 13): the express
            # descriptor (MAC/xid/vlan/cid lane columns) is extracted
            # exactly once, HERE — batch close stages descriptor rows
            # straight to the device with no second peek at the frame
            # bytes. None (AOT off / frame the device would PASS anyway)
            # rides along and retires through the slow path.
            desc = parse_express(frame) if self._aot_ready else None
            ok = self.express.push(frame, from_access, now, tag, desc=desc)
            return LANE_EXPRESS if ok else None
        return lane_name if self.bulk.push(frame, from_access, now, tag) else None

    # -- the beat --------------------------------------------------------

    def poll(self, now: float | None = None) -> int:
        """One scheduler beat: express strictly first (an express dispatch
        is never queued behind a bulk close waiting in THIS beat), then
        bulk ring management. Returns frames retired."""
        now = now if now is not None else self.clock()
        retired = 0
        retired += self._pump_express(now)
        retired += self._pump_bulk(now)
        return retired

    def flush(self, now: float | None = None) -> int:
        """Ship every queued frame (partial batches close immediately)
        and retire everything in flight — the shutdown/test barrier."""
        now = now if now is not None else self.clock()
        retired = 0
        while len(self.express):
            # let the close policy label full/aged batches honestly; only
            # the partial tail is a forced flush close (the close-reason
            # stats feed the bench JSON — they must stay meaningful in
            # the process() facade, which flushes every batch)
            reason = self.express.close_reason(now) or CLOSE_FLUSH
            pend, reason = self.express.close_batch(now, reason)
            retired += self._dispatch_express(pend, now, reason)
        retired += self._retire_express_all()
        while len(self.bulk):
            reason = self.bulk.close_reason(now) or CLOSE_FLUSH
            pend, reason = self.bulk.close_batch(now, reason)
            over = self._dispatch_bulk(pend, now, reason)
            if over is not None:
                retired += self._retire_bulk(over)
        for entry in self._bulk_ring.drain():
            retired += self._retire_bulk(entry)
        self._flush_prefetched()
        return retired

    close = flush  # CLI cleanup symmetry

    def _flush_prefetched(self) -> None:
        """Apply a prefetched drain no bulk batch consumed (traffic went
        quiet after the prefetch): its dirty slots are already drained
        host-side, so it MUST reach the device — a dropped batch would
        leave HBM stale behind healthy-looking host mirrors."""
        upd = self._prefetched_upd
        if upd is None:
            return
        self._prefetched_upd = None
        t0 = tele.t()
        self.engine.apply_updates_now(upd)
        tele.lap(tele.DRAIN, t0)
        self._drains_applied += 1

    def quiesce(self, now: float | None = None) -> int:
        """Checkpoint drain barrier: ship every queued frame, retire every
        in-flight dispatch on BOTH completion rings (flush), then block
        until the threaded device table state (express dhcp chain AND the
        bulk-threaded tables) has materialized. After quiesce() returns,
        no table scatter is in flight and no pending FastPathUpdates wait
        in a dispatched-but-unretired step — a snapshot taken now can
        fetch the HBM arrays without interleaving with an update. The
        lanes stay usable; traffic resumes on the next submit/poll."""
        retired = self.flush(now)
        jax.block_until_ready(jax.tree_util.tree_leaves(self.engine.tables))
        return retired

    def adopt_engine(self, engine) -> int:
        """Blue/green flip (runtime/ops.py): retire everything in flight
        against the OLD engine's programs, then atomically re-point both
        lanes at the standby. The bulk dhcp replica is invalidated — it
        derives from the old authoritative chain — and rebuilds from the
        new engine's leaves on the next bulk dispatch. Returns frames
        retired by the drain (the batches-deferred cost of the flip)."""
        retired = self.flush()
        self.engine = engine
        self._bulk_dhcp = None
        self._replica_resync = -1
        self._ensure_engine_staging()  # the standby's pool starts at
        # the construction default; re-declare this scheduler's depths
        if self._aot_enabled:
            # the standby's geometry usually matches (cache hit); a
            # changed geometry compiles here, at the flip, not on the
            # first post-flip dispatch
            self._compile_express_aot()
        return retired

    # -- express lane ----------------------------------------------------

    def _pump_express(self, now: float) -> int:
        retired = 0
        while True:
            reason = self.express.close_reason(now)
            if (reason is None and len(self.express)
                    and self.bulk.close_reason(now) is not None):
                # a bulk close is waiting in THIS beat, and its pack,
                # dispatch and drain take some 14 ms of this thread: the
                # express frames that came in with it would pass their
                # deadline seventy times over before the next look, and
                # then ship behind that very step (PERF.md section 6,
                # PR 33: `lane_wait` p95 15.6 ms against a 200 us deadline)
                reason = CLOSE_DEADLINE
            if reason is None:
                break
            pend, reason = self.express.close_batch(now, reason)
            retired += self._dispatch_express(pend, now, reason)
        return retired + self._retire_express_all()

    def _dispatch_express(self, pend, now: float, reason: str) -> int:
        """Dispatch one closed express batch; returns frames retired
        as a side effect of the completion ring overflowing its depth.

        AOT path: descriptor rows (staged at admission) go straight to
        the compiled minimal program. A geometry miss — the compiled
        executable for this batch shape is absent (compile failed, lane
        geometry changed under a live scheduler) — falls back to the
        jit-full `_dhcp_jit` path, counts `bng_express_aot_miss_total`
        (+ the bng_express_fallback_total family) and drops a
        flight-recorder note: a fallback storm can never masquerade as
        a healthy express hit."""
        if not pend:
            return 0
        eng = self.engine
        tok = tele.begin_batch(tele.LANE_EXPRESS_L, len(pend))
        if tok is not None:
            # lane wait of the batch's OLDEST frame — the worst case the
            # deadline close bounds (computed from enqueue stamps, so the
            # per-frame submit path pays no telemetry cost at all)
            tele.observe(tele.LANE_WAIT, (now - pend[0].enq_t) * 1e6, tok)
        exe = None
        if self._aot_enabled:
            # _aot_ready gate: pending frames carry descriptors only
            # when the init-time compile succeeded — an executable from
            # the shared cache must not serve descriptor-less frames
            exe = (eng.express_aot(self.express.cfg.batch,
                                   self._express_dev)
                   if self._aot_ready else None)
            if exe is None:
                self.express_aot_misses += 1
                # counted into the rung-fallback family too (no extra
                # log line — a miss storm already triggers per batch)
                self.express_fallbacks["geometry_miss"] = (
                    self.express_fallbacks.get("geometry_miss", 0) + 1)
                tele.trigger(TRIG_EXPRESS_AOT_MISS,
                             f"no compiled express program for batch="
                             f"{self.express.cfg.batch}: jit-full "
                             f"fallback served")
        t0 = tele.t()
        cfg_epoch = None
        try:
            if exe is not None:
                # descriptor rows staged into a cycling preallocated
                # buffer (run_express_aot copies host->device, so the
                # buffer is free to rewrite after depth+1 dispatches);
                # the fill is ONE stacked numpy assignment, not a
                # per-frame copy loop
                tp = tele.t()
                desc = self._desc_bufs[self._desc_i]
                self._desc_i = (self._desc_i + 1) % len(self._desc_bufs)
                desc[:] = 0
                rows = [p.desc.words for p in pend if p.desc is not None]
                if rows:
                    idxs = [i for i, p in enumerate(pend)
                            if p.desc is not None]
                    desc[idxs] = rows
                tele.lap(tele.PACK, tp, tok)
                res = eng.run_express_aot(exe, desc, now,
                                          device=self._express_dev)
                # snapshot the pool/server config of THIS dispatch's
                # table epoch: the retire (one poll later at depth>1)
                # must render from the rows the device verdict saw, not
                # from mirrors a control-plane write may have moved on
                cfg_epoch = (eng.fastpath.pools.copy(),
                             eng.fastpath.server.copy())
                self.express_aot_dispatches += 1
                tele.set_meta("express_program", "aot-express")
            else:
                tp = tele.t()
                pkt, length = eng._pack_frames([p.frame for p in pend],
                                               self.express.cfg.batch)
                tele.lap(tele.PACK, tp, tok)
                res = eng._run_dhcp_batch(pkt, length, now,
                                          device=self._express_dev)
                self.express_jit_dispatches += 1
                tele.set_meta("express_program", "jit-full")
        except BaseException:
            tele.cancel_batch(tok)  # a failed dispatch must not leak a slot
            raise
        tele.lap(tele.DISPATCH, t0, tok)
        shares = self._express_dev in (None, self._bulk_dev)
        behind = shares and len(self._bulk_ring) > 0
        if behind:
            self.express_behind_bulk += 1
        tele.device_up(tok, 0 if shares else 1, sample=not behind)
        self._observe_dispatch(LANE_EXPRESS, len(pend), reason)
        over = self._express_ring.push(
            InflightEntry(res, pend, now, reason, trace=tok,
                          meta=cfg_epoch))
        return self._retire_express(over) if over is not None else 0

    def _retire_express_all(self) -> int:
        n = 0
        while True:
            entry = self._express_ring.pop_oldest()
            if entry is None:
                return n
            n += self._retire_express(entry)

    def _retire_express(self, entry: InflightEntry) -> int:
        """Force + demux one express batch (TX replies / PASS to the slow
        path). Blocks only on the express program's own outputs."""
        if isinstance(entry.res, _ExpressAotResult):
            return self._retire_express_aot(entry)
        eng = self.engine
        res = entry.res
        n = len(entry.pending)
        tele.focus(entry.trace)
        t0 = tele.t()
        # armed: the wait apart from the reads (spans.py ready)
        tf = tele.ready(res.verdict, entry.trace)
        verdict = np.asarray(res.verdict)[:n]
        out_len = np.asarray(res.out_len)
        tele.fetched(tf, res.verdict, res.out_len)
        tele.lap(tele.DEVICE_WAIT, t0, entry.trace)
        out_rows = None
        eng._fold_stats(res)
        now = self.clock()
        # batched slow-path fan-out (the fleet hook): collect every
        # PASS lane, drain once, replies re-merged in lane order — the
        # per-frame enqueue time rides along for deadline shedding
        slow_items = [(i, p.frame, p.enq_t)
                      for i, p in enumerate(entry.pending)
                      if verdict[i] != VERDICT_TX]
        replies = dict(eng._handle_slow_lanes(slow_items,
                                              path="sched_express"))
        t0 = tele.t()
        for i, p in enumerate(entry.pending):
            if verdict[i] == VERDICT_TX:
                if out_rows is None:
                    out_rows = self._fetch_rows(res.out_pkt)
                frame = bytes(out_rows[i, : int(out_len[i])])
                eng.stats.tx += 1
                self._complete(p, LANE_EXPRESS, "tx", frame, now)
            else:
                eng.stats.passed += 1
                self._complete(p, LANE_EXPRESS, "slow", replies.get(i), now)
        tele.lap(tele.REPLY, t0, entry.trace)
        self._trace_sojourn(entry, tele.LANE_EXPRESS_L)
        tele.end_batch(entry.trace)
        self._observe_retire(LANE_EXPRESS, entry, now)
        return n

    def _retire_express_aot(self, entry: InflightEntry) -> int:
        """Retire one AOT express batch: force the verdict block, patch
        on-device answers into preassembled wire templates
        (control/dhcp_codec.ExpressWireTemplate — UNCONDITIONALLY; the
        express retire path never re-enters the generic per-option
        reply encode), hand the rest to the slow path."""
        eng = self.engine
        n = len(entry.pending)
        tele.focus(entry.trace)
        t0 = tele.t()
        tf = tele.ready(entry.res.block, entry.trace)
        block = np.asarray(entry.res.block)[:n]
        tele.fetched(tf, entry.res.block)
        tele.lap(tele.DEVICE_WAIT, t0, entry.trace)
        eng._fold_stats(entry.res)
        now = self.clock()
        slow_items = [(i, p.frame, p.enq_t)
                      for i, p in enumerate(entry.pending)
                      if not block[i, VB_VERDICT]]
        replies = dict(eng._handle_slow_lanes(slow_items,
                                              path="sched_express"))
        t0 = tele.t()
        pools, server = entry.meta  # the dispatch-epoch config snapshot
        txr = (self._express_replies_vec(entry.pending, block, pools,
                                         server) if self._vec else None)
        for i, p in enumerate(entry.pending):
            if block[i, VB_VERDICT]:
                eng.stats.tx += 1
                self._complete(p, LANE_EXPRESS, "tx",
                               txr[i] if txr is not None else
                               self._express_reply(p, block[i], pools,
                                                   server), now)
            else:
                eng.stats.passed += 1
                self._complete(p, LANE_EXPRESS, "slow", replies.get(i), now)
        tele.lap(tele.REPLY, t0, entry.trace)
        self._trace_sojourn(entry, tele.LANE_EXPRESS_L)
        tele.end_batch(entry.trace)
        self._observe_retire(LANE_EXPRESS, entry, now)
        return n

    def _express_replies_vec(self, pend, block: np.ndarray,
                             pools: np.ndarray,
                             server: np.ndarray) -> dict:
        """Batched express reply render (ISSUE 14): TX lanes grouped by
        (template, addressing) identity — one storm batch is typically
        ONE group — then each group's per-client words are patched in a
        single vectorized pass (ExpressWireTemplate.render_batch,
        byte-identical to the per-frame render). Returns lane->bytes."""
        server_ip0 = int(server[SC_IP])
        server_mac = (int(server[SC_MAC_HI]).to_bytes(2, "big")
                      + int(server[SC_MAC_LO]).to_bytes(4, "big"))
        groups: dict[tuple, list] = {}
        for i, p in enumerate(pend):
            if block[i, VB_VERDICT]:
                d = p.desc
                groups.setdefault(
                    (int(block[i, VB_POOL]), int(block[i, VB_LEASE_T]),
                     d.msg_type, d.vlan_off, d.dhcp_off, d.relayed,
                     d.use_bcast), []).append(i)
        out: dict[int, bytes] = {}
        for key, lanes in groups.items():
            (pool_id, lease_t, msg, vlan_off, dhcp_off, relayed,
             use_bcast) = key
            prow = pools[pool_id]
            tmpl = self._express_templates.get(
                server_mac, server_ip0 or int(prow[PV_GATEWAY]),
                int(prow[PV_GATEWAY]), int(prow[PV_DNS1]),
                int(prow[PV_DNS2]), lease_t,
                prefix_to_mask(int(prow[PV_PREFIX])),
                OFFER if msg == DISCOVER else ACK)
            fmat, _l = hostpath.pack_rows([pend[i].frame for i in lanes])
            reps = tmpl.render_batch(
                fmat, vlan_off, dhcp_off, relayed, use_bcast,
                block[np.asarray(lanes, dtype=np.int64), VB_YIADDR])
            out.update(zip(lanes, reps))
        return out

    def _express_reply(self, p, row: np.ndarray, pools: np.ndarray,
                       server: np.ndarray) -> bytes:
        """One verdict row -> reply bytes: select the per-(pool, reply
        type) wire template and patch the per-client words. Pool/server
        config comes from the DISPATCH-EPOCH snapshot (the device
        pools/server arrays were refreshed from exactly those rows at
        dispatch; reading the live mirrors here could mix a newer
        config into a verdict computed against the old one); the lease
        words come from the DEVICE-reported block, so the rendered
        lease triplet always reflects the serving table."""
        prow = pools[int(row[VB_POOL])]
        server_ip = int(server[SC_IP]) or int(prow[PV_GATEWAY])
        server_mac = (int(server[SC_MAC_HI]).to_bytes(2, "big")
                      + int(server[SC_MAC_LO]).to_bytes(4, "big"))
        d = p.desc
        tmpl = self._express_templates.get(
            server_mac, server_ip, int(prow[PV_GATEWAY]),
            int(prow[PV_DNS1]), int(prow[PV_DNS2]), int(row[VB_LEASE_T]),
            prefix_to_mask(int(prow[PV_PREFIX])),
            OFFER if d.msg_type == DISCOVER else ACK)
        return tmpl.render(p.frame, d.vlan_off, d.dhcp_off, d.relayed,
                           d.use_bcast, int(row[VB_YIADDR]))

    # -- bulk lane -------------------------------------------------------

    def _pump_bulk(self, now: float) -> int:
        retired = 0
        # opportunistic: retire the already-finished FIFO prefix
        ready = self._bulk_ring.pop_ready(self._entry_ready)
        for i, entry in enumerate(ready):
            # first seen ready HERE; a second entry ready at the same look
            # finished at a time nobody saw: no `device` sample for it
            tele.device_down(entry.trace, clean=i == 0)
        for entry in ready:
            retired += self._retire_bulk(entry)
        while True:
            reason = self.bulk.close_reason(now)
            if reason is None:
                break
            pend, reason = self.bulk.close_batch(now, reason)
            over = self._dispatch_bulk(pend, now, reason)
            if over is not None:
                # the completion ring overflowed its depth: the single
                # place the bulk lane blocks on device results
                self.bulk_blocked_retires += 1
                retired += self._retire_bulk(over)
        return retired

    @staticmethod
    def _entry_ready(entry: InflightEntry) -> bool:
        is_ready = getattr(entry.res.verdict, "is_ready", None)
        return bool(is_ready()) if is_ready is not None else False

    def _ensure_bulk_replica(self) -> None:
        eng = self.engine
        refresh_due = (self.cfg.dhcp_refresh_every > 0
                       and self._bulk_seq % self.cfg.dhcp_refresh_every == 0)
        if (self._bulk_dhcp is not None and not refresh_due
                and self._replica_resync == eng.resync_count):
            return
        t0 = tele.t()
        self._bulk_dhcp = eng.dhcp_replica(self._copy_to_bulk)
        tele.lap(tele.DRAIN, t0)
        self._replica_resync = eng.resync_count
        self._replica_refreshes += 1

    def build_bulk_rungs(self) -> None:
        """Start-up's: every rung of the fused step's ladder a bulk batch
        can take, built and run once over the replica before the lane
        takes frames, and the bulk tables' packet-free apply program
        (Engine.build_step_rungs)."""
        if self._express_dev is not None:
            # the express lane's placement first (a resync undoes it): the
            # replica is then the copy across devices every refresh makes,
            # and the rungs are built for the tables a step finds later
            self.engine._place_dhcp_chain(self._express_dev)
        # the first dispatch's refresh replaces (and counts) this replica
        self._bulk_dhcp = self.engine.dhcp_replica(self._copy_to_bulk)
        self._replica_resync = self.engine.resync_count
        batch = self.bulk.cfg.batch
        # with the packet-free program a dirty or a prefetched drain takes
        # (Engine.apply_updates_now; _flush_prefetched when the traffic
        # goes quiet behind a prefetch)
        self._bulk_dhcp = self.engine.build_step_rungs(
            batch, batch=batch, dhcp=self._bulk_dhcp)

    def _copy_to_bulk(self, x):
        """A buffer the bulk chain may freely donate: device transfer when
        the authority lives elsewhere, a fresh same-device copy otherwise
        (device_put to the same device can alias, and donating an aliased
        buffer would consume the express chain's live tables)."""
        if self._bulk_dev not in x.devices():
            return jax.device_put(x, self._bulk_dev)
        # placed like the copy across devices: the chain an express program
        # returns is committed and a fresh upload is not, and a replica
        # that changed with them would have the fused step built again for
        # each (one committed input commits every output of a step)
        return jax.device_put(jnp.copy(x), self._bulk_dev)

    def _dispatch_bulk(self, pend, now: float,
                       reason: str) -> InflightEntry | None:
        """Dispatch one bulk batch (async); returns the completion-ring
        overflow entry the caller must retire, if any."""
        if not pend:
            return None
        eng = self.engine
        tok = tele.begin_batch(tele.LANE_BULK_L, len(pend))
        if tok is not None:
            tele.observe(tele.LANE_WAIT, (now - pend[0].enq_t) * 1e6, tok)
        # the rung that holds the batch, not the configured batch: the
        # step costs the device by the lanes it is traced at
        B = step_rung(len(pend), self.bulk.cfg.batch)
        tele.step_lanes(B)
        t0 = tele.t()
        pkt, length = eng._pack_frames([p.frame for p in pend], B)
        fa = np.zeros((B,), dtype=bool)
        fa[: len(pend)] = [p.from_access for p in pend]
        tele.lap(tele.PACK, t0, tok)
        t0 = tele.t()
        try:
            self._ensure_bulk_replica()
            # a pending prefetched drain is consumed the moment a bulk
            # step ships, whatever the cadence says — stranding it would
            # desync host mirrors from HBM (its dirty slots are already
            # drained host-side)
            upd = self._prefetched_upd
            self._prefetched_upd = None
            drain = (upd is not None
                     or self.cfg.drain_every <= 1
                     or self._bulk_seq % self.cfg.drain_every == 0)
            if any(p.desc is SECOND_PASS for p in pend):
                # the first packet of a flow the host has just admitted:
                # its session was written after any prefetched drain was
                # built, so that batch goes first and a fresh drain
                # follows, whatever the cadence says
                eng.apply_updates_now(upd)
                upd, drain = None, True
            before = eng.resync_count
            try:
                res, self._bulk_dhcp = eng.dispatch_scheduled_bulk(
                    pkt, length, fa, now, self._bulk_dhcp, drain=drain,
                    upd=upd)
            except BaseException:
                # the batch is lost but the prefetched drain must not be:
                # its dirty slots are already drained host-side, so it
                # re-queues for the next dispatch (or _flush_prefetched)
                self._prefetched_upd = upd
                raise
        except BaseException:
            tele.cancel_batch(tok)  # a failed dispatch must not leak a slot
            raise
        tele.lap(tele.DISPATCH, t0, tok)
        tele.device_up(tok)
        if eng.resync_count != before:
            # a bulk-build resync fired inside the drain: the replica we
            # just threaded derives from pre-resync leaves; rebuild next
            # dispatch (this step's results stay valid)
            self._replica_resync = -1
        self._bulk_seq += 1
        if drain:
            self._drains_applied += 1
        if (self.cfg.overlap_drain
                and (self.cfg.drain_every <= 1
                     or self._bulk_seq % self.cfg.drain_every == 0)):
            # step N is on the device; build + start uploading step N+1's
            # bounded scatter NOW so the next dispatch pays no drain cost
            t0 = tele.t()
            self._prefetched_upd = eng.prefetch_bulk_updates()
            tele.lap(tele.DRAIN, t0, tok)
            self._drains_prefetched += 1
        self._observe_dispatch(LANE_BULK, len(pend), reason)
        return self._bulk_ring.push(
            InflightEntry(res, pend, now, reason, trace=tok))

    def _retire_bulk(self, entry: InflightEntry) -> int:
        """Force + demux one bulk batch's verdicts (the completion-ring
        block point)."""
        eng = self.engine
        res = entry.res
        n = len(entry.pending)
        tele.focus(entry.trace)
        t0 = tele.t()
        # armed: the wait apart from the reads (spans.py ready)
        tf = tele.ready(res.verdict, entry.trace)
        vv = np.asarray(res.verdict)[:n]
        out_len = np.asarray(res.out_len)
        punt = np.asarray(res.nat_punt)[:n]
        viol = np.asarray(res.spoof_violation)[:n]
        mir = (getattr(res, "mirror", None)
               if eng.mirror_sink is not None else None)
        mirw = np.asarray(mir)[:n] if mir is not None else None
        tele.fetched(tf, res.verdict, res.out_len, res.nat_punt,
                     res.spoof_violation, mir)
        tele.lap(tele.DEVICE_WAIT, t0, entry.trace)
        out_rows = None
        eng._fold_stats(res)
        now = self.clock()
        # NAT punts stay inline (parent-owned manager); everything else
        # drains through the batched slow path in one fan-out
        slow_items = []
        # the punted lanes, served in one batch after the walk: their
        # indices, and a hold each that keeps the lane's frame for a second
        # pass in `again` (lane -> its frame: the lane's own queue holds it,
        # at its head, below, so this loop has no other). Built at the
        # first punt: a retire without one allocates nothing for it
        again: dict = None
        for i, p in enumerate(entry.pending):
            if (int(vv[i]) in (VERDICT_TX, VERDICT_FWD, VERDICT_DROP)
                    or p.desc is SECOND_PASS):
                continue
            if punt[i]:
                if again is None:
                    again, lanes, holds = {}, [], []
                lanes.append(i)
                holds.append(functools.partial(self._hold_for_second_pass,
                                               again, i, p))
            else:
                slow_items.append((i, p.frame, p.enq_t))
        punts = 0
        if again is not None:
            punts = len(lanes)
            eng.newflows.punt_many(
                [entry.pending[i].frame for i in lanes], [0] * punts,
                int(entry.dispatch_t), eng.pppoe is not None,
                on_error=eng.punt_reporter("sched_bulk", lanes), hold=holds)
        if again:
            self.bulk.requeue_front(list(again.values()))
        replies = dict(eng._handle_slow_lanes(slow_items, path="sched_bulk"))
        t0 = tele.t()
        for i, p in enumerate(entry.pending):
            v = int(vv[i])
            if p.desc is SECOND_PASS and not eng.newflows.second_pass(v):
                v = VERDICT_DROP  # punted again, or lost: a counted drop
            if v == VERDICT_TX or v == VERDICT_FWD:
                if out_rows is None:
                    out_rows = self._fetch_rows(res.out_pkt)
                frame = bytes(out_rows[i, : int(out_len[i])])
                kind = "tx" if v == VERDICT_TX else "fwd"
                if v == VERDICT_TX:
                    eng.stats.tx += 1
                else:
                    eng.stats.fwd += 1
                self._complete(p, LANE_BULK, kind, frame, now)
            elif v == VERDICT_DROP:
                eng.stats.dropped += 1
                self._complete(p, LANE_BULK, "drop", None, now)
            else:
                eng.stats.passed += 1
                if again and i in again:
                    continue  # not done: it completes on its second pass
                if punt[i]:  # a refused flow's frame: a counted drop
                    eng.stats.dropped += 1
                    self._complete(p, LANE_BULK, "drop", None, now)
                else:
                    self._complete(p, LANE_BULK, "slow", replies.get(i), now)
            if viol[i] and eng.violation_sink is not None:
                eng.violation_sink(i, p.frame)
        if mirw is not None:
            tm = tele.t()
            for i in np.nonzero(mirw)[0]:
                # the frame as it was submitted, whatever its verdict
                eng.mirror_sink(int(i), entry.pending[i].frame,
                                int(mirw[i]))
            tele.lap(tele.MIRROR, tm, entry.trace)
        tele.lap(tele.REPLY, t0, entry.trace)
        self._trace_sojourn(entry, tele.LANE_BULK_L)
        tele.end_batch(entry.trace, punt=punts)
        self._observe_retire(LANE_BULK, entry, now)
        return n

    def _hold_for_second_pass(self, again: dict, i: int, p, _frame=None,
                              _flags=None) -> bool:
        """Keep lane `i`'s frame of a retiring batch for the head of the
        bulk lane, while the lane has room for it (one of
        `NewFlows.punt_many`'s `hold`: it passes the frame and its flags,
        which `p` holds)."""
        if len(self.bulk) + len(again) >= self.bulk.cfg.max_queue:
            return False
        again[i] = p._replace(desc=SECOND_PASS)
        return True

    @staticmethod
    def _fetch_rows(out_pkt) -> np.ndarray:
        """A retired batch's packet slots to the host, whole (the rung's
        `[b, L]`), at the first lane that needs its bytes: one `fetch`
        lap inside `reply`."""
        t0 = tele.t()
        rows = np.asarray(out_pkt)
        tele.fetched(t0, out_pkt)
        return rows

    # -- completion delivery / observability -----------------------------

    _COMPLETIONS_CAP = 1 << 17

    def _complete(self, p, lane: str, verdict: str, frame, now: float) -> None:
        if len(self.completions) >= self._COMPLETIONS_CAP:
            self.completions.popleft()
            self.completions_dropped += 1
        self.completions.append(Completion(
            p.tag, lane, verdict, frame, p.from_access, now - p.enq_t))

    def _trace_sojourn(self, entry: InflightEntry, lane: int) -> None:
        """Armed only: every frame's enqueue -> completion, once a
        retired batch (the `sojourn` stage of its lane)."""
        if tele.enabled():
            done = self.clock()
            tele.observe_many(tele.SOJOURN,
                              [(done - p.enq_t) * 1e6 for p in entry.pending],
                              entry.trace, lane=lane)

    def drain_completions(self) -> list[Completion]:
        out = list(self.completions)
        self.completions.clear()
        return out

    def _observe_dispatch(self, lane: str, n: int, reason: str) -> None:
        m = self.metrics
        if m is None:
            return
        batch = (self.express if lane == LANE_EXPRESS else self.bulk).cfg.batch
        m.sched_dispatches.inc(lane=lane, close=reason)
        m.sched_batch_occupancy.observe(n / batch, lane=lane)

    def _observe_retire(self, lane: str, entry: InflightEntry,
                        now: float) -> None:
        m = self.metrics
        if m is None:
            return
        # oldest frame of the batch = the batch's worst-case latency
        if entry.pending:
            m.sched_dispatch_latency.observe(now - entry.pending[0].enq_t,
                                             lane=lane)
        m.sched_frames.inc(len(entry.pending), lane=lane)

    def stats_snapshot(self) -> dict:
        """Poll-style counters for metrics collection / bench JSON."""
        out = {}
        for name, lane, ring in ((LANE_EXPRESS, self.express, self._express_ring),
                                 (LANE_BULK, self.bulk, self._bulk_ring)):
            s = lane.stats
            out[name] = {
                "queue_depth": len(lane),
                "inflight": len(ring),
                "enqueued": s.enqueued,
                "dropped_overflow": s.dropped_overflow,
                "frames_dispatched": s.frames_dispatched,
                "batches": s.batches,
                "batches_full": s.batches_full,
                "batches_deadline": s.batches_deadline,
                "batches_flush": s.batches_flush,
                "occupancy_avg": round(s.occupancy_avg(), 4),
            }
        out["bulk"]["drains_applied"] = self._drains_applied
        out["bulk"]["drains_prefetched"] = self._drains_prefetched
        out["bulk"]["replica_refreshes"] = self._replica_refreshes
        out["express"]["own_device"] = (str(self._express_dev)
                                        if self._express_dev is not None
                                        else None)
        out["express"]["aot_enabled"] = self._aot_enabled
        out["express"]["aot_dispatches"] = self.express_aot_dispatches
        out["express"]["jit_dispatches"] = self.express_jit_dispatches
        out["express"]["aot_misses"] = self.express_aot_misses
        out["express"]["fallbacks"] = dict(self.express_fallbacks)
        out["express"]["behind_bulk"] = self.express_behind_bulk
        out["bulk"]["blocked_retires"] = self.bulk_blocked_retires
        # the Tracer's tiling and device-occupancy sums (armed, or as the
        # last disarm left them; zeros if never armed)
        out["trace"] = tele.trace_sums()
        out["completions_dropped"] = self.completions_dropped
        out["oversize_dropped"] = self.oversize_dropped
        return out

    # -- batch-synchronous facade (loadtest harness / tests) -------------

    # Engine.process-shaped surface so DHCPBenchmark can drive the
    # scheduler unmodified (it reads .stats/.fastpath for counters).
    @property
    def stats(self):
        return self.engine.stats

    @property
    def fastpath(self):
        return self.engine.fastpath

    def process(self, frames: list[bytes],
                from_access: list[bool] | bool = True,
                now: float | None = None) -> dict:
        """Submit a frame list, flush, and return Engine.process-shaped
        verdict lists keyed by submission index. The express/bulk split
        still applies inside — a mixed batch fans out to both programs."""
        out = {"tx": [], "fwd": [], "dropped": [], "slow": []}
        start = self._seq
        for i, f in enumerate(frames):
            fa = from_access if isinstance(from_access, bool) else from_access[i]
            if self.submit(f, fa, now=now) is None:
                out["dropped"].append(i)
        self.flush(now=now)
        for c in self.drain_completions():
            if not isinstance(c.tag, int) or c.tag < start:
                continue  # a stray completion from earlier poll-mode use
            i = c.tag - start
            if c.verdict in ("tx", "fwd"):
                out[c.verdict].append((i, c.frame))
            elif c.verdict == "drop":
                out["dropped"].append(i)
            else:
                out["slow"].append((i, c.frame))
        for k in ("tx", "fwd", "slow"):
            out[k].sort(key=lambda t: t[0])
        out["dropped"].sort()
        return out
