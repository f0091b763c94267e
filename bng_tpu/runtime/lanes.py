"""Latency-class lanes for the tiered dataplane scheduler.

A lane is a host-side staging queue with a batch-close policy and a
bounded completion ring — the building blocks runtime/scheduler.py
composes into the express (DHCP) / bulk (fused pipeline) split. The
shape is Orca-style iteration-level scheduling re-hosted: instead of one
monolithic fused step where an OFFER waits behind a 512-frame NAT+QoS
batch, each latency class closes and dispatches batches on its own
terms:

- CLOSE_FULL: the batch reached the lane's device batch size.
- CLOSE_DEADLINE: the oldest queued frame aged past max_wait_us — a
  partial batch ships rather than letting the tail latency grow while
  the queue fills (continuous-batching deadline close).

The completion ring bounds device-side pipelining: dispatches enter as
futures; push() hands back the overflow entry the caller must retire
(block on) — `block_until_ready` happens only there, never per step.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, NamedTuple

LANE_EXPRESS = "express"
LANE_BULK = "bulk"

CLOSE_FULL = "full"
CLOSE_DEADLINE = "deadline"
CLOSE_FLUSH = "flush"


@dataclass
class LaneConfig:
    name: str
    batch: int  # lanes per device dispatch (compile shape)
    max_wait_us: float  # oldest-frame age that forces a partial close
    depth: int  # max in-flight dispatches (completion ring size)
    max_queue: int = 1 << 16  # backpressure bound; beyond it push() drops


class PendingFrame(NamedTuple):
    frame: bytes
    from_access: bool
    enq_t: float  # lane clock at submit (dispatch-latency origin)
    tag: object  # caller correlation token (e.g. submission index)
    # express descriptor (ops/express.ExpressDesc), extracted ONCE at
    # admission — the AOT express dispatch consumes these columns
    # directly instead of re-peeking the frame bytes at batch close.
    # None on the bulk lane and on the jit-full express path.
    desc: object = None


@dataclass
class LaneStats:
    enqueued: int = 0
    dropped_overflow: int = 0
    frames_dispatched: int = 0
    batches: int = 0
    batches_full: int = 0
    batches_deadline: int = 0
    batches_flush: int = 0
    occupancy_sum: float = 0.0  # sum of n/batch over dispatches

    def occupancy_avg(self) -> float:
        return self.occupancy_sum / self.batches if self.batches else 0.0


class Lane:
    """One latency class: staging queue + close policy + counters."""

    def __init__(self, cfg: LaneConfig, clock: Callable[[], float] = time.time):
        self.cfg = cfg
        self.clock = clock
        self.q: deque[PendingFrame] = deque()
        self.stats = LaneStats()

    def __len__(self) -> int:
        return len(self.q)

    def push(self, frame: bytes, from_access: bool, now: float | None = None,
             tag: object = None, desc: object = None) -> bool:
        """Queue a frame; False = lane over max_queue (frame dropped —
        the caller counts it as backpressure, like an RX ring overflow)."""
        if len(self.q) >= self.cfg.max_queue:
            self.stats.dropped_overflow += 1
            return False
        now = now if now is not None else self.clock()
        self.q.append(PendingFrame(frame, from_access, now, tag, desc))
        self.stats.enqueued += 1
        return True

    def requeue_front(self, frames: list[PendingFrame]) -> None:
        """Frames this lane dispatched once, back at its head in their
        order for a second pass (the first packets of NAT flows the host
        has just admitted, runtime/newflow.py). They keep their enqueue
        time, so the oldest-age close fires for them at once; not counted
        as enqueued again."""
        self.q.extendleft(reversed(frames))

    def oldest_age_us(self, now: float) -> float:
        return (now - self.q[0].enq_t) * 1e6 if self.q else 0.0

    def close_reason(self, now: float) -> str | None:
        """Why a batch should close right now (None = keep filling)."""
        if len(self.q) >= self.cfg.batch:
            return CLOSE_FULL
        if self.q and self.oldest_age_us(now) >= self.cfg.max_wait_us:
            return CLOSE_DEADLINE
        return None

    def close_batch(self, now: float,
                    reason: str | None = None) -> tuple[list[PendingFrame], str]:
        """Pop up to `batch` frames and account the close. With no
        explicit reason the close policy decides; callers flushing pass
        CLOSE_FLUSH to ship a partial batch regardless of deadline."""
        reason = reason or self.close_reason(now)
        if reason is None or not self.q:
            return [], reason or CLOSE_FLUSH
        n = min(len(self.q), self.cfg.batch)
        out = [self.q.popleft() for _ in range(n)]
        st = self.stats
        st.batches += 1
        st.frames_dispatched += n
        st.occupancy_sum += n / self.cfg.batch
        if reason == CLOSE_FULL:
            st.batches_full += 1
        elif reason == CLOSE_DEADLINE:
            st.batches_deadline += 1
        else:
            st.batches_flush += 1
        return out, reason


@dataclass
class InflightEntry:
    """One dispatched-but-unretired device batch."""

    res: object  # device result (futures)
    pending: list[PendingFrame]
    dispatch_t: float
    close_reason: str
    trace: object = None  # telemetry batch-record token (None = disarmed)
    # dispatch-epoch snapshot the retire path must read instead of the
    # live host mirrors (the AOT express retire renders replies from
    # pool/server config that must match the table epoch the device
    # verdict was computed against — a config rewrite between dispatch
    # and retire would otherwise produce a mixed-epoch reply)
    meta: object = None


class CompletionRing:
    """Bounded in-flight window (depth-N async pipelining).

    push() returns the entry that OVERFLOWED the ring — the single point
    where the scheduler is allowed to block on device results. pop_ready
    lets callers retire early finishers opportunistically without
    blocking (jax.Array.is_ready probes)."""

    def __init__(self, depth: int):
        self.depth = max(1, depth)
        self._ring: deque[InflightEntry] = deque()

    def __len__(self) -> int:
        return len(self._ring)

    def push(self, entry: InflightEntry) -> InflightEntry | None:
        self._ring.append(entry)
        if len(self._ring) > self.depth:
            return self._ring.popleft()
        return None

    def pop_oldest(self) -> InflightEntry | None:
        return self._ring.popleft() if self._ring else None

    def pop_ready(self, is_ready: Callable[[InflightEntry], bool]
                  ) -> list[InflightEntry]:
        """Retire the FIFO prefix whose device results are already done
        (retire order stays dispatch order — lane-level FIFO semantics)."""
        out = []
        while self._ring and is_ready(self._ring[0]):
            out.append(self._ring.popleft())
        return out

    def drain(self) -> list[InflightEntry]:
        out = list(self._ring)
        self._ring.clear()
        return out
