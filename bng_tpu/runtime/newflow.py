"""What becomes of the first packet of a new NAT flow.

The device finds no session for an upstream frame and passes it up with
`nat_punt` set. The source translates that same packet and lets it go
(bpf/nat44.c:686-801: session miss -> EIM mapping -> port from the
subscriber's block -> session and reverse entry -> SNAT rewrite of the
packet in hand, TC_ACT_OK; no block or a full one: TC_ACT_SHOT,
:698-705). Here the host creates the session (`NATManager.handle_new_flow`)
and the frame goes **through the chip a second time**: the tables are dirty
after the create, the packet-free apply program runs ahead of the next step
to be dispatched, and the frame, at the head of that step's window, is
translated by the one NAT rewrite the tree has (PPPoE decap, tag handling
and both checksums with it). A later packet of the flow that punts before
the session is on the chip gets the same mapping (the create is idempotent)
and follows the first, in order. One class serves the three loops:

- the engine's ring loop and the mesh's hold the frame here and stage it
  into the next window themselves (`Engine._fill_window`,
  `ShardedCluster._fill_window`); its verdict is applied by `retire_held`,
  which puts a forwarded frame on the ring's FWD side (`fwd_inject`);
- the scheduler's bulk lane is its own queue: the frame goes back to its
  head marked `SECOND_PASS` (`Lane.requeue_front`) and retires like any
  other lane, its verdict counted by `second_pass`.

Nothing is forwarded twice and nothing loops: a frame that comes back from
its second pass with anything but FWD is dropped and counted (`again` where
it punted again: its session was applied ahead of the step, so that is a
fault, not a race). A refused flow (no block, block full), a frame the host
cannot decode and a hold queue that is full are counted drops too.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass

import numpy as np

from bng_tpu.telemetry import spans as tele

VERDICT_PASS, VERDICT_FWD = 0, 3  # ops/pipeline.py VERDICT_*
# a bulk-lane PendingFrame's `desc` while it waits for its second pass
SECOND_PASS = "second-pass"


@dataclass
class NewFlowStats:
    admitted: int = 0  # punts whose flow holds a session after the create
    refused: int = 0  # no block, block full, or not an IPv4 frame: dropped
    requeued: int = 0  # frames sent through the chip a second time
    again: int = 0  # ... that punted again: dropped
    hold_full: int = 0  # admitted, but no room to wait in: dropped
    lost: int = 0  # second pass gave DROP / TX, or the FWD ring refused
    hold_high: int = 0  # the most frames that waited at once

    def dropped(self) -> int:
        return self.refused + self.again + self.hold_full + self.lost


def strip_pppoe(frame: bytes) -> bytes:
    """Host-side mirror of the device decap for NAT punt frames: the punt
    handler sees the ORIGINAL ring bytes, which for a PPPoE subscriber
    still carry the session framing the device stripped. Returns the
    inner Ethernet+IPv4 view (or the frame unchanged)."""
    off = 12
    et = int.from_bytes(frame[off: off + 2], "big")
    while et in (0x8100, 0x88A8) and len(frame) >= off + 8:
        off += 4
        et = int.from_bytes(frame[off: off + 2], "big")
    if et != 0x8864 or len(frame) < off + 10:
        return frame
    if int.from_bytes(frame[off + 8: off + 10], "big") != 0x0021:
        return frame
    return frame[:off] + b"\x08\x00" + frame[off + 10:]


class NewFlows:
    """The punt handler of one loop: the create, the counts, and (for the
    ring loops) the frames waiting for their second pass."""

    def __init__(self, handle_new_flow, bound: int):
        # (src_ip, dst_ip, src_port, dst_port, proto, pkt_len, now) ->
        # (nat_ip, nat_port) | None; read at call time by the owner
        self.handle_new_flow = handle_new_flow
        self.bound = bound
        self.stats = NewFlowStats()
        self._held: deque = deque()  # (frame as it arrived, ring flags)

    def __len__(self) -> int:
        return len(self._held)

    # -- the create ------------------------------------------------------

    def create(self, frame: bytes, now: int, pppoe: bool) -> bool:
        """Create the session of a punted frame's flow (packet 1 of a new
        flow; parity with the conntrack-hybrid slow path). True where the
        flow holds a session now. Alone, it is `Engine.process`'s: that
        caller holds the frame itself."""
        from bng_tpu.control import packets as P

        view = strip_pppoe(frame) if pppoe else frame
        try:
            d = P.decode(view)
        except Exception:  # noqa: BLE001 — untrusted input
            return False
        if d.ethertype != 0x0800:
            return False
        src_port = d.icmp_id if d.proto == 1 else d.src_port
        dst_port = 0 if d.proto == 1 else d.dst_port
        return self.handle_new_flow(d.src_ip, d.dst_ip, src_port, dst_port,
                                    d.proto, len(view), now) is not None

    def _hold(self, frame: bytes, flags: int) -> bool:
        if len(self._held) >= self.bound:
            return False
        self._held.append((frame, flags))
        return True

    def punt(self, frame: bytes, flags: int, now: int, pppoe: bool,
             hold=None) -> bool:
        """One punted frame: create the session, then hand the frame back
        for its second pass -- into this queue, or through `hold(frame,
        flags) -> bool` where the loop has a queue of its own (the
        scheduler's bulk lane). False: the frame is dropped, and counted
        here. One `punt` lap a frame, inside the retire's `reply`."""
        t0 = tele.t()
        st = self.stats
        try:
            if not self.create(frame, now, pppoe):
                st.refused += 1
                tele.new_flows(refused=1)
                return False
            st.admitted += 1
            if not (hold or self._hold)(frame, flags):
                st.hold_full += 1
                tele.new_flows(admitted=1, hold_full=1)
                return False
            st.hold_high = max(st.hold_high, len(self._held))
            tele.new_flows(admitted=1, hold_high=len(self._held))
            return True
        finally:
            tele.lap(tele.PUNT, t0)

    # -- the second pass ---------------------------------------------------

    @staticmethod
    def stage(pkt, length, flags, lane: int, frame: bytes, fl: int) -> None:
        """One held frame into lane `lane` of a staging buffer, whole (zero
        beyond its length), as a ring's assemble writes a lane."""
        pkt[lane, : len(frame)] = np.frombuffer(frame, dtype=np.uint8)
        pkt[lane, len(frame):] = 0
        length[lane] = len(frame)
        flags[lane] = fl

    def take(self, k: int) -> list:
        """Up to `k` held frames, oldest first, for a window's lanes."""
        return [self._held.popleft() for _ in range(min(k, len(self._held)))]

    def put_back(self, frames: list) -> None:
        """Frames `take` gave out that found no lane: back to the head,
        in their order."""
        self._held.extendleft(reversed(frames))

    def second_pass(self, verdict: int) -> bool:
        """Count one frame's second pass by the verdict it came back with.
        True: FWD, the frame leaves translated. False: dropped, counted
        (`again` where it punted again)."""
        fwd, again = verdict == VERDICT_FWD, verdict == VERDICT_PASS
        lost = not (fwd or again)
        st = self.stats
        st.requeued += 1
        st.again += again
        st.lost += lost
        tele.new_flows(requeued=1, again=int(again), lost=int(lost))
        return fwd

    def retire_held(self, ring, held: list, lanes, verdict, out_pkt,
                    out_len) -> tuple[int, int]:
        """Apply a second pass's verdicts on a ring loop: `held[i]` went
        through lane `lanes[i]`. FWD leaves on the ring's forward side as
        the chip wrote it; anything else, and a frame the FWD ring
        refuses, is dropped and counted. Returns (forwarded, dropped)."""
        fwd = 0
        for (_frame, flags), lane in zip(held, lanes):
            if not self.second_pass(int(verdict[lane])):
                continue
            if ring.fwd_inject(bytes(out_pkt[lane, : int(out_len[lane])]),
                               flags):
                fwd += 1
            else:
                self.stats.lost += 1
                tele.new_flows(lost=1)
        return fwd, len(held) - fwd
