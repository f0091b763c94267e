"""What becomes of the first packet of a new NAT flow.

The device finds no session for an upstream frame and passes it up with
`nat_punt` set. The source translates that same packet and lets it go
(bpf/nat44.c:686-801: session miss -> EIM mapping -> port from the
subscriber's block -> session and reverse entry -> SNAT rewrite of the
packet in hand, TC_ACT_OK; no block or a full one: TC_ACT_SHOT,
:698-705). Here the host creates the sessions of the flows a retired window
punted, in one batch (`NATManager.handle_new_flows`), and each frame goes
**through the chip a second time**: the tables are dirty after the create, the packet-free apply program runs ahead of the next step
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

import struct
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


_U16 = struct.Struct("!H")
_IPV4 = struct.Struct("!B8xB2xII")  # version/IHL, protocol, source, destination
# source and destination port by protocol, over the bytes `packets.decode`
# reads of that header (so the same frames count as truncated): UDP's
# eight, TCP's up to the checksum, an ICMP echo's id as the source port
_L4 = {17: struct.Struct("!HH4x"), 6: struct.Struct("!HH14x"),
       1: struct.Struct("!4xH")}


def flow_of(frame: bytes, pppoe: bool) -> tuple | None:
    """(src_ip, dst_ip, src_port, dst_port, proto, length) of a punted
    frame, read at its fixed offsets after the VLAN / PPPoE strip: field
    for field what `packets.decode` gives (the header length honoured, an
    ICMP echo's id as its source port and 0 as its destination port, the
    length that of the stripped view). None: not an IPv4 frame, or cut
    short of what it says it holds."""
    view = strip_pppoe(frame) if pppoe else frame
    try:
        off = 12
        et = _U16.unpack_from(view, off)[0]
        while et in (0x8100, 0x88A8):
            off += 4
            et = _U16.unpack_from(view, off)[0]
        if et != 0x0800:
            return None
        ver_ihl, proto, src_ip, dst_ip = _IPV4.unpack_from(view, off + 2)
        l4 = _L4.get(proto)
        ports = (l4.unpack_from(view, off + 2 + (ver_ihl & 0x0F) * 4)
                 if l4 is not None else ())
    except struct.error:
        return None
    src_port, dst_port = (*ports, 0, 0)[:2]
    return src_ip, dst_ip, src_port, dst_port, proto, len(view)


class NewFlows:
    """The punt handler of one loop: the create, the counts, and (for the
    ring loops) the frames waiting for their second pass."""

    def __init__(self, handle_new_flows, bound: int):
        # (src_ips, dst_ips, src_ports, dst_ports, protos, pkt_lens, now)
        # -> per flow (nat_ip, nat_port) | None | the error that flow
        # alone met (control/nat.py NATManager.handle_new_flows); read at
        # call time by the owner
        self.handle_new_flows = handle_new_flows
        self.bound = bound
        self.stats = NewFlowStats()
        self._held: deque = deque()  # (frame as it arrived, ring flags)

    def __len__(self) -> int:
        return len(self._held)

    # -- the create ------------------------------------------------------

    def _open(self, frames, now: int, pppoe: bool) -> list:
        """Create the sessions of punted frames' flows (packet 1 of a new
        flow; parity with the conntrack-hybrid slow path), in one batch.
        Per frame: the mapping its flow holds now, None (refused: no
        block, block full, not an IPv4 frame), or the error the create met
        for it. An error that is not one flow's is every flow's."""
        parsed = [flow_of(frame, pppoe) for frame in frames]
        flows = [f for f in parsed if f is not None]
        if not flows:
            return parsed
        try:
            got = iter(self.handle_new_flows(*zip(*flows), now))
        except Exception as e:  # noqa: BLE001 — untrusted input
            return [None if f is None else e for f in parsed]
        return [None if f is None else next(got) for f in parsed]

    def create(self, frame: bytes, now: int, pppoe: bool) -> bool:
        """The batch of one, with no hand-back: `Engine.process`'s, whose
        caller holds the frame itself. True where the flow holds a session
        now; an error the create met is raised."""
        got = self._open([frame], now, pppoe)[0]
        if isinstance(got, Exception):
            raise got
        return got is not None

    def _hold(self, frame: bytes, flags: int) -> bool:
        if len(self._held) >= self.bound:
            return False
        self._held.append((frame, flags))
        return True

    def punt_many(self, frames, flags, now: int, pppoe: bool, *, on_error,
                  hold=None) -> list[bool]:
        """The frames a retired window punted, in lane order: create their
        sessions in one batch, then hand each frame back for its second
        pass -- into this queue, or through `hold[i](frame, flags) -> bool`
        where the loop has a queue of its own (the scheduler's bulk lane).
        False: that frame is dropped, and counted here (`refused`,
        `hold_full`) or by `on_error(i, error)` where the create raised
        for it; the frames around it are served. One `punt` lap a batch,
        inside the retire's `reply`."""
        t0 = tele.t()
        st = self.stats
        admitted = refused = hold_full = 0
        kept = [False] * len(frames)
        for i, got in enumerate(self._open(frames, now, pppoe)):
            if got is None:
                refused += 1
            elif isinstance(got, Exception):
                on_error(i, got)
            else:
                admitted += 1
                if (hold[i] if hold else self._hold)(frames[i], flags[i]):
                    kept[i] = True
                else:
                    hold_full += 1
        st.admitted += admitted
        st.refused += refused
        st.hold_full += hold_full
        st.hold_high = max(st.hold_high, len(self._held))
        tele.new_flows(admitted=admitted, refused=refused, hold_full=hold_full,
                       hold_high=len(self._held))
        tele.lap(tele.PUNT, t0)
        return kept

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
