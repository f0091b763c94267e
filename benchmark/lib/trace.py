"""Reduction of a `jax.profiler` trace to device busy time, idle gaps and
per-program times. The idea is bng_tpu/utils/profiling.py's; here it
reads the .xplane.pb with `jax.profiler.ProfileData` and works on a plain
form that the tests keep small recordings of (lib/testdata/):

    {"planes": {"/device:TPU:0": {"XLA Ops": [[name, start_ns, dur_ns], ...],
                                   "XLA Modules": [...]},
                "/host:CPU": {"bench": [["bench.push", start_ns, dur_ns], ...],
                              "beats": [[start_ns, dur_ns, clock_ns, beat], ...]}}}

Programs are found by their `jit_<function>` names on the "XLA Modules"
line. `beats` are the program's own `bng.beat` annotations (telemetry/
spans.py: one a `drive_once`, while a profiler runs), each with the Tracer's
clock reading at its start: through them a lap of the Tracer's event log
lands on the trace's timeline, and an idle gap of the device is named by
the stage the program was in.

The labels of `breakdown.idle_gaps` (letters, digits, `_`, `.`, `-`; the
ledger rewrites anything else), a gap divided among them by overlap:

    drive_once.<stage>      inside `bench.drive_once`, under a host lap of the
                            Tracer (the innermost one)
    drive_once.no_lap       inside `bench.drive_once`, under no lap
    between_beats.<stage>   outside the harness's spans, under a lap (the
                            harness calls `app.tick()` there)
    bench.push, bench.pop   inside the harness's own push / pop
    between beats           outside them, under no lap

Without anchors in the trace, or without an event log, no lap is known and
the part inside `bench.drive_once` keeps that bare name.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "alltoall", "allreduce")
BENCH_SPANS = ("bench.push", "bench.drive_once", "bench.pop")
DRIVE, BETWEEN = "bench.drive_once", "between beats"
BEAT = "bng.beat"  # the Tracer's anchor annotation (telemetry/spans.py)
# the Tracer's stages that are laps of the host thread (the rest are fed
# durations: lane_wait, device, sojourn; the container: beat; or span batches
# across beats: total). `upload` and `fetch` (PR 37) close inside `dispatch`
# and `reply`, `mirror` (PR 49: the hand-over to the intercept sink) inside
# `reply`, and the innermost lap wins. A stage a later program stamps and
# this list lacks reads as `no_lap`. The original is
# bng_tpu/utils/profiling.py HOST_LAPS
HOST_LAPS = ("ring", "admit", "dispatch", "upload", "device_wait", "fetch",
             "fleet", "slow_path", "reply", "mirror", "ops", "wire_rx",
             "wire_tx", "pack", "drain", "tx")
EVENTS_FILE = "events.json"  # the event log's slice beside a recorded trace


def load_xplane(path: str) -> dict:
    """The device planes' op and module lines, and from the host plane the
    harness's own annotations and the program's beat anchors, as plain
    lists."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            planes[plane.name] = {
                line.name: [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events]
                for line in plane.lines if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name == "/host:CPU":
            spans, beats = [], []
            for line in plane.lines:
                for e in line.events:
                    if e.name in BENCH_SPANS:
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns)])
                    elif e.name == BEAT:
                        st = dict(e.stats)
                        if "clock_ns" in st and "beat" in st:
                            beats.append([float(e.start_ns),
                                          float(e.duration_ns),
                                          int(st["clock_ns"]), int(st["beat"])])
            planes[plane.name] = {"bench": sorted(spans, key=lambda s: s[1]),
                                  "beats": sorted(beats)}
    return {"planes": planes}


def _union(events) -> list[list[float]]:
    """Merged [start, end] intervals of events [[name, start, dur], ...]."""
    out: list[list[float]] = []
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], start + dur)
        else:
            out.append([start, start + dur])
    return out


# --------------------------------------------------------------------------
# idle gaps, named by what the host was doing
# --------------------------------------------------------------------------

def flatten(spans) -> list[tuple[float, float, str]]:
    """Spans [(start, end, name), ...] of one thread, nested or apart, as
    disjoint ascending segments in which the innermost span wins: a span
    keeps the parts of itself that no span opened inside it covers."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, float, str]] = []
    at = float("-inf")  # segments are written up to here

    def emit(until: float) -> None:
        nonlocal at
        if stack and until > at:
            out.append((at, until, stack[-1][2]))
        at = max(at, until)

    for span in sorted((s for s in spans if s[1] > s[0]),
                       key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= span[0]:
            emit(stack[-1][1])
            stack.pop()
        emit(span[0])
        stack.append(span)
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def _shares(segments, starts, lo: float, hi: float):
    """(name or None, start, end) for each part of [lo, hi]: under a
    segment its name, between segments None."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    at = lo
    while at < hi:
        a, b, name = segments[i] if i < len(segments) else (hi, hi, None)
        if b <= at:
            i += 1
            continue
        if a > at:  # up to the next segment, or to the end
            a, b, name = at, a, None
        else:
            i += 1
        yield name, at, min(b, hi)
        at = min(b, hi)


def laps_on_trace(beats, events: dict | None) -> list[tuple[float, float, str]]:
    """The event log's host laps as (start, end, stage) on the trace's
    timeline. A lap goes through the anchor of the beat it ran under; one
    between beats (id -1), or under a beat the trace holds no anchor of,
    through the anchor nearest in time: one process, one clock, so the
    offset is a constant but for the clocks' drift."""
    if not beats or not events or not len(events["events"]):
        return []
    import numpy as np

    ev = np.asarray(events["events"], np.int64).reshape(-1, 4)
    under = np.asarray(events["beats"], np.int64)
    stages = list(events["stages"])
    keep = (ev[:, 3] > 0) & np.isin(
        ev[:, 0], [stages.index(s) for s in HOST_LAPS if s in stages])
    ev, under = ev[keep], under[keep]
    if not len(ev):
        return []
    anchors = np.asarray(sorted((b[2], b[0] - b[2], b[3]) for b in beats),
                         np.float64)
    clock, offset, ids = anchors[:, 0], anchors[:, 1], anchors[:, 2]
    last = len(clock) - 1
    at = np.searchsorted(clock, ev[:, 2])
    left, right = np.clip(at - 1, 0, last), np.clip(at, 0, last)
    near = np.where(np.abs(clock[left] - ev[:, 2])
                    <= np.abs(clock[right] - ev[:, 2]), left, right)
    by_id = np.argsort(ids)
    own = by_id[np.clip(np.searchsorted(ids[by_id], under), 0, last)]
    start = ev[:, 2] + offset[np.where(ids[own] == under, own, near)]
    return [(s, s + d, stages[k]) for s, d, k
            in zip(start.tolist(), ev[:, 3].tolist(), ev[:, 0].tolist())]


def label_gaps(gaps, bench, laps) -> dict[str, float]:
    """Seconds of the gaps [(start, end), ...] by label (the module's
    docstring has the table): each gap is divided among the harness's
    spans, and inside `bench.drive_once` and between the spans among the
    laps, by overlap. `laps` None: no lap is known (no anchors, or no event
    log) and `bench.drive_once` keeps its bare name."""
    outer = flatten([(s, s + d, name) for name, s, d in bench])
    inner = flatten(laps or [])
    o_starts, i_starts = [s[0] for s in outer], [s[0] for s in inner]
    out: dict[str, float] = {}

    def add(label: str, ns: float) -> None:
        if ns > 0:
            out[label] = out.get(label, 0.0) + ns / 1e9

    for lo, hi in gaps:
        for where, a, b in _shares(outer, o_starts, lo, hi):
            if where in ("bench.push", "bench.pop") or laps is None:
                add(where or BETWEEN, b - a)
                continue
            head = "drive_once." if where == DRIVE else "between_beats."
            for stage, c, d in _shares(inner, i_starts, a, b):
                add(head + stage if stage else
                    "drive_once.no_lap" if where == DRIVE else BETWEEN, d - c)
    return out


def reduce(data: dict, n_devices: int, window_s: float,
           events: dict | None = None) -> dict | None:
    """busy_s (mean over the devices used), idle_share and
    collective_share (worst device), the programs of the first device, and
    the breakdown the result line carries; `gaps` is the whole label table
    the breakdown's ten are cut from. `events` is the Tracer's event log in
    the shape `Tracer.write_events` writes (`stages`, `events`, `beats`).
    None where no device plane has an event: that run drove no device."""
    devices = sorted(p for p in data["planes"] if DEVICE_PLANE.match(p))
    devices = devices[:n_devices]
    host = data["planes"].get("/host:CPU", {})
    beats = host.get("beats", [])
    laps = laps_on_trace(beats, events) or None
    busy, coll, op_time = [], [], {}
    gaps: dict[str, float] = {}
    for k, dev in enumerate(devices):
        lines = data["planes"][dev]
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        merged = _union(ops)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        coll.append(sum(d for name, _s, d in ops
                        if any(c in name.lower() for c in COLLECTIVES)) / 1e9)
        for name, _s, d in ops:
            op_time[name] = op_time.get(name, 0.0) + d / 1e9 / len(devices)
        if k == 0:
            gaps = label_gaps([(end, start) for (_a, end), (start, _b)
                               in zip(merged, merged[1:])],
                              host.get("bench", []), laps)
    if not busy or max(busy) <= 0:
        return None
    top = lambda d: [[k, v] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "idle_share": 1.0 - min(busy) / window_s,
        "collective_share": max(coll) / window_s,
        "programs": data["planes"][devices[0]].get(MODULES_LINE, []),
        "gaps": gaps,
        "breakdown": {"device_ops": top(op_time), "idle_gaps": top(gaps)},
    }


def events_of(tracer) -> dict | None:
    """A Tracer's event log in the shape `Tracer.write_events` writes."""
    from bng_tpu.telemetry import spans as tele

    if tracer is None or not tracer.events:
        return None
    return {"stages": list(tele.STAGE_NAMES), "events": list(tracer.events),
            "beats": list(tracer.event_beats)}


def events_near(events: dict | None, beats, margin_ns: float = 1e9) -> dict | None:
    """The slice of an event log that can touch the traced window (the
    anchors' span of the Tracer's clock, a second either side), as plain
    lists: what is kept beside a recorded trace."""
    if not events or not beats or not len(events["events"]):
        return events
    import numpy as np

    ev = np.asarray(events["events"], np.int64).reshape(-1, 4)
    lo = min(b[2] for b in beats) - margin_ns
    hi = max(b[2] + b[1] for b in beats) + margin_ns
    keep = (ev[:, 2] + ev[:, 3] >= lo) & (ev[:, 2] <= hi)
    return {"stages": list(events["stages"]), "events": ev[keep].tolist(),
            "beats": np.asarray(events["beats"], np.int64)[keep].tolist()}


def _newest_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce_dir(trace_dir: str, n_devices: int, window_s: float,
               events: dict | None = None) -> dict | None:
    """Reduce the newest trace under `trace_dir`. The event log's slice
    around the traced window is left beside the trace (`events.json`), so
    that the recording can be reduced again."""
    path = _newest_xplane(trace_dir)
    if not path:
        return None
    data = load_xplane(path)
    if events is not None:
        events = events_near(
            events, data["planes"].get("/host:CPU", {}).get("beats", []))
        with open(os.path.join(trace_dir, EVENTS_FILE), "w") as f:
            json.dump(events, f)
    return reduce(data, n_devices, window_s, events)


def main(argv=None) -> int:
    """`python -m benchmark.lib.trace <trace_dir> <out.json> [n] [programs]`:
    the plain form of a recorded trace, each line cut to its first n events,
    and beside it (`<out>.events.json`) the slice of the event log a traced
    run left there, cut to the laps that touch what is left -- how
    lib/testdata/ was made, and the way to look at a trace by hand. With
    `programs` the op lines are left out, so that n events span many steps
    and busy time is the programs' own."""
    import sys

    args = sys.argv[1:] if argv is None else argv
    trace_dir, out, n = args[0], args[1], int(args[2]) if len(args) > 2 else 400
    path = _newest_xplane(trace_dir)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            print(f"{plane.name} | {line.name} | {len(events)} events | "
                  f"{sorted({e.name for e in events[:2000]})[:12]}")
    data = load_xplane(path)
    for lines in data["planes"].values():
        if "programs" in args[3:]:
            lines.pop(OPS_LINE, None)
        for name in lines:
            lines[name] = lines[name][:n]
    with open(out, "w") as f:
        json.dump(data, f)
    kept = os.path.join(trace_dir, EVENTS_FILE)
    if os.path.exists(kept):
        with open(kept) as f:
            events = json.load(f)
        beats = data["planes"].get("/host:CPU", {}).get("beats", [])
        with open(out[:-5] + ".events.json", "w") as f:
            json.dump(events_near(events, beats, 0.0), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
