"""Reduction of a `jax.profiler` trace to device busy time, idle gaps and
per-program times. The idea is bng_tpu/utils/profiling.py's; here it
reads the .xplane.pb with `jax.profiler.ProfileData` and works on a plain
form that the tests keep a small recording of (lib/testdata/):

    {"planes": {"/device:TPU:0": {"XLA Ops": [[name, start_ns, dur_ns], ...],
                                   "XLA Modules": [...]},
                "/host:CPU": {"bench": [["bench.push", start_ns, dur_ns], ...]}}}

Programs are found by their `jit_<function>` names on the "XLA Modules"
line: no `jax.named_scope` exists in the program, so kernels inside a
program cannot be told apart yet.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "alltoall", "allreduce")
BENCH_SPANS = ("bench.push", "bench.drive_once", "bench.pop")


def load_xplane(path: str) -> dict:
    """The device planes' op and module lines, and the harness's own
    annotations from the host plane, as plain lists."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            planes[plane.name] = {
                line.name: [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events]
                for line in plane.lines if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name == "/host:CPU":
            spans = [[e.name, float(e.start_ns), float(e.duration_ns)]
                     for line in plane.lines for e in line.events
                     if e.name in BENCH_SPANS]
            planes[plane.name] = {"bench": sorted(spans, key=lambda s: s[1])}
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


def _label(spans, at: float) -> str:
    for name, start, dur in spans:
        if start <= at < start + dur:
            return name
    return "between beats"


def reduce(data: dict, n_devices: int, window_s: float) -> dict | None:
    """busy_s (mean over the devices used), idle_share and
    collective_share (worst device), the programs of the first device, and
    the breakdown the result line carries. None where no device plane has
    an event: that run drove no device."""
    devices = sorted(p for p in data["planes"] if DEVICE_PLANE.match(p))
    devices = devices[:n_devices]
    host = data["planes"].get("/host:CPU", {}).get("bench", [])
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
            for (_a, end), (start, _b) in zip(merged, merged[1:]):
                what = _label(host, (end + start) / 2)
                gaps[what] = gaps.get(what, 0.0) + (start - end) / 1e9
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
        "breakdown": {"device_ops": top(op_time), "idle_gaps": top(gaps)},
    }


def _newest_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce_dir(trace_dir: str, n_devices: int, window_s: float) -> dict | None:
    path = _newest_xplane(trace_dir)
    return reduce(load_xplane(path), n_devices, window_s) if path else None


def main(argv=None) -> int:
    """`python -m benchmark.lib.trace <trace_dir> <out.json> [n]`: the plain
    form of a recorded trace, each line cut to its first n events -- how
    lib/testdata/ was made, and the way to look at a trace by hand."""
    import json
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
        for name in lines:
            lines[name] = lines[name][:n]
    with open(out, "w") as f:
        json.dump(data, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
