"""Op-level device profiling: the reducer over a recorded profiler trace.

The reference leans on perf/bpftool-style tracing to find its hot spots;
the TPU analog is the XLA profiler. For a trace the benchmark recorded
(`.bench_trace/<cell>`, a `--trace 1` run of `benchmark/run.py`) there is
one reducer, `reduce_trace`:

    python -m bng_tpu.utils.profiling .bench_trace/<cell> [events.json]

device time by program and by `jax.named_scope` stage, and the idle gaps
by program stage through the Tracer's `bng.beat` anchors.
"""

from __future__ import annotations

import glob
import json
import os

# the `jax.named_scope` names of ops/pipeline.py, ops/express.py, the
# update scatter (runtime/engine.py) and the sharded step's psums
SCOPES = ("parse", "antispoof", "dhcp", "garden", "nat44", "qos", "edge",
          "pppoe", "v6", "qinq", "rewrite", "updates", "stats")
BEAT = "bng.beat"  # the Tracer's anchor annotation (telemetry/spans.py)
# stages that are laps of the host thread (the rest are fed durations:
# lane_wait, device, sojourn; or span batches across beats: total).
# `upload`, `fetch`, `mirror` and `punt` are children of other laps: the
# shortest lap over a gap's midpoint names it, so a child wins over its parent
HOST_LAPS = ("ring", "admit", "dispatch", "device_wait", "fleet",
             "slow_path", "reply", "ops", "wire_rx", "wire_tx", "pack",
             "drain", "tx", "upload", "fetch", "mirror", "punt")


def _xplane_pb2():
    """The XSpace protobuf module. `jax.profiler.ProfileData` does not show
    an op's metadata (`tf_op`, the named-scope path; `bytes_accessed`), the
    raw proto does; TensorFlow ships its generated module, loaded here by
    path so that TensorFlow itself is not imported."""
    import importlib.util

    spec = importlib.util.find_spec("tensorflow")
    path = os.path.join(os.path.dirname(spec.origin) if spec and spec.origin
                        else "", "tsl", "profiler", "protobuf", "xplane_pb2.py")
    if not os.path.exists(path):
        raise SystemExit("profiling: no xplane_pb2 here (it comes with "
                         "TensorFlow): cannot read a recorded trace")
    spec = importlib.util.spec_from_file_location("_bng_xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _stats(plane, holder) -> dict:
    """{stat name: value} of an event or of an event's metadata."""
    out = {}
    for st in holder.stats:
        kind = st.WhichOneof("value")
        value = getattr(st, kind) if kind else None
        if kind == "ref_value":
            value = plane.stat_metadata[value].name
        out[plane.stat_metadata[st.metadata_id].name] = value
    return out


def _events(plane, line_name: str):
    """(name, start ns, duration ns, event, metadata) of a plane's line."""
    for line in plane.lines:
        if line.name == line_name or line_name is None:
            for ev in line.events:
                meta = plane.event_metadata[ev.metadata_id]
                yield (meta.name, line.timestamp_ns + ev.offset_ps / 1e3,
                       ev.duration_ps / 1e3, ev, meta)


def _scope_of(op_path: str) -> str:
    """The innermost named scope on an op's `tf_op` path: a scope opened
    inside another stage's (the v6 destination window inside `parse`, the
    control test inside `antispoof`) owns what it adds."""
    return next((part for part in reversed(op_path.split("/"))
                 if part in SCOPES), "(no scope)")


def _lap_over(laps, at: float):
    """The innermost lap (start, duration, stage) that covers `at`: the
    shortest one, so a child (`fetch` inside `device_wait`, `upload` inside
    `dispatch`) names a gap and not its parent."""
    return min((ln for ln in laps if ln[0] <= at < ln[0] + ln[1]),
               key=lambda ln: ln[1], default=None)


def reduce_trace(trace_dir: str, events_path: str | None = None) -> dict:
    """Device time by program and by named scope, and the idle gaps of the
    first device by program stage, from a recorded `jax.profiler` trace
    directory (`.bench_trace/<cell>`, or one `.xplane.pb`). `events_path`
    is the Tracer's event log (`BNG_TRACE_EVENTS=<file>` makes `disarm()`
    write it): with it an idle gap is named by the innermost Tracer lap that
    covers it, mapped onto the device timeline through its beat's `bng.beat`
    anchor; without it, by beat."""
    found = [trace_dir] if trace_dir.endswith(".pb") else sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise SystemExit(f"profiling: no .xplane.pb under {trace_dir}")
    space = _xplane_pb2().XSpace()
    with open(found[-1], "rb") as f:
        space.ParseFromString(f.read())
    planes = {pl.name: pl for pl in space.planes}
    devices = sorted(n for n in planes if n.startswith("/device:TPU:"))
    anchors = []  # (trace start ns, duration ns, Tracer clock ns, beat id)
    if "/host:CPU" in planes:
        host = planes["/host:CPU"]
        for name, t, d, ev, _meta in _events(host, None):
            if name == BEAT:
                args = _stats(host, ev)
                anchors.append((t, d, int(args["clock_ns"]), int(args["beat"])))
    anchors.sort()
    out = {"trace": found[-1], "devices": len(devices),
           "anchors": len(anchors), "programs": {}, "scopes": {},
           "top_ops": [], "idle": {}}
    if not devices:
        return out
    dev = planes[devices[0]]
    progs = sorted((t, d, name) for name, t, d, _e, _m
                   in _events(dev, "XLA Modules"))
    for _t, d, name in progs:
        p = out["programs"].setdefault(name, {"count": 0, "sum_us": 0.0})
        p["count"] += 1
        p["sum_us"] += d / 1e3
    starts = [t for t, _d, _n in progs]
    ops, busy = {}, []
    import bisect

    for name, t, d, _ev, meta in _events(dev, "XLA Ops"):
        i = bisect.bisect_right(starts, t) - 1
        prog = progs[i][2] if i >= 0 and t < progs[i][0] + progs[i][1] \
            else "(no program)"
        info = _stats(dev, meta)
        scope = _scope_of(str(info.get("tf_op", "")))
        by = out["scopes"].setdefault(prog, {})
        by[scope] = by.get(scope, 0.0) + d / 1e3
        key = (meta.display_name or name.split(" = ")[0], scope, prog,
               str(info.get("source", "")), int(info.get("bytes_accessed")
                                                or 0))
        ops[key] = ops.get(key, 0.0) + d / 1e3
        busy.append((t, t + d))
    out["top_ops"] = [[*k, us] for k, us in
                      sorted(ops.items(), key=lambda kv: -kv[1])[:20]]
    # idle gaps of the first device, by program stage
    laps = []
    if events_path:
        with open(events_path) as f:
            log = json.load(f)
        clock_of = {a[3]: a for a in anchors}
        for (stage, _lane, t0, dur), beat in zip(log["events"],
                                                 log["beats"]):
            a = clock_of.get(beat)
            if a is not None and dur > 0 and log["stages"][stage] in \
                    HOST_LAPS:
                laps.append((a[0] + (t0 - a[2]), dur, log["stages"][stage]))
        # the Tracer's own charge of starvation over its whole armed span,
        # to set beside the trace's gaps below
        out["tracer_starved_us"] = {
            k: v / 1e3 for k, v in sorted(log["sums"]["starved_ns"].items(),
                                          key=lambda kv: -kv[1]) if v}
    gaps: dict[str, float] = {}
    busy.sort()
    end = busy[0][1] if busy else 0.0
    for t0, t1 in busy[1:]:
        if t0 > end:
            mid = (end + t0) / 2
            inner = _lap_over(laps, mid)
            beat = next((a for a in anchors if a[0] <= mid < a[0] + a[1]),
                        None)
            what = (inner[2] if inner else "beat (no lap)" if beat
                    else "between beats")
            gaps[what] = gaps.get(what, 0.0) + (t0 - end) / 1e3
        end = max(end, t1)
    out["idle"] = {"total_us": sum(gaps.values()),
                   "by_stage": dict(sorted(gaps.items(),
                                           key=lambda kv: -kv[1]))}
    return out


def main(argv=None) -> int:
    """`python -m bng_tpu.utils.profiling <trace dir> [events.json]`"""
    import sys

    args = sys.argv[1:] if argv is None else argv
    if not args:
        print(main.__doc__, file=sys.stderr)
        return 2
    print(json.dumps(reduce_trace(args[0], args[1] if len(args) > 1 else None),
                     indent=1))
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
