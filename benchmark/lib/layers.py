"""From spans, counters and the trace to metrics.

End-to-end metrics are computed here from the loop's own stamps. A
per-layer metric is a data file, `layers/<name>.json`, whose `read` names
one of five readers implemented once below:

  span           a stage of the program's Tracer (telemetry/spans.py)
  counter        a dotted path into app.counters(): sched.*, engine.*,
                 sharded.*, ring.*, device.*, host.*; the window's delta,
                 optionally per frame, per second or per another counter
  bench_span     the harness's own spans around push, drive_once, pop
  trace_program  device-track program events by name prefix
  trace_device   device busy / idle / collective time

A reader that finds nothing to read returns None and the metric is left
out of the line.
"""

from __future__ import annotations

import glob
import json
import os
import time
from dataclasses import dataclass

import numpy as np

from benchmark.lib import trace as tracelib

SOURCE_OF_KIND = {"span": "program_span", "counter": "program_counter",
                  "bench_span": "host_clock", "trace_program": "device_trace",
                  "trace_device": "device_trace"}


def pct(values, q: float):
    values = np.asarray(values, np.float64)
    return float(np.percentile(values, q)) if len(values) else None


@dataclass
class Context:
    plan: object
    loop: object
    window: float
    served: int
    c0: dict
    c1: dict
    tracer: object
    profile: object
    setup_s: float
    n_devices: int
    trace: dict | None = None
    reduce_s: float = 0.0  # what reducing the trace and the event log took
    left_out: tuple = ()  # per-layer metrics whose reader found nothing
    _lat: dict | None = None

    @property
    def lat(self) -> dict:
        if self._lat is None:
            self._lat = latencies(self.plan, self.loop, self.window)
        return self._lat


def latencies(plan, loop, window: float) -> dict:
    """Microseconds from when a frame was due to when its reply was popped
    (fixed_rate mixes). A frame that found no room was held and offered at
    a later beat: its time runs from when it was due all the same. A frame
    lost, or never offered, is censored at the window's end, so it sits in
    the tail and not outside the sample."""
    if plan.flood:
        return {"dhcp_us": [], "data_us": [], "late_us": []}
    done = np.full(plan.n, np.nan)
    for t_end, got in loop.kept:
        for raw, _fl in got:
            _is_d, fid = plan.reply_id(raw)
            if 0 <= fid < plan.n and np.isnan(done[fid]):
                done[fid] = t_end
    offered = np.zeros(plan.n, bool)  # due while the loop was beating
    for st in plan.streams:
        offered[st.ids[:st.seen]] = True
    done = np.where(np.isnan(done), np.maximum(window, plan.due), done)
    us = (done - plan.due) * 1e6
    pushed = loop.push_t >= 0
    return {"dhcp_us": us[offered & plan.is_dhcp],
            "data_us": us[offered & ~plan.is_dhcp],
            "dhcp_due": plan.due[offered & plan.is_dhcp],
            "data_due": plan.due[offered & ~plan.is_dhcp],
            "late_us": (loop.push_t - plan.due)[pushed] * 1e6}


def end_to_end(ctx: Context, bench: dict, cell: str) -> dict:
    have = {
        "served_kpps": lambda: ctx.served / ctx.window / 1000.0,
        "offer_p50_us": lambda: pct(ctx.lat["dhcp_us"], 50),
        "offer_p95_us": lambda: pct(ctx.lat["dhcp_us"], 95),
        "fwd_p95_us": lambda: pct(ctx.lat["data_us"], 95),
        "setup_s": lambda: ctx.setup_s,
    }
    out = {}
    for m in bench["end_to_end"]:
        if cell in m.get("workloads", [cell]):
            out[m["name"]] = {"value": have[m["name"]](), "unit": m["unit"]}
    return out


def summary(ctx: Context, slice_s: float = 0.0) -> list[str]:
    """What the window read, in full, whichever metrics the line carries;
    with `slice_s`, the same over each slice of the window."""
    def kpps(a: float, b: float) -> float:
        sp = np.asarray(ctx.loop.spans, np.float64)
        end = sp[:, 3] - ctx.loop.t_open
        return float(sp[(end >= a) & (end < b), 5].sum() / (b - a) / 1000.0)

    def tails(a: float, b: float) -> dict:
        out = {}
        for kind, name in (("dhcp", "offer"), ("data", "fwd")):
            due = ctx.lat[kind + "_due"]
            us = ctx.lat[kind + "_us"][(due >= a) & (due < b)]
            out[name] = {"n": len(us), **({f"p{q}_us": round(pct(us, q), 1)
                                           for q in (50, 95, 99)}
                                          if len(us) else {})}
        return out

    read = (lambda a, b: {"popped_kpps": round(kpps(a, b), 4)}) \
        if ctx.plan.flood else tails
    lines = ["read " + json.dumps(read(0.0, ctx.window))]
    if slice_s > 0:
        edges = np.arange(0.0, ctx.window - slice_s + 1e-9, slice_s)
        lines += [f"slice {a:.0f}-{a + slice_s:.0f} s "
                  + json.dumps(read(a, a + slice_s)) for a in edges]
    return lines


# --------------------------------------------------------------------------
# the five readers
# --------------------------------------------------------------------------

def _stat(values, stat: str, ctx: Context):
    values = np.asarray(values, np.float64)
    if not len(values):
        return None
    if stat in ("p50", "p99"):
        return float(np.percentile(values, int(stat[1:])))
    if stat == "sum_per_frame":
        return float(values.sum() / ctx.served) if ctx.served else None
    if stat == "share_of_window":
        return float(values.sum() / (ctx.window * 1e6))
    return float({"sum": values.sum(), "mean": values.mean(),
                  "count": len(values)}[stat])


def read_span(read: dict, ctx: Context):
    from bng_tpu.telemetry import spans as tele

    if ctx.tracer is None or not ctx.tracer.events:
        return None
    # a stage or lane this program's Tracer does not have (the parent of the
    # PR that added it, on which the driver runs the same files) stamps
    # nothing: nothing to read, not an error
    if (read["stage"] not in tele.STAGE_NAMES
            or read.get("lane") not in (None, *tele.LANE_NAMES)):
        return None
    stage = tele.STAGE_NAMES.index(read["stage"])
    lane = tele.LANE_NAMES.index(read["lane"]) if read.get("lane") else None
    us = [d / 1000.0 for s, ln, _t0, d in ctx.tracer.events
          if s == stage and (lane is None or ln == lane)]
    return _stat(us, read["stat"], ctx)


def dig(tree, path: str):
    """`a.b.*.c` into nested dicts and lists; `*` fans out over a list."""
    parts = path.split(".")
    for i, p in enumerate(parts):
        if p == "*":
            rest = ".".join(parts[i + 1:])
            return [dig(x, rest) if rest else x for x in tree]
        if not isinstance(tree, dict) or p not in tree:
            return None
        tree = tree[p]
    return tree


def read_counter(read: dict, ctx: Context):
    after = dig(ctx.c1, read["path"])
    if after is None:
        return None
    before = dig(ctx.c0, read["path"])  # every counter is read as the window's own
    after = ([a - b for a, b in zip(after, before)]
             if isinstance(after, list) else after - before)
    if isinstance(after, list):
        vals = np.asarray(after, np.float64)
        if not len(vals) or vals.mean() == 0:
            return None
        after = {"max_over_mean": vals.max() / vals.mean(),
                 "sum": vals.sum()}[read["reduce"]]
    per = read.get("per")
    if per:
        named = {"frame": ctx.served, "second": ctx.window,
                 "dhcp_offered": int(ctx.plan.is_dhcp.sum())
                 if not ctx.plan.flood else None}
        # or another counter's path: the two move over the same window
        den = (named[per] if per in named
               else (dig(ctx.c1, per) or 0) - (dig(ctx.c0, per) or 0))
        if not den:
            return None
        after = after / den
    return float(after)


def read_bench_span(read: dict, ctx: Context):
    sp = np.asarray(ctx.loop.spans, np.float64)
    if not len(sp):
        return None
    if read["span"] in ("late", "dhcp", "data"):  # per frame, not per beat
        return _stat(ctx.lat[read["span"] + "_us"], read["stat"], ctx)
    t0, t1, t2, t3 = sp[:, 0], sp[:, 1], sp[:, 2], sp[:, 3]
    us = {"push": t1 - t0, "drive_once": t2 - t1, "pop": t3 - t2,
          "beat": t3 - t0, "gen": (t1 - t0) + (t3 - t2)}[read["span"]] * 1e6
    return _stat(us, read["stat"], ctx)


def read_trace_program(read: dict, ctx: Context):
    if ctx.trace is None:
        return None
    by_name: dict[str, list[float]] = {}
    for name, _t, d in ctx.trace["programs"]:
        if name.startswith(read["prefix"]):
            by_name.setdefault(name, []).append(d / 1000.0)
    if not by_name:
        return None
    # programs carry no name of their own yet (`jit_step(<hash>)` is both
    # the fused step and the express step), so a file picks among those
    # that share its prefix by their median time
    pick = {"longest": max, "shortest": min}[read["pick"]]
    us = pick(by_name.values(), key=lambda v: float(np.median(v)))
    return _stat(us, read["stat"], ctx)


def read_trace_device(read: dict, ctx: Context):
    if ctx.trace is None:
        return None
    return ctx.trace.get(read["stat"])


READERS = {"span": read_span, "counter": read_counter,
           "bench_span": read_bench_span, "trace_program": read_trace_program,
           "trace_device": read_trace_device}


def layer_files(bench_dir: str) -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(bench_dir, "layers", "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def per_layer(ctx: Context, bench_dir: str, cell: str) -> dict:
    """Every layer file that lists this cell, read once."""
    prof = ctx.profile
    if prof is not None and prof.t_stop is not None:
        t0 = time.perf_counter()
        ctx.trace = tracelib.reduce_dir(prof.dir, ctx.n_devices,
                                        prof.t_stop - prof.t_start,
                                        tracelib.events_of(ctx.tracer))
        ctx.reduce_s = time.perf_counter() - t0
    out, left_out = {}, []
    for m in layer_files(bench_dir):
        if cell not in m["cells"]:
            continue
        value = READERS[m["read"]["kind"]](m["read"], ctx)
        if value is None:
            left_out.append(m["name"])
            continue
        out[m["name"]] = {"value": value * m["read"].get("scale", 1.0),
                          "unit": m["unit"]}
    ctx.left_out = tuple(left_out)
    return out
