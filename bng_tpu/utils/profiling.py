"""Op-level device profiling — the tracing subsystem (SURVEY.md §5).

The reference leans on perf/bpftool-style tracing to find its hot spots;
the TPU analog is the XLA profiler. This module institutionalizes the
workflow that diagnosed the round-2 QoS bottleneck (narrow-gather fusions
at ~7ns/element): capture a `jax.profiler` trace around a callable, parse
the Chrome-trace export, and aggregate per-op device time.

    from bng_tpu.utils.profiling import profile_op_times
    report = profile_op_times(lambda: step(tables, pkt, ln), iters=10)
    print(format_report(report))

Used by `python -m bng_tpu.utils.profiling` (smoke) and available to
bench.py via BNG_BENCH_PROFILE=1.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import tempfile
from collections import defaultdict
from dataclasses import dataclass
from typing import Callable


@dataclass
class OpTime:
    name: str
    us_per_iter: float
    calls_per_iter: float


@dataclass
class ProfileReport:
    device_total_us: float  # sum of top-level device program time per iter
    host_total_us: float
    ops: list[OpTime]  # device ops, descending by time
    trace_dir: str


def profile_op_times(fn: Callable[[], object], iters: int = 10,
                     trace_dir: str | None = None) -> ProfileReport:
    """Run fn() `iters` times under the profiler; aggregate device ops.

    fn should be pre-compiled (call it once before) so the trace holds
    steady-state executions, not compilation. With no explicit trace_dir
    the raw trace (tens of MB for a big pipeline) is parsed and DELETED —
    pass trace_dir to keep it for tensorboard.
    """
    import shutil

    import jax

    keep = trace_dir is not None
    trace_dir = trace_dir or tempfile.mkdtemp(prefix="bng-prof-")
    try:
        with jax.profiler.trace(trace_dir):
            out = None
            for _ in range(iters):
                out = fn()
            jax.block_until_ready(out)

        traces = sorted(glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*",
                         "*.trace.json.gz")))
        if not traces:
            return ProfileReport(0.0, 0.0, [],
                                 trace_dir if keep else "(discarded)")
        with gzip.open(traces[-1]) as f:
            tr = json.load(f)
    finally:
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir = "(discarded)"
    ev = tr.get("traceEvents", [])
    pids = {e["pid"]: e["args"].get("name", "") for e in ev
            if e.get("ph") == "M" and e.get("name") == "process_name"}

    dev_agg: dict[str, float] = defaultdict(float)
    dev_cnt: dict[str, int] = defaultdict(int)
    dev_top = 0.0
    host_top = 0.0
    for e in ev:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        where = pids.get(e["pid"], "")
        name = e["name"]
        if "TPU" in where or "GPU" in where or "device" in where.lower():
            if name.startswith("jit_") or name.startswith("pjit"):
                dev_top += e["dur"]
            else:
                dev_agg[name] += e["dur"]
                dev_cnt[name] += 1
        elif "CPU" in where and name.startswith("PjitFunction"):
            host_top += e["dur"]

    ops = [OpTime(n, d / iters, dev_cnt[n] / iters)
           for n, d in sorted(dev_agg.items(), key=lambda kv: -kv[1])]
    # NOTE: XLA:CPU emits no separate device track (only /host:CPU), so on
    # CPU this degrades to host dispatch totals — op attribution needs an
    # accelerator backend (the tool's purpose is the real chip anyway).
    return ProfileReport(device_total_us=dev_top / iters,
                         host_total_us=host_top / iters,
                         ops=ops, trace_dir=trace_dir)


@dataclass
class StepDurations:
    """Per-execution program durations from one profiled run.

    source: which trace signal supplied them —
      "device"   top-level jit_/pjit events on the accelerator track
                 (true device time, the <50us OFFER target's quantity)
      "cpu-exec" TfrtCpuExecutable::ExecuteHelper on the host track
                 (XLA:CPU per-execution runtime — no separate device
                 track exists there, this is the closest isolate)
    """

    us: list[float]
    source: str

    def __post_init__(self):
        self._sorted = None  # lazy sort cache, built once per instance

    def percentile(self, q: float) -> float:
        """Linearly interpolated percentile over a ONCE-sorted copy.

        Callers ask for several quantiles per run (p50/p99 per bench
        section); re-sorting per call was O(n log n) each time. Linear
        interpolation matches numpy.percentile's default method
        (pinned by tests/test_telemetry.py against numpy directly)."""
        if not self.us:
            return 0.0
        if self._sorted is None:
            import numpy as _np

            self._sorted = _np.sort(_np.asarray(self.us, dtype=_np.float64))
        s = self._sorted
        if not 0.0 <= q <= 100.0:
            raise ValueError(f"percentile q={q} outside [0, 100]")
        pos = (len(s) - 1) * (q / 100.0)
        lo = int(pos)
        frac = pos - lo
        if frac == 0.0 or lo + 1 >= len(s):
            return float(s[lo])
        return float(s[lo] + (s[lo + 1] - s[lo]) * frac)


def profile_step_durations(fn: Callable[[], object], iters: int = 50,
                           trace_dir: str | None = None) -> StepDurations:
    """Per-iteration execution durations of fn's jitted program.

    Where profile_op_times aggregates (mean us/iter), this keeps the
    DISTRIBUTION — the p99 the latency targets constrain is a tail
    statistic that an aggregate cannot recover. Blocked wall-clock
    timing includes host dispatch and the blocking sync; the profiler
    events isolate the execution itself. fn must be pre-compiled and should run
    exactly ONE jitted program per call (extra programs would interleave
    into the sample list).
    """
    import shutil

    import jax

    keep = trace_dir is not None
    trace_dir = trace_dir or tempfile.mkdtemp(prefix="bng-prof-")
    try:
        with jax.profiler.trace(trace_dir):
            out = None
            for _ in range(iters):
                out = fn()
            jax.block_until_ready(out)
        traces = sorted(glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*",
                         "*.trace.json.gz")))
        if not traces:
            return StepDurations([], "none")
        with gzip.open(traces[-1]) as f:
            tr = json.load(f)
    finally:
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
    ev = tr.get("traceEvents", [])
    pids = {e["pid"]: e["args"].get("name", "") for e in ev
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    device, cpu_exec = [], []
    for e in ev:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        where = pids.get(e["pid"], "")
        name = e["name"]
        if ("TPU" in where or "GPU" in where or "device" in where.lower()):
            if name.startswith("jit_") or name.startswith("pjit"):
                device.append((e.get("ts", 0), float(e["dur"])))
        elif name == "TfrtCpuExecutable::ExecuteHelper":
            cpu_exec.append((e.get("ts", 0), float(e["dur"])))
    for samples, source in ((device, "device"), (cpu_exec, "cpu-exec")):
        if samples:
            samples.sort()  # execution order, so warmup skew trims cleanly
            return StepDurations([d for _, d in samples], source)
    return StepDurations([], "none")


def format_report(r: ProfileReport, top: int = 15) -> str:
    lines = [f"device program: {r.device_total_us:9.1f} us/iter   "
             f"(host dispatch {r.host_total_us:.1f} us)   trace: {r.trace_dir}"]
    for op in r.ops[:top]:
        lines.append(f"  {op.us_per_iter:9.1f} us  x{op.calls_per_iter:4.1f}  {op.name}")
    return "\n".join(lines)


def _smoke() -> None:  # pragma: no cover - manual tool
    import jax
    import jax.numpy as jnp

    x = jnp.ones((4096, 4096), jnp.float32)
    f = jax.jit(lambda a: (a @ a).sum())
    jax.block_until_ready(f(x))
    print(format_report(profile_op_times(lambda: f(x), iters=5)))


if __name__ == "__main__":  # pragma: no cover
    _smoke()
