"""Telemetry subsystem: span tracing, flight recorder, mergeable
stage-latency histograms (see spans.py / recorder.py / hist.py).

Import surface: `from bng_tpu.telemetry import spans` at instrumented
call sites (module-level hooks, fault_point-style disarmed cost);
Tracer/FlightRecorder/LatencyHist here for composition roots. The SLO
engine (slo.py) is imported as a submodule by its consumers.
"""

from bng_tpu.telemetry.hist import LatencyHist, NBUCKETS
from bng_tpu.telemetry.recorder import (FlightRecorder, RecorderConfig,
                                        chrome_trace, default_trace_dir)
from bng_tpu.telemetry.slo import (DEFAULT_SLOS, HEADLINE_TARGETS,
                                   BudgetLine, SLOMonitor, SLOSpec,
                                   check_budget)
from bng_tpu.telemetry.spans import (NSTAGES, STAGE_NAMES, Tracer, arm,
                                     armed, disarm)

__all__ = [
    "LatencyHist", "NBUCKETS", "FlightRecorder", "RecorderConfig",
    "chrome_trace", "default_trace_dir", "NSTAGES", "STAGE_NAMES",
    "Tracer", "arm", "armed", "disarm", "SLOSpec", "SLOMonitor",
    "DEFAULT_SLOS", "HEADLINE_TARGETS", "BudgetLine", "check_budget",
]
