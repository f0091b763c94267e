"""Per-stage SLO engine — declarative latency budgets over the fixed
stage vocabulary, evaluated everywhere a stage histogram exists.

The paper's headline targets (>=100 Mpps NAT44+DHCP aggregate, p99
OFFER device time < 50us) were instrumented by PR 5 but enforced
nowhere: storm budgets lived as ad-hoc tuples inside chaos/storms.py
and `bng run` evaluated nothing live. This module is the ONE registry
those consumers now share. It judges a run; it keeps no history (the
record of the system's speed is the benchmark's, `benchmark/` and
`PERF_LEDGER.jsonl`):

- ``SLOSpec`` — a per-stage p99 budget (stage name validated against
  spans.STAGE_NAMES at construction: an SLO on a stage that does not
  exist is a configuration bug, not a silent no-op — Dapper's lesson
  that the unbudgeted stage is where the regression hides).
- ``DEFAULT_SLOS`` / ``HEADLINE_TARGETS`` — the shipped registry: one
  envelope per stage of the packet lifecycle plus the paper's headline
  numbers.
- ``evaluate(breakdown)`` — one-shot p99 verdict over a
  Tracer.breakdown() dict (loadtest reports, bench artifacts).
- ``SLOMonitor`` — the live half for `bng run`: rolling burn-rate
  windows over the armed tracer's stage histograms (windowed p99 from
  bucket-count deltas — the mergeable-histogram property pointed at
  time instead of workers), breach -> ``slo_breach`` flight-recorder
  trigger + the bng_slo_* metric families (control/metrics.py).
- ``BudgetLine`` / ``check_budget`` — the storm-suite budget checker,
  re-homed here from chaos/storms.py so storm budgets and production
  SLOs are one vocabulary. Verdict semantics are byte-identical to the
  PR-8 originals (mean-based, `per` amortization, required stages with
  zero samples FAIL as coverage holes) — the verify-chaos
  bit-determinism gate depends on that.

Thread model: SLOMonitor.tick runs on the `bng run` loop (under the
app's _ctl, like every other 1 Hz sweep); snapshot() is called from the
metrics scrape thread — both serialize on the monitor's own lock so the
concurrency pass (BNG060/062) can prove the discipline.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass

import numpy as np

from bng_tpu.telemetry import spans as tele
from bng_tpu.telemetry.hist import counts_percentile
from bng_tpu.telemetry.spans import LANE_NAMES, STAGE_NAMES

# the paper's headline targets (BASELINE.md / PAPER.md): the `device`
# envelope below is the first of them.
HEADLINE_TARGETS = {
    # <50us p99 for the device-only OFFER program @1M subscribers
    "offer_device_only_p99_us": 50.0,
    # >=100 Mpps aggregate on a v5e-8 = 12.5 Mpps per chip
    "mpps_per_chip_floor": 12.5,
}


def _valid_stage(stage: str) -> None:
    if stage not in STAGE_NAMES:
        raise ValueError(
            f"unknown stage {stage!r}: SLOs bind to the fixed span "
            f"vocabulary {STAGE_NAMES}")


@dataclass(frozen=True)
class SLOSpec:
    """One per-stage p99 latency budget.

    ``per`` amortizes batch-scoped laps over the units of work one lap
    covers (frames per batch), mirroring BudgetLine. ``required=False``
    stages are skipped when they recorded nothing: in `bng run` most
    device-side stages only exist under bench instrumentation, and a
    live monitor must not page on absent traffic.
    """

    stage: str
    p99_limit_us: float
    per: float = 1.0
    required: bool = False
    description: str = ""
    # "" = every lane's samples; a lane name = that lane's histogram only
    # (Tracer.lane_hist), so another lane's samples of the same stage
    # can neither breach nor dilute the budget
    lane: str = ""

    def __post_init__(self):
        _valid_stage(self.stage)
        if self.lane and self.lane not in LANE_NAMES:
            raise ValueError(f"SLOSpec({self.stage}): unknown lane "
                             f"{self.lane!r}, lanes are {LANE_NAMES}")
        if self.p99_limit_us <= 0 or self.per <= 0:
            raise ValueError(
                f"SLOSpec({self.stage}): limit and per must be positive")


# The shipped per-stage registry. Envelopes sit one to two orders above
# the CPU-dev observed means (PERF_NOTES §10/§12) so a healthy run can
# never flap, while a genuine order-of-magnitude excursion pages within
# burn_windows windows. `device` carries the paper target itself and
# reads the EXPRESS lane only: that lane's `device` samples are an
# express dispatch's occupancy BY READINESS on the served path (scheduler
# retire; an upper bound on execution: launch latency and the delay until
# the host looks are inside it; the outputs' copy is not, an armed retire
# blocks on its first output before it reads any: spans.py `ready`). Nothing
# else feeds that lane: a bulk step's sample goes to lane `bulk`. The
# target is NOT met today, so a `bng run --telemetry-enabled` monitor breaches
# `device` in every window with express traffic, and is meant to: on a
# v5e the served path reads p50 1,829 / p99 4,918 us against a fenced
# express step of 829 us (PERF.md §5, renew cell), until the step's cost
# (S-c) and the launch path come down. `--slo-budgets device:<us>` sets
# another limit and keeps the lane.
DEFAULT_SLOS: tuple[SLOSpec, ...] = (
    SLOSpec("ring", 5_000.0, description="ring pop + staging, per batch"),
    SLOSpec("admit", 2_000.0, description="admission verdicts, per batch"),
    SLOSpec("lane_wait", 50_000.0,
            description="scheduler enqueue -> dispatch (oldest frame)"),
    SLOSpec("dispatch", 50_000.0, description="host-side jitted dispatch"),
    SLOSpec("device", HEADLINE_TARGETS["offer_device_only_p99_us"],
            lane="express",
            description="express dispatch on the device, by readiness "
                        "(paper target)"),
    SLOSpec("device_wait", 200_000.0,
            description="host blocked forcing device outputs"),
    SLOSpec("fleet", 100_000.0, description="slow-path scatter/gather"),
    SLOSpec("worker", 20_000.0, description="per-frame worker handler"),
    SLOSpec("slow_path", 200_000.0, description="slow-path drain total"),
    SLOSpec("reply", 20_000.0, description="verdict demux + reply encode"),
    SLOSpec("ops", 2_000_000.0,
            description="zero-downtime transition phases"),
    SLOSpec("wire_rx", 5_000.0,
            description="wire pump ingress: kernel fill+RX -> ring "
                        "submit, per pump round"),
    SLOSpec("wire_tx", 5_000.0,
            description="wire pump egress: ring verdicts -> kernel TX "
                        "+ completion reap, per pump round"),
    SLOSpec("beat", 500_000.0, description="one drive_once, entry to exit"),
    SLOSpec("pack", 20_000.0,
            description="frame packing + flag columns before a dispatch"),
    SLOSpec("drain", 200_000.0,
            description="table-update work outside a step"),
    SLOSpec("tx", 20_000.0, description="completions -> TX ring, per beat"),
    SLOSpec("sojourn", 1_000_000.0,
            description="per frame, enqueue -> completion"),
    SLOSpec("upload", 200_000.0,
            description="host inside host-to-device calls of a batch "
                        "(time to return, not to land)"),
    SLOSpec("fetch", 200_000.0,
            description="host inside device-to-host reads of a batch's "
                        "outputs, after they were seen ready"),
    SLOSpec("mirror", 200_000.0,
            description="host handing a retired batch's mirrored lanes to "
                        "the intercept sink"),
    SLOSpec("punt", 200_000.0,
            description="host serving one frame NAT punted for a new flow: "
                        "the create and the hand-back for its second pass"),
    SLOSpec("total", 500_000.0, description="batch begin -> end"),
)


def parse_budgets(specs: list[str]) -> tuple[SLOSpec, ...]:
    """Parse `stage:limit_us[:per]` strings into SLOSpecs — the
    `bng run --slo-budgets` / config-file `slo_budgets:` override
    surface. Unknown stages raise loudly."""
    out = []
    for s in specs:
        parts = s.split(":")
        if len(parts) not in (2, 3):
            raise ValueError(
                f"bad SLO budget {s!r}: want stage:limit_us[:per]")
        stage, limit = parts[0], float(parts[1])
        per = float(parts[2]) if len(parts) == 3 else 1.0
        # an override moves the limit, not which lane's samples it judges
        lane = next((d.lane for d in DEFAULT_SLOS if d.stage == stage), "")
        out.append(SLOSpec(stage, limit, per=per, lane=lane))
    return tuple(out)


def evaluate(breakdown: dict, slos: tuple[SLOSpec, ...] = DEFAULT_SLOS) -> dict:
    """One-shot p99 verdict over a Tracer.breakdown() dict.

    Same report shape as check_budget (ok + sorted breach names, with
    `stage:missing` for required stages that recorded nothing) so
    loadtest JSON, bench artifacts and storm reports stay diffable with
    one vocabulary."""
    breaches = []
    # a lane-bound spec reads `stage@lane` (Tracer.breakdown(lanes=True));
    # a breakdown without lane entries is read merged
    by_lane = any("@" in k for k in breakdown)
    for spec in slos:
        s = breakdown.get(f"{spec.stage}@{spec.lane}"
                          if spec.lane and by_lane else spec.stage)
        if s is None:
            if spec.required:
                breaches.append(f"{spec.stage}:missing")
            continue
        if s["p99_us"] / spec.per > spec.p99_limit_us:
            breaches.append(spec.stage)
    return {"ok": not breaches, "breaches": sorted(breaches)}


# ---------------------------------------------------------------------------
# storm budgets (re-homed from chaos/storms.py — PR 8) — the mean-based
# envelope checker the deterministic storm reports embed. Kept verbatim:
# the verify-chaos gate compares report bytes across runs and across the
# re-home, so the verdict dict must not change by a byte.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BudgetLine:
    """One stage envelope: the stage's mean lap, divided by `per` (the
    units of work one lap covers — frames per batch for batch-scoped
    stages), must stay under `limit_us`. `required` stages must have
    samples at all: a storm whose instrumented stage recorded NOTHING
    is a coverage hole, not a pass."""

    stage: str
    limit_us: float
    per: float = 1.0
    required: bool = True

    def __post_init__(self):
        _valid_stage(self.stage)


def check_budget(tracer, lines: tuple[BudgetLine, ...]) -> dict:
    """Evaluate the envelope. Only deterministic facts reach the report:
    the verdict and WHICH stages breached — measured values go to the
    flight recorder / PERF_NOTES, never into the bit-compared bytes."""
    bd = tracer.breakdown() if tracer is not None else {}
    breaches = []
    for ln in lines:
        s = bd.get(ln.stage)
        if s is None:
            if ln.required:
                breaches.append(f"{ln.stage}:missing")
            continue
        if s["mean_us"] / ln.per > ln.limit_us:
            breaches.append(ln.stage)
    if breaches:
        tele.trigger("slo_breach",
                     f"storm budget breached: {sorted(breaches)}")
    return {"ok": not breaches, "breaches": sorted(breaches)}


# ---------------------------------------------------------------------------
# live burn-rate monitor (`bng run`)
# ---------------------------------------------------------------------------

# windowed percentiles evaluate hist.py's shared rank/cumsum/midpoint
# core directly on bucket-count DELTAS (counts_now - window_start) —
# one implementation, so the monitor's p99 can never drift from every
# other p99 in the system
_counts_percentile = counts_percentile


class SLOMonitor:
    """Rolling burn-rate evaluation of per-stage SLOs over the armed
    tracer's histograms.

    Every `window_s` seconds the monitor diffs each budgeted stage's
    bucket counts against the previous window boundary (mergeable
    histograms subtract as cleanly as they add) and computes the
    WINDOWED p99 — not the since-boot p99, which dilutes a fresh
    regression under hours of healthy history. A stage whose windowed
    p99 exceeds its budget for `burn_windows` consecutive windows is a
    breach: the `slo_breach` flight-recorder trigger fires (the last-N
    batch records around the breach are the evidence) and the breach
    counter increments (bng_slo_breaches_total). Windows with fewer
    than `min_samples` laps are skipped — no traffic is not a breach.
    """

    min_samples = 16

    def __init__(self, tracer, slos: tuple[SLOSpec, ...] = DEFAULT_SLOS,
                 window_s: float = 30.0, burn_windows: int = 2,
                 clock=time.monotonic):
        self.tracer = tracer
        self.slos = tuple(slos)
        self.window_s = float(window_s)
        self.burn_windows = max(1, int(burn_windows))
        self.clock = clock
        self._lock = threading.Lock()
        self._win_start: float | None = None
        self._snap: dict[str, np.ndarray] = {}
        self._burning: dict[str, int] = {s.stage: 0 for s in self.slos}
        self._window_p99: dict[str, float] = {}
        self.breaches: dict[str, int] = {s.stage: 0 for s in self.slos}
        self.windows_evaluated = 0

    def _counts(self, spec: SLOSpec) -> np.ndarray:
        """The bucket counts the spec judges: the stage's merged
        histogram, or its lane's alone."""
        i = STAGE_NAMES.index(spec.stage)
        if not spec.lane:
            return self.tracer.stage_hist(i).counts
        return self.tracer.lane_hist(LANE_NAMES.index(spec.lane), i).counts

    def tick(self, now: float | None = None) -> list[str]:
        """Evaluate the window if it elapsed; returns the stages that
        breached this tick (empty most of the time). Called from the
        run loop's 1 Hz heartbeat."""
        now = now if now is not None else self.clock()
        with self._lock:
            breached = self._tick_locked(now)
        if breached:
            tele.trigger("slo_breach",
                         f"burn-rate breach ({self.burn_windows} windows "
                         f"x {self.window_s:.0f}s): {sorted(breached)}")
        return breached

    def _tick_locked(self, now: float) -> list[str]:
        if self._win_start is None:
            self._win_start = now
            for spec in self.slos:
                self._snap[spec.stage] = self._counts(spec).copy()
            return []
        if now - self._win_start < self.window_s:
            return []
        self._win_start = now
        self.windows_evaluated += 1
        breached = []
        for spec in self.slos:
            counts = self._counts(spec)
            prev = self._snap.get(spec.stage)
            delta = counts - prev if prev is not None else counts.copy()
            self._snap[spec.stage] = counts.copy()
            n = int(delta.sum())
            if n < self.min_samples:
                self._burning[spec.stage] = 0
                self._window_p99.pop(spec.stage, None)
                continue
            p99 = _counts_percentile(delta, 99.0)
            self._window_p99[spec.stage] = p99
            if p99 / spec.per > spec.p99_limit_us:
                self._burning[spec.stage] += 1
            else:
                self._burning[spec.stage] = 0
            if self._burning[spec.stage] >= self.burn_windows:
                self.breaches[spec.stage] += 1
                self._burning[spec.stage] = 0  # re-arm for the next burn
                breached.append(spec.stage)
        return breached

    def snapshot(self) -> dict:
        """Scrape-thread view (control/metrics.py collect_slo)."""
        with self._lock:
            return {
                "windows": self.windows_evaluated,
                "window_s": self.window_s,
                "burn_windows": self.burn_windows,
                "budgets_us": {s.stage: s.p99_limit_us for s in self.slos},
                "window_p99_us": dict(self._window_p99),
                "burning": dict(self._burning),
                "breaches": dict(self.breaches),
                "ok": not any(self._burning.values()),
            }
