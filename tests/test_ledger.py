"""Perf-ledger + gate tests (telemetry/ledger.py): schema append,
legacy import round-trip, cohort identity (CPU-vs-TPU refusal), and the
trend gate detecting a planted single-stage 2x p99 regression — named,
and including the non-headline stages (`lane_wait`, `device_wait`,
`fleet`). Runs jax-free; `make verify-perf` runs the `perf` marker."""

from __future__ import annotations

import copy
import json
import os
import shutil

import pytest

from bng_tpu.telemetry import ledger

pytestmark = pytest.mark.perf

REPO_LEDGER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench_runs.jsonl")

STAGES = {"dispatch": 100.0, "device": 40.0, "device_wait": 500.0,
          "lane_wait": 30.0, "fleet": 200.0, "worker": 80.0,
          "total": 800.0}


def _tpu_line(i: int, scale: float = 1.0) -> dict:
    """One current-era schema'd TPU line with a full stage breakdown
    (what a healthy post-PR bench round appends)."""
    return {
        "schema_version": 1, "run_id": f"r{i:02d}",
        "metric": "Mpps/chip DHCP+NAT44 fast path",
        "value": 0.05 * scale, "unit": "Mpps",
        "batch": 8192, "subscribers": 1_000_000, "flows": 1_000_000,
        "offer_device_only_p99_us": 45.0,
        "device": "TPU v5e chip0",
        "env": {"platform": "tpu", "device_kind": "TPU v5e",
                "host": "tpu-host", "jaxlib": "0.4.37"},
        "stage_breakdown": {
            s: {"count": 200, "p50_us": v / 2,
                "p99_us": v * (1 + 0.02 * i), "p999_us": v * 1.2,
                "mean_us": v / 2, "max_us": v * 1.3}
            for s, v in STAGES.items()},
    }


def _cohort(n: int = 5) -> list[dict]:
    return [_tpu_line(i) for i in range(n)]


@pytest.fixture
def real_lines():
    return ledger.read(REPO_LEDGER)


# ---------------------------------------------------------------------------
# acceptance: the repo's real ledger
# ---------------------------------------------------------------------------

class TestRealLedger:
    def test_gate_real_ledger_clean(self):
        rep = ledger.gate_file(REPO_LEDGER)
        assert rep.rc == ledger.GATE_OK, rep.to_dict()

    def test_cli_gate_real_ledger_rc0(self, capsys):
        from bng_tpu.cli import main

        rc = main(["perf", "gate", "--ledger", REPO_LEDGER])
        assert rc == 0
        assert "CLEAN" in capsys.readouterr().out

    @pytest.mark.parametrize("stage",
                             ["lane_wait", "device_wait", "fleet",
                              "dispatch", "device"])
    def test_planted_2x_single_stage_regression_named(self, stage,
                                                      tmp_path):
        """The acceptance shape: real ledger + a current-era cohort +
        ONE line whose single stage p99 doubled — the gate exits
        non-zero and NAMES the stage, headline or not."""
        path = str(tmp_path / "ledger.jsonl")
        shutil.copyfile(REPO_LEDGER, path)
        for line in _cohort():
            ledger.append(path, line)
        bad = _tpu_line(9)
        bad["stage_breakdown"][stage]["p99_us"] *= 2
        ledger.append(path, bad)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_REGRESSION
        assert [r["key"] for r in rep.regressions] == [f"stage:{stage}"]

    def test_clean_candidate_after_cohort(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        shutil.copyfile(REPO_LEDGER, path)
        for line in _cohort() + [_tpu_line(9)]:
            ledger.append(path, line)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_OK, rep.to_dict()
        # every stage was actually trend-checked, not just the headline
        checked = set(rep.checked)
        assert {f"stage:{s}" for s in STAGES} <= checked
        assert "value" in checked
        assert "offer_device_only_p99_us" in checked


# ---------------------------------------------------------------------------
# cohort identity: backend / geometry refusal
# ---------------------------------------------------------------------------

class TestCohorts:
    def test_cpu_fallback_never_scored_against_tpu(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        for line in _cohort():
            ledger.append(path, line)
        cpu = _tpu_line(9)
        cpu["backend_fallback"] = "cpu"
        cpu["device"] = "TFRT_CPU_0"
        cpu["env"] = {"platform": "cpu", "device_kind": "TFRT_CPU"}
        ledger.append(path, cpu)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_INCOMPARABLE
        assert "refusing the cross-identity comparison" in rep.notes[0]

    def test_pallas_never_scored_against_xla_history(self, tmp_path):
        """ISSUE 11: the table-probe impl is cohort identity. A Pallas
        candidate against an xla-only history (legacy lines default to
        xla) is the rc=3 refusal, never a silent comparison."""
        path = str(tmp_path / "ledger.jsonl")
        for line in _cohort():
            ledger.append(path, line)  # no table_impl stamp -> 'xla'
        pallas = _tpu_line(9, scale=5.0)  # looks like a huge regression
        pallas["table_impl"] = "pallas"
        ledger.append(path, pallas)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_INCOMPARABLE
        assert "'pallas'" in rep.notes[0]
        assert "xla" in rep.notes[0]

    def test_pallas_cohort_gates_within_itself(self, tmp_path):
        """Once Pallas history exists, a regressed Pallas run is caught
        against ITS cohort (and the xla lines never dilute it)."""
        path = str(tmp_path / "ledger.jsonl")
        for line in _cohort():
            ledger.append(path, line)  # xla history at scale 1.0
        for i in range(4):  # pallas cohort: 2x the xla throughput
            ln = _tpu_line(20 + i, scale=2.0)
            ln["table_impl"] = "pallas"
            ledger.append(path, ln)
        bad = _tpu_line(30, scale=1.1)  # ~45% below the pallas median,
        bad["table_impl"] = "pallas"    # yet still above xla's history
        ledger.append(path, bad)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_REGRESSION, rep.to_dict()

    def test_cluster_width_is_cohort_identity(self, tmp_path):
        """ISSUE 16: instance count joins the cohort key. A 4-instance
        cluster headline against single-instance history (legacy lines
        default to 1) is the rc=3 refusal naming both widths — a
        cluster aggregate is a different machine, not a 4x win."""
        path = str(tmp_path / "ledger.jsonl")
        for line in _cohort():
            ledger.append(path, line)  # no n_instances stamp -> 1
        wide = _tpu_line(9, scale=4.0)
        wide["n_instances"] = 4
        ledger.append(path, wide)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_INCOMPARABLE
        assert "instances=4" in rep.notes[0]
        assert "instances=1" in rep.notes[0]

    def test_cluster_width_gates_within_itself(self, tmp_path):
        """Once 4-instance history exists, a regressed 4-instance run is
        caught against ITS cohort."""
        path = str(tmp_path / "ledger.jsonl")
        for i in range(4):
            ln = _tpu_line(40 + i, scale=4.0)
            ln["n_instances"] = 4
            ledger.append(path, ln)
        bad = _tpu_line(50, scale=2.0)  # half the cluster trend
        bad["n_instances"] = 4
        ledger.append(path, bad)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_REGRESSION, rep.to_dict()

    def test_autotune_depth_is_cohort_identity(self, tmp_path):
        """Sweep points differing only in pipeline depth are different
        operating points: a depth-2 point must not be trend-gated
        against depth-8 history (a fabricated 2-4x 'regression')."""
        path = str(tmp_path / "ledger.jsonl")
        for i in range(4):  # depth-8 history: 4x the depth-2 throughput
            ledger.append(path, {
                "metric": "autotune sweep point", "value": 40.0,
                "unit": "Mpps", "batch": 8192, "depth": 8,
                "table_impl": "pallas",
                "env": {"platform": "tpu", "device_kind": "TPU v5e"},
                "device": "TPU v5e chip0"})
        point = {"metric": "autotune sweep point", "value": 10.0,
                 "unit": "Mpps", "batch": 8192, "depth": 2,
                 "table_impl": "pallas",
                 "env": {"platform": "tpu", "device_kind": "TPU v5e"},
                 "device": "TPU v5e chip0"}
        ledger.append(path, point)
        rep = ledger.gate_file(path)
        # different cohort (depth differs) -> vacuous pass, never rc=1/3
        assert rep.rc == ledger.GATE_OK, rep.to_dict()
        assert rep.cohort_n == 0

    def test_host_class_lines_never_impl_split(self, tmp_path):
        """A pure-host metric (config-1 control plane: no device, no
        table probe) keeps ONE cohort whatever BNG_TABLE_IMPL said —
        the stamp cannot affect the metric, so it must not void the
        regression history behind an rc=3 refusal."""
        path = str(tmp_path / "ledger.jsonl")
        for i in range(4):
            ledger.append(path, {
                "metric": "DHCP slow-path req/s (config 1)",
                "value": 50_000.0, "unit": "req/s",
                "env": {"host": "h", "jaxlib": "0.4.37"}})
        bad = {"metric": "DHCP slow-path req/s (config 1)",
               "value": 20_000.0, "unit": "req/s",
               "table_impl": "pallas",  # stamped, but host-class
               "env": {"host": "h", "jaxlib": "0.4.37",
                       "table_impl": "pallas"}}
        ledger.append(path, bad)
        assert ledger.backend_class(bad) == "host"
        assert ledger.table_impl(bad) == "xla"
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_REGRESSION, rep.to_dict()

    def test_vector_host_path_never_scored_against_scalar(self, tmp_path):
        """ISSUE 14: the host serving path is cohort identity. A
        vectorized-host candidate against scalar-only history (legacy
        lines default to scalar) is the rc=3 refusal naming BOTH host
        paths, never a silent comparison."""
        path = str(tmp_path / "ledger.jsonl")
        for line in _cohort():
            ledger.append(path, line)  # no host_path stamp -> 'scalar'
        vec = _tpu_line(9, scale=5.0)  # looks like a huge regression
        vec["host_path"] = "vector"
        ledger.append(path, vec)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_INCOMPARABLE
        assert "host='vector'" in rep.notes[0]
        assert "host=scalar" in rep.notes[0]
        assert "vectorized host path never trends" in rep.notes[0]

    def test_host_path_cohort_gates_within_itself(self, tmp_path):
        """Once vector-host history exists, a regressed vector run is
        caught against ITS cohort."""
        path = str(tmp_path / "ledger.jsonl")
        for line in _cohort():
            ledger.append(path, line)  # scalar history
        for i in range(4):
            ln = _tpu_line(20 + i, scale=2.0)
            ln["host_path"] = "vector"
            ledger.append(path, ln)
        bad = _tpu_line(30, scale=1.1)
        bad["host_path"] = "vector"
        ledger.append(path, bad)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_REGRESSION, rep.to_dict()

    def test_host_path_env_spelling_reaches_cohort(self):
        a = {"metric": "m", "value": 1.0, "unit": "Mpps", "batch": 64,
             "device": "TPU v5e_0", "host_path": "vector"}
        b = {"metric": "m", "value": 1.0, "unit": "Mpps", "batch": 64,
             "device": "TPU v5e_0", "env": {"host_path": "vector"}}
        assert ledger.cohort_key(a) == ledger.cohort_key(b)
        assert ledger.host_path({"metric": "m"}) == "scalar"  # legacy

    def test_env_fingerprint_table_impl_reaches_cohort(self, tmp_path):
        """The bench emitters stamp table_impl inside env too; either
        spelling lands in the same cohort key."""
        a = {"metric": "m", "value": 1.0, "unit": "Mpps", "batch": 64,
             "device": "TPU v5e_0", "table_impl": "pallas"}
        b = {"metric": "m", "value": 1.0, "unit": "Mpps", "batch": 64,
             "device": "TPU v5e_0", "env": {"table_impl": "pallas"}}
        assert ledger.cohort_key(a) == ledger.cohort_key(b)
        assert ledger.table_impl({"metric": "m"}) == "xla"  # legacy default

    def test_young_same_backend_cohort_is_vacuous_not_refused(
            self, tmp_path):
        """After a backend migration (cpu history, first tpu runs) a
        merely YOUNG same-backend cohort passes vacuously; rc=3 is
        reserved for ZERO same-backend history (review finding,
        reproduced): only run 1 on the new backend refuses, runs 2+
        accumulate history instead of staying CI-red."""
        path = str(tmp_path / "l.jsonl")
        cpu_lines = _cohort()
        for line in cpu_lines:
            line = dict(line)
            line["device"] = "TFRT_CPU_0"
            line["env"] = {"platform": "cpu", "device_kind": "cpu"}
            ledger.append(path, line)
        # run 1 on tpu: zero tpu history -> explicit refusal
        ledger.append(path, _tpu_line(7))
        assert ledger.gate_file(path).rc == ledger.GATE_INCOMPARABLE
        # run 2: one tpu line exists -> young cohort, vacuous pass
        ledger.append(path, _tpu_line(8))
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_OK
        assert any("cohort too small" in n for n in rep.notes)

    def test_fallback_flag_wins_over_healthy_looking_fields(self):
        line = _tpu_line(0)
        line["backend_fallback"] = "cpu"
        assert ledger.backend_class(line) == "cpu"

    def test_no_device_is_host_class(self):
        assert ledger.backend_class({"metric": "m"}) == "host"

    def test_device_kind_strips_ordinal(self):
        assert ledger.device_kind({"device": "TFRT_CPU_0"}) == "TFRT_CPU"
        assert ledger.device_kind(
            {"env": {"device_kind": "TPU v5e"}}) == "TPU v5e"

    def test_device_kind_prefers_device_string_for_continuity(self):
        """A new-schema line carries BOTH the legacy `device` string and
        the jax env.device_kind spelling ('cpu'); the cohort key must
        follow the `device` string or every new run silently loses its
        legacy cohort and the gate passes vacuously (review finding,
        reproduced against the real ledger)."""
        new = {"device": "TFRT_CPU_0",
               "env": {"device_kind": "cpu", "platform": "cpu"}}
        legacy = ledger.normalize_legacy({"device": "TFRT_CPU_0"})
        assert ledger.device_kind(new) == ledger.device_kind(legacy)

    def test_new_schema_line_cohorts_with_legacy_history(self, tmp_path):
        """End to end: a regressed new-schema headline run on the same
        host/device as the legacy history must be SCORED against it,
        not vacuously passed."""
        path = str(tmp_path / "l.jsonl")
        shutil.copyfile(REPO_LEDGER, path)
        bad = {"metric": "Mpps/chip DHCP+NAT44 fast path",
               "value": 0.0003, "unit": "Mpps",  # ~10x under the trend
               "batch": 512, "subscribers": 2000, "flows": 2000,
               "device": "TFRT_CPU_0",
               "env": {"platform": "cpu", "device_kind": "cpu",
                       "host": "h", "jaxlib": "0.4.36"}}
        ledger.append(path, bad)
        rep = ledger.gate_file(path)
        assert rep.cohort_n >= 3, rep.to_dict()
        assert rep.rc == ledger.GATE_REGRESSION
        assert rep.regressions[0]["key"] == "value"

    def test_geometry_splits_cohorts(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        for line in _cohort():
            ledger.append(path, line)
        other = _tpu_line(9)
        other["batch"] = 512  # different geometry: not comparable
        other["stage_breakdown"]["fleet"]["p99_us"] *= 10
        ledger.append(path, other)
        rep = ledger.gate_file(path)
        # no same-geometry history at all -> vacuous pass, never a
        # cross-geometry comparison
        assert rep.rc == ledger.GATE_OK
        assert any("cohort too small" in n for n in rep.notes)

    def test_young_ledger_vacuous_pass(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        ledger.append(path, _tpu_line(0))
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_OK
        assert any("cohort too small" in n for n in rep.notes)


# ---------------------------------------------------------------------------
# gate coverage beyond stages
# ---------------------------------------------------------------------------

class TestGateKeys:
    def test_headline_value_regression(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        for line in _cohort():
            ledger.append(path, line)
        slow = _tpu_line(9)
        slow["value"] = 0.02  # Mpps halved-and-then-some
        ledger.append(path, slow)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_REGRESSION
        assert rep.regressions[0]["key"] == "value"
        assert rep.regressions[0]["direction"] == "higher-better"

    def test_offer_device_p99_regression(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        for line in _cohort():
            ledger.append(path, line)
        bad = _tpu_line(9)
        bad["offer_device_only_p99_us"] = 95.0
        ledger.append(path, bad)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_REGRESSION
        assert rep.regressions[0]["key"] == "offer_device_only_p99_us"

    def test_vanished_stage_is_a_coverage_hole(self, tmp_path):
        """Dapper's failure mode: a stage every cohort line carries
        disappearing from the candidate is flagged, not ignored."""
        path = str(tmp_path / "ledger.jsonl")
        for line in _cohort():
            ledger.append(path, line)
        hole = _tpu_line(9)
        del hole["stage_breakdown"]["lane_wait"]
        ledger.append(path, hole)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_REGRESSION
        assert rep.regressions[0]["key"] == "stage:lane_wait"
        assert "coverage hole" in rep.regressions[0]["detail"]

    def test_untraced_candidate_is_a_note_not_a_regression(self,
                                                           tmp_path):
        """A candidate with NO stage_breakdown (loadtest without
        --trace) against a traced cohort must not fabricate a
        coverage-hole regression per stage — it gets a loud note and
        the headline checks still run (review finding, reproduced)."""
        path = str(tmp_path / "l.jsonl")
        for line in _cohort():
            ledger.append(path, line)
        plain = _tpu_line(9)
        del plain["stage_breakdown"]
        ledger.append(path, plain)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_OK, rep.to_dict()
        assert any("no stage_breakdown" in n for n in rep.notes)
        assert "value" in rep.checked  # headline still trended

    def test_2x_always_trips_even_in_noisy_cohort(self, tmp_path):
        """The hard cap bounds tolerated excess at 90% of the median:
        a 2x regression can never hide inside cohort noise."""
        path = str(tmp_path / "ledger.jsonl")
        # wildly noisy cohort: p99 swings 3x run to run
        for i, scale in enumerate((0.5, 1.0, 1.5, 0.7, 1.3)):
            line = _tpu_line(i)
            line["stage_breakdown"]["fleet"]["p99_us"] = 200.0 * scale
            ledger.append(path, line)
        bad = _tpu_line(9)
        bad["stage_breakdown"]["fleet"]["p99_us"] = 2 * 200.0  # 2x median
        ledger.append(path, bad)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_REGRESSION
        assert "stage:fleet" in [r["key"] for r in rep.regressions]

    def test_newest_gateable_index(self):
        """A caller ties its verdict to THIS run by comparing this
        index against the pre-run line count: an error-only or
        append-less run must never earn a CLEAN verdict about stale
        history."""
        lines = [_tpu_line(0), _tpu_line(1),
                 {"metric": "m", "value": 0.0, "error": "child rc=1"}]
        assert ledger.newest_gateable_index(lines) == 1
        assert ledger.newest_gateable_index(
            [{"metric": "m", "error": "x"}]) is None
        assert ledger.newest_gateable_index([]) is None

    def test_error_lines_never_gate_or_serve_as_history(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        for line in _cohort() + [_tpu_line(9)]:
            ledger.append(path, line)
        ledger.append(path, {"metric": "Mpps/chip DHCP+NAT44 fast path",
                             "value": 0.0, "unit": "Mpps",
                             "error": "child rc=1"})
        rep = ledger.gate_file(path)
        # candidate is the last GATEABLE line, and it is clean
        assert rep.rc == ledger.GATE_OK
        assert rep.candidate["run_id"] == "r09"


# ---------------------------------------------------------------------------
# schema append / read / legacy import round-trip
# ---------------------------------------------------------------------------

class TestSchema:
    def test_append_stamps_schema(self, tmp_path):
        path = str(tmp_path / "l.jsonl")
        stamped = ledger.append(path, {"metric": "m", "value": 1.0})
        assert stamped["schema_version"] == ledger.SCHEMA_VERSION
        assert stamped["run_id"] and stamped["ts"]
        back = ledger.read(path)
        assert back[0] == stamped
        # ts leads the line (the bench_runs.jsonl convention)
        raw = open(path).read()
        assert raw.startswith('{"ts":')

    def test_corrupt_line_noted_not_fatal(self, tmp_path):
        path = str(tmp_path / "l.jsonl")
        for line in _cohort() + [_tpu_line(9)]:
            ledger.append(path, line)
        with open(path, "a") as f:
            f.write("{not json\n")
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_OK
        assert any("corrupt" in n for n in rep.notes)

    def test_unreadable_ledger_rc2(self):
        rep = ledger.gate_file("/nonexistent/ledger.jsonl")
        assert rep.rc == ledger.GATE_INTERNAL

    def test_import_round_trip(self, real_lines, tmp_path):
        migrated = ledger.import_legacy(real_lines)
        assert len(migrated) == len(real_lines)
        assert all(ln["schema_version"] == 0 for ln in migrated)
        assert all(ln["run_id"].startswith("legacy-") for ln in migrated)
        # every original field survives the migration
        for orig, mig in zip(real_lines, migrated):
            for k, v in orig.items():
                assert mig[k] == v
        # device-bearing lines recover a device_kind fingerprint
        dev = [m for o, m in zip(real_lines, migrated) if o.get("device")]
        assert dev and all(
            m["env"]["device_kind"] == "TFRT_CPU" for m in dev)
        # idempotent: importing the migrated set changes nothing
        again = ledger.import_legacy(migrated)
        assert again == migrated
        # and the migrated ledger still gates clean
        path = str(tmp_path / "migrated.jsonl")
        with open(path, "w") as f:
            for ln in migrated:
                f.write(json.dumps(ln) + "\n")
        assert ledger.gate_file(path).rc == ledger.GATE_OK

    def test_gate_can_exclude_legacy(self, tmp_path):
        """The schema_version 0 tag is the explicit include-or-exclude
        handle: --no-legacy drops pre-schema lines from cohorts."""
        path = str(tmp_path / "l.jsonl")
        shutil.copyfile(REPO_LEDGER, path)
        rep = ledger.gate_file(path, include_legacy=False)
        assert rep.rc == ledger.GATE_OK
        assert any("nothing to gate" in n for n in rep.notes)

    def test_cli_import_writes_out(self, tmp_path, capsys):
        from bng_tpu.cli import main

        out = str(tmp_path / "migrated.jsonl")
        rc = main(["perf", "import", "--ledger", REPO_LEDGER,
                   "--out", out])
        assert rc == 0
        lines = ledger.read(out)
        assert len(lines) == len(ledger.read(REPO_LEDGER)) >= 54
        assert all("schema_version" in ln for ln in lines)

    def test_cli_gate_rc_contract(self, tmp_path, capsys):
        """rc=1 regression via the CLI (the documented contract)."""
        from bng_tpu.cli import main

        path = str(tmp_path / "l.jsonl")
        for line in _cohort():
            ledger.append(path, line)
        bad = _tpu_line(9)
        bad["stage_breakdown"]["fleet"]["p99_us"] *= 2
        ledger.append(path, bad)
        rc = main(["perf", "gate", "--ledger", path, "--json"])
        out = capsys.readouterr()
        assert rc == 1
        assert "stage:fleet" in out.err
        doc = json.loads(out.out)
        assert doc["rc"] == 1 and not doc["ok"]


class TestFingerprint:
    def test_fingerprint_never_imports_jax(self):
        """config-1 calls this before any backend probe: the
        fingerprint must read only already-imported state."""
        import subprocess
        import sys

        code = (
            "import sys; "
            "from bng_tpu.telemetry.ledger import environment_fingerprint;"
            "env = environment_fingerprint(); "
            "assert 'jax' not in sys.modules, 'fingerprint imported jax'; "
            "assert env.get('host'); print('ok')"
        )
        res = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=60)
        assert res.returncode == 0, res.stderr
        assert "ok" in res.stdout

    def test_fingerprint_with_jax_loaded(self):
        env = ledger.environment_fingerprint()
        assert env["host"]
        # conftest initialized jax on cpu: device identity rides along
        assert env.get("platform") == "cpu"


# ---------------------------------------------------------------------------
# express_loop cohort identity of recorded lines
# ---------------------------------------------------------------------------

_STAGES = {"dispatch": 100.0, "device": 40.0, "total": 800.0}


def _line(i: int, scale: float = 1.0) -> dict:
    return {
        "schema_version": 1, "run_id": f"dl{i:02d}",
        "metric": "Mpps/chip DHCP+NAT44 fast path",
        "value": 0.05 * scale, "unit": "Mpps",
        "batch": 8192, "subscribers": 1_000_000, "flows": 1_000_000,
        "device": "TPU v5e chip0",
        "env": {"platform": "tpu", "device_kind": "TPU v5e"},
        "stage_breakdown": {
            s: {"count": 200, "p50_us": v / 2, "p99_us": v * (1 + 0.02 * i),
                "p999_us": v * 1.2, "mean_us": v / 2, "max_us": v * 1.3}
            for s, v in _STAGES.items()},
    }


class TestExpressLoopCohort:
    def test_accessor_defaults_to_per_batch(self):
        assert ledger.express_loop({}) == "per-batch"
        assert ledger.express_loop({"express_loop": "devloop"}) == "devloop"

    def test_devloop_never_scored_against_per_batch_history(self,
                                                            tmp_path):
        """The loop changes what a `dispatch` lap measures (one batch
        vs an amortized ring share): rc=3 refusal, never a trend."""
        path = str(tmp_path / "ledger.jsonl")
        for i in range(5):
            ledger.append(path, _line(i))  # unstamped -> per-batch
        cand = _line(9, scale=5.0)  # would look like a huge move
        cand["express_loop"] = "devloop"
        ledger.append(path, cand)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_INCOMPARABLE
        assert "devloop" in rep.notes[0]

    def test_devloop_cohort_gates_within_itself(self, tmp_path):
        path = str(tmp_path / "ledger.jsonl")
        for i in range(5):
            ledger.append(path, _line(i))
        for i in range(4):  # devloop history: 2x the per-batch headline
            ln = _line(20 + i, scale=2.0)
            ln["express_loop"] = "devloop"
            ledger.append(path, ln)
        bad = _line(30, scale=1.1)  # regressed vs ITS cohort only
        bad["express_loop"] = "devloop"
        ledger.append(path, bad)
        rep = ledger.gate_file(path)
        assert rep.rc == ledger.GATE_REGRESSION, rep.to_dict()
