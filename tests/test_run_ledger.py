"""Run ledger & differential attribution (ISSUE 17 tentpole).

Three layers under test, device-free end to end:

* ``observability.ledger`` — the common artifact envelope
  (``stamp_envelope``), schema classification over every committed
  artifact shape, ``run_manifest/v1`` records, and the append-only
  :class:`RunLedger` with per-(device_kind, schema) baseline selection;
* ``observability.diffing`` — differential attribution between two
  recorded runs: bucket decompositions, per-(link, owner) occupancy,
  per-stage timings, exact streaming-histogram quantile deltas, and the
  regression localizer (the acceptance bar: replaying
  ``tests/data/degraded_dcn_spans.json`` against its healthy twin must
  produce a ``run_diff/v1`` naming ``dcn_comm``);
* the wiring — ``tools/ledger.py`` CLI, ``perf_gate --ledger``, the
  ``artifact-drift`` lint rule, and the committed r17 artifacts.
"""

import json
import os
import subprocess
import sys

import pytest

from chainermn_tpu.observability.ledger import (
    KNOWN_SCHEMAS,
    RunLedger,
    build_manifest,
    classify_artifact,
    ingest_artifacts,
    stamp_envelope,
)
from chainermn_tpu.observability import diffing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEALTHY = os.path.join(REPO, "tests", "data", "healthy_dcn_spans.json")
DEGRADED = os.path.join(REPO, "tests", "data", "degraded_dcn_spans.json")


def _run(cmd, **kw):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, capture_output=True, text=True,
                          cwd=REPO, env=env, timeout=300, **kw)


# ---------------------------------------------------------------------------
# envelope + classification
# ---------------------------------------------------------------------------

def test_stamp_envelope_fills_gaps_never_clobbers():
    doc = {"schema": "online_tune/v1", "backend": "tpu", "n_devices": 4}
    stamp_envelope(doc, backend="cpu", n_devices=8, device_kind="x")
    assert doc["backend"] == "tpu"          # present fields survive
    assert doc["n_devices"] == 4
    assert doc["device_kind"] == "x"
    assert doc["schema_version"] == 1
    assert doc["git_sha"]                   # stamped from this checkout


def test_classify_declared_legacy_and_unknown():
    ok = classify_artifact({"schema": "online_tune/v1"}, "X_r01.json")
    assert ok == {"schema": "online_tune/v1", "schema_version": 1,
                  "legacy": False} or ok["schema"] == "online_tune/v1"
    legacy = classify_artifact({"suite": "tpu_smoke", "checks": {}},
                               "TPU_EVIDENCE_r05.json")
    assert legacy["schema"] == "tpu_smoke/v1" and legacy["legacy"]
    assert classify_artifact({"schema": "bogus/v9"}, "B_r01.json") is None
    assert classify_artifact({"what": 1}, "B_r01.json") is None


def test_build_manifest_extracts_round_metrics_and_rates():
    doc = {"schema": "online_tune/v1", "device_kind": "cpu",
           "observed_gbps": {"dcn": 2.0, "ici": 16.0},
           "retune": {"best_speedup": 4.0}}
    man = build_manifest(doc, "ONLINE_TUNE_r12.json", root=REPO)
    assert man["schema"] == "run_manifest/v1"
    assert man["round"] == "r12"
    assert man["artifact_schema"] == "online_tune/v1"
    assert man["link_gbps_measured"] == {"dcn": 2.0, "ici": 16.0}
    assert man["metrics"]["retune_speedup"] == 4.0
    assert man["git_sha_source"] == "ingest"   # no stamp in the doc


def test_manifest_infers_noise_for_negative_overhead():
    """A pre-guard tracing artifact publishing a negative overhead is
    physically impossible (hooks cannot speed a program up) — ingest
    marks it noise_dominated so it never becomes a baseline."""
    with open(os.path.join(REPO, "TRACING_OVERHEAD_r16.json")) as f:
        r16 = json.load(f)
    assert r16["tracing_overhead_pct"] < 0   # the artifact under fire
    man = build_manifest(r16, "TRACING_OVERHEAD_r16.json", root=REPO)
    assert man["noise_dominated"] is True


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

def _rec(artifact, schema, dk, metric, value, **extra):
    r = {"schema": "run_manifest/v1", "artifact": artifact,
         "round": artifact.split("_")[-1].split(".")[0],
         "artifact_schema": schema, "device_kind": dk,
         "metrics": {metric: value}}
    r.update(extra)
    return r


def test_ledger_baseline_is_per_device_kind_cell():
    led = RunLedger()
    led.append(_rec("A_r01.json", "s/v1", "cpu", "tput", 100.0))
    led.append(_rec("A_r02.json", "s/v1", "cpu", "tput", 120.0))
    led.append(_rec("A_r03.json", "s/v1", "TPU v4", "tput", 900.0))
    base = led.baseline("s/v1", "cpu", "tput")
    assert base["metrics"]["tput"] == 120.0        # best cpu, not TPU
    base = led.baseline("s/v1", "TPU v4", "tput")
    assert base["metrics"]["tput"] == 900.0
    # lower-is-better flips the pick; own artifact is excluded
    base = led.baseline("s/v1", "cpu", "tput", direction="lower",
                        exclude_artifact="A_r01.json")
    assert base["artifact"] == "A_r02.json"


def test_ledger_baseline_skips_noise_dominated_records():
    led = RunLedger()
    led.append(_rec("T_r10.json", "t/v1", "cpu", "pct", 1.5))
    led.append(_rec("T_r16.json", "t/v1", "cpu", "pct", -2.8,
                    noise_dominated=True))
    base = led.baseline("t/v1", "cpu", "pct", direction="lower")
    assert base["artifact"] == "T_r10.json"        # noise never the bar
    # ...but the record stays in the trend
    assert [t["value"] for t in led.trend("pct")] == [1.5, -2.8]


def test_ledger_jsonl_persistence_roundtrip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    led = RunLedger(path)
    led.append(_rec("A_r01.json", "s/v1", "cpu", "m", 1.0))
    led.append(_rec("A_r02.json", "s/v1", "cpu", "m", 2.0))
    again = RunLedger(path)                        # replay the file
    assert len(again.records()) == 2
    assert again.baseline("s/v1", "cpu", "m")["metrics"]["m"] == 2.0
    snap = again.to_doc()
    assert snap["schema"] == "run_ledger/v1"
    assert RunLedger.from_doc(snap).baseline(
        "s/v1", "cpu", "m")["metrics"]["m"] == 2.0


def test_backfill_registers_every_committed_artifact():
    """The acceptance bar: the backfill ingester walks every committed
    ``*_r*.json`` / ``BENCH_*.json`` in the repo root and registers ALL
    of them — zero unknown-schema entries."""
    led = RunLedger()
    manifests, problems = ingest_artifacts(REPO, led)
    assert problems == []
    assert len(manifests) >= 38
    for man in manifests:
        assert man["artifact_schema"] in KNOWN_SCHEMAS, man["artifact"]
        assert man["git_sha"], man["artifact"]     # always anchored


# ---------------------------------------------------------------------------
# differential attribution
# ---------------------------------------------------------------------------

def test_diff_localizes_degraded_dcn_to_dcn_comm():
    """Replaying the committed degraded-DCN span dump against its
    healthy twin must localize the regression to the dcn_comm bucket,
    with magnitude and stage evidence (the ISSUE 17 acceptance run)."""
    diff = diffing.diff_runs(HEALTHY, DEGRADED)
    assert diff["schema"] == "run_diff/v1"
    reg = diff["regression"]
    assert reg["bucket"] == "dcn_comm"
    # 8 MiB at 0.5 GB/s vs 2 GB/s over 12 iterations: 4x, ~151 ms
    assert reg["ratio"] == pytest.approx(4.0, rel=0.05)
    assert reg["delta_s"] == pytest.approx(0.151, rel=0.05)
    assert reg["confidence"] > 0.9
    assert reg["evidence"]["link"] == "dcn"
    stage = reg["evidence"]["stage"]
    assert "dcn" in stage["stage"]
    assert stage["base_gbps"] == pytest.approx(2.0, rel=0.05)
    assert stage["cand_gbps"] == pytest.approx(0.5, rel=0.05)


def test_diff_healthy_vs_itself_reports_no_regression():
    diff = diffing.diff_runs(HEALTHY, HEALTHY)
    assert diff["regression"] is None


def test_histogram_diff_is_exact_on_shared_grid():
    from chainermn_tpu.observability.registry import StreamingHistogram

    def grid(values):
        h = StreamingHistogram("step_s", lo=1e-4, hi=10.0)
        for v in values:
            h.observe(v)
        return {"lo": h.lo, "hi": h.hi,
                "buckets_per_decade": h.buckets_per_decade,
                "series": [{"state": h.state()}]}

    a = {"step_s": grid([0.010] * 100)}
    b = {"step_s": grid([0.020] * 100)}
    out = diffing.diff_histograms(a, b, quantiles=(0.5,))
    row = out["step_s"]["p50"]
    assert row["a"] == pytest.approx(0.010, rel=0.35)  # bucket resolution
    assert row["b"] > row["a"] and row["delta"] > 0
    # mismatched grids must refuse, not mis-merge
    c = {"step_s": dict(b["step_s"], buckets_per_decade=5)}
    assert diffing.diff_histograms(a, c)["step_s"]["grid_mismatch"]


def test_diff_manifests_flags_metric_drift():
    a = _rec("A_r01.json", "s/v1", "cpu", "tput", 100.0)
    b = _rec("A_r02.json", "s/v1", "cpu", "tput", 50.0)
    d = diffing.diff_manifests(a, b)
    assert d["schema"] == "run_diff/v1"
    row = {m["metric"]: m for m in d["metrics"]}["tput"]
    assert row["ratio"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# committed r17 artifacts (pinned)
# ---------------------------------------------------------------------------

def test_committed_ledger_r17_pin():
    with open(os.path.join(REPO, "LEDGER_r17.json")) as f:
        doc = json.load(f)
    assert doc["schema"] == "run_ledger/v1"
    assert doc["problems"] == []
    assert len(doc["records"]) >= 40
    for rec in doc["records"]:
        assert rec["artifact_schema"] in KNOWN_SCHEMAS, rec["artifact"]


def test_committed_regression_diff_r17_pin():
    with open(os.path.join(REPO, "REGRESSION_DIFF_r17.json")) as f:
        doc = json.load(f)
    assert doc["schema"] == "run_diff/v1"
    assert doc["regression"]["bucket"] == "dcn_comm"
    assert doc["regression"]["ratio"] == pytest.approx(4.0, rel=0.05)
    assert doc["regression"]["evidence"]["link"] == "dcn"


def test_committed_tracing_overhead_r17_has_noise_guard():
    with open(os.path.join(REPO, "TRACING_OVERHEAD_r17.json")) as f:
        doc = json.load(f)
    assert doc["schema"] == "tracing_overhead/v1"
    assert doc["git_sha"] and doc["device_kind"]   # enveloped writer
    assert isinstance(doc["noise_dominated"], bool)
    assert doc["tracing_overhead_pct"] >= 0.0      # never a fake win
    assert len(doc["per_repeat_pct"]) == doc["repeats"]
    assert doc["spread_pct"] >= 0.0


# ---------------------------------------------------------------------------
# artifact-drift lint rule
# ---------------------------------------------------------------------------

def _write(root, name, doc):
    with open(os.path.join(str(root), name), "w") as f:
        json.dump(doc, f)


def test_artifact_drift_rule_fires_and_localizes(tmp_path):
    from chainermn_tpu.analysis.lint import lint_step

    # latest measured rates for device kind "cpu": dcn = 2 GB/s
    _write(tmp_path, "ONLINE_TUNE_r02.json",
           {"schema": "online_tune/v1", "schema_version": 1,
            "device_kind": "cpu", "backend": "cpu", "git_sha": "x",
            "observed_gbps": {"dcn": 2.0}})
    # models dcn at 0.25 GB/s on the same kind: x8 apart -> drift
    _write(tmp_path, "SWEEP_r03.json",
           {"schema": "allreduce_sweep/v1", "schema_version": 1,
            "device_kind": "cpu", "backend": "cpu", "git_sha": "x",
            "n_devices": 8, "link_gbps": {"dcn": 0.25}, "rows": []})
    # unregistered schema -> error
    _write(tmp_path, "BOGUS_r04.json", {"schema": "bogus/v9"})
    # pre-envelope artifact -> aggregated info
    _write(tmp_path, "OLD_r01.json", {"suite": "tpu_smoke", "checks": {}})

    rep = lint_step(None, artifact_root=str(tmp_path),
                    rules=["artifact-drift"], hlo=False,
                    raise_on_error=False, name="census")
    by_sev = {}
    for f in rep.findings:
        by_sev.setdefault(f.severity, []).append(f)
    assert len(by_sev["error"]) == 1
    assert "BOGUS_r04.json" in by_sev["error"][0].message
    drift = by_sev["warning"]
    assert len(drift) == 1
    assert drift[0].details["link"] == "dcn"
    assert drift[0].details["modeled_gbps"] == 0.25
    assert drift[0].details["measured_gbps"] == 2.0
    assert "OLD_r01.json" in by_sev["info"][0].message


def test_artifact_drift_within_tolerance_is_quiet(tmp_path):
    from chainermn_tpu.analysis.lint import lint_step

    _write(tmp_path, "ONLINE_TUNE_r02.json",
           {"schema": "online_tune/v1", "schema_version": 1,
            "device_kind": "cpu", "backend": "cpu", "git_sha": "x",
            "observed_gbps": {"dcn": 2.0}})
    _write(tmp_path, "SWEEP_r03.json",
           {"schema": "allreduce_sweep/v1", "schema_version": 1,
            "device_kind": "cpu", "backend": "cpu", "git_sha": "x",
            "n_devices": 8, "link_gbps": {"dcn": 1.5}, "rows": []})
    # different device kind never cross-contaminates
    _write(tmp_path, "SWEEP_r04.json",
           {"schema": "allreduce_sweep/v1", "schema_version": 1,
            "device_kind": "TPU v4", "backend": "tpu", "git_sha": "x",
            "n_devices": 8, "link_gbps": {"dcn": 50.0}, "rows": []})
    rep = lint_step(None, artifact_root=str(tmp_path),
                    rules=["artifact-drift"], hlo=False,
                    raise_on_error=False, name="census")
    assert rep.ok
    assert [f for f in rep.findings if f.severity == "warning"] == []


def test_artifact_drift_skipped_without_root():
    from chainermn_tpu.analysis.lint import lint_step

    rep = lint_step(None, rules=["artifact-drift"], hlo=False,
                    raise_on_error=False, name="census")
    assert rep.ok and not rep.findings      # skipped, not failed


# ---------------------------------------------------------------------------
# CLI + gate wiring (subprocess)
# ---------------------------------------------------------------------------

def test_ledger_cli_diff_names_dcn_comm(tmp_path):
    out = str(tmp_path / "diff.json")
    p = _run([sys.executable, os.path.join(REPO, "tools", "ledger.py"),
              "diff", HEALTHY, DEGRADED, "--out", out])
    assert p.returncode == 0, p.stderr
    verdict = json.loads(p.stdout.strip().splitlines()[-1])
    assert verdict["regressed"] and verdict["bucket"] == "dcn_comm"
    assert json.load(open(out))["schema"] == "run_diff/v1"


def test_perf_gate_ledger_passes_on_committed_state():
    p = _run([sys.executable, os.path.join(REPO, "tools", "perf_gate.py"),
              "--ledger", os.path.join(REPO, "LEDGER_r17.json")])
    assert p.returncode == 0, p.stderr
    verdict = json.loads(p.stdout.strip().splitlines()[-1])
    assert verdict["ok"] and verdict["failed"] == 0
    assert verdict["ledger_baselines"] >= 1   # history actually used
