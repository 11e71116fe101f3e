"""Evidence-tool surface tests (tools/tpu_smoke.py, convergence ledger).

The per-round hardware/convergence ledgers are driver-facing artifacts;
these tests pin the CLI behaviors that keep them trustworthy: typo'd
check names must fail loudly (an empty-but-green ledger is worse than no
ledger), and --only re-runs must merge into the existing ledger instead
of discarding the other checks' evidence.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "tpu_smoke.py")


def _run(args, timeout=300):
    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                "JAX_NUM_CPU_DEVICES": "1"})
    return subprocess.run([sys.executable, TOOL] + args, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_unknown_check_rejected(tmp_path):
    out = tmp_path / "ev.json"
    r = _run(["--only", "bogus_check", "--out", str(out)])
    assert r.returncode != 0
    assert "unknown check" in (r.stderr + r.stdout)
    assert not out.exists(), "a rejected run must not write a ledger"


def test_only_run_merges_into_ledger(tmp_path):
    out = tmp_path / "ev.json"
    # Seed a ledger with a fake passing check from the same backend.
    json.dump({"suite": "tpu_smoke", "backend": "cpu",
               "checks": {"seeded": {"ok": True}}}, open(out, "w"))
    # the cheapest check on the CPU: it records that it is chip-only
    r = _run(["--only", "flash_train_T256k", "--out", str(out)])
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.load(open(out))
    assert doc["checks"]["flash_train_T256k"]["ok"] is True
    assert doc["checks"]["seeded"]["ok"] is True, "merge dropped evidence"
    assert doc["ok"] is True


def test_multichip_day1_dry_run():
    """The hardware-day runbook (round-5): DRY_RUN=1 prints every step
    with its artifact and command, executes nothing, exits 0 — so the
    runbook itself cannot rot before hardware day."""
    env = dict(os.environ, DRY_RUN="1")
    r = subprocess.run(
        ["bash", os.path.join(REPO, "tools", "multichip_day1.sh")],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = r.stdout
    for step in ("tpu_smoke", "convergence ledger", "allreduce scaling",
                 "combiner/barrier split", "five BASELINE configs",
                 "ring attention", "multi-controller",
                 "cmn-lint static preflight", "perf gate",
                 "collective-planner autotune gate",
                 "run-ledger leg"):
        assert step in out, f"runbook lost its '{step}' step:\n{out}"
    assert out.count("DRY_RUN: not executed") >= 9, out
    assert "artifact:" in out
    # the watchdog-knob preflight is hardware-free, so it runs (and must
    # pass) even under DRY_RUN — a hardware day must not discover that a
    # CHAINERMN_TPU_WATCHDOG_* env knob stopped round-tripping
    assert "knobs round-trip OK" in out, out
    assert "CHAINERMN_TPU_WATCHDOG_DEADLINE" in out, out


def test_check_db_overlap_cpu_verdict(tmp_path, devices):
    """On the 8-device CPU mesh the db-overlap checker must exit 0 and
    reach its documented CPU-side verdict (merged form: the CPU pipeline
    erases the optimization_barrier before the combiner runs —
    docs/performance.md) with a non-empty collectives list."""
    out = tmp_path / "db.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_db_overlap.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
                 JAX_NUM_CPU_DEVICES="8"))
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-1000:])
    doc = json.loads(out.read_text())
    assert doc["backend"] == "cpu" and doc["n_devices"] == 8
    assert doc["collectives"], doc
    assert "verdict" in doc


def test_convergence_ledger_rejects_unknown_check():
    """A typo must not produce an empty-but-green convergence ledger
    (same guard as tpu_smoke --only)."""
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "convergence_ledger.py"),
         "--only", "no_such_check"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "unknown check" in (r.stdout + r.stderr)


def test_empty_ledger_is_not_green(tmp_path, monkeypatch):
    """A run in which no check executes must exit nonzero with ok=false
    (the all([])==True pitfall), behaviorally."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import importlib

        import tpu_smoke

        importlib.reload(tpu_smoke)
        out = tmp_path / "ev.json"
        monkeypatch.setattr(tpu_smoke, "CHECKS", [])
        monkeypatch.setattr(sys, "argv",
                            ["tpu_smoke.py", "--out", str(out)])
        rc = tpu_smoke.main()
        assert rc == 1
        doc = json.load(open(out))
        assert doc["ok"] is False and doc["checks"] == {}
    finally:
        sys.path.pop(0)


def test_obs_report_renders_metrics_jsonl(tmp_path):
    """tools/obs_report.py turns a mixed metrics JSONL (metric lines +
    step/straggler/bench records) into the four tables, exit 0."""
    path = tmp_path / "metrics.jsonl"
    records = [
        {"kind": "metric", "name": "comm_collective_calls",
         "type": "counter", "labels": {"op": "allreduce_grad",
                                       "comm": "NaiveCommunicator"},
         "value": 3, "ts": 1.0},
        {"kind": "metric", "name": "comm_collective_bytes",
         "type": "counter", "labels": {"op": "allreduce_grad",
                                       "comm": "NaiveCommunicator",
                                       "dtype": "bfloat16"},
         "value": 1048576, "ts": 1.0},
        {"kind": "metric", "name": "comm_collective_seconds",
         "type": "histogram", "labels": {"op": "allreduce_grad",
                                         "comm": "NaiveCommunicator"},
         "count": 3, "sum": 0.03, "min": 0.005, "max": 0.015,
         "quantiles": {"0.5": 0.01, "0.9": 0.014, "0.99": 0.015}, "ts": 1.0},
        {"kind": "step_report", "iteration": 10, "epoch": 1, "steps": 10,
         "examples_per_sec": 1234.5, "data_load_s_mean": 0.001,
         "host_put_s_mean": 0.002, "dispatch_s_mean": 0.003,
         "device_block_s_mean": 0.004, "step_s_mean": 0.01},
        {"kind": "straggler_report", "n_ranks": 2, "median_step_s": 0.01,
         "threshold": 1.5,
         "ranks": [{"rank": 0, "count": 10, "mean_s": 0.01, "p50_s": 0.01,
                    "p95_s": 0.012, "max_s": 0.013},
                   {"rank": 1, "count": 10, "mean_s": 0.03, "p50_s": 0.03,
                    "p95_s": 0.031, "max_s": 0.032}],
         "stragglers": [{"rank": 1, "mean_s": 0.03,
                         "ratio_vs_median": 3.0}]},
        {"kind": "bench_allreduce", "communicator": "naive", "devices": 8,
         "payload_mib": 64.0, "time_ms": 10.0, "busbw_gbps": 11.2},
    ]
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         str(path)],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    out = r.stdout
    assert "per-step summary" in out
    assert "per-collective summary" in out
    assert "allreduce_grad" in out and "1.0MiB" in out
    assert "STRAGGLER" in out          # rank 1 flagged in the table
    assert "bench_allreduce" in out
    # empty file is a loud error, not an empty report
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         str(empty)],
        env=env, capture_output=True, text=True, timeout=120)
    assert r2.returncode == 1


def test_obs_report_flight_merges_golden_dumps(tmp_path):
    """--flight on the checked-in golden hang (tests/data/flight_*.json,
    a 2-rank world where rank 1 wedged in the input pipeline while rank 0
    opened allreduce seq 4): the merged report must name the
    desynchronized rank, highlight the stalled collective in the
    timeline, and exit 0."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    data = os.path.join(REPO, "tests", "data")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--flight", data],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    out = r.stdout
    assert "flight dumps (2 rank(s))" in out
    assert "DESYNCHRONIZED rank(s): 1" in out
    assert "<< STALLED" in out
    assert "collective_timeout:allreduce" in out
    assert "merged timeline" in out
    # individual files work the same as the directory form
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--flight", os.path.join(data, "flight_0.json"),
         os.path.join(data, "flight_1.json")],
        env=env, capture_output=True, text=True, timeout=120)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "DESYNCHRONIZED rank(s): 1" in r2.stdout
    # no dumps -> loud failure, not an empty report
    r3 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--flight", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=120)
    assert r3.returncode == 1


def test_obs_report_attribution_metrics_section(tmp_path):
    """--section attribution renders the per-emit bucket table from
    step_attribution records plus the regression-watch counters."""
    path = tmp_path / "metrics.jsonl"
    records = [
        {"kind": "step_attribution", "iteration": 10, "rank": 0,
         "step_s": 0.02,
         "buckets": {"compute": 0.010, "ici_comm": 0.002,
                     "dcn_comm": 0.004, "host_input": 0.003,
                     "checkpoint": 0.0, "stall": 0.001},
         "sum_frac": 1.0},
        {"kind": "metric", "name": "attribution_regressions_total",
         "type": "counter", "labels": {"bucket": "dcn_comm"}, "value": 2,
         "ts": 1.0},
    ]
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--section", "attribution", str(path)],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "step-time attribution" in r.stdout
    assert "it10" in r.stdout and "100.0%" in r.stdout
    assert "attribution regressions" in r.stdout
    assert "dcn_comm" in r.stdout


def test_obs_report_flight_attribution_golden(tmp_path):
    """--flight --attribution on the checked-in attribution goldens
    (tests/data/attr_flight_*.json — 2 ranks x 2 steps, rank 1 owns a
    2x-slower DCN hop): per-rank bucket rows must sum to 100%, and the
    critical path must name a (rank, span) pair that descends into the
    slow plan stage."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    data = os.path.join(REPO, "tests", "data")
    dumps = [os.path.join(data, "attr_flight_0.json"),
             os.path.join(data, "attr_flight_1.json")]
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--flight"] + dumps + ["--attribution"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    out = r.stdout
    assert "step-time attribution" in out
    assert out.count("100.0%") >= 4          # 2 steps x 2 ranks, exact sums
    assert "critical path" in out
    assert "plan_stage hierarchical:1" in out  # descends into the DCN stage
    assert "critical path of the slowest step" in out

    # --trace exports Chrome/Perfetto trace-event JSON that round-trips
    trace = tmp_path / "trace.json"
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--flight"] + dumps + ["--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=120)
    assert r2.returncode == 0, r2.stderr[-2000:]
    doc = json.load(open(trace))
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert xs, "no complete events in the exported trace"
    assert {e["pid"] for e in xs} == {0, 1}
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)

    # --trace without --flight is a usage error, not a silent no-op
    r3 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--trace", str(tmp_path / "x.json"), dumps[0]],
        env=env, capture_output=True, text=True, timeout=120)
    assert r3.returncode != 0
    assert "--flight" in (r3.stderr + r3.stdout)


def test_obs_report_flight_ring_overflow_messaging(tmp_path):
    """A dump whose recorder overwrote ring slots must surface the loss:
    the summary grows a dropped column and the timeline leads with a
    RING OVERFLOW banner; --events truncation is reported with the
    recovery knob."""
    data = os.path.join(REPO, "tests", "data")
    src = json.load(open(os.path.join(data, "attr_flight_0.json")))
    src["dropped_events"] = 7
    p = tmp_path / "flight_0.json"
    json.dump(src, open(p, "w"))
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--flight", str(p), "--events", "5"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    out = r.stdout
    assert "dropped" in out                      # summary column
    assert "RING OVERFLOW: rank 0 lost 7 event(s)" in out
    assert "CHAINERMN_TPU_FLIGHT_CAPACITY" in out
    assert "older event(s) truncated" in out     # --events window notice
    assert "raise --events" in out
