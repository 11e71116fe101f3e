"""Artifact census (ISSUE 17 satellite): every committed ``*_r*.json``
/ ``BENCH_*.json`` in the repo root must carry (or classify to) a
schema registered in ``observability.ledger.KNOWN_SCHEMAS``.

This is the longitudinal contract behind the run ledger: an artifact
the registry cannot name lands outside every gate, trend, and diff —
silently.  A new artifact landing here with a new schema must register
it (and stamp its writer with ``stamp_envelope``) before this test
lets it merge.
"""

import json
import os
import subprocess
import sys

from chainermn_tpu.observability.ledger import (
    KNOWN_SCHEMAS,
    classify_artifact,
    iter_artifacts,
    schema_version,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _census():
    rows = []
    for path in iter_artifacts(REPO):
        with open(path) as f:
            doc = json.load(f)
        rows.append((os.path.basename(path), doc,
                     classify_artifact(doc, path)))
    return rows


def test_repo_root_has_committed_artifacts():
    assert len(_census()) >= 38      # the walk actually finds the set


def test_every_committed_artifact_has_a_registered_schema():
    unknown = [name for name, _doc, cls in _census() if cls is None]
    assert unknown == [], (
        f"unregistered artifact schema(s): {unknown} — register in "
        f"observability.ledger.KNOWN_SCHEMAS and stamp the writer")
    for name, _doc, cls in _census():
        assert cls["schema"] in KNOWN_SCHEMAS, name


def test_enveloped_artifacts_declare_consistent_versions():
    for name, doc, cls in _census():
        if not isinstance(doc, dict) or "schema" not in doc:
            continue
        assert doc["schema"] in KNOWN_SCHEMAS, name
        declared = doc.get("schema_version")
        if declared is not None:
            assert declared == schema_version(doc["schema"]), name


def test_artifact_drift_lint_clean_on_committed_state():
    """The ``artifact-drift`` rule over the committed repo: no errors
    (every schema registered), no drift warnings (no committed modeled
    rate disagrees with a same-device-kind measured rate)."""
    from chainermn_tpu.analysis.lint import lint_step

    rep = lint_step(None, artifact_root=REPO, rules=["artifact-drift"],
                    hlo=False, raise_on_error=False, name="census")
    assert rep.ok, [f.render() for f in rep.findings]
    assert [f for f in rep.findings if f.severity == "error"] == []


def test_cmn_lint_artifacts_lane(tmp_path):
    out = str(tmp_path / "lint.json")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "cmn_lint.py"),
         "--artifacts", REPO, "--out", out],
        capture_output=True, text=True, cwd=REPO, timeout=300,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout + p.stderr
    doc = json.load(open(out))
    assert doc["suite"] == "cmn_lint" and doc["ok"]
    assert doc["schema"] == "cmn_lint/v1"     # the writer stamps itself


def test_obs_report_renders_ledger_and_diff_lanes():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--ledger", os.path.join(REPO, "LEDGER_r17.json"),
         "--diff", os.path.join(REPO, "REGRESSION_DIFF_r17.json")],
        capture_output=True, text=True, cwd=REPO, timeout=300,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout + p.stderr
    assert "dcn_comm" in p.stdout             # the diff verdict renders
    assert "run ledger" in p.stdout.lower()
