#!/usr/bin/env python
"""cmn-lint CLI — statically prove an entry point's collective schedules
safe before they ever run.

Lints a named example/benchmark entry point (the same build the example
performs, at toy width) with every applicable rule from
``chainermn_tpu.analysis`` and reports findings with stable rule IDs.
Exit status is non-zero iff any error-severity finding fired, so this
drops straight into CI and into ``tools/multichip_day1.sh``'s preflight:
a schedule bug fails at submit time on a CPU host, not at step 40k on a
v4 pod.

Usage::

    python tools/cmn_lint.py examples/mnist
    python tools/cmn_lint.py examples/mnist --json --flavors xla,flat
    python tools/cmn_lint.py examples/long_context --out lint.json
    python tools/cmn_lint.py --protocol --out PROTOCOL_LINT_r20.json
    python tools/cmn_lint.py --protocol --events dumps/  # replay triage
    python tools/cmn_lint.py --list

Rendered JSON feeds ``tools/obs_report.py --lint`` (the findings lane
next to the flight timeline).  Rule catalog: docs/static_analysis.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="trace-time SPMD static analyzer (cmn-lint)")
    p.add_argument("entry", nargs="?",
                   help="entry point to lint (see --list)")
    p.add_argument("--json", action="store_true",
                   help="emit the findings document as JSON on stdout")
    p.add_argument("--out", default=None,
                   help="also write the findings JSON to this path "
                        "(the obs_report --lint artifact)")
    p.add_argument("--flavors", default=None,
                   help="comma-separated communicator flavors "
                        "(entry points that sweep flavors only; "
                        "default: all seven)")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule IDs to run (default: all)")
    p.add_argument("--devices", type=int, default=8,
                   help="minimum device count to lint over; hosts with "
                        "fewer accelerators get a virtual CPU mesh of "
                        "this size (default 8 — a single device makes "
                        "every collective degenerate and the lint "
                        "vacuous)")
    p.add_argument("--no-hlo", action="store_true",
                   help="skip compiling the step (jaxpr-only rules; "
                        "faster, but async-pair/wire-dtype need HLO)")
    p.add_argument("--events", metavar="PATH", default=None,
                   help="lint RECORDED flight events instead of an "
                        "entry point: a flight_<rank>.json dump, a "
                        "directory of them, or a raw JSON event list — "
                        "runs the dynamic rules (default: "
                        "overlapping-collectives) over the spans "
                        "rebuilt from the recording")
    p.add_argument("--artifacts", metavar="ROOT", default=None,
                   help="lint COMMITTED artifacts instead of an entry "
                        "point: walk ROOT for *_r*.json / BENCH_*.json "
                        "and run the longitudinal rules (default: "
                        "artifact-drift — unknown schemas, missing "
                        "envelopes, modeled link rates that disagree "
                        "with the latest measured rates per device "
                        "kind); combinable with --events")
    p.add_argument("--protocol", action="store_true",
                   help="lint the CONTROL PLANE instead of an entry "
                        "point: build the static protocol model of "
                        "every host object-plane call site "
                        "(analysis/protocol.py) and run the protocol "
                        "rules (tag-band-collision, lockstep-divergence, "
                        "unmatched-send-recv, wrapper-surface-drift); "
                        "with --events, additionally replays the "
                        "recorded per-rank object-plane sequences "
                        "against the model (protocol-replay-desync) — "
                        "the elastic_run incident-triage path; emits a "
                        "protocol_lint/v1 document")
    p.add_argument("--protocol-root", metavar="PATH", default=None,
                   help="tree to extract the protocol model from "
                        "(default: the installed chainermn_tpu package)")
    p.add_argument("--list", action="store_true", dest="list_entries",
                   help="list entry points and rules, then exit")
    return p


def _load_events(path: str) -> dict:
    """``{rank: events}`` from a flight dump, a directory of
    ``flight_<rank>.json`` dumps, or a bare JSON event list."""
    import glob

    paths = sorted(glob.glob(os.path.join(path, "flight_*.json"))) \
        if os.path.isdir(path) else [path]
    if not paths:
        raise SystemExit(f"cmn-lint --events: no flight_*.json under {path}")
    out = {}
    for i, p in enumerate(paths):
        with open(p) as fh:
            doc = json.load(fh)
        if isinstance(doc, list):
            out[i] = doc
        else:
            out[int(doc.get("rank", i))] = doc.get("events", [])
    return out


def main(argv=None) -> int:
    args = _build_parser().parse_args(argv)

    if args.protocol or args.events or args.artifacts:
        from chainermn_tpu.analysis.lint import lint_step
        if args.rules:
            rules = args.rules.split(",")
        else:
            rules = []
            if args.protocol:
                rules += ["tag-band-collision", "lockstep-divergence",
                          "unmatched-send-recv", "wrapper-surface-drift"]
                if args.events:
                    rules += ["protocol-replay-desync"]
            if args.events:
                rules += ["overlapping-collectives"]
            if args.artifacts:
                rules += ["artifact-drift"]
        entry = ":".join(filter(None, [
            (f"protocol:{args.protocol_root or 'chainermn_tpu'}"
             if args.protocol else None),
            f"events:{args.events}" if args.events else None,
            f"artifacts:{args.artifacts}" if args.artifacts else None]))
        model = None
        if args.protocol:
            from chainermn_tpu.analysis.protocol import extract_protocol
            model = extract_protocol(args.protocol_root)
        rep = lint_step(None,
                        flight_events=(_load_events(args.events)
                                       if args.events else None),
                        artifact_root=args.artifacts,
                        protocol_root=model,
                        rules=rules, hlo=False, raise_on_error=False,
                        name=entry)
        doc = {
            "suite": "cmn_lint",
            "entry": entry,
            "ok": rep.ok,
            "findings": [f.as_dict() for f in rep.findings],
            "reports": [rep.to_json()],
        }
        if args.protocol:
            # summarize the model the rules ran over (full model on
            # request via analysis.extract_protocol().to_json())
            from chainermn_tpu.runtime.control_plane import (
                RESERVED_TAG_BANDS)
            subsystems: dict = {}
            for s in model.sites:
                subsystems[s.subsystem] = subsystems.get(s.subsystem, 0) + 1
            doc["protocol"] = {
                "root": model.root,
                "n_sites": len(model.sites),
                "n_class_ops": len(model.class_ops),
                "sites_by_subsystem": subsystems,
                "bands": [b.as_dict()
                          for b in RESERVED_TAG_BANDS.values()],
                "parse_errors": model.errors,
            }
        from chainermn_tpu.observability.ledger import stamp_envelope
        stamp_envelope(doc,
                       "protocol_lint/v1" if args.protocol
                       else "cmn_lint/v1")
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as fh:
                json.dump(doc, fh, indent=2)
                fh.write("\n")
        if args.json:
            print(json.dumps(doc, indent=2))
        else:
            print(rep.render_text())
            verdict = "CLEAN" if rep.ok else \
                f"{len(rep.errors)} ERROR FINDING(S)"
            print(f"cmn-lint {doc['entry']}: {verdict} "
                  f"({len(rep.findings)} finding(s))")
        return 0 if doc["ok"] else 1

    if not args.list_entries:
        # Real accelerators win; otherwise bring up a virtual CPU mesh so
        # the linted schedules are the multi-device ones.
        from chainermn_tpu.utils import cpu_mesh
        cpu_mesh.ensure_device_count(args.devices)

    from chainermn_tpu.analysis import all_rules
    from chainermn_tpu.analysis.entrypoints import (
        ENTRY_POINTS, lint_entry_point)

    if args.list_entries:
        print("entry points:")
        for name, entry in sorted(ENTRY_POINTS.items()):
            print(f"  {name}: {entry['help']}")
        print("rules:")
        for r in all_rules():
            print(f"  {r.id} [{r.severity}]: {r.summary}")
        return 0
    if not args.entry:
        _build_parser().error("an entry point is required (see --list)")

    flavors = args.flavors.split(",") if args.flavors else None
    rules = args.rules.split(",") if args.rules else None
    reports = lint_entry_point(args.entry, flavors=flavors, rules=rules,
                               hlo=not args.no_hlo)

    findings = [dict(f.as_dict()) for rep in reports for f in rep.findings]
    doc = {
        "suite": "cmn_lint",
        "entry": args.entry,
        "ok": all(rep.ok for rep in reports),
        "findings": findings,
        "reports": [rep.to_json() for rep in reports],
    }
    from chainermn_tpu.observability.ledger import stamp_envelope
    stamp_envelope(doc, "cmn_lint/v1")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=2)
            fh.write("\n")
    if args.json:
        print(json.dumps(doc, indent=2))
    else:
        for rep in reports:
            print(rep.render_text())
        n_err = sum(len(rep.errors) for rep in reports)
        verdict = "CLEAN" if doc["ok"] else f"{n_err} ERROR FINDING(S)"
        print(f"cmn-lint {args.entry}: {verdict} "
              f"({len(reports)} target(s) linted)")
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
