"""``lint_step`` — run every applicable cmn-lint rule on one train step.

The one-line self-check the tentpole asks for::

    from chainermn_tpu.analysis import lint_step
    lint_step(step, params, opt_state, batch, comm=comm,
              loss=loss_fn, loss_args=(params, batch))

traces the step ONCE (jaxpr), compiles it ONCE (HLO, skipped with
``hlo=False``), derives the auxiliary probes each rule needs (the
in-SPMD gradient probe for ``unpinned-transpose``, the per-flavor
compiled allreduce for ``census-drift``), runs the registry, and raises
:class:`LintError` on any error-severity finding (``raise_on_error=False``
returns the :class:`LintReport` instead — the CLI's path).

Inputs a rule needs that the caller did not provide make the rule
*skipped with a reason*, never a crash: ``lint_step(step, *args)`` with
nothing else still runs ``captured-constant`` / ``donation-alias`` /
``async-pair`` and reports the rest as skipped.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from chainermn_tpu.analysis.captured import DEFAULT_MAX_BYTES
from chainermn_tpu.analysis.rules import Finding, all_rules, get_rule
from chainermn_tpu.analysis.schedule import (
    CollectiveSchedule, extract_schedule, schedule_from_hlo)

_UNSET = object()


class LintError(AssertionError):
    """One or more error-severity lint findings.  The message is the
    rendered report; ``report`` carries the structured findings."""

    def __init__(self, report: "LintReport"):
        self.report = report
        super().__init__(report.render_text())


@dataclass
class LintReport:
    """Findings plus per-rule skip reasons for one linted target."""
    target: str = ""
    findings: List[Finding] = field(default_factory=list)
    skipped: Dict[str, str] = field(default_factory=dict)

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "error"]

    @property
    def ok(self) -> bool:
        return not self.errors

    def to_json(self) -> dict:
        return {
            "suite": "cmn_lint",
            "target": self.target,
            "ok": self.ok,
            "findings": [f.as_dict() for f in self.findings],
            "skipped": dict(self.skipped),
        }

    def render_text(self) -> str:
        lines = [f"cmn-lint: {self.target or '<anonymous step>'} — "
                 f"{len(self.errors)} error(s), "
                 f"{len(self.findings) - len(self.errors)} other finding(s), "
                 f"{len(self.skipped)} rule(s) skipped"]
        for f in self.findings:
            lines.append("  " + f.render())
        for rule_id, why in sorted(self.skipped.items()):
            lines.append(f"  [skipped] {rule_id}: {why}")
        return "\n".join(lines)

    def raise_for_errors(self) -> "LintReport":
        if self.errors:
            raise LintError(self)
        return self


class LintContext:
    """Lazy per-target inputs the rules read.

    Every derived artifact (jaxpr, compiled HLO, gradient probe, census
    HLO) is computed at most once and memoized; a derivation that fails
    or lacks its inputs yields ``None`` with the reason recorded in
    ``unavailable`` — the driver turns that into a skip, so one broken
    probe never hides the other rules' findings.
    """

    def __init__(self, fn, args, kwargs, *, name="", comm=None, flavor=None,
                 inter_size=None, plan=None, loss=None, loss_args=None,
                 donate_argnums=(), fsdp_meta=None, fsdp_state=None,
                 variants=None, census=False, hlo=True,
                 max_const_bytes=DEFAULT_MAX_BYTES, flight_events=None,
                 artifact_root=None, protocol_root=None):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs or {}
        self.name = name or getattr(fn, "__name__", "") or "step"
        self.comm = comm
        # an explicit plan is a first-class census/wire spec
        # (census-drift and wire-dtype-mismatch read it); when only a
        # communicator is given its flavor names the spec instead
        self.plan = plan
        self.flavor = (flavor if flavor is not None
                       else getattr(comm, "flavor", None)
                       if plan is None else flavor)
        self.inter_size = (inter_size if inter_size is not None
                           else getattr(comm, "inter_size", 1) or 1)
        self.loss = loss
        self.loss_args = loss_args
        self.donate_argnums = tuple(donate_argnums or ())
        self.fsdp_meta = fsdp_meta
        self.fsdp_state = fsdp_state
        self._variants_spec = variants
        self.flight_events = flight_events
        self.artifact_root = artifact_root
        self.protocol_root = protocol_root
        self.census = census
        self.hlo = hlo
        self.max_const_bytes = max_const_bytes
        self.unavailable: Dict[str, str] = {}
        self._cache: Dict[str, Any] = {}

    # -- memoized derivations -------------------------------------------

    def _memo(self, key: str, build: Callable[[], Any]):
        if key in self._cache:
            return self._cache[key]
        try:
            val = build()
        except ImportError:
            # the linter's own breakage (an API that moved), never a
            # property of the target: an error, not a skip
            raise
        except Exception as e:  # noqa: BLE001 — reason becomes the skip
            self.unavailable[key] = f"{type(e).__name__}: {e}"
            val = None
        self._cache[key] = val
        return val

    @property
    def closed_jaxpr(self):
        def build():
            if self.fn is None:
                self.unavailable["closed_jaxpr"] = "no step function given"
                return None
            return jax.make_jaxpr(self.fn)(*self.args, **self.kwargs)
        return self._memo("closed_jaxpr", build)

    @property
    def schedule(self) -> Optional[CollectiveSchedule]:
        def build():
            closed = self.closed_jaxpr
            if closed is None:
                return None
            return extract_schedule(closed, label=self.name)
        return self._memo("schedule", build)

    @property
    def hlo_text(self) -> Optional[str]:
        def build():
            if not self.hlo:
                self.unavailable["hlo_text"] = "hlo=False"
                return None
            if self.fn is None:
                self.unavailable["hlo_text"] = "no step function given"
                return None
            fn = self.fn
            if not hasattr(fn, "lower"):
                fn = jax.jit(fn)
            return fn.lower(*self.args, **self.kwargs).compile().as_text()
        return self._memo("hlo_text", build)

    @property
    def hlo_schedule(self) -> Optional[CollectiveSchedule]:
        def build():
            text = self.hlo_text
            if text is None:
                return None
            return schedule_from_hlo(text, label=f"{self.name}:hlo")
        return self._memo("hlo_schedule", build)

    @property
    def census_schedule(self) -> Optional[CollectiveSchedule]:
        """The compiled program census-drift checks against the declared
        spec.  ``census=True`` compiles the communicator's own
        ``allreduce_grad`` (the training seam); ``census=<hlo text>``
        audits that HLO directly; ``census=<callable>`` is invoked lazily
        (no args) to produce the HLO — the serving/router entry points
        use this to put their OWN compiled program (fused decode step,
        multicast weight distribution) under the same drift check."""
        def build():
            if not self.census:
                self.unavailable["census_schedule"] = "census=False"
                return None
            if callable(self.census):
                try:
                    text = self.census()
                except Exception as e:  # noqa: BLE001 — probe, not crash
                    self.unavailable["census_schedule"] = \
                        f"census probe failed: {e}"
                    return None
                if not isinstance(text, str):
                    self.unavailable["census_schedule"] = \
                        (f"census callable returned "
                         f"{type(text).__name__}, want HLO text")
                    return None
                return schedule_from_hlo(text, label=f"{self.name}:census")
            if isinstance(self.census, str):
                return schedule_from_hlo(self.census,
                                         label=f"{self.name}:census")
            if self.comm is None:
                self.unavailable["census_schedule"] = "no communicator given"
                return None
            return schedule_from_hlo(
                allreduce_hlo(self.comm),
                label=f"{self.flavor or 'comm'}:allreduce_grad")
        return self._memo("census_schedule", build)

    @property
    def grad_probe(self) -> Optional[Dict[str, CollectiveSchedule]]:
        def build():
            if self.loss is None or self.loss_args is None:
                self.unavailable["grad_probe"] = \
                    "no loss/loss_args given (pass loss=, loss_args=)"
                return None
            if self.comm is None:
                self.unavailable["grad_probe"] = "no communicator given"
                return None
            return build_grad_probe(self.comm, self.loss, self.loss_args,
                                    label=self.name)
        return self._memo("grad_probe", build)

    @property
    def variants(self) -> Optional[Dict[str, CollectiveSchedule]]:
        def build():
            spec = self._variants_spec
            if not spec:
                self.unavailable["variants"] = \
                    "no variants given (pass variants={label: ...})"
                return None
            out: Dict[str, CollectiveSchedule] = {}
            for label, v in spec.items():
                if isinstance(v, CollectiveSchedule):
                    sched = v
                elif callable(v):
                    # a builder returning either a schedule or a traceable
                    # step function (traced with THIS context's args)
                    built = v()
                    sched = built if isinstance(built, CollectiveSchedule) \
                        else extract_schedule(built, *self.args, label=label,
                                              **self.kwargs)
                elif isinstance(v, tuple):
                    vfn, vargs = v[0], tuple(v[1:])
                    sched = extract_schedule(vfn, *vargs, label=label)
                else:
                    raise TypeError(
                        f"variants[{label!r}] must be a CollectiveSchedule, "
                        f"a callable, or a (fn, *args) tuple; got {type(v)}")
                sched.label = sched.label or label
                out[label] = sched
            return out
        return self._memo("variants", build)

    @property
    def flight_spans(self) -> Optional[Dict[int, list]]:
        """Per-rank paired spans rebuilt from flight-recorder events —
        the ``overlapping-collectives`` input.  ``flight_events`` is a
        flat event list (linted as rank 0) or ``{rank: events}``; a
        flight dump's ``events`` list feeds it directly."""
        def build():
            ev = self.flight_events
            if not ev:
                self.unavailable["flight_spans"] = \
                    "no flight_events given (pass flight_events=)"
                return None
            from chainermn_tpu.observability.spans import pair_events
            if isinstance(ev, dict):
                by_rank = {int(r): list(e) for r, e in ev.items()}
            else:
                by_rank = {0: list(ev)}
            return {r: pair_events(e, rank=r)
                    for r, e in sorted(by_rank.items())}
        return self._memo("flight_spans", build)

    @property
    def protocol_model(self):
        """Static control-plane protocol model (``analysis/protocol.py``)
        — the input of the tag-band-collision / lockstep-divergence /
        unmatched-send-recv / wrapper-surface-drift / replay rules.
        ``protocol_root=True`` walks the installed ``chainermn_tpu``
        package; a path walks that tree (the fixture tests' path); an
        already-built :class:`~chainermn_tpu.analysis.protocol.
        ProtocolModel` (or its ``to_json()`` dict) is used as-is."""
        def build():
            root = self.protocol_root
            if not root:
                self.unavailable["protocol_model"] = \
                    "no protocol_root given (pass protocol_root=)"
                return None
            from chainermn_tpu.analysis.protocol import (
                ProtocolModel, extract_protocol)
            if isinstance(root, ProtocolModel):
                return root
            if isinstance(root, dict):
                return ProtocolModel.from_json(root)
            return extract_protocol(None if root is True else root)
        return self._memo("protocol_model", build)

    @property
    def artifact_census(self) -> Optional[List[dict]]:
        """Every committed artifact under ``artifact_root``, parsed and
        classified against the run-ledger schema registry — the
        ``artifact-drift`` input.  One row per artifact: ``path``
        (relative), ``doc``, ``classification`` (``None`` =
        unknown schema), ``manifest`` (the ``run_manifest/v1`` record,
        carrying device kind and modeled/measured link rates)."""
        def build():
            root = self.artifact_root
            if not root:
                self.unavailable["artifact_census"] = \
                    "no artifact_root given (pass artifact_root=)"
                return None
            from chainermn_tpu.observability.ledger import (
                build_manifest, classify_artifact, iter_artifacts)
            rows: List[dict] = []
            for path in iter_artifacts(root):
                row = {"path": os.path.relpath(path, root)}
                try:
                    with open(path) as fh:
                        doc = json.load(fh)
                except Exception as e:  # noqa: BLE001 — itself a finding
                    row["error"] = f"{type(e).__name__}: {e}"
                    rows.append(row)
                    continue
                cls = classify_artifact(doc, path)
                row["doc"] = doc
                row["classification"] = cls
                row["manifest"] = build_manifest(
                    doc, path, root=root, classification=cls)
                rows.append(row)
            return rows
        return self._memo("artifact_census", build)


def allreduce_hlo(comm, nelems: int = 1024, dtype=jnp.float32,
                  plan=None) -> str:
    """Optimized HLO of the communicator's compiled ``allreduce_grad``
    over one flat ``nelems`` gradient — the census-drift probe (and the
    program ``bench_allreduce.py --census`` pins as an artifact).

    The census probe always compiles the communicator's OWN program (a
    ``LintContext.plan`` is the spec the program is checked AGAINST,
    never the program itself — otherwise census-drift could not catch a
    communicator that ignores its declared plan).  The explicit ``plan``
    argument here is for callers building their own probe of a specific
    plan through the same seam (``allreduce_grad(g, compressor=plan)``),
    e.g. to audit the plan compiler's census including per-hop
    compression."""
    stacked = jnp.zeros((comm.size, nelems), dtype)
    if plan is not None:
        return comm.compiled_hlo(
            lambda g: comm.allreduce_grad(g, compressor=plan), stacked)
    return comm.compiled_hlo(lambda g: comm.allreduce_grad(g), stacked)


def build_grad_probe(comm, loss, loss_args, label: str = "") \
        -> Dict[str, CollectiveSchedule]:
    """Primal vs backward collective schedules of ``loss`` differentiated
    INSIDE the communicator's SPMD region, traced WITHOUT varying-axes
    tracking (``check_vma=False``) — the mode in which an unpinned psum
    transpose is both statically visible (an extra backward psum) and
    numerically wrong (grads inflated by the axis size).

    ``loss(params, *rest)`` must return a scalar per-rank loss (or an
    ``(loss, aux)`` tuple); ``loss_args = (params, *rest)`` in GLOBAL
    layout — params replicated, the rest sharded on their leading axis
    over the communicator's data axes (a stacked ``[size, ...]`` batch).
    """
    from chainermn_tpu.utils import pvary
    from jax.sharding import PartitionSpec as P

    axes = comm.data_axes
    params, rest = loss_args[0], tuple(loss_args[1:])

    def scalarize(p, rest_local):
        out = loss(p, *rest_local)
        val = out[0] if isinstance(out, tuple) else out
        return jnp.asarray(val)

    def primal_body(p, *rest_local):
        p = jax.tree.map(lambda x: pvary(x, axes), p)
        return scalarize(p, rest_local)[None]

    def grad_body(p, *rest_local):
        p = jax.tree.map(lambda x: pvary(x, axes), p)
        g = jax.grad(lambda q: scalarize(q, rest_local))(p)
        return jax.tree.map(lambda a: jnp.asarray(a)[None], g)

    in_specs = (P(),) + tuple(P(axes) for _ in rest)

    def mapped(body):
        return jax.shard_map(body, mesh=comm.mesh, in_specs=in_specs,
                          out_specs=P(axes), check_vma=False)

    return {
        "primal": extract_schedule(mapped(primal_body), params, *rest,
                                   label=f"{label}:primal"),
        "grad": extract_schedule(mapped(grad_body), params, *rest,
                                 label=f"{label}:grad"),
    }


def lint_step(fn, *args, comm=None, flavor=None, inter_size=None,
              plan=None, loss=None, loss_args=None, donate_argnums=(),
              fsdp_meta=None, fsdp_state=None, variants=None,
              census=False, hlo: bool = True,
              max_const_bytes: int = DEFAULT_MAX_BYTES,
              flight_events=None, artifact_root=None, protocol_root=None,
              rules: Optional[Sequence[str]] = None,
              raise_on_error: bool = True, name: str = "",
              **kwargs) -> LintReport:
    """Lint one train step (and its optional auxiliary probes).

    ``fn``/``*args``: the step exactly as it is called (a jitted function
    is lowered as-is, preserving donation; a plain function is traced and
    jitted for the HLO view).  Optional inputs unlock optional rules —
    see :class:`LintContext`.  Returns the :class:`LintReport`; raises
    :class:`LintError` on error findings unless ``raise_on_error=False``.
    """
    ctx = LintContext(fn, args, kwargs, name=name, comm=comm, flavor=flavor,
                      inter_size=inter_size, plan=plan,
                      loss=loss, loss_args=loss_args,
                      donate_argnums=donate_argnums, fsdp_meta=fsdp_meta,
                      fsdp_state=fsdp_state, variants=variants,
                      census=census, hlo=hlo,
                      max_const_bytes=max_const_bytes,
                      flight_events=flight_events,
                      artifact_root=artifact_root,
                      protocol_root=protocol_root)
    report = LintReport(target=ctx.name)
    selected = [get_rule(r) for r in rules] if rules else all_rules()
    for rule in selected:
        missing = rule.missing(ctx)
        if missing:
            reasons = [ctx.unavailable.get(m, f"{m} not provided")
                       for m in missing]
            report.skipped[rule.id] = "; ".join(reasons)
            continue
        # a rule that crashes is a bug in the linter and raises: recorded
        # as a skip it once hid an ImportError for a whole JAX upgrade
        report.findings.extend(rule.run(ctx))
    report.findings.sort(
        key=lambda f: ("error", "warning", "info").index(f.severity))
    if raise_on_error:
        report.raise_for_errors()
    return report


__all__ = ["LintContext", "LintError", "LintReport", "allreduce_hlo",
           "build_grad_probe", "lint_step"]
