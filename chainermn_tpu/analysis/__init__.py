"""cmn-lint — trace-time SPMD static analysis.

Every hang class the runtime observability stack (flight recorder, hang
watchdog — PR 2) diagnoses *after* a mesh is wedged is statically
visible in the jaxpr/HLO before a single step runs.  This package is
that check: a :class:`CollectiveSchedule` extractor over traced jaxprs
and compiled HLO, a rule registry (``schedule-desync``,
``census-drift``, ``unpinned-transpose``, ``captured-constant``,
``donation-alias``, ``wire-dtype-mismatch``, ``async-pair``), the
:func:`lint_step` one-liner, and the named entry points behind
``tools/cmn_lint.py``.  Rule catalog: ``docs/static_analysis.md``.

The *control-plane* half lives in ``analysis/protocol.py``: an AST
protocol model of every host object-plane call site (tags, roots, rank
guards, exception paths) feeding the ``tag-band-collision``,
``lockstep-divergence``, ``unmatched-send-recv``,
``wrapper-surface-drift``, and ``protocol-replay-desync`` rules —
``cmn_lint --protocol``.
"""

from chainermn_tpu.analysis.captured import (
    CapturedConstantError,
    DEFAULT_MAX_BYTES,
    assert_no_captured_constants,
    find_captured_constants,
)
from chainermn_tpu.analysis.compiled import compiled_step_census
from chainermn_tpu.analysis.hlo import (
    HloCollective,
    HloParse,
    all_reduce_overlap_census,
    collective_census,
    parse_hlo_collectives,
)
from chainermn_tpu.analysis.lint import (
    LintContext,
    LintError,
    LintReport,
    allreduce_hlo,
    build_grad_probe,
    lint_step,
)
from chainermn_tpu.analysis.protocol import (
    CallSite,
    ProtocolModel,
    extract_protocol,
    load_events_by_rank,
    replay_flight,
)
from chainermn_tpu.analysis.rules import (
    Finding,
    all_rules,
    expected_kinds,
    get_rule,
    rule,
)
from chainermn_tpu.analysis.schedule import (
    COLLECTIVE_PRIMITIVES,
    CollectiveOp,
    CollectiveSchedule,
    extract_schedule,
    schedule_from_hlo,
)

__all__ = [
    "COLLECTIVE_PRIMITIVES", "CallSite", "CapturedConstantError",
    "CollectiveOp", "CollectiveSchedule", "DEFAULT_MAX_BYTES",
    "Finding", "HloCollective", "HloParse",
    "LintContext", "LintError", "LintReport", "ProtocolModel",
    "all_reduce_overlap_census", "all_rules", "allreduce_hlo",
    "assert_no_captured_constants", "build_grad_probe",
    "collective_census", "compiled_step_census", "expected_kinds",
    "extract_protocol", "extract_schedule", "find_captured_constants",
    "get_rule",
    "lint_step", "load_events_by_rank", "parse_hlo_collectives",
    "replay_flight", "rule", "schedule_from_hlo",
]
