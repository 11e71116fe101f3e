"""``chipbench.run`` with the scope of every device event in the EVENTS
document, and the per-layer metrics that read it:

    python3 -m chipbench.scoped_run --workload <cell> --seed <n> --seconds <s> --trace 1

Same arguments, same lines, same result object as ``chipbench.run``; beside
them the metrics of ``scoped_metrics.json`` and one ``"phase": "scopes"``
line.  With ``--keep-trace FILE`` the events written carry ``"scopes"``.

Why this file exists.  A PR that adds to the benchmark may not edit a file
the benchmark has, and two of them stand between the program's named scopes
and a reader (PERF.md section 7): ``harness.Run.compile`` holds the compiled
program's text, the only place an instruction's ``op_name`` can be read
(``scopes.py``), and lets it go; ``run.py`` hands the readers what
``reduce_trace.reduce_directory`` returns.  So this module wraps those two
calls, and ``spec.load_benchmark`` to append the new entries, for the length
of one ``run.main`` — no copy of the run's flow, nothing left patched.  When
a ``benchmark`` PR makes the two edits, ``python3 -m chipbench.run`` prints
these metrics itself and this file goes.
"""

import contextlib
import json
import os
import sys
from unittest import mock


def main(argv=None):
    from chipbench import harness, reduce_trace, run, scopes, spec

    program = {"table": {}, "mixed": []}
    plain_compile = harness.Run.compile
    plain_reduce = reduce_trace.reduce_directory
    plain_benchmark = spec.load_benchmark

    def compile_and_read_scopes(self):
        # JAX's persistent cache keys a program with its debug info
        # stripped, so it would serve an executable compiled before a scope
        # was added, and as_text() would read the OLD names (PR 24's first
        # ResNet run read the parent's).  Key this compile on the names too.
        import jax

        key_on_names = "jax_compilation_cache_include_metadata_in_key"
        before = getattr(jax.config, key_on_names)
        jax.config.update(key_on_names, True)
        try:
            info = plain_compile(self)
        finally:
            jax.config.update(key_on_names, before)
        parsed = scopes.parse(self.compiled.as_text())
        program["table"] = scopes.instruction_scopes(parsed)
        program["mixed"] = scopes.mixed_fusions(parsed)
        return info

    def reduce_with_scopes(trace_dir):
        events = plain_reduce(trace_dir)
        events["scopes"] = scopes.event_scopes(events, program["table"])
        run.emit(phase="scopes", **describe(events, program))
        return events

    def benchmark_with_scoped_metrics(root=spec.CHECKOUT):
        bench = plain_benchmark(root)
        with open(os.path.join(root, "chipbench", "scoped_metrics.json")) as f:
            bench["per_layer"] = bench["per_layer"] + json.load(f)["per_layer"]
        return bench

    with contextlib.ExitStack() as stack:
        for owner, name, wrapped in (
                (harness.Run, "compile", compile_and_read_scopes),
                (reduce_trace, "reduce_directory", reduce_with_scopes),
                (spec, "load_benchmark", benchmark_with_scoped_metrics)):
            stack.enter_context(mock.patch.object(owner, name, wrapped))
        return run.main(argv)


def describe(events, program):
    """How far the names reach: instructions with an op_name, the first
    device's self time by top-level scope (milliseconds over the traced
    window), and how much of it lies in fusions that mix top-level scopes
    (a fusion carries one op_name, its root's) or in compiler-made
    instructions named after their consumer."""
    from chipbench import reduce_trace, scopes

    named = events["scopes"]
    line = {"instructions_with_op_name": len(program["table"]),
            "event_names": len(named),
            "event_names_without_scope": sum(not v for v in named.values()),
            "mixed_fusions": len(program["mixed"])}
    if events["devices"]:
        mixed = set(program["mixed"])
        own = reduce_trace.self_times(reduce_trace.first_device(events))
        line["window_ms_by_top_level"] = {
            scope: ns / 1e6 for scope, ns
            in scopes.by_top_level(events).items()}
        line["window_ms_in_mixed_fusions"] = sum(
            ns for name, ns in own.items()
            if scopes.instruction_of(name) in mixed) / 1e6
        line["window_ms_named_by_consumer"] = sum(
            ns for name, ns in own.items()
            if named[name].startswith(scopes.INHERITED)) / 1e6
    return line


if __name__ == "__main__":
    sys.exit(main())
