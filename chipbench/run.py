"""One run of one cell:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  Set-up (timed as ``setup_s``, from the start of
this process to the first timed step): place the compile cache, find the
chip, make weights and a ring of batches on the device from the seed, build
the cell's step through the program's public entry points, trace and compile
it once, take the check's first three steps and the warm-up steps.  Then the
window: ``--trace 0`` drives the step for ``--seconds`` with no profiler and
reports the cell's end-to-end metrics; ``--trace 1`` profiles a short steady
window, names every device event by the scope it ran under (``scopes.py``;
one ``"phase": "scopes"`` line says how far the names reach) and reports the
cell's per-layer metrics, with a breakdown.  After the
window the program's state is freed and the plain reference follows the
same first three steps; every number compared is printed beside its limit.
The last line of standard output is the result object.

Without a TPU, with another number of chips than the cell's, or with a
``device_kind`` that ``peaks.json`` lacks, the run exits non-zero and
prints no result.  ``--rehearse`` is the only way to run on the CPU: toy
sizes, no device metric, ``platform: cpu``.
"""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

TRACE_DIR_NAME = ".chipbench_trace"
# the traced window warms for this many untraced steady steps first
STEADY_STEPS_BEFORE_TRACE = 4


def emit(**record):
    print(json.dumps(record), flush=True)


def percentile(values, q):
    """The q-th percentile by linear interpolation between order statistics
    (numpy's default), without numpy's rounding of the index."""
    ordered = sorted(values)
    if len(ordered) == 1:
        return ordered[0]
    position = (len(ordered) - 1) * q / 100.0
    low = math.floor(position)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (position - low)


def end_to_end_values(cell, driven, setup_s, peaks):
    """The window's end-to-end numbers by the quantity each is: throughput
    over all completed steps and all the time between the first and the
    last completion, the 90th percentile of the time per step over every
    ``interval_steps`` successive completions, the share of the peak, and
    set-up."""
    family, sizes = cell.family, cell.sizes
    done = driven["completed_at"]
    units = family.units_per_step(sizes, cell.chips)
    rate = (len(done) - 1) * units / (done[-1] - done[0]) / cell.chips
    stride = int(sizes["interval_steps"])
    intervals = [(done[i] - done[i - stride]) / stride * 1e3
                 for i in range(stride, len(done), stride)]
    values = {
        family.THROUGHPUT_METRIC: rate,
        "step_ms_p90": percentile(intervals, 90),
        "setup_s": setup_s,
    }
    if peaks is not None:
        values["mfu"] = (100.0 * rate * family.flop_per_unit(sizes)
                         / (peaks["bf16_tflops"] * 1e12))
    return values, {"intervals": len(intervals),
                    "step_ms_median": statistics.median(intervals)}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU dress rehearsal at the cell's toy sizes")
    parser.add_argument("--root", default=None,
                        help="directory holding BENCHMARK.json and "
                             "chipbench/ (default: this checkout)")
    parser.add_argument("--keep-trace", default=None, metavar="FILE",
                        help="with --trace 1, also write the trace's events "
                             "and their scopes as gzipped JSON (a recording "
                             "for the tests)")
    args = parser.parse_args(argv)

    from chipbench import check, harness, reduce_trace, scopes, spec

    root = os.path.abspath(args.root) if args.root else spec.CHECKOUT
    try:
        cell = spec.resolve(args.workload, root, rehearse=args.rehearse)
        cache_dir = harness.place_compile_cache(root, args.rehearse)
        devices, device, peaks = harness.find_devices(cell, args.rehearse)
    except (spec.SpecError, harness.HarnessFailure) as error:
        print(f"chipbench: {error}", file=sys.stderr)
        return 2
    events = harness.CompileEvents()
    emit(phase="start", cell=cell.name, seed=args.seed, device=device,
         rehearsal=args.rehearse, compile_cache_dir=cache_dir, why=cell.why)

    run = harness.Run(cell, devices)
    program, warm, info, problems, setup_s = set_up(run, args, events)
    driven, trace_dir = window(run, args, root, events, problems)
    losses = warm["losses"] + driven["losses"]
    failed = sum(not math.isfinite(v) for v in driven["losses"])
    for part in (warm, driven):
        if part["raised"]:
            failed += 1
            problems.append(f"a step raised: {part['raised']}")
    if failed == 0:
        health(run, program, losses, problems)
    device["memory_peak_bytes"] = (
        0 if args.rehearse else run.memory_peak_bytes())
    emit(phase="memory", memory_peak_bytes=device["memory_peak_bytes"],
         runtime_counters={k: v for k, v in (
             devices[0].memory_stats() or {}).items()
             if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")},
         compiled_step={k: info[k] for k in (
             "argument_bytes", "temp_bytes", "output_bytes", "alias_bytes")})
    first_batches = run.release()

    metrics, breakdown = {}, None
    if not args.trace and len(driven["completed_at"]) >= 3:
        values, shape = end_to_end_values(cell, driven, setup_s, peaks)
        emit(phase="window", steps_completed=len(driven["completed_at"]),
             seconds=driven["ended_at"] - driven["began_at"], **shape)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    elif args.trace and driven["enqueued"]:
        reduced = reduce_trace.reduce_directory(trace_dir)
        reduced["scopes"] = scopes.event_scopes(
            reduced, run.instruction_scopes)
        emit(phase="scopes", **scopes.describe(
            reduced, run.instruction_scopes, run.mixed_fusions))
        if args.keep_trace:
            reduce_trace.dump_events(reduced, args.keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = per_layer_values(cell, reduced, driven, info, peaks)
        if not args.rehearse:
            device["busy_s"] = reduce_trace.mean_busy_s(reduced)
            device["window_s"] = reduce_trace.window_s(reduced)
            breakdown = reduce_trace.breakdown(reduced)
    if args.rehearse:
        # a CPU run's numbers never go under a device metric's name
        emit(phase="rehearsal_numbers", platform="cpu",
             **{"cpu_" + name: m["value"] for name, m in metrics.items()})
        metrics = {}

    # the plain reference, once the program's state is gone, and the
    # comparison: every number beside its limit
    t0 = time.perf_counter()
    reference = harness.reference_readings(
        cell, args.seed, first_batches, devices)
    rows, within = check.judge(check.numbers(program, reference), cell.limits)
    for row in rows:
        emit(phase="check", **row)
    emit(phase="reference", seconds=time.perf_counter() - t0)
    for problem in problems:
        emit(phase="problem", problem=problem)

    result = {"correct": bool(within and not problems and failed == 0),
              "attempted": driven["enqueued"], "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


def set_up(run, args, events):
    """Everything before the first timed step, timed as ``setup_s``: weights,
    state and ring from the seed, the one compile, the check's first three
    steps and the warm-up.  Returns the first steps' readings, the warm-up's
    losses, what the compiled step says of itself, the problems found so
    far and ``setup_s``."""
    from chipbench import harness

    cell = run.cell
    timeline = {"imports_and_devices": time.perf_counter() - PROCESS_START}
    t0 = time.perf_counter()
    run.seed(args.seed)
    timeline["weights_state_ring"] = time.perf_counter() - t0
    mark = events.mark()
    t0 = time.perf_counter()
    info = run.compile(read_scopes=bool(args.trace))
    timeline["trace_and_compile"] = time.perf_counter() - t0
    emit(phase="compile", seconds=timeline["trace_and_compile"],
         **events.since(mark), **info)
    problems = harness.check_placement(run)
    if not args.rehearse:
        want = cell.family.min_kernels(cell.sizes)
        if info["tpu_custom_calls"] < want:
            problems.append(f"{info['tpu_custom_calls']} tpu_custom_call in "
                            f"the compiled step, expected at least {want}")
        if info["pallas_calls_interpreted"]:
            problems.append(f"{info['pallas_calls_interpreted']} Pallas "
                            "calls were traced in interpret mode")
    t0 = time.perf_counter()
    program = run.first_steps()
    timeline["first_three_steps"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    steps = max(0, int(cell.sizes["warmup_steps"]) - run.steps_taken)
    if args.trace:
        steps += STEADY_STEPS_BEFORE_TRACE
    warm = run.drive(steps=steps)
    timeline["warm_up"] = time.perf_counter() - t0
    setup_s = time.perf_counter() - PROCESS_START
    emit(phase="setup", setup_s=setup_s, steps=run.steps_taken,
         timeline=timeline, **events.since((0, 0, 0)))
    return program, warm, info, problems, setup_s


def window(run, args, root, events, problems):
    """The measured window (``--trace 0``: ``--seconds`` of steps, no
    profiler) or the traced one (``--trace 1``: ``trace_steps`` steps under
    the profiler).  A compilation inside it is a problem."""
    mark = events.mark()
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(root, TRACE_DIR_NAME, run.cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        driven = run.traced(trace_dir)
    else:
        driven = run.drive(seconds=args.seconds)
    inside = events.since(mark)
    if inside["compile_requests"] or inside["compile_calls"]:
        problems.append(f"compilation inside the window: {inside}")
    if len(driven["completed_at"]) < 3:
        problems.append("fewer than three steps completed in the window")
    return driven, trace_dir


def health(run, program, losses, problems):
    """After the window: the loss went below where it started, and at least
    half the parameter leaves changed.  SGD at the source's learning rate
    overshoots and comes back, so "lower at the end" would swing with the
    length of the run; a wrong sign or scale of the update is caught by the
    third step's loss against the reference already."""
    first, lowest = program["losses"][0], min(losses)
    if not lowest < first:
        problems.append(f"the loss never fell below the first step's: "
                        f"first {first}, lowest after {lowest}")
    changed = run.parameter_change() > 0
    if changed.sum() * 2 < changed.size:
        problems.append(f"only {int(changed.sum())} of {changed.size} "
                        "parameter leaves changed")
    emit(phase="health", first_loss=first, lowest_loss=lowest,
         first_losses=losses[:4], last_losses=losses[-4:],
         leaves_changed=f"{int(changed.sum())}/{changed.size}")


def per_layer_values(cell, reduced, driven, info, peaks):
    """Every per-layer metric of the cell whose reader finds something."""
    host = {"dispatch_s": driven["dispatch_s"], "steps": driven["enqueued"],
            "compile_info": info}
    context = {"sizes": cell.sizes, "chips": cell.chips, "peaks": peaks}
    metrics = {}
    for metric in cell.per_layer:
        value = cell.layer_reader(metric["name"]).read(reduced, host, context)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return metrics


if __name__ == "__main__":
    sys.exit(main())
