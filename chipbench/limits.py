#!/usr/bin/env python3
"""The readings that the limits of ``limits/<cell>.json`` are set from
("How correct is decided", steps 2 to 5): in ONE process at the cell's own
size, for each of a dozen seeds the program's first three steps against the
plain float32 reference, and for the first few seeds the CONTROL: the same
reference computed in int8 (one precision below the configuration's
bfloat16), and in bfloat16 for comparison, each against the float32 one.
Training's readings need no measured window.

    chiprun -- python3 -m chipbench.limits --workload resnet50-b256 \\
        --seeds 101,202,... --control-seeds 3

Prints one JSON line per reading and, last, the largest sound reading and
the smallest control reading of every number compared.  A limit belongs
above the first and below the second, with room on both sides; the loss and
the parameter change hardly move with precision and are held to about three
times the sound runs' largest.  Writes the same to ``--out`` if given.
"""

import argparse
import json
import sys
import time


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--controls", default="int8,bfloat16")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--out", default=None)
    parser.add_argument("--set", action="append", default=[],
                        metavar="KEY=JSON", help="try another value of a "
                        "size (dots descend), e.g. optimizer.learning_rate="
                        "0.001; for exploring, never for a committed limit")
    parser.add_argument("--extra-steps", type=int, default=0,
                        help="print the losses of this many further steps")
    args = parser.parse_args()

    from chipbench import check, harness, spec

    cell = spec.resolve(args.workload, rehearse=args.rehearse)
    for assignment in args.set:
        key, _, value = assignment.partition("=")
        target = cell.sizes
        *parents, leaf = key.split(".")
        for parent in parents:
            index = int(parent) if isinstance(target, list) else parent
            target[index] = (list(target[index])
                             if isinstance(target[index], list)
                             else dict(target[index]))
            target = target[index]
        target[leaf] = json.loads(value)
    harness.place_compile_cache(cell.root, args.rehearse)
    devices, device, _ = harness.find_devices(cell, args.rehearse)
    run = harness.Run(cell, devices)
    seeds = [int(s) for s in args.seeds.split(",")]
    records = []
    for index, seed in enumerate(seeds):
        t0 = time.perf_counter()
        run.seed(seed)
        if run.compiled is None:
            run.compile()
        program = run.first_steps()
        more = run.drive(steps=args.extra_steps)["losses"]
        names = run.leaf_names()
        batches = run.release(keep_compiled=True)
        t1 = time.perf_counter()
        reference = harness.reference_readings(cell, seed, batches,
                                               devices)
        t2 = time.perf_counter()
        record = {"seed": seed, "who": "program",
                  "numbers": {k: v["value"] for k, v in
                              check.numbers(program, reference).items()},
                  "losses": program["losses"] + more,
                  "worst": check.worst_leaves(program, reference, names),
                  "program_s": t1 - t0, "reference_s": t2 - t1}
        records.append(record)
        print(json.dumps(record), flush=True)
        if index < args.control_seeds:
            for precision in args.controls.split(","):
                control = harness.reference_readings(
                    cell, seed, batches, devices, precision)
                record = {"seed": seed, "who": precision,
                          "worst": check.worst_leaves(control, reference,
                                                      names),
                          "numbers": {k: v["value"] for k, v in check.numbers(
                              control, reference).items()}}
                records.append(record)
                print(json.dumps(record), flush=True)
    summary = {"cell": cell.name, "device": device, "seeds": seeds}
    for who in ["program"] + args.controls.split(","):
        rows = [r["numbers"] for r in records if r["who"] == who]
        if rows:
            summary[who] = {name: {"min": min(r[name] for r in rows),
                                   "max": max(r[name] for r in rows)}
                            for name in rows[0]}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as handle:
            json.dump({"summary": summary, "records": records}, handle,
                      indent=1)


if __name__ == "__main__":
    sys.exit(main())
