#!/usr/bin/env python3
"""Which depth fits: compile a cell's step for a DESCRIBED v5e (no chip
needed; on-chip-measurement guide, section 2) at several values of one
configuration key, and print ``memory_analysis()`` for each.  A script, not
a test and not a chip run: a compile that passes is a compile.

    JAX_PLATFORMS=cpu python3 -m chipbench.fit --workload starcoder1b-t8192 \\
        --key n_layer --values 4,5,6,7,8

The state is built on CPU devices at the full size and handed to the
lowering as shapes with shardings on the described mesh;
``jax.default_backend`` is steered to ``"tpu"`` only while the step is
traced, so that the Pallas kernels leave interpret mode (the pattern of
``tools/compile_for_chip.py``).  What fits is what the compiler accepts with
``arguments + temporaries`` under the chip's memory, less what is resident
beside the step (the ring of batches, the loss).
"""

import argparse
import json
import os
import sys
import time
import unittest.mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")

HBM_USABLE_BYTES = 16_909_336_064    # bytes_limit the chip reported (PR 21)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--key", default="n_layer")
    parser.add_argument("--values", required=True,
                        help="comma-separated values of --key to try")
    args = parser.parse_args()

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding

    from chipbench import generator, spec, weights

    # a described-topology compile can be written to the persistent cache
    # but never read back without a chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    cell = spec.resolve(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cpus = jax.devices()
    if len(cpus) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} CPU devices to "
                         "hold its state: set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={cell.chips}")
    for value in args.values.split(","):
        sizes = dict(cell.sizes, **{args.key: int(value)})
        on_cpu = cell.family.make_comm(sizes, cpus[:cell.chips])
        described = cell.family.make_comm(
            sizes, list(topo.devices)[:cell.chips])
        params = jax.jit(lambda key: cell.family.make_params(sizes, key))(
            weights.seed_key(0, 0))
        n_params = sum(x.size for x in jax.tree.leaves(params))
        step, state = cell.family.build(described, sizes, params,
                                        state_comm=on_cpu)
        ring = generator.make_ring(dict(sizes, ring=1), cell.chips,
                                   weights.seed_key(0, 1), on_cpu.mesh,
                                   on_cpu.data_axes)
        ring_bytes = int(sizes["ring"]) * sum(
            x.nbytes for x in jax.tree.leaves(ring[0])) // cell.chips

        def shape_on_chip(x):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=NamedSharding(described.mesh, x.sharding.spec))

        shapes = jax.tree.map(shape_on_chip, (tuple(state), ring[0]))
        t0 = time.perf_counter()
        record = {"compiled_for": f"described v5e:2x2, {cell.chips} chip(s)"
                                  " - not a chip run",
                  "cell": cell.name, args.key: int(value),
                  "parameters": n_params}
        try:
            with unittest.mock.patch.object(jax, "default_backend",
                                            lambda: "tpu"):
                lowered = step.lower(*shapes[0], shapes[1])
            compiled = lowered.compile()
        except Exception as error:  # the compiler's refusal is the answer
            record["refused"] = str(error).splitlines()[0][:300]
        else:
            mem = compiled.memory_analysis()
            beside = ring_bytes
            need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                    + mem.output_size_in_bytes - mem.alias_size_in_bytes
                    + beside)
            gib = 2 ** 30
            record.update({
                "compile_s": round(time.perf_counter() - t0, 1),
                "tpu_custom_calls": compiled.as_text().count(
                    "tpu_custom_call"),
                "arguments_GiB": round(mem.argument_size_in_bytes / gib, 3),
                "temporaries_GiB": round(mem.temp_size_in_bytes / gib, 3),
                "ring_GiB": round(ring_bytes / gib, 3),
                "needed_GiB": round(need / gib, 3),
                "usable_GiB": round(HBM_USABLE_BYTES / gib, 3),
                "fits": bool(need < HBM_USABLE_BYTES),
            })
        print(json.dumps(record), flush=True)
        del step, state, params, ring, shapes


if __name__ == "__main__":
    sys.exit(main())
