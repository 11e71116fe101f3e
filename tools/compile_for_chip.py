#!/usr/bin/env python
"""Compile chip_smoke.py's step programs for a described v5e:2x2 — the
rehearsal that needs no chip (on-chip-measurement guide, section 2).

The TPU compiler is installed with libtpu and compiles for a chip that is
described, not attached.  This builds each program exactly as
``chip_smoke.py`` does, through the public training path, with two
substitutions the missing chip forces: state and batch are placed on CPU
devices and handed to the lowering as shapes with shardings on the described
mesh, and ``jax.default_backend`` is steered to ``"tpu"`` while tracing so
the Pallas entries leave interpret mode.  What it establishes: the compiler
accepts every kernel inside the whole step, the program fits the chip's
memory (``memory_analysis``), the kernels are in the program
(``tpu_custom_call``) and which collectives the compiler put in.  Nothing
runs: a compile that passes is a compile, never a chip run.

    JAX_PLATFORMS=cpu python tools/compile_for_chip.py resnet50 lm_flash
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python tools/compile_for_chip.py lm_dp4 lm_one_chip_b4
"""

import argparse
import json
import os
import sys
import time
import unittest.mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PROGRAMS = {
    # name: (chips, builder, extra builder arguments)
    "resnet50": (1, "build_resnet", {}),
    "lm_flash": (1, "build_lm", {}),
    "lm_dp4": (4, "build_lm", {"double_buffering": False}),
    "lm_one_chip_b4": (1, "build_lm", {"double_buffering": False,
                                       "global_batch": 4}),
}


def compile_program(name, topo):
    import jax
    from jax.sharding import NamedSharding

    import chip_smoke
    from chainermn_tpu.analysis.hlo import (all_reduce_overlap_census,
                                            parse_hlo_collectives)

    chips, builder, extra = PROGRAMS[name]
    cpus = jax.devices()
    if len(cpus) < chips:
        raise SystemExit(
            f"{name} needs {chips} CPU devices to hold its state: set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={chips}")
    on_cpu = chip_smoke.make_comm(cpus[:chips])
    described = chip_smoke.make_comm(list(topo.devices)[:chips])
    cfg = chip_smoke.FULL["resnet" if builder == "build_resnet" else "lm"]
    step, state, batch = getattr(chip_smoke, builder)(
        described, cfg, state_comm=on_cpu, **extra)

    def shape_on_chip(x):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(described.mesh, x.sharding.spec))

    state, batch = jax.tree.map(shape_on_chip, (state, batch))
    t0 = time.perf_counter()
    # steered only while the step is traced: state was built on the CPU,
    # where the kernels must stay interpreted
    with unittest.mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = step.lower(*state, batch)
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 1 << 30
    print(json.dumps({
        "compiled_for": f"described v5e:2x2, {chips} chip(s) — not a chip run",
        "program": name,
        "compile_s": round(seconds, 1),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "collectives": parse_hlo_collectives(text).count_by_kind(),
        "all_reduce_census": all_reduce_overlap_census(text),
        "per_device_GiB": {
            "arguments": round(mem.argument_size_in_bytes / gib, 3),
            "outputs": round(mem.output_size_in_bytes / gib, 3),
            "aliased": round(mem.alias_size_in_bytes / gib, 3),
            "temporaries": round(mem.temp_size_in_bytes / gib, 3),
            "program": round(mem.generated_code_size_in_bytes / gib, 3),
        },
    }), flush=True)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("programs", nargs="+", choices=sorted(PROGRAMS))
    args = parser.parse_args()

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described-topology compile can be written to the persistent cache
    # but never read back without a chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in args.programs:
        compile_program(name, topo)


if __name__ == "__main__":
    main()
