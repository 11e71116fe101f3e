"""The parts of a run: find the chip, make weights and batches from the
seed, build the program's step through the cell's family, compile it once,
drive it, trace it, and read the plain reference.  ``run.py`` strings them
together; ``limits.py`` reads many seeds in one process with the same parts.
Nothing here names a cell, a family or a metric.
"""

from __future__ import annotations

import collections
import contextlib
import os
import time

import numpy as np

from chipbench import generator, scopes, spec, weights

CACHE_DIR_NAME = ".jax_cache"
STEPS_AHEAD = 2


class HarnessFailure(Exception):
    """The run cannot produce a result (no chip, wrong chip, bad spec)."""


class CompileEvents:
    """Counts what JAX compiles: requests that consulted the persistent
    cache, how many of them it served, and calls into the compile path
    (which a cache hit also makes; without a cache it is the only count)."""

    def __init__(self):
        import jax

        self.requests = self.hits = self.backend_compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def mark(self):
        return (self.requests, self.hits, self.backend_compiles)

    def since(self, mark):
        return {"compile_requests": self.requests - mark[0],
                "cache_hits": self.hits - mark[1],
                "compile_calls": self.backend_compiles - mark[2]}


def place_compile_cache(root, rehearse):
    """JAX's persistent cache at its one place: what
    ``JAX_COMPILATION_CACHE_DIR`` says (then nothing is set in code), else
    ``<checkout>/.jax_cache``.  Every program is cached, however quick its
    compile, so that a warm run compiles nothing.  A rehearsal's CPU
    programs are of no use to a chip run and are not cached."""
    import jax

    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(root, CACHE_DIR_NAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def find_devices(cell, rehearse):
    """The cell's devices and the run's device record, or HarnessFailure:
    no accelerator, another count than the cell's chips, or a device_kind
    without published peaks."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform == "tpu":
            raise HarnessFailure("--rehearse is the CPU dress rehearsal; on "
                                 "the chip run without it")
        if len(devices) < cell.chips:
            raise HarnessFailure(
                f"the rehearsal of {cell.name} needs {cell.chips} CPU devices"
                f" (XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{cell.chips}), found {len(devices)}")
        devices, peaks = devices[:cell.chips], None
    else:
        if platform != "tpu":
            raise HarnessFailure(
                f"no accelerator: JAX found {platform} devices, the "
                "benchmark measures on a TPU (--rehearse is the CPU dress "
                "rehearsal)")
        if len(devices) != cell.chips:
            raise HarnessFailure(
                f"{len(devices)} chips attached, cell {cell.name} is "
                f"defined on {cell.chips}")
        peaks = spec.load_peaks(devices[0].device_kind, cell.root)
    record = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    return devices, record, peaks


class Run:
    """One cell's program, built from one seed, and the one compiled step
    that set-up, the check's first steps and the window all call."""

    def __init__(self, cell, devices):
        self.cell, self.devices = cell, devices
        self.comm = cell.family.make_comm(cell.sizes, devices)
        self.compiled = None
        self.compile_info = None
        self.instruction_scopes, self.mixed_fusions = {}, []
        self.steps_taken = 0
        self._norms = self._change = None

    def _seeded_params(self, key):
        return self.cell.family.make_params(self.cell.sizes, key)

    # -- state from a seed -------------------------------------------------
    def seed(self, seed):
        """Weights, program state and the ring of batches for ``seed``."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        cell, comm = self.cell, self.comm
        self.seed_value = seed
        replicated = NamedSharding(comm.mesh, P())
        params = jax.jit(self._seeded_params, out_shardings=replicated)(
            weights.seed_key(seed, 0))
        self.step, self.state = cell.family.build(comm, cell.sizes, params)
        self.state = list(self.state)
        self.ring = generator.make_ring(
            cell.sizes, cell.chips, weights.seed_key(seed, 1), comm.mesh,
            comm.data_axes)
        self.steps_taken = 0

    # -- the one compile ---------------------------------------------------
    def compile(self, read_scopes=False):
        """Trace and compile the step ONCE, from this one call site (a
        Pallas kernel's cache key carries its callers' line numbers), and
        read what the compiled program says of itself.  ``read_scopes`` (a
        traced run) also keeps the compiled text's ``{instruction:
        op_name}`` and its mixed fusions for ``scopes.event_scopes``, and
        keys this one compile on the names: JAX's persistent cache keys a
        program with its debug info stripped, so it would serve an
        executable compiled before a scope was added, whose ``as_text()``
        holds the OLD names (PR 24's first ResNet run read the parent's).
        Without it the compile, its cache key and ``setup_s`` are as they
        were."""
        traced = self.step.trace(*self.state, self.ring[0])
        with (_cache_keyed_on_names() if read_scopes
              else contextlib.nullcontext()):
            compiled = traced.lower().compile()
        memory = compiled.memory_analysis()
        interpreted = _pallas_interpret_flags(traced.jaxpr)
        self.compiled = compiled
        text = compiled.as_text()
        if read_scopes:
            parsed = scopes.parse(text)
            self.instruction_scopes = scopes.instruction_scopes(parsed)
            self.mixed_fusions = scopes.mixed_fusions(parsed)
        self.compile_info = {
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "pallas_calls": len(interpreted),
            "pallas_calls_interpreted": int(sum(interpreted)),
            "argument_bytes": int(memory.argument_size_in_bytes),
            "output_bytes": int(memory.output_size_in_bytes),
            "alias_bytes": int(memory.alias_size_in_bytes),
            "temp_bytes": int(memory.temp_size_in_bytes),
        }
        return self.compile_info

    def call(self, batch):
        """One step through the compiled program; returns the loss array
        (not read).  The state is donated and replaced."""
        *state, loss = self.compiled(*self.state, batch)
        self.state = state
        self.steps_taken += 1
        return loss

    def next_batch(self):
        return self.ring[self.steps_taken % len(self.ring)]

    # -- the check's first steps -------------------------------------------
    def first_steps(self):
        """Steps one to three through the window's own call, on ring
        batches that all differ, and what the check compares of them."""
        import jax
        import jax.numpy as jnp

        from chipbench.references.common import leaf_norms

        cell = self.cell
        family = cell.family
        if self._norms is None:     # once a process, whatever the seeds
            self._norms = jax.jit(leaf_norms)
            self._change = jax.jit(lambda params, key: leaf_norms(
                jax.tree.map(jnp.subtract, params, self._seeded_params(key))))
        grad_after = family.first_gradient_after(cell.sizes)
        readings = {"losses": []}
        for index in range(3):
            loss = self.call(self.ring[index])
            readings["losses"].append(float(loss))
            if index + 1 == grad_after:
                readings["grad_norms"] = np.asarray(self._norms(
                    family.first_gradient_of(self.state)))
        readings["delta_norms"] = self.parameter_change()
        return readings

    def leaf_names(self):
        import jax

        paths, _ = jax.tree_util.tree_flatten_with_path(
            self.cell.family.params_of(self.state))
        return ["/".join(str(getattr(k, "key", k)) for k in path)
                for path, _ in paths]

    def parameter_change(self):
        """Per-leaf norm of params - seeded params (the start is made again
        from the seed, not kept)."""
        return np.asarray(self._change(
            self.cell.family.params_of(self.state),
            weights.seed_key(self.seed_value, 0)))

    # -- driving -----------------------------------------------------------
    def drive(self, *, seconds=None, steps=None, annotate=False):
        """The host loop: call the step, staying ``STEPS_AHEAD`` steps in
        front of the device; after enqueuing step i read the loss VALUE of
        step i - STEPS_AHEAD and stamp its completion.  Stops after
        ``seconds`` or ``steps``, then drains."""
        import jax

        span = (jax.profiler.TraceAnnotation if annotate
                else (lambda name: contextlib.nullcontext()))
        pending = collections.deque()
        out = {"enqueued": 0, "completed_at": [], "losses": [],
               "dispatch_s": [], "raised": None}

        def read_one():
            with span("read_loss"):
                value = float(pending.popleft())
            out["completed_at"].append(time.perf_counter())
            out["losses"].append(value)

        out["began_at"] = time.perf_counter()
        deadline = None if seconds is None else out["began_at"] + seconds
        try:
            while ((deadline is None or time.perf_counter() < deadline)
                   and (steps is None or out["enqueued"] < steps)):
                batch = self.next_batch()
                t0 = time.perf_counter()
                with span("dispatch"):
                    pending.append(self.call(batch))
                out["dispatch_s"].append(time.perf_counter() - t0)
                out["enqueued"] += 1
                if len(pending) > STEPS_AHEAD:
                    read_one()
            while pending:
                read_one()
        except Exception as error:  # a step that raises fails the run
            out["raised"] = repr(error)
        out["ended_at"] = time.perf_counter()
        return out

    def traced(self, trace_dir):
        """A short steady window under the profiler: ``trace_steps`` steps
        with the host's phases annotated."""
        import jax

        jax.profiler.start_trace(trace_dir)
        try:
            out = self.drive(steps=int(self.cell.sizes["trace_steps"]),
                             annotate=True)
        finally:
            jax.profiler.stop_trace()
        return out

    # -- memory ------------------------------------------------------------
    def memory_peak_bytes(self):
        """The peak on the fullest chip.  The runtime's own counter leaves a
        program's temporaries out (PERF.md), so the peak is the larger of
        that counter and what is resident beside the step (state, ring)
        plus the step's temporaries and the outputs it does not alias."""
        info = self.compile_info
        peaks = []
        for device in self.devices:
            stats = device.memory_stats() or {}
            resident = stats.get("bytes_in_use", 0)
            while_running = (resident + info["temp_bytes"]
                             + info["output_bytes"] - info["alias_bytes"])
            peaks.append(max(stats.get("peak_bytes_in_use", 0),
                             while_running))
        return int(max(peaks))

    def release(self, keep_batches=3, keep_compiled=False):
        """Free the program's state, its compiled step and the ring but for
        its first batches (the reference's inputs), which are returned."""
        import jax

        kept = self.ring[:keep_batches]
        for leaf in jax.tree.leaves((self.state, self.ring[keep_batches:])):
            leaf.delete()
        self.state = self.ring = self.step = None
        if not keep_compiled:
            self.compiled = None
        return kept


@contextlib.contextmanager
def _cache_keyed_on_names():
    """For the length of one compile, make the persistent cache's key hold
    the program's metadata (the scope names among it)."""
    import jax

    option = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, option)
    jax.config.update(option, True)
    try:
        yield
    finally:
        jax.config.update(option, before)


def _pallas_interpret_flags(jaxpr):
    """``interpret`` of every pallas_call reachable from ``jaxpr`` (copied
    from chip_smoke.py)."""
    flags = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            flags.append(bool(eqn.params["interpret"]))
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    flags.extend(_pallas_interpret_flags(inner))
    return flags


def check_placement(run):
    """Parameters replicated on every chip of the cell, every batch leaf
    split by rows into equal parts (chip_smoke.check_placement's checks)."""
    import jax

    n = len(run.devices)
    problems = []
    for leaf in jax.tree.leaves(run.ring[0]):
        rows = sorted(s.data.shape[0] for s in leaf.addressable_shards)
        if (len(leaf.sharding.device_set) != n
                or rows != [leaf.shape[0] // n] * n):
            problems.append(f"batch leaf {leaf.shape} has shard rows {rows} "
                            f"on {len(leaf.sharding.device_set)} devices")
    for leaf in jax.tree.leaves(run.cell.family.params_of(run.state)):
        if len(leaf.sharding.device_set) != n or not leaf.is_fully_replicated:
            problems.append(f"parameter leaf {leaf.shape} is not replicated "
                            f"on {n} devices")
            break
    return problems


def reference_readings(cell, seed, first_batches, devices,
                       precision="float32"):
    """The plain reference's first three steps from the same seed, after the
    program's state is gone: on the first of ``devices``, but for the row
    blocks' losses and gradients, one device each.  ``first_batches`` are the
    ring's first three (inputs, made by the benchmark from the seed)."""
    import jax

    from chipbench.references import common

    make = jax.jit(
        lambda key: cell.family.make_params(cell.sizes, key),
        out_shardings=jax.sharding.SingleDeviceSharding(devices[0]))
    batches = [jax.device_put(batch, devices[0]) for batch in first_batches]
    loss = cell.reference.make_loss(cell.sizes, precision)
    return common.follow_three_steps(
        loss, lambda: make(weights.seed_key(seed, 0)), batches,
        cell.sizes["optimizer"], devices=list(devices))
