"""Trainer extensions (the Chainer ``training.extensions`` role).

The reference gates these to rank 0 in every example
(``if comm.rank == 0: trainer.extend(...)`` — SURVEY.md §5.5); the same
pattern applies here.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Callable, List, Optional

import jax
import numpy as np


def _to_float(v):
    try:
        return float(np.asarray(v))
    except Exception:
        return v


class LogReport:
    """Aggregate per-iteration observations; emit one averaged record per
    emit trigger.  Writes ``log`` (JSON) under ``trainer.out``.

    Runs every iteration (it must see each observation); ``trigger`` here is
    the *emit* cadence, mirroring Chainer's LogReport semantics.

    Output formats: ``format="json"`` (default) keeps the reference's
    one-JSON-array file but writes it atomically (tmp file + rename — a
    crash mid-write can no longer truncate the log, readers never see a
    torn file).  ``format="jsonl"`` appends one record per line instead —
    O(record) per emit rather than O(run-so-far), the right choice for
    long runs; it shares the sink with the observability metrics JSONL.
    A ``.jsonl`` filename implies ``format="jsonl"``.
    """

    priority = 50
    name = "LogReport"
    trigger = (1, "iteration")  # called every iteration; emits on _emit

    def __init__(self, trigger=(1, "epoch"), filename: str = "log",
                 format: Optional[str] = None):
        if format is None:
            format = "jsonl" if filename.endswith(".jsonl") else "json"
        if format not in ("json", "jsonl"):
            raise ValueError(f"format must be 'json' or 'jsonl', got "
                             f"{format!r}")
        self._emit = trigger
        self._filename = filename
        self._format = format
        self._accum: dict = {}
        self._counts: dict = {}
        self.log: List[dict] = []

    def __call__(self, trainer):
        from chainermn_tpu.observability import append_jsonl, atomic_write_json
        from chainermn_tpu.training.trainer import _trigger_fires

        for k, v in trainer.observation.items():
            # accumulate without converting: jax scalars stay on device so
            # the hot loop never blocks on the just-dispatched step
            self._accum[k] = (self._accum[k] + v) if k in self._accum else v
            self._counts[k] = self._counts.get(k, 0) + 1
        if not _trigger_fires(self._emit, trainer.updater):
            return
        record = {k: _to_float(self._accum[k]) / self._counts[k]
                  for k in self._accum}
        record.update({
            "epoch": trainer.updater.epoch,
            "iteration": trainer.updater.iteration,
            "elapsed_time": trainer.elapsed_time,
        })
        self.log.append(record)
        self._accum, self._counts = {}, {}
        path = os.path.join(trainer.out, self._filename)
        if self._format == "jsonl":
            append_jsonl(path, record)
        else:
            atomic_write_json(path, self.log)


class MetricsReport:
    """Runtime-observability extension: per-step timing breakdown,
    communicator counters, and the periodic cross-rank straggler report,
    all appended to one metrics JSONL (schema shared with the benchmark
    emitters; render with ``tools/obs_report.py``).

    On ``initialize`` it installs a
    :class:`~chainermn_tpu.observability.StepTelemetry` on the updater —
    but only when observability is enabled
    (``chainermn_tpu.observability.enable()`` or the
    ``CHAINERMN_TPU_OBSERVABILITY`` env var); otherwise the extension is
    inert and the trainer hot path stays untimed.

    Add it on **every** rank (the straggler report allgathers summaries
    over the control plane, so all ranks must participate at the same
    trigger); only rank 0 writes files.
    """

    priority = 45
    name = "MetricsReport"
    trigger = (1, "iteration")  # called every iteration; emits on _emit

    def __init__(self, trigger=(1, "epoch"), filename: str = "metrics.jsonl",
                 straggler_every: int = 1, straggler_threshold: float = 1.5,
                 prometheus: Optional[str] = None, registry=None,
                 tokens_per_example: Optional[int] = None,
                 watchdog: Optional[bool] = None,
                 attribution: bool = True,
                 attribution_factor: float = 2.0,
                 profile_dir: Optional[str] = None,
                 stream_telemetry: bool = False):
        if straggler_every < 1:
            raise ValueError(f"straggler_every must be >= 1, got "
                             f"{straggler_every}")
        self._emit = trigger
        self._filename = filename
        self._straggler_every = straggler_every
        self._straggler_threshold = straggler_threshold
        self._prometheus = prometheus
        self._registry = registry
        self._tokens_per_example = tokens_per_example
        # watchdog=True starts the hang watchdog (flight dumps land next
        # to the metrics JSONL); None defers to CHAINERMN_TPU_WATCHDOG.
        self._want_watchdog = watchdog
        self._watchdog = None
        # attribution=True (and the flight recorder on) runs the online
        # per-bucket regression watch over each completed step's span
        # tree; profile_dir arms the jax.profiler capture hook that
        # snapshots a flagged step.
        self._want_attribution = attribution
        self._attribution_factor = attribution_factor
        self._profile_dir = profile_dir
        self._attr = None
        # stream_telemetry=True ships each rank's compact per-window
        # summary (occupancy, dropped events, step times, serving
        # latency histograms) to rank 0 over the control plane at every
        # emit and appends the folded fleet_telemetry document to the
        # JSONL (obs_report --contention / --live render it).  Off by
        # default: zero control-plane traffic when unset, and the whole
        # aggregator only exists when observability is enabled.
        self._want_stream = stream_telemetry
        self._stream = None
        self._active = False

    def initialize(self, trainer):
        from chainermn_tpu import observability as obs

        self._active = obs.enabled()
        if not self._active:
            return
        reg = self._registry if self._registry is not None else \
            obs.get_registry()
        comm = trainer.updater.comm
        self._reg = reg
        self._comm = comm
        self._tele = obs.StepTelemetry(
            registry=reg, comm=comm,
            straggler_threshold=self._straggler_threshold)
        trainer.updater.telemetry = self._tele
        self._is_writer = getattr(comm, "rank", 0) == 0
        self._path = os.path.join(trainer.out, self._filename)
        self._win = {"steps": 0, "examples": 0,
                     **{p: 0.0 for p in self._tele.PHASES}}
        self._t_last_emit = time.perf_counter()
        self._emits = 0
        self._fr = obs.get_flight_recorder()
        self._attr_seq = -1
        self._last_attr = None
        if self._want_attribution and self._fr is not None:
            from chainermn_tpu.observability.straggler import \
                AttributionWatch
            self._attr = AttributionWatch(
                registry=reg, flight=self._fr,
                factor=self._attribution_factor,
                profile_dir=self._profile_dir)
        if self._want_stream:
            from chainermn_tpu.observability.streaming import \
                TelemetryAggregator
            self._stream = TelemetryAggregator(comm)
        want_wd = self._want_watchdog
        if want_wd is None:
            want_wd = os.environ.get("CHAINERMN_TPU_WATCHDOG", "") \
                not in ("", "0", "false", "off")
        if want_wd and self._watchdog is None:
            from chainermn_tpu.observability import start_watchdog

            self._watchdog = start_watchdog(
                control_plane=getattr(comm, "_cp", None),
                out_dir=trainer.out)

    def _observe_attribution(self) -> None:
        """Feed every newly-completed step's span tree to the
        attribution watch (incremental: only events past the last
        consumed step are re-read from the ring)."""
        if self._attr is None:
            return
        evs = self._fr.events_since(self._attr_seq)
        step_evs = [e for e in evs if e.get("kind") == "step"]
        if not step_evs:
            return
        last_seq = step_evs[-1].get("seq", self._attr_seq)
        window = [e for e in evs if e.get("seq", 0) <= last_seq]
        from chainermn_tpu.observability import attribution as _attribution
        from chainermn_tpu.observability import spans as _spans
        for tree in _spans.build_step_trees(
                window, rank=getattr(self._comm, "rank", 0)):
            self._last_attr = _attribution.attribute_step(tree)
            self._attr.observe(self._last_attr)
        self._attr_seq = last_seq

    def _emit_record(self, trainer) -> dict:
        import time as _t

        now = time.perf_counter()
        dt = max(now - self._t_last_emit, 1e-9)
        self._t_last_emit = now
        w = self._win
        n = max(w["steps"], 1)
        record = {
            "kind": "step_report",
            "ts": _t.time(),
            "iteration": trainer.updater.iteration,
            "epoch": trainer.updater.epoch,
            "elapsed_time": trainer.elapsed_time,
            "steps": w["steps"],
            "examples_per_sec": w["examples"] / dt,
            "steps_per_sec": w["steps"] / dt,
        }
        if self._tokens_per_example:
            record["tokens_per_sec"] = (
                w["examples"] * self._tokens_per_example / dt)
        for p in self._tele.PHASES:
            record[f"{p}_s_mean"] = w[p] / n
        record["step_s_mean"] = sum(w[p] for p in self._tele.PHASES) / n
        self._win = {"steps": 0, "examples": 0,
                     **{p: 0.0 for p in self._tele.PHASES}}
        return record

    def __call__(self, trainer):
        from chainermn_tpu.observability import (
            append_jsonl, write_prometheus, write_snapshot_jsonl)
        from chainermn_tpu.training.trainer import _trigger_fires

        if not self._active:
            return
        last = self._tele.last
        if last is not None:
            w = self._win
            w["steps"] += 1
            w["examples"] += last["examples"]
            for p in self._tele.PHASES:
                w[p] += last[f"{p}_s"]
            self._tele.last = None
        self._observe_attribution()
        if not _trigger_fires(self._emit, trainer.updater):
            return
        record = self._emit_record(trainer)
        self._emits += 1
        straggler = None
        if self._emits % self._straggler_every == 0:
            # COLLECTIVE over the control plane — every rank reaches this
            # at the same trigger; do not gate it on the writer rank.
            straggler = self._tele.straggler.report()
        fleet = None
        if self._stream is not None:
            # COLLECTIVE (control-plane gather to rank 0): every rank
            # ships its telemetry window at this trigger.
            fleet = self._stream.collect(trainer.updater.iteration)
        if not self._is_writer:
            return
        append_jsonl(self._path, record)
        write_snapshot_jsonl(self._path, self._reg.snapshot(),
                             rank=self._comm.rank)
        if fleet is not None:
            append_jsonl(self._path, dict(fleet, ts=time.time()))
        if straggler is not None:
            straggler = dict(straggler,
                             iteration=trainer.updater.iteration)
            append_jsonl(self._path, straggler)
        if self._last_attr is not None:
            append_jsonl(self._path, dict(self._last_attr,
                                          kind="step_attribution",
                                          ts=time.time()))
            self._last_attr = None
        if self._prometheus:
            write_prometheus(self._prometheus, self._reg.snapshot())

    def finalize(self, trainer):
        from chainermn_tpu.observability import append_jsonl, write_snapshot_jsonl

        if self._watchdog is not None:
            # stop before the run goes quiet — a finished trainer must
            # not read as a step stall
            self._watchdog.stop()
            self._watchdog = None
        if not self._active or self._win["steps"] == 0:
            return
        record = self._emit_record(trainer)
        straggler = self._tele.straggler.report()
        if not self._is_writer:
            return
        append_jsonl(self._path, record)
        write_snapshot_jsonl(self._path, self._reg.snapshot(),
                             rank=self._comm.rank)
        append_jsonl(self._path, dict(straggler,
                                      iteration=trainer.updater.iteration))


class PrintReport:
    priority = 40

    def __init__(self, entries: List[str], log_report: str = "LogReport",
                 out=sys.stdout):
        self.trigger = (1, "epoch")
        self._entries = entries
        self._log_report = log_report
        self._out = out
        self._header_done = False

    def __call__(self, trainer):
        lr = trainer.get_extension(self._log_report)
        if not lr.log:
            return
        rec = lr.log[-1]
        if not self._header_done:
            self._out.write("  ".join(f"{e:>16}" for e in self._entries) + "\n")
            self._header_done = True
        row = []
        for e in self._entries:
            v = rec.get(e, "")
            row.append(f"{v:16.6g}" if isinstance(v, float) else f"{v!s:>16}")
        self._out.write("  ".join(row) + "\n")
        self._out.flush()


class Evaluator:
    """Run an eval function over a validation iterator; put mean metrics in
    ``trainer.observation`` under ``validation/<key>``.

    ``eval_fn(params, batch) -> dict`` should return *already
    device-averaged* metrics (build it with the communicator's SPMD helpers
    — see ``chainermn_tpu.extensions.create_multi_node_evaluator`` for the
    cross-host aggregation wrapper, the reference's multi-node evaluator).
    """

    priority = 60
    trigger = (1, "epoch")
    name = "validation"

    def __init__(self, iterator, eval_fn: Callable, comm,
                 prefix: str = "validation",
                 state_getter: Optional[Callable] = None):
        if not hasattr(iterator, "reset") or \
                not getattr(iterator, "rewindable", True):
            raise ValueError(
                f"Evaluator needs a rewindable iterator, got "
                f"{type(iterator).__name__} (evaluation calls reset() every "
                f"epoch).  Wrap the eval dataset in TransformDataset + "
                f"SerialIterator instead of PrefetchIterator.")
        self.iterator = iterator
        self.eval_fn = eval_fn
        self.comm = comm
        self.prefix = prefix
        # For stateful models (BatchNorm running stats): pulls the CURRENT
        # model state from the trainer at evaluation time, and eval_fn
        # becomes eval_fn(params, state, batch) — pair with
        # make_eval_fn(..., with_model_state=True).
        self.state_getter = state_getter

    def evaluate(self, params, state=None) -> dict:
        from chainermn_tpu.training.trainer import put_global_batch

        totals: dict = {}
        count = 0
        self.iterator.reset()
        for batch in self.iterator:
            # wrap-pad the final partial batch so its leading dim divides the
            # device count (same equal-length policy as scatter_dataset)
            batch = put_global_batch(self.comm, batch, pad_to_multiple=True)
            if state is not None:
                metrics = self.eval_fn(params, state, batch)
            else:
                metrics = self.eval_fn(params, batch)
            for k, v in metrics.items():
                totals[k] = totals.get(k, 0.0) + _to_float(v)
            count += 1
        return {k: v / max(count, 1) for k, v in totals.items()}

    def __call__(self, trainer):
        state = (self.state_getter(trainer)
                 if self.state_getter is not None else None)
        result = self.evaluate(trainer.updater.params, state)
        trainer.observation.update(
            {f"{self.prefix}/{k}": v for k, v in result.items()})


class Snapshot:
    """Periodic checkpoint via a checkpointer object (see
    ``chainermn_tpu.extensions.checkpoint``)."""

    priority = 30

    def __init__(self, checkpointer, state_getter: Callable,
                 trigger=(1, "epoch")):
        self.trigger = trigger
        self._ckpt = checkpointer
        self._get = state_getter

    def __call__(self, trainer):
        self._ckpt.save(self._get(trainer), trainer.updater.iteration)
