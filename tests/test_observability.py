"""Observability subsystem tests (ISSUE 1 tentpole).

Covers: registry semantics (labels, quantiles, reset, type conflicts),
sink round-trips (JSONL, atomic JSON, Prometheus golden text),
instrumented-communicator byte/latency accounting over the real CPU mesh,
straggler aggregation with a synthetically slow rank, the MetricsReport
end-to-end artifact, and the zero-cost-when-disabled guarantee on the
trainer hot path.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu import observability as obs
from chainermn_tpu.observability import (
    Counter,
    Gauge,
    Histogram,
    InstrumentedCommunicator,
    MetricsRegistry,
    append_jsonl,
    atomic_write_json,
    instrument_communicator,
    prometheus_text,
    read_jsonl,
    straggler_report,
    summarize_durations,
    write_snapshot_jsonl,
)
from chainermn_tpu.observability.registry import StreamingHistogram
from chainermn_tpu.observability.straggler import StragglerDetector, StepTelemetry
from chainermn_tpu.optimizers import init_opt_state, make_train_step
from chainermn_tpu.parallel.expert import ExpertParallelMLP
from chainermn_tpu.parallel.fsdp import fsdp_init, make_fsdp_train_step
from chainermn_tpu.planner import alltoall_plans, execute_plan, striped_plan
from chainermn_tpu.planner.plans import compressed_two_dimensional
from chainermn_tpu.training.trainer import put_global_batch


@pytest.fixture
def comm():
    return chainermn_tpu.create_communicator("naive", intra_size=4)


@pytest.fixture
def enabled_obs():
    """Enable the switch for one test; restore disabled + empty registry."""
    obs.enable()
    obs.get_registry().reset()
    yield obs
    obs.get_registry().reset()
    obs.disable()


# ---- registry ---------------------------------------------------------------

class TestRegistry:
    def test_counter_labels_are_distinct_series(self):
        c = Counter("calls")
        c.inc(op="allreduce")
        c.inc(2, op="allreduce")
        c.inc(op="bcast")
        assert c.value(op="allreduce") == 3.0
        assert c.value(op="bcast") == 1.0
        assert c.value(op="never") == 0.0
        # label ORDER must not create new series
        c.inc(op="x", comm="naive")
        c.inc(comm="naive", op="x")
        assert c.value(op="x", comm="naive") == 2.0

    def test_gauge_set_and_inc(self):
        g = Gauge("depth")
        g.set(4)
        g.inc(-1)
        assert g.value() == 3.0

    def test_histogram_quantiles_and_stats(self):
        h = Histogram("lat", window_size=100)
        for v in range(1, 101):  # 1..100
            h.observe(float(v))
        assert h.count() == 100
        assert h.sum() == pytest.approx(5050.0)
        assert h.quantile(0.0) == 1.0
        assert h.quantile(1.0) == 100.0
        assert h.quantile(0.5) == pytest.approx(50.5)
        assert h.quantile(0.9) == pytest.approx(90.1)
        assert h.quantile(0.3, nope="x") is None  # unseen labels
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_histogram_window_keeps_recent(self):
        h = Histogram("lat", window_size=10)
        for v in range(100):
            h.observe(float(v))
        # count/sum are exact over the lifetime...
        assert h.count() == 100
        # ...quantiles come from the last 10 observations (90..99)
        assert h.quantile(0.0) == 90.0

    def test_registry_get_or_create_and_type_conflict(self):
        r = MetricsRegistry()
        c1 = r.counter("x", "help")
        assert r.counter("x") is c1
        with pytest.raises(TypeError, match="already registered"):
            r.gauge("x")
        assert r.names() == ["x"]

    def test_registry_reset_and_snapshot_sorted(self):
        r = MetricsRegistry()
        r.counter("b").inc()
        r.counter("a").inc()
        snap = r.snapshot()
        assert [s["name"] for s in snap] == ["a", "b"]
        r.reset()
        assert r.snapshot() == []

    def test_timer_records_elapsed(self):
        r = MetricsRegistry()
        t = r.timer("took_seconds", phase="x")
        with t:
            pass
        assert t.elapsed is not None and t.elapsed >= 0.0
        assert r.get("took_seconds").count(phase="x") == 1
        with t:  # reusable
            pass
        assert r.get("took_seconds").count(phase="x") == 2

    def test_enable_disable_switch(self):
        assert not obs.enabled()
        obs.enable()
        try:
            assert obs.enabled()
        finally:
            obs.disable()
        assert not obs.enabled()


# ---- sinks ------------------------------------------------------------------

class TestSinks:
    def test_jsonl_round_trip_and_torn_tail(self, tmp_path):
        p = str(tmp_path / "m.jsonl")
        append_jsonl(p, {"kind": "a", "v": 1})
        append_jsonl(p, {"kind": "b", "v": 2.5})
        with open(p, "a") as f:
            f.write('{"kind": "torn"')  # crashed writer
        recs = read_jsonl(p)
        assert [r["kind"] for r in recs] == ["a", "b"]

    def test_atomic_write_json(self, tmp_path):
        p = str(tmp_path / "log")
        atomic_write_json(p, [{"x": 1}])
        atomic_write_json(p, [{"x": 1}, {"x": 2}])
        assert json.load(open(p)) == [{"x": 1}, {"x": 2}]
        assert os.listdir(tmp_path) == ["log"], "tmp files must not leak"

    def test_snapshot_jsonl_stamps_ts_and_extra(self, tmp_path):
        r = MetricsRegistry()
        r.counter("c").inc(3, op="x")
        p = str(tmp_path / "m.jsonl")
        n = write_snapshot_jsonl(p, r.snapshot(), ts=123.0, rank=2)
        assert n == 1
        rec = read_jsonl(p)[0]
        assert rec["kind"] == "metric" and rec["ts"] == 123.0
        assert rec["rank"] == 2 and rec["value"] == 3.0

    def test_prometheus_golden(self):
        r = MetricsRegistry()
        r.counter("comm_calls").inc(5, op="allreduce")
        r.gauge("devices").set(8)
        h = r.histogram("step_seconds")
        for v in (1.0, 2.0, 3.0):
            h.observe(v, phase="dispatch")
        golden = (
            'chainermn_tpu_comm_calls_total{op="allreduce"} 5\n'
            'chainermn_tpu_devices 8\n'
            'chainermn_tpu_step_seconds{phase="dispatch",quantile="0.5"} 2\n'
            'chainermn_tpu_step_seconds{phase="dispatch",quantile="0.9"} 2.8\n'
            'chainermn_tpu_step_seconds{phase="dispatch",quantile="0.99"}'
            ' 2.98\n'
            'chainermn_tpu_step_seconds_sum{phase="dispatch"} 6\n'
            'chainermn_tpu_step_seconds_count{phase="dispatch"} 3\n'
        )
        text = prometheus_text(r.snapshot())
        body = "\n".join(l for l in text.splitlines()
                         if not l.startswith("#")) + "\n"
        assert body == golden
        assert "# TYPE chainermn_tpu_comm_calls_total counter" in text
        assert "# TYPE chainermn_tpu_step_seconds summary" in text
        assert "# TYPE chainermn_tpu_devices gauge" in text

    def test_prometheus_sanitizes_metric_and_label_names(self):
        # a "plan:inter" seam in a metric name or a "wire-dtype" label
        # key must not emit lines every scraper rejects
        r = MetricsRegistry()
        r.counter("plan:inter.bytes").inc(7, **{"wire-dtype": "bf16"})
        r.gauge("9devices").set(1)
        text = prometheus_text(r.snapshot())
        assert ('chainermn_tpu_plan:inter_bytes_total'
                '{wire_dtype="bf16"} 7') in text
        assert "chainermn_tpu_9devices 1" in text
        for line in text.splitlines():
            if line.startswith("#"):
                continue
            name = line.split("{")[0].split(" ")[0]
            assert not name[0].isdigit()
            assert all(c.isalnum() or c in "_:" for c in name)

    def test_prometheus_streaming_histogram_native_buckets(self):
        r = MetricsRegistry()
        h = r.streaming_histogram("ttft", lo=0.001, hi=1.0,
                                  buckets_per_decade=3)
        for v in (0.002, 0.02, 0.2):
            h.observe(v, model="m0")
        text = prometheus_text(r.snapshot())
        assert "# TYPE chainermn_tpu_ttft histogram" in text
        assert "# TYPE chainermn_tpu_ttft_quantile gauge" in text
        buckets = [l for l in text.splitlines()
                   if l.startswith("chainermn_tpu_ttft_bucket")]
        # cumulative counts end in the +Inf bucket carrying the total
        assert buckets[-1].endswith(" 3") and 'le="+Inf"' in buckets[-1]
        counts = [int(l.rsplit(" ", 1)[1]) for l in buckets]
        assert counts == sorted(counts)
        assert 'chainermn_tpu_ttft_count{model="m0"} 3' in text
        assert 'chainermn_tpu_ttft_sum{model="m0"}' in text
        assert 'quantile="0.5"' in text


# ---- streaming histogram (the fleet-mergeable latency kind) -----------------

class TestStreamingHistogram:
    def test_observe_count_sum_quantile(self):
        h = StreamingHistogram("lat", lo=1e-3, hi=1e2)
        for v in (0.01, 0.02, 0.04, 0.08):
            h.observe(v)
        assert h.count() == 4
        assert h.sum() == pytest.approx(0.15)
        q50 = h.quantile(0.5)
        assert 0.01 <= q50 <= 0.04  # exact to bucket resolution
        assert h.quantile(0.5, model="never") is None
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_state_merge_roundtrip_is_exact(self):
        a = StreamingHistogram("lat")
        b = StreamingHistogram("lat")
        for v in (0.01, 0.03):
            a.observe(v, model="m")
        for v in (0.02, 0.05, 0.09):
            b.observe(v, model="m")
        fleet = StreamingHistogram("lat")
        fleet.merge(a.state(model="m"), model="m")
        fleet.merge(b.state(model="m"), model="m")
        assert fleet.count(model="m") == 5
        assert fleet.sum(model="m") == pytest.approx(0.20)
        # fleet percentiles equal observing the union directly
        union = StreamingHistogram("lat")
        for v in (0.01, 0.03, 0.02, 0.05, 0.09):
            union.observe(v, model="m")
        for q in (0.5, 0.95, 0.99):
            assert fleet.quantile(q, model="m") == \
                pytest.approx(union.quantile(q, model="m"))

    def test_merge_refuses_grid_mismatch(self):
        a = StreamingHistogram("lat", lo=1e-3, hi=1e2)
        b = StreamingHistogram("lat", lo=1e-5, hi=1e3)
        a.observe(0.01)
        with pytest.raises(ValueError, match="buckets"):
            b.merge(a.state())

    def test_bad_bounds_rejected(self):
        with pytest.raises(ValueError):
            StreamingHistogram("x", lo=0.0, hi=1.0)
        with pytest.raises(ValueError):
            StreamingHistogram("x", lo=1.0, hi=0.5)

    def test_registry_factory_and_type_conflict(self):
        r = MetricsRegistry()
        h = r.streaming_histogram("x")
        assert r.streaming_histogram("x") is h
        with pytest.raises(TypeError, match="already registered"):
            r.histogram("x")


# ---- instrumented communicator ----------------------------------------------

class TestInstrumentedCommunicator:
    def test_disabled_returns_unwrapped(self, comm):
        assert not obs.enabled()
        assert instrument_communicator(comm) is comm

    def test_enabled_wraps_and_is_idempotent(self, comm, enabled_obs):
        icomm = instrument_communicator(comm)
        assert isinstance(icomm, InstrumentedCommunicator)
        assert instrument_communicator(icomm) is icomm
        assert icomm.wrapped is comm
        assert icomm.size == comm.size  # delegation

    def test_eager_bcast_data_bytes_and_latency(self, comm):
        reg = MetricsRegistry()
        icomm = InstrumentedCommunicator(comm, registry=reg)
        params = {"w": np.ones((16, 4), np.float32),
                  "b": np.ones((4,), np.float32)}
        out = icomm.bcast_data(params)
        np.testing.assert_allclose(np.asarray(out["b"]), 1.0)
        labels = dict(op="bcast_data", comm=type(comm).__name__)
        assert reg.get("comm_collective_calls").value(**labels) == 1
        assert reg.get("comm_collective_bytes").value(
            dtype="float32", **labels) == (16 * 4 + 4) * 4
        lat = reg.get("comm_collective_seconds")
        assert lat.count(**labels) == 1
        assert lat.sum(**labels) > 0.0

    def test_traced_allreduce_grad_records_once_per_trace(self, comm):
        reg = MetricsRegistry()
        icomm = InstrumentedCommunicator(comm, registry=reg)
        n = comm.size
        grads = jnp.tile(jnp.arange(n, dtype=jnp.float32)[:, None], (1, 8))

        def body(g):
            return icomm.allreduce_grad(g)

        labels = dict(op="allreduce_grad", comm=type(comm).__name__)
        for _ in range(3):  # one trace, three executions
            out = icomm.run_spmd(body, grads)
        np.testing.assert_allclose(np.asarray(out), (n - 1) / 2.0)
        assert reg.get("comm_collective_calls").value(**labels) == 1
        # per-rank payload under trace: one (8,) float32 row
        assert reg.get("comm_collective_bytes").value(
            dtype="float32", **labels) == 8 * 4
        # tracing is not communication: no latency under a collective's name
        assert reg.get("comm_collective_seconds").count(**labels) == 0

    def test_object_plane_and_barrier(self, comm):
        reg = MetricsRegistry()
        icomm = InstrumentedCommunicator(comm, registry=reg)
        assert icomm.allgather_obj({"r": 0}) == [{"r": 0}]
        icomm.barrier()
        calls = reg.get("comm_object_calls")
        assert calls.value(op="allgather_obj",
                           comm=type(comm).__name__) == 1
        assert calls.value(op="barrier", comm=type(comm).__name__) == 1

    def test_split_axes_stays_instrumented(self, comm):
        reg = MetricsRegistry()
        icomm = InstrumentedCommunicator(comm, registry=reg)
        sub = icomm.split_axes(["intra"])
        assert isinstance(sub, InstrumentedCommunicator)


# ---- straggler --------------------------------------------------------------

class TestStraggler:
    def test_summarize_durations(self):
        s = summarize_durations([0.1, 0.2, 0.3, 0.4])
        assert s["count"] == 4
        assert s["mean_s"] == pytest.approx(0.25)
        assert s["p50_s"] == pytest.approx(0.25)
        assert s["max_s"] == pytest.approx(0.4)
        empty = summarize_durations([])
        assert empty["count"] == 0 and empty["mean_s"] is None

    def test_slow_rank_is_flagged(self):
        """4 healthy ranks + 1 synthetically delayed rank -> exactly that
        rank flagged, with its ratio vs the healthy median."""
        summaries = []
        for rank in range(4):
            s = summarize_durations([0.10, 0.11, 0.09, 0.10])
            s["rank"] = rank
            summaries.append(s)
        slow = summarize_durations([0.30, 0.32, 0.31, 0.29])
        slow["rank"] = 4
        summaries.append(slow)
        rep = straggler_report(summaries, threshold=1.5)
        assert rep["kind"] == "straggler_report"
        assert rep["n_ranks"] == 5
        assert [s["rank"] for s in rep["stragglers"]] == [4]
        assert rep["stragglers"][0]["ratio_vs_median"] == pytest.approx(
            3.05, rel=0.05)

    def test_threshold_must_exceed_one(self):
        with pytest.raises(ValueError, match="threshold"):
            straggler_report([], threshold=1.0)
        with pytest.raises(ValueError, match="threshold"):
            StragglerDetector(threshold=0.9)

    def test_detector_single_host_report(self, comm):
        det = StragglerDetector(comm, threshold=2.0, window_size=8)
        for v in (0.1, 0.2, 0.3):
            det.record(v)
        rep = det.report(reset=True)
        assert rep["n_ranks"] == 1
        assert rep["ranks"][0]["count"] == 3
        assert rep["ranks"][0]["rank"] == comm.rank
        assert det.report()["ranks"][0]["count"] == 0  # reset took

    def test_step_telemetry_records_all_layers(self, comm):
        reg = MetricsRegistry()
        tele = StepTelemetry(registry=reg, comm=comm)
        tele.record_step(data_load=0.01, host_put=0.02, dispatch=0.03,
                         device_block=0.04, examples=64)
        assert tele.last["step_s"] == pytest.approx(0.10)
        assert reg.get("train_examples").value() == 64
        assert reg.get("train_iterations").value() == 1
        assert reg.get("step_phase_seconds").count(phase="dispatch") == 1
        assert reg.get("step_seconds").count() == 1


# ---- trainer integration ----------------------------------------------------

def _make_trainer(comm, tmp_path, n_iters=4, extension=None):
    from chainermn_tpu.datasets import TupleDataset
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.training import StandardUpdater, Trainer

    x = np.arange(32 * 4, dtype=np.float32).reshape(32, 4)
    it = SerialIterator(TupleDataset(x, np.zeros(32, np.int32)),
                        batch_size=16, shuffle=False)

    def step(params, opt_state, batch):
        return params, opt_state, jnp.sum(batch[0])

    updater = StandardUpdater(it, step, {"w": jnp.zeros(2)}, None, comm)
    trainer = Trainer(updater, (n_iters, "iteration"), out=str(tmp_path))
    if extension is not None:
        trainer.extend(extension)
    return trainer


def test_disabled_hot_path_makes_zero_observability_calls(
        comm, tmp_path, monkeypatch):
    """The acceptance guarantee: switch off => the updater/iterator hot
    path performs no observability work at all.  Every recording
    primitive is patched to explode; iterations must still run."""
    from chainermn_tpu.observability import registry as regmod
    from chainermn_tpu.training import extensions

    assert not obs.enabled()

    def boom(*a, **k):
        raise AssertionError("observability call on the disabled hot path")

    monkeypatch.setattr(regmod.Counter, "inc", boom)
    monkeypatch.setattr(regmod.Gauge, "set", boom)
    monkeypatch.setattr(regmod.Histogram, "observe", boom)
    monkeypatch.setattr(regmod._Timer, "__enter__", boom)
    monkeypatch.setattr(StepTelemetry, "record_step", boom)

    trainer = _make_trainer(comm, tmp_path,
                            extension=extensions.MetricsReport())
    trainer.run()
    assert trainer.updater.iteration == 4
    assert trainer.updater.telemetry is None
    assert not os.path.exists(os.path.join(str(tmp_path), "metrics.jsonl"))


# ---- the switches do not reach the program -----------------------------------

def _spmd_text(comm, body, *stacked):
    """Lowered text of the program ``comm.run_spmd(body, *stacked)`` runs."""
    return comm._spmd_program(body).lower(tuple(stacked)).as_text()


def _naive_2x4():
    return chainermn_tpu.create_communicator("naive", intra_size=4)


def _exchange(flavor):
    def build():
        comm = chainermn_tpu.create_communicator(
            flavor, intra_size=8 if flavor == "single_node" else 4)
        grads = {"w": jnp.ones((comm.size, 3, 5)),
                 "b": jnp.ones((comm.size, 7))}
        return _spmd_text(comm, lambda g: comm.allreduce_grad(g), grads)
    return build


def _cells_stack():
    """What every benchmark cell trains through: the ``xla`` flavor on a
    bfloat16 wire under the double-buffering optimizer."""
    comm = chainermn_tpu.create_communicator(
        "xla", allreduce_grad_dtype="bfloat16")
    params = comm.bcast_data({"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))})
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm, double_buffering=True)
    state = init_opt_state(comm, optimizer, params)
    step = make_train_step(
        comm, lambda p, b: jnp.mean((b[0] @ p["w"] + p["b"]) ** 2),
        optimizer, donate=False)
    batch = put_global_batch(comm, (jnp.ones((comm.size * 2, 4)),))
    return step.lower(params, state, batch).as_text()


def _plan_exchange(plan):
    def build():
        comm = _naive_2x4()
        return _spmd_text(comm, lambda g: execute_plan(plan, comm, g),
                          jnp.ones((comm.size, 2048)))
    return build


def _int8_allreduce_grad():
    comm = _naive_2x4()
    grads = {"w": jnp.ones((comm.size, 3, 5)), "b": jnp.ones((comm.size, 7))}
    state = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (comm.size,) + a.shape),
        comm.init_compression_state(grads, "int8"))
    return _spmd_text(
        comm,
        lambda g, s: comm.allreduce_grad(g, compressor="int8", state=s),
        grads, state)


def _fsdp_step(**init_args):
    def build():
        comm = _naive_2x4()
        params = {"a": jnp.ones((16, 16)), "b": jnp.ones((16,)),
                  "c": jnp.ones((16, 8))}
        state, meta = fsdp_init(comm, params, optax.adam(0.01),
                                num_buckets=2, **init_args)
        step = make_fsdp_train_step(
            comm,
            lambda p, b: jnp.mean(
                (jnp.tanh(b[0] @ p["a"] + p["b"]) @ p["c"]) ** 2),
            optax.adam(0.01), meta, donate=False)
        batch = put_global_batch(comm, (jnp.ones((comm.size * 2, 16)),))
        return step.lower(state, batch).as_text()
    return build


def _moe_exchange():
    comm = chainermn_tpu.create_communicator("hierarchical", intra_size=4)
    plan = {p.name: p for p in alltoall_plans(comm.plan_topology())}[
        "alltoall_hier_bfloat16_dcn"]
    axes = tuple(name for name, _ in comm.plan_topology().axes)
    model = ExpertParallelMLP(hidden=16, axis_name=axes, top_k=2,
                              num_experts=8, plan=plan)
    x = jnp.ones((16, 8))

    def body(z):
        return model.apply(model.init(jax.random.key(0), x), x) + z

    return jax.jit(jax.shard_map(
        body, mesh=comm.mesh, in_specs=P(), out_specs=P(axes),
        check_vma=False)).lower(jnp.zeros(())).as_text()


PROGRAMS = {
    **{flavor: _exchange(flavor) for flavor in (
        "naive", "flat", "hierarchical", "two_dimensional", "single_node")},
    "xla_bf16_double_buffered": _cells_stack,
    "striped_plan": _plan_exchange(striped_plan(0.5)),
    "per_hop_int8_plan": _plan_exchange(
        compressed_two_dimensional({"name": "int8"})),
    "int8_allreduce_grad": _int8_allreduce_grad,
    "fsdp": _fsdp_step(),
    "fsdp_int8": _fsdp_step(bucket_compressors="int8"),
    "moe_apply_plan": _moe_exchange,
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_switches_do_not_reach_the_program(program):
    """One program whatever the switches: traced with the metrics switch
    on and a flight recorder installed (what ``start_watchdog(force=True)``
    and ``install_crash_dumps(force=True)`` do), every exchange, FSDP step
    and planned MoE layer lowers to the text it lowers to with both off,
    and that text calls nothing on the host."""
    from chainermn_tpu.observability import (install_flight_recorder,
                                             reset_flight_recorder)

    assert not obs.enabled()
    reset_flight_recorder()
    off = PROGRAMS[program]()
    obs.enable()
    install_flight_recorder()
    try:
        on = PROGRAMS[program]()
    finally:
        obs.get_registry().reset()
        obs.disable()
        reset_flight_recorder()
    assert "callback" not in off
    assert on == off


def test_metrics_report_end_to_end(comm, tmp_path, enabled_obs):
    """Enabled run produces the metrics JSONL artifact: step reports with
    the phase breakdown, registry metric lines, straggler reports."""
    from chainermn_tpu.training import extensions

    report = extensions.MetricsReport(trigger=(2, "iteration"))
    trainer = _make_trainer(comm, tmp_path, n_iters=4, extension=report)
    trainer.run()

    recs = read_jsonl(os.path.join(str(tmp_path), "metrics.jsonl"))
    kinds = {r["kind"] for r in recs}
    assert {"step_report", "metric", "straggler_report"} <= kinds

    steps = [r for r in recs if r["kind"] == "step_report"]
    assert [s["iteration"] for s in steps] == [2, 4]
    for s in steps:
        assert s["steps"] == 2
        for phase in ("data_load", "host_put", "dispatch", "device_block"):
            assert s[f"{phase}_s_mean"] >= 0.0
        assert s["examples_per_sec"] > 0.0

    names = {r["name"] for r in recs if r["kind"] == "metric"}
    assert {"step_phase_seconds", "step_seconds", "train_examples",
            "train_iterations"} <= names
    # global batch = 16 local x 1 host -> 16 examples/step, cumulative
    examples = [r["value"] for r in recs
                if r["kind"] == "metric" and r["name"] == "train_examples"]
    assert examples[-1] == 64.0

    stragglers = [r for r in recs if r["kind"] == "straggler_report"]
    assert stragglers and stragglers[-1]["n_ranks"] == 1
    assert stragglers[-1]["ranks"][0]["count"] == 4


def test_metrics_report_inert_without_switch(comm, tmp_path):
    """MetricsReport added while disabled must not install telemetry."""
    from chainermn_tpu.training import extensions

    trainer = _make_trainer(comm, tmp_path,
                            extension=extensions.MetricsReport())
    trainer.run()
    assert trainer.updater.telemetry is None


def test_serial_iterator_instruments_when_enabled(enabled_obs):
    from chainermn_tpu.iterators import SerialIterator

    it = SerialIterator(list(range(8)), batch_size=4, shuffle=False,
                        collate=False)
    it.next()
    it.next()
    hist = obs.get_registry().get("iterator_next_seconds")
    assert hist is not None
    assert hist.count(iterator="SerialIterator") == 2


# ---- LogReport satellite ----------------------------------------------------

def _fake_trainer(tmp_path, iteration=1):
    updater = types.SimpleNamespace(iteration=iteration, epoch=0,
                                    is_new_epoch=False)
    return types.SimpleNamespace(out=str(tmp_path), updater=updater,
                                 observation={"main/loss": 0.5},
                                 elapsed_time=1.0)


class TestLogReport:
    def test_json_mode_atomic_full_history(self, tmp_path):
        from chainermn_tpu.training.extensions import LogReport

        lr = LogReport(trigger=(1, "iteration"))
        for i in (1, 2, 3):
            lr(_fake_trainer(tmp_path, iteration=i))
        doc = json.load(open(tmp_path / "log"))
        assert [r["iteration"] for r in doc] == [1, 2, 3]
        assert doc[0]["main/loss"] == 0.5
        assert os.listdir(tmp_path) == ["log"], "tmp files must not leak"

    def test_jsonl_mode_appends(self, tmp_path):
        from chainermn_tpu.training.extensions import LogReport

        lr = LogReport(trigger=(1, "iteration"), filename="log.jsonl")
        assert lr._format == "jsonl"  # inferred from the extension
        for i in (1, 2):
            lr(_fake_trainer(tmp_path, iteration=i))
        recs = read_jsonl(str(tmp_path / "log.jsonl"))
        assert [r["iteration"] for r in recs] == [1, 2]

    def test_bad_format_rejected(self):
        from chainermn_tpu.training.extensions import LogReport

        with pytest.raises(ValueError, match="format"):
            LogReport(format="xml")
