"""Online autotuning tests — the attribution-closed re-tuning loop
(``chainermn_tpu/planner/online.py``): link-rate recovery from
``plan_stage`` spans, sweep-row synthesis against observed rates, the
re-tune decision under a degraded DCN link, the step-boundary hot-swap
(flight event, active-table pin, jit-cache drop, bit-exact landing
step), the checkpoint sidecar refusal, row dedup in
``autotune_from_rows``, the FSDP prefetch recommendation, and the
offline replay / perf-gate path over the committed degraded-DCN dump.
The 2-process same-step swap test rides the ``slow`` lane."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chainermn_tpu
from chainermn_tpu.observability.flight_recorder import FlightRecorder
from chainermn_tpu.planner import (
    Plan,
    PlanTable,
    PlanTopology,
    Stage,
    autotune_from_rows,
    flavor_plan,
    size_bucket,
    validate_sweep_rows,
)
from chainermn_tpu.planner.online import (
    LinkObservations,
    ONLINE_TUNE_SCHEMA,
    OnlineTuner,
    active_plan_table_meta,
    clear_active_plan_table,
    get_active_plan_table,
    plan_table_hash,
    recommend_prefetch_depth,
    set_active_plan_table,
    synthesize_sweep_rows,
)
from chainermn_tpu.utils.proc_world import spawn_world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN_DUMP = os.path.join(REPO, "tests", "data", "degraded_dcn_spans.json")

TOPO_2D = PlanTopology(axes=(("inter", 2), ("intra", 4)))


@pytest.fixture(autouse=True)
def _fresh_active_table():
    """The active-table registry is module-global process state — every
    test starts and ends without a pin."""
    clear_active_plan_table()
    yield
    clear_active_plan_table()


def _stage_pair(t0, plan, stage, link, nbytes, gbps, group=None):
    """One completed plan_stage begin/end edge pair at an exact rate."""
    dur = nbytes / (gbps * 1e9)
    base = dict(plan=plan, stage=stage, op="all_reduce",
                scope="intra" if link == "ici" else "inter",
                link=link, nbytes=nbytes)
    if group is not None:
        base["group"] = group
    return [dict(kind="plan_stage_begin", ts=t0, **base),
            dict(kind="plan_stage_end", ts=t0 + dur, **base)], t0 + dur


def degraded_dcn_events(steps=8, dcn_gbps=0.5, ici_gbps=16.0):
    """The degraded-link scenario: the active flat plan pushes 8 MiB
    over a ~0.5 GB/s DCN hop while 1 MiB ICI spans show healthy links."""
    events, t = [], 0.0
    for _ in range(steps):
        pair, t = _stage_pair(t, "flat", 0, "dcn", 8 << 20, dcn_gbps)
        events += pair
        pair, t = _stage_pair(t, "hierarchical", 0, "ici", 1 << 20,
                              ici_gbps)
        events += pair
        t += 0.01
    return events


DCN_REGRESSION = [{"bucket": "dcn_comm", "value_s": 0.0168,
                   "baseline_s": 0.0042, "ratio": 4.0, "iteration": 100}]


# ---------------------------------------------------------------------------
# observation store
# ---------------------------------------------------------------------------

class TestLinkObservations:
    def test_rates_recovered_from_events(self):
        obs = LinkObservations()
        n = obs.ingest_events(degraded_dcn_events())
        assert n == 16
        gbps = obs.observed_gbps()
        assert gbps["dcn"] == pytest.approx(0.5, rel=1e-6)
        assert gbps["ici"] == pytest.approx(16.0, rel=1e-6)

    def test_aggregate_is_byte_weighted_not_mean_of_rates(self):
        # 1 GiB at 1 GB/s + 1 KiB at 1000 GB/s: a mean of per-span
        # rates would say ~500 GB/s; bytes-over-seconds stays ~1
        obs = LinkObservations()
        obs.add("dcn", 1 << 30, (1 << 30) / 1e9)
        obs.add("dcn", 1 << 10, (1 << 10) / 1e12)
        assert obs.observed_gbps()["dcn"] == pytest.approx(1.0, rel=1e-3)

    def test_garbage_samples_dropped(self):
        obs = LinkObservations()
        obs.add("dcn", 0, 1.0)        # no bytes
        obs.add("dcn", 1024, 0.0)     # no time
        obs.add("dcn", 1024, -1.0)    # negative time
        obs.add("", 1024, 1.0)        # no link class
        obs.add(None, 1024, 1.0)
        assert obs.n_samples("dcn") == 0
        assert obs.observed_gbps() == {}

    def test_min_samples_gates_a_link(self):
        obs = LinkObservations()
        obs.add("ici", 1 << 20, 1e-4)
        assert "ici" in obs.observed_gbps(min_samples=1)
        assert "ici" not in obs.observed_gbps(min_samples=2)

    def test_non_plan_stage_spans_ignored(self):
        obs = LinkObservations()
        events = [dict(kind="collective_begin", op="x", op_seq=0, ts=0.0),
                  dict(kind="collective_end", op="x", op_seq=0, ts=1.0)]
        assert obs.ingest_events(events) == 0

    def test_stage_link_timings_export(self):
        from chainermn_tpu.observability.spans import stage_link_timings

        events, _ = _stage_pair(0.0, "flat", 0, "dcn", 1 << 20, 1.0)
        # an open begin (no end) and a link-less stage must not export
        events.append(dict(kind="plan_stage_begin", plan="flat", stage=1,
                           op="all_reduce", scope="all", link="dcn",
                           nbytes=4096, ts=9.0))
        (t,) = stage_link_timings(events)
        assert t == ("dcn", 1 << 20, pytest.approx((1 << 20) / 1e9))


# ---------------------------------------------------------------------------
# sweep-row synthesis
# ---------------------------------------------------------------------------

class TestSynthesizeSweepRows:
    def test_rows_validate_and_cover_the_zoo(self):
        rows = synthesize_sweep_rows(
            TOPO_2D, "float32", 8 << 20, {"ici": 16.0, "dcn": 0.5})
        validate_sweep_rows(rows)   # autotune_from_rows eats them as-is
        names = {r["plan"] for r in rows}
        assert "flat" in names and "hierarchical" in names
        assert any(n.startswith("striped") for n in names)
        for r in rows:
            assert r["us"] > 0 and r["bytes"] == 8 << 20
            assert r["plan_spec"]  # specs survive into the tuned table

    def test_degraded_dcn_depresses_dcn_heavy_plans(self):
        rows = synthesize_sweep_rows(
            TOPO_2D, "float32", 8 << 20, {"ici": 16.0, "dcn": 0.5})
        by_name = {r["plan"]: r["us"] for r in rows}
        # flat is all-scope (DCN-priced); hierarchical only moves the
        # inter-reduced shard over DCN
        assert by_name["hierarchical"] < by_name["flat"]


# ---------------------------------------------------------------------------
# row dedup in autotune_from_rows (satellite)
# ---------------------------------------------------------------------------

class TestAutotuneRowDedup:
    def test_colliding_rows_mean_merge_and_count(self):
        tkey = TOPO_2D.key()
        wire = Plan(name="flat_bfloat16", packing="flat",
                    wire_dtype="bfloat16", stages=(Stage(op="all-reduce"),))
        rows = [
            # two sweeps landed the same (cell, plan, bytes) rung: the
            # duplicate must mean-merge (150), not double-weight flat
            {"topology": tkey, "dtype": "float32", "bytes": 1 << 20,
             "plan": "flat", "us": 100.0},
            {"topology": tkey, "dtype": "float32", "bytes": 1 << 20,
             "plan": "flat", "us": 200.0},
            {"topology": tkey, "dtype": "float32", "bytes": 1 << 20,
             "plan": "flat_bfloat16", "us": 160.0,
             "plan_spec": wire.to_dict()},
        ]
        table, comparison = autotune_from_rows(rows)
        assert table.meta["rows_merged"] == 1
        # merged flat = 150us beats the 160us wire plan
        assert table.lookup(TOPO_2D, "float32", 1 << 20).name == "flat"
        (cell,) = comparison
        assert cell["tuned_us"] == pytest.approx(150.0)

    def test_clean_sweep_reports_zero_merged(self):
        tkey = TOPO_2D.key()
        rows = [{"topology": tkey, "dtype": "float32", "bytes": 2048,
                 "plan": "flat", "us": 10.0}]
        table, _ = autotune_from_rows(rows)
        assert table.meta["rows_merged"] == 0


# ---------------------------------------------------------------------------
# active-table registry + hash
# ---------------------------------------------------------------------------

class TestActiveTableRegistry:
    def test_set_get_meta_clear(self):
        assert active_plan_table_meta() is None
        assert get_active_plan_table() is None
        table = PlanTable()
        table.put(TOPO_2D, "float32", "<=1MiB", flavor_plan("hierarchical"))
        meta = set_active_plan_table(table, step=42)
        assert meta == {"table_hash": plan_table_hash(table),
                        "swap_step": 42}
        assert get_active_plan_table() is table
        clear_active_plan_table()
        assert active_plan_table_meta() is None

    def test_hash_is_content_addressed(self):
        table = PlanTable()
        table.put(TOPO_2D, "float32", "<=1MiB", flavor_plan("hierarchical"))
        # a semantically-equal copy hashes equal; different content not
        assert plan_table_hash(PlanTable.from_dict(table.to_dict())) == \
            plan_table_hash(table)
        other = PlanTable()
        other.put(TOPO_2D, "float32", "<=1MiB", flavor_plan("flat"))
        assert plan_table_hash(other) != plan_table_hash(table)


# ---------------------------------------------------------------------------
# the re-tune decision
# ---------------------------------------------------------------------------

class TestRetune:
    def _tuner(self, **kw):
        kw.setdefault("topology", TOPO_2D)
        kw.setdefault("min_samples", 1)
        kw.setdefault("flight", FlightRecorder(capacity=256))
        return OnlineTuner(**kw)

    def test_degraded_dcn_triggers_profitable_swap(self):
        tuner = self._tuner()
        assert tuner.ingest(degraded_dcn_events()) == 16
        assert not tuner.armed
        assert tuner.on_regression(DCN_REGRESSION)
        assert tuner.armed
        d = tuner.retune()
        assert d is not None and d["schema"] == ONLINE_TUNE_SCHEMA
        assert d["swap"] is True
        assert d["best_speedup"] >= 1.05   # the retune_speedup budget
        assert d["observed_gbps"]["dcn"] == pytest.approx(0.5, rel=1e-6)
        # every observed cell starts from the flat fallback and finds
        # a plan that routes around the degraded DCN hop
        assert {c["old_plan"] for c in d["cells"]} == {"flat"}
        for c in d["cells"]:
            assert c["new_modeled_s"] < c["old_modeled_s"]
        # the shipped table is content-addressed by the decision hash
        assert plan_table_hash(PlanTable.from_dict(d["table"])) == \
            d["table_hash"]
        assert d["evidence"] == DCN_REGRESSION

    def test_no_observations_returns_none(self):
        assert self._tuner().retune() is None

    def test_fallback_prices_unobserved_links(self):
        # only ICI spans observed; without a DCN figure the model would
        # price DCN as free — the fallback supplies the static rate
        events, _ = _stage_pair(0.0, "hierarchical", 0, "ici", 1 << 20,
                                16.0)
        tuner = self._tuner(fallback_gbps={"dcn": 2.0})
        tuner.ingest(events)
        d = tuner.retune()
        assert d is not None
        assert d["observed_gbps"]["dcn"] == pytest.approx(2.0)
        assert d["observed_gbps"]["ici"] == pytest.approx(16.0, rel=1e-6)

    def test_only_comm_buckets_arm(self):
        tuner = self._tuner()
        assert not tuner.on_regression(
            [{"bucket": "compute", "ratio": 9.0}])
        assert not tuner.armed
        assert tuner.on_regression([{"bucket": "ici_comm", "ratio": 2.0}])
        assert tuner.armed

    def test_threshold_must_be_at_least_one(self):
        with pytest.raises(ValueError, match="threshold"):
            self._tuner(threshold=0.9)

    def test_retune_records_flight_event(self):
        fr = FlightRecorder(capacity=256)
        tuner = self._tuner(flight=fr)
        tuner.ingest(degraded_dcn_events())
        tuner.retune()
        kinds = [e["kind"] for e in fr.events_since(-1)]
        assert "plan_table_retune" in kinds

    def test_state_record_shape(self):
        tuner = self._tuner()
        tuner.ingest(degraded_dcn_events())
        st = tuner.state()
        assert st["kind"] == "plan_table_state"
        assert st["table_hash"] == plan_table_hash(tuner.table)
        assert st["last_swap_step"] is None
        assert st["observed_gbps"]["dcn"] == pytest.approx(0.5, rel=1e-6)


# ---------------------------------------------------------------------------
# the step-boundary hot-swap (single controller)
# ---------------------------------------------------------------------------

class TestHotSwap:
    def _armed_tuner(self, comm, fr):
        tuner = OnlineTuner(comm=comm, flight=fr, min_samples=1)
        tuner.ingest(degraded_dcn_events())
        tuner.on_regression(DCN_REGRESSION)
        return tuner

    def test_maybe_swap_applies_pins_and_records(self, devices):
        comm = chainermn_tpu.create_communicator("auto", intra_size=4)
        fr = FlightRecorder(capacity=256)
        tuner = self._armed_tuner(comm, fr)
        assert comm.plan_table.entries == {}   # pre-swap: flat fallback
        decision = tuner.maybe_swap(step=7)
        assert decision is not None and decision["step"] == 7
        # the communicator's table flipped and its SPMD cache dropped
        assert comm.plan_table.entries
        assert len(comm._jit_cache) == 0
        for nbytes in (1 << 20, 8 << 20):
            assert comm.plan_for(nbytes, "float32").name != "flat"
        # the sidecar pin names the swapped table and the landing step
        meta = active_plan_table_meta()
        assert meta == {"table_hash": decision["table_hash"],
                        "swap_step": 7}
        # the boundary is visible in the flight timeline
        swaps = [e for e in fr.events_since(-1)
                 if e["kind"] == "plan_table_swap"]
        assert len(swaps) == 1 and swaps[0]["step"] == 7
        assert swaps[0]["table_hash"] == decision["table_hash"]
        # disarmed after the boundary: the next call is a no-op
        assert not tuner.armed
        assert tuner.maybe_swap(step=8) is None

    def test_below_threshold_keeps_the_table(self, devices):
        comm = chainermn_tpu.create_communicator("auto", intra_size=4)
        fr = FlightRecorder(capacity=256)
        tuner = OnlineTuner(comm=comm, flight=fr, min_samples=1,
                            threshold=1e9)   # unreachable bar
        tuner.ingest(degraded_dcn_events())
        tuner.on_regression(DCN_REGRESSION)
        assert tuner.maybe_swap(step=7) is None
        assert comm.plan_table.entries == {}
        assert active_plan_table_meta() is None

    def test_unarmed_tuner_never_retunes(self, devices):
        comm = chainermn_tpu.create_communicator("auto", intra_size=4)
        tuner = OnlineTuner(comm=comm, flight=FlightRecorder(capacity=64),
                            min_samples=1)
        tuner.ingest(degraded_dcn_events())
        assert tuner.maybe_swap(step=3) is None
        assert tuner.last_decision is None

    def test_swap_plan_table_drops_jit_cache(self, devices):
        comm = chainermn_tpu.create_communicator("auto", intra_size=4)
        comm._jit_cache[("sentinel", True)] = object()
        table = PlanTable()
        table.put(TOPO_2D, "float32", "<=1MiB", flavor_plan("hierarchical"))
        comm.swap_plan_table(table)
        assert len(comm._jit_cache) == 0
        assert comm.plan_for(1 << 20, "float32").name == "hierarchical"
        # dict form too (the broadcast wire format)
        comm.swap_plan_table(table.to_dict())
        assert comm.plan_for(1 << 20, "float32").name == "hierarchical"


class TestSwapLandingStepNumerics:
    def test_same_plan_swap_is_bit_exact(self, devices):
        """A hot-swap whose table selects the plan already running must
        not change the landing step's numerics at all — the swap
        machinery (table assign + cache drops + retrace) is bitwise
        invisible when the selected decomposition is unchanged."""
        import optax
        from chainermn_tpu.optimizers import init_opt_state, make_train_step
        from chainermn_tpu.training import put_global_batch

        comm = chainermn_tpu.create_communicator("auto", intra_size=4)
        rng = np.random.RandomState(0)
        params = {"w": jnp.asarray(rng.randn(8, 8) / 4.0, jnp.float32),
                  "b": jnp.zeros((8,), jnp.float32)}

        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(1e-2), comm)
        opt_state = init_opt_state(comm, opt, params)
        step = make_train_step(comm, loss_fn, opt, donate=False)
        batch = put_global_batch(
            comm, (rng.randn(comm.size * 2, 8).astype(np.float32),
                   rng.randn(comm.size * 2, 8).astype(np.float32)))
        for _ in range(2):
            params, opt_state, _ = step(params, opt_state, batch)

        # landing step WITHOUT a swap
        p_ref, s_ref, l_ref = step(params, opt_state, batch)

        # the swap: a table that (for every bucket, via nearest-bucket
        # fallback) selects flat — exactly the plan the empty table was
        # already falling back to
        table = PlanTable()
        table.put(TOPO_2D, "float32", "<=1MiB", flavor_plan("flat"))
        comm.swap_plan_table(table)
        step.clear_cache()   # what MetricsReport does after maybe_swap
        p_new, s_new, l_new = step(params, opt_state, batch)

        assert float(l_new) == float(l_ref)
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_new)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(s_ref), jax.tree.leaves(s_new)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# checkpoint sidecar pin
# ---------------------------------------------------------------------------

class TestCheckpointPlanTablePin:
    def _ckpt(self, comm, tmp_path, name="ot"):
        from chainermn_tpu.extensions import create_multi_node_checkpointer
        return create_multi_node_checkpointer(comm, str(tmp_path), name)

    def _state(self):
        return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)}

    def _table(self, plan="hierarchical"):
        t = PlanTable()
        t.put(TOPO_2D, "float32", "<=1MiB", flavor_plan(plan))
        return t

    def test_no_swap_no_sidecar(self, tmp_path):
        comm = chainermn_tpu.create_communicator("flat")
        ckpt = self._ckpt(comm, tmp_path)
        ckpt.save(self._state(), 1)
        restored, gen = ckpt.resume(
            jax.tree.map(jnp.zeros_like, self._state()))
        assert gen == 1
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(self._state()["w"]))

    def test_pin_roundtrips_with_matching_table(self, tmp_path):
        comm = chainermn_tpu.create_communicator("flat")
        set_active_plan_table(self._table(), step=5)
        ckpt = self._ckpt(comm, tmp_path)
        ckpt.save(self._state(), 1)
        _, gen = ckpt.resume(jax.tree.map(jnp.zeros_like, self._state()))
        assert gen == 1

    def test_mismatched_table_hash_refused(self, tmp_path):
        comm = chainermn_tpu.create_communicator("flat")
        set_active_plan_table(self._table("hierarchical"), step=5)
        ckpt = self._ckpt(comm, tmp_path)
        ckpt.save(self._state(), 1)
        set_active_plan_table(self._table("two_dimensional"), step=9)
        with pytest.raises(ValueError, match="pins plan table"):
            ckpt.resume(jax.tree.map(jnp.zeros_like, self._state()))

    def test_resume_without_live_table_refused(self, tmp_path):
        comm = chainermn_tpu.create_communicator("flat")
        set_active_plan_table(self._table(), step=5)
        ckpt = self._ckpt(comm, tmp_path)
        ckpt.save(self._state(), 1)
        clear_active_plan_table()
        with pytest.raises(ValueError, match="no active plan table"):
            ckpt.resume(jax.tree.map(jnp.zeros_like, self._state()))


# ---------------------------------------------------------------------------
# FSDP prefetch recommendation (the non-collective knob)
# ---------------------------------------------------------------------------

class TestPrefetchRecommendation:
    def test_sustained_stall_deepens_by_one(self):
        assert recommend_prefetch_depth([0.3] * 9, current=1,
                                        num_buckets=4) == 2

    def test_bounded_by_bucket_count(self):
        assert recommend_prefetch_depth([0.5] * 9, current=3,
                                        num_buckets=4) == 3

    def test_healthy_run_keeps_depth(self):
        assert recommend_prefetch_depth([0.01] * 9, current=1,
                                        num_buckets=4) == 1

    def test_median_not_mean(self):
        # one huge outlier must not deepen the window
        fracs = [0.01] * 8 + [5.0]
        assert recommend_prefetch_depth(fracs, current=1, num_buckets=4) == 1

    def test_no_evidence_keeps_depth(self):
        assert recommend_prefetch_depth([], current=2, num_buckets=8) == 2

    def test_tuner_emits_recommendation_event(self):
        fr = FlightRecorder(capacity=64)
        tuner = OnlineTuner(topology=TOPO_2D, flight=fr, min_samples=1)
        for _ in range(9):
            tuner.observe_attribution(
                {"step_s": 1.0, "buckets": {"stall": 0.3}})
        assert tuner.recommend_prefetch(current=1, num_buckets=4) == 2
        kinds = [e["kind"] for e in fr.events_since(-1)]
        assert "fsdp_prefetch_recommendation" in kinds


# ---------------------------------------------------------------------------
# offline replay + perf gate over the committed dump (satellites)
# ---------------------------------------------------------------------------

class TestReplayAndGate:
    def test_replay_reproduces_the_retune_decision(self, tmp_path):
        out = tmp_path / "ONLINE_TUNE.json"
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks",
                                          "bench_allreduce.py"),
             "--replay-spans", SPAN_DUMP,
             "--replay-topology", "inter:2,intra:4",
             "--replay-out", str(out)],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr
        doc = json.loads(out.read_text())
        assert doc["schema"] == ONLINE_TUNE_SCHEMA
        assert doc["n_spans"] == 24
        assert doc["regression_events"] == 4
        assert doc["observed_gbps"]["dcn"] == pytest.approx(0.5, rel=1e-3)
        assert doc["retune"]["swap"] is True
        assert doc["retune"]["best_speedup"] >= 1.05
        assert doc["retune"]["table_hash"]

    def test_perf_gate_passes_committed_artifact(self):
        artifact = os.path.join(REPO, "ONLINE_TUNE_r12.json")
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "perf_gate.py"),
             "--online-tune", artifact],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr
        assert json.loads(r.stdout.splitlines()[-1])["ok"] is True

    def test_perf_gate_fails_unprofitable_decision(self, tmp_path):
        doc = {"schema": ONLINE_TUNE_SCHEMA,
               "retune": {"best_speedup": 1.01, "swap": False,
                          "table_hash": "abc", "cells": []}}
        p = tmp_path / "weak.json"
        p.write_text(json.dumps(doc))
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "perf_gate.py"),
             "--online-tune", str(p)],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 1
        assert "below" in r.stderr and "declined" in r.stderr


# ---------------------------------------------------------------------------
# 2-process: both controllers swap on the same step
# ---------------------------------------------------------------------------

_SWAP_WORKER = r"""
import json, os, sys
sys.path.insert(0, os.environ["CHAINERMN_TPU_REPO"])
import chainermn_tpu

chainermn_tpu.init_distributed(local_device_count=4)

import jax
assert jax.process_count() == 2 and jax.device_count() == 8

from chainermn_tpu.observability.flight_recorder import FlightRecorder
from chainermn_tpu.planner.online import OnlineTuner, active_plan_table_meta

comm = chainermn_tpu.create_communicator("auto")
fr = FlightRecorder(capacity=256)
tuner = OnlineTuner(comm=comm, flight=fr, min_samples=1)

# ONLY rank 0 observes the degraded link and arms — rank 1 must still
# flip on the same step, proving the decision rides the broadcast
if comm.rank == 0:
    events, t = [], 0.0
    for _ in range(8):
        for plan, link, nbytes, gbps in ((u"flat", u"dcn", 8 << 20, 0.5),
                                         (u"hierarchical", u"ici",
                                          1 << 20, 16.0)):
            dur = nbytes / (gbps * 1e9)
            base = dict(plan=plan, stage=0, op=u"all_reduce",
                        scope=u"intra" if link == u"ici" else u"inter",
                        link=link, nbytes=nbytes)
            events.append(dict(kind=u"plan_stage_begin", ts=t, **base))
            events.append(dict(kind=u"plan_stage_end", ts=t + dur, **base))
            t += dur
        t += 0.01
    tuner.ingest(events)
    tuner.on_regression([{u"bucket": u"dcn_comm", u"ratio": 4.0,
                          u"iteration": 100}])

decision = tuner.maybe_swap(step=11)   # COLLECTIVE: both ranks call
swaps = [e for e in fr.events_since(-1) if e[u"kind"] == u"plan_table_swap"]
meta = active_plan_table_meta()
print("RESULT " + json.dumps({
    "rank": comm.rank,
    "swapped": decision is not None,
    "step": decision[u"step"] if decision else None,
    "table_hash": decision[u"table_hash"] if decision else None,
    "best_speedup": decision[u"best_speedup"] if decision else None,
    "pin": meta,
    "n_swap_events": len(swaps),
    "plan_8mib": comm.plan_for(8 << 20, u"float32").name,
}))
"""


@pytest.mark.slow
def test_two_controllers_swap_on_the_same_step():
    results = spawn_world(_SWAP_WORKER, n_procs=2, local_devices=4,
                          timeout=300, repo=REPO)
    for r in results.values():
        assert r["swapped"] is True
        assert r["n_swap_events"] == 1
    # SAME decision everywhere: same landing step, same table hash, the
    # same sidecar pin, the same re-selected plan
    assert results[0]["step"] == results[1]["step"] == 11
    assert results[0]["table_hash"] == results[1]["table_hash"]
    assert results[0]["pin"] == results[1]["pin"]
    assert results[0]["pin"]["swap_step"] == 11
    assert results[0]["plan_8mib"] == results[1]["plan_8mib"] != "flat"
    assert results[0]["best_speedup"] >= 1.05


# ---------------------------------------------------------------------------
# the joint (whole-workload) retune path
# ---------------------------------------------------------------------------

from chainermn_tpu.observability.contention import feed_link_observations  # noqa: E402
from chainermn_tpu.planner import plan_modeled_time_s  # noqa: E402
from chainermn_tpu.planner.schedule import (  # noqa: E402
    clear_plan_slots,
    get_slot_plan,
    plan_workload_signature,
    register_plan_slot,
)


@pytest.fixture(autouse=True)
def _fresh_plan_slots():
    """The plan-slot registry is module-global process state."""
    clear_plan_slots()
    yield
    clear_plan_slots()


class TestDeratedObservationPricing:
    def test_feed_link_observations_beats_fallback_in_retune(self):
        """Regression for the observed-rate path: contention-derated
        samples pushed through feed_link_observations must WIN over
        fallback_gbps in retune() pricing — the tuner prices the link
        at what it delivers UNDER measured overlap, and the cell's
        old-plan price is exactly plan_modeled_time_s at that rate."""
        tuner = OnlineTuner(topology=TOPO_2D, min_samples=1,
                            fallback_gbps={"ici": 16.0, "dcn": 2.0})
        events, _ = _stage_pair(0.0, "hierarchical", 0, "ici", 1 << 20,
                                16.0)
        tuner.ingest(events)
        # PR 16 link_rates shape: the dcn link delivered 0.05 GB/s
        # effective under overlap (a 40x derate vs the 2.0 fallback)
        derated = {"dcn": {"bytes": 8 << 20,
                           "busy_s": (8 << 20) / 0.05e9,
                           "derate": 0.025}}
        feed_link_observations(tuner.observations, derated)
        d = tuner.retune()
        assert d is not None
        assert d["observed_gbps"]["dcn"] == pytest.approx(0.05, rel=1e-6)
        priced = {"ici": d["observed_gbps"]["ici"], "dcn": 0.05}
        cell = next(c for c in d["cells"] if c["bytes"] == 1 << 20)
        assert cell["old_modeled_s"] == pytest.approx(
            plan_modeled_time_s(flavor_plan("flat"), TOPO_2D, 1 << 20,
                                priced), rel=1e-9)

    def test_zero_byte_rates_are_ignored(self):
        obs = LinkObservations()
        feed_link_observations(obs, {"dcn": {"bytes": 0, "busy_s": 1.0},
                                     "ici": {"busy_s": 0.0}})
        assert obs.observed_gbps(1) == {}


class TestJointRetune:
    def _register_two_slots(self):
        register_plan_slot("allreduce", nbytes=4 << 20, op="all-reduce",
                           owners=("plan:", "fsdp", "collective"))
        register_plan_slot("moe", nbytes=8 << 20, op="all-to-all",
                           owners=("moe",))

    def test_joint_decision_and_atomic_apply(self):
        """joint=True retune over two registered slots yields a
        mode="joint" decision; apply_decision installs the non-table
        slot's plan through the schedule registry in the SAME apply as
        the table swap, both tagged with the workload signature."""
        self._register_two_slots()
        fr = FlightRecorder(capacity=256)
        tuner = OnlineTuner(topology=TOPO_2D, min_samples=1, joint=True,
                            flight=fr,
                            fallback_gbps={"ici": 0.2, "dcn": 0.02})
        d = tuner.retune()
        assert d is not None and d.get("mode") == "joint"
        joint = d["joint"]
        assert joint["speedup_vs_independent"] >= 1.05
        assert joint["changed_slots"]
        assert set(joint["slot_plans"]) == {"moe"}
        assert d["swap"] is True
        assert d["table_hash"] == plan_table_hash(
            PlanTable.from_dict(d["table"]))

        tuner.apply_decision(d, step=7)
        live = get_slot_plan("moe")
        assert live is not None
        assert plan_workload_signature(live.name) == joint["signature"]
        ar = tuner.table.lookup(TOPO_2D, "float32", 4 << 20)
        assert ar is not None
        assert plan_workload_signature(ar.name) == joint["signature"]
        kinds = [e["kind"] for e in fr.events_since(-1)]
        assert "workload_swap" in kinds
        assert "plan_table_swap" in kinds
        ws = next(e for e in fr.events_since(-1)
                  if e["kind"] == "workload_swap")
        assert ws["workload_signature"] == joint["signature"]
        assert ws["step"] == 7

    def test_timeline_evidence_gates_the_joint_path(self):
        """Occupancy timelines showing only ONE registered slot's owner
        leave fewer than two slots in flight — the joint path declines
        and the tuner stays on its per-plan path."""
        self._register_two_slots()
        tuner = OnlineTuner(topology=TOPO_2D, min_samples=1, joint=True,
                            fallback_gbps={"ici": 0.2, "dcn": 0.02})
        tuner.observe_timelines({"ici": {"fsdp": [(0.0, 1.0)]}})
        assert tuner.retune() is None  # no per-plan payloads observed

    def test_joint_mode_off_by_default(self):
        self._register_two_slots()
        tuner = OnlineTuner(topology=TOPO_2D, min_samples=1,
                            fallback_gbps={"ici": 0.2, "dcn": 0.02})
        d = tuner.retune()
        assert d is None or d.get("mode") != "joint"
