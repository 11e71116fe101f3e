"""Heterogeneous link striping tests (ISSUE 11 tentpole).

Contracts pinned here:

1. **IR** — concurrent stage groups serialize (dict/JSON/file) and
   validate: ratios must sum to 1, each group's chain must balance its
   shard stack, groups are flat-packing-only and exclusive with a
   top-level stage list.
2. **Compiler** — ``plan_group_lengths`` partitions the packed buffer
   exactly; a striped plan computes the gradient mean on the 8-device
   CPU mesh (compressed DCN stripe included); a ratio-1.0 single-group
   plan is BIT-EXACT with the equivalent flat plan (no slice/concat on
   the degenerate path); per-hop EF state is keyed ``(group, stage)``
   and sized to the stripe's shard.
3. **Cost model** — ``plan_link_bytes`` prices per (scope, link class);
   ``plan_modeled_time_s`` is max(slowest chain, busiest link), which
   is exactly what lets a tuned intermediate ratio beat BOTH
   single-path endpoints on heterogeneous links while never predicting
   below a physical link bound.
4. **Autotuner** — striped candidates enter the zoo via
   ``stripe_ratios``; the comparison rows grow the striped-vs-best-
   single lane; ``PlanTable.lookup`` breaks equidistant bucket ties
   toward the smaller bucket, deterministically.
5. **Lint** — census-drift checks a striped plan's compiled schedule
   as an INTERLEAVING of per-group sequences (kinds, then
   (kind, dtype) lanes); wire-dtype-mismatch walks concurrent groups.
6. **Observability** — plan-stage metrics/spans carry the ``group``
   label and pair begin/end per (plan, group, stage).
7. **Artifacts/CLI** — ``perf_gate --require-striped`` gates on
   striped wins; the committed r11 artifacts clear the acceptance bar
   (tuned striped beats best single-path in >= 2 cells).
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chainermn_tpu
from chainermn_tpu.analysis import get_rule, lint_step, schedule_from_hlo
from chainermn_tpu.analysis.lint import allreduce_hlo
from chainermn_tpu.analysis.rules import _interleaves
from chainermn_tpu.compression.error_feedback import compression_layout
from chainermn_tpu.planner import (
    LINK_CLASS,
    Plan,
    PlanError,
    PlanTable,
    PlanTopology,
    Stage,
    StageGroup,
    autotune_from_rows,
    broadcast_plans,
    candidate_plans,
    execute_plan,
    flavor_plan,
    init_plan_compression_states,
    load_plan,
    multicast_plan,
    plan_census_kinds,
    plan_compressed_hops,
    plan_group_lengths,
    plan_link_bytes,
    plan_modeled_time_s,
    plan_stage_lengths,
    plan_wire_bytes,
    plan_wire_dtypes,
    striped_plan,
)
from chainermn_tpu.planner.plans import _two_dimensional_stages

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOPO_2D = PlanTopology(axes=(("inter", 2), ("intra", 4)))

INT8_SPEC = {"name": "int8", "stochastic": False}


def make_comm(name="naive", **kwargs):
    return chainermn_tpu.create_communicator(name, intra_size=4, **kwargs)


def _group(ratio, wire_dtype=None, dcn_comp=None, name=None):
    return StageGroup(stages=_two_dimensional_stages(wire_dtype, dcn_comp),
                      ratio=ratio, name=name)


# ---------------------------------------------------------------------------
# IR: serialization and validation
# ---------------------------------------------------------------------------

class TestStripedIR:
    @pytest.mark.parametrize("plan", [
        striped_plan(0.7),
        striped_plan(0.5, dcn_comp=dict(INT8_SPEC)),
        striped_plan(1.0),
        striped_plan(0.9, wire_dtype=None),
    ], ids=lambda p: p.name)
    def test_striped_plan_round_trips(self, plan):
        assert plan.is_striped
        assert Plan.from_dict(json.loads(json.dumps(plan.to_dict()))) \
            == plan
        assert Plan.from_json(plan.to_json()) == plan

    def test_striped_save_load(self, tmp_path):
        p = striped_plan(0.6, dcn_comp=dict(INT8_SPEC))
        path = tmp_path / "striped.json"
        p.save(str(path))
        assert Plan.load(str(path)) == p
        assert load_plan(str(path)) == p
        d = p.to_dict()
        assert "stages" not in d
        assert [g["ratio"] for g in d["groups"]] == [0.6, 0.4]

    def test_plain_plan_has_synthetic_group(self):
        p = flavor_plan("two_dimensional")
        assert not p.is_striped
        groups = p.stage_groups()
        assert len(groups) == 1 and groups[0].ratio == 1.0
        assert groups[0].stages == p.stages
        assert "groups" not in p.to_dict()

    @pytest.mark.parametrize("bad", [
        # ratios must sum to 1
        lambda: Plan(name="short", packing="flat",
                     groups=(_group(0.5), _group(0.3))),
        lambda: Plan(name="long", packing="flat",
                     groups=(_group(0.8), _group(0.4))),
        # groups and stages are exclusive
        lambda: Plan(name="both", packing="flat",
                     stages=(Stage(op="all-reduce"),),
                     groups=(_group(1.0),)),
        # groups need flat packing (the split is on the packed buffer)
        lambda: Plan(name="leafg", packing="leaf", groups=(_group(1.0),)),
        # ratio out of range
        lambda: StageGroup(stages=(Stage(op="all-reduce"),), ratio=0.0),
        lambda: StageGroup(stages=(Stage(op="all-reduce"),), ratio=1.5),
        # empty group
        lambda: StageGroup(stages=(), ratio=1.0),
        # a group's chain must balance its shard stack
        lambda: Plan(name="sharded", packing="flat", groups=(
            StageGroup(stages=(Stage(op="reduce-scatter", scope="intra"),),
                       ratio=1.0),)),
        lambda: striped_plan(0.0),
        lambda: striped_plan(1.2),
    ])
    def test_invalid_striped_plans_rejected(self, bad):
        with pytest.raises(PlanError):
            bad()

    def test_group_names_survive(self):
        g = _group(1.0, name="ici_stripe")
        p = Plan(name="named", packing="flat", groups=(g,))
        assert Plan.from_dict(p.to_dict()).groups[0].name == "ici_stripe"


# ---------------------------------------------------------------------------
# Compiler: buffer partition and striped execution
# ---------------------------------------------------------------------------

class TestStripedCompiler:
    def test_group_lengths_partition_exactly(self):
        p = striped_plan(0.7, dcn_comp=dict(INT8_SPEC))
        assert plan_group_lengths(p, 1000) == [700, 300]
        assert plan_group_lengths(p, 10) == [7, 3]
        # tiny buffers can round a stripe to nothing — never negative,
        # always summing to the buffer
        assert plan_group_lengths(striped_plan(0.9), 1) == [1, 0]
        assert sum(plan_group_lengths(p, 37)) == 37
        assert plan_group_lengths(striped_plan(1.0), 123) == [123]

    def test_striped_numerics_gradient_mean(self, devices):
        comm = make_comm()
        n = comm.size
        grads = jnp.tile(jnp.arange(n, dtype=jnp.float32).reshape(n, 1),
                         (1, 333))
        for plan in (striped_plan(0.7),
                     striped_plan(0.5, dcn_comp=dict(INT8_SPEC)),
                     striped_plan(0.9, dcn_comp=dict(INT8_SPEC))):
            out = comm.run_spmd(lambda g: execute_plan(plan, comm, g),
                                grads)
            np.testing.assert_allclose(np.asarray(out), (n - 1) / 2.0,
                                       rtol=2e-2, err_msg=plan.name)

    def test_ratio_one_bit_exact_with_flat_plan(self, devices):
        """The acceptance criterion: a single-group ratio-1.0 striped
        plan runs the chain on the whole buffer (no slice/concat) and
        matches the equivalent flat plan bit for bit."""
        comm = make_comm()
        n = comm.size
        flat = Plan(name="flat2d", packing="flat",
                    stages=_two_dimensional_stages("bfloat16"))
        striped = striped_plan(1.0)
        rng = np.random.RandomState(11)
        grads = jnp.asarray(rng.randn(n, 1237), jnp.float32)
        out_f = comm.run_spmd(lambda g: execute_plan(flat, comm, g), grads)
        out_s = comm.run_spmd(lambda g: execute_plan(striped, comm, g),
                              grads)
        assert out_f.dtype == out_s.dtype
        assert np.array_equal(np.asarray(out_f), np.asarray(out_s))

    def test_tiny_payload_zero_length_stripe(self, devices):
        comm = make_comm()
        n = comm.size
        grads = jnp.arange(n, dtype=jnp.float32).reshape(n, 1)
        out = comm.run_spmd(
            lambda g: execute_plan(striped_plan(0.9), comm, g), grads)
        np.testing.assert_allclose(np.asarray(out), (n - 1) / 2.0,
                                   rtol=1e-2)

    def test_per_group_census_and_wire_dtypes(self):
        p = striped_plan(0.7, dcn_comp=dict(INT8_SPEC))
        chain = ("reduce-scatter", "all-reduce", "all-reduce")
        assert plan_census_kinds(p, TOPO_2D) == chain + chain
        assert plan_census_kinds(p, TOPO_2D, group=0) == chain
        assert plan_census_kinds(p, TOPO_2D, group=1) == chain
        assert plan_wire_dtypes(p, TOPO_2D, group=0) == \
            ("bfloat16", "bfloat16", "bfloat16")
        assert plan_wire_dtypes(p, TOPO_2D, group=1) == \
            ("bfloat16", "int8", "bfloat16")

    def test_stage_lengths_keyed_by_group(self):
        p = striped_plan(0.7, dcn_comp=dict(INT8_SPEC))
        # 2048 splits [1434, 614]; each stripe pads to its intra shard
        assert plan_stage_lengths(p, TOPO_2D, 2048) == {
            (0, 0): 1434, (0, 1): 359, (0, 2): 359,
            (1, 0): 614, (1, 1): 154, (1, 2): 154}

    def test_ef_state_keyed_by_group_and_stage(self):
        p = striped_plan(0.7, dcn_comp=dict(INT8_SPEC))
        hops = plan_compressed_hops(p, TOPO_2D)
        assert list(hops) == [(1, 1)] and hops[(1, 1)].name == "int8"
        states = init_plan_compression_states(p, TOPO_2D, 2048)
        assert set(states) == {(1, 1)}
        st = states[(1, 1)]
        assert st.hop == (1, 1)
        assert st.ef.shape == (hops[(1, 1)]._padded(154),)
        # the checkpoint sidecar formats tuple hop keys fine — swapping
        # which stripe carries the codes changes the layout string
        layout = compression_layout({"s": st})
        assert layout["hops"] == [f"{(1, 1)}:{st.spec}"]
        # uncompressed striped plans carry no state
        assert init_plan_compression_states(
            striped_plan(0.7), TOPO_2D, 2048) is None

    def test_striped_state_threads_through_execute(self, devices):
        comm = make_comm()
        n = comm.size
        plan = striped_plan(0.5, dcn_comp=dict(INT8_SPEC))
        states = init_plan_compression_states(plan, comm.plan_topology(),
                                              2048)
        st = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape), states)
        grads = jnp.tile(jnp.arange(n, dtype=jnp.float32).reshape(n, 1),
                         (1, 2048))
        out, new = comm.run_spmd(
            lambda g, s: execute_plan(plan, comm, g, states=s), grads, st)
        np.testing.assert_allclose(np.asarray(out), (n - 1) / 2.0,
                                   rtol=2e-2)
        assert set(new) == {(1, 1)}
        assert float(np.asarray(new[(1, 1)].step)[0][0]) == 1.0
        assert new[(1, 1)].hop == (1, 1)


# ---------------------------------------------------------------------------
# Cost model: per-link bytes and modeled time
# ---------------------------------------------------------------------------

class TestLinkCostModel:
    def test_link_class_table(self):
        assert LINK_CLASS == {"intra": "ici", "inter": "dcn",
                              "all": "dcn"}

    def test_link_bytes_match_scope_bytes(self):
        nbytes = 4 << 20
        for plan in (flavor_plan("flat"), flavor_plan("two_dimensional"),
                     striped_plan(0.6, dcn_comp=dict(INT8_SPEC))):
            scoped = plan_wire_bytes(plan, TOPO_2D, nbytes)
            linked = plan_link_bytes(plan, TOPO_2D, nbytes)
            assert linked == {(s, LINK_CLASS[s]): v
                              for s, v in scoped.items()}

    def test_striped_bytes_are_ratio_weighted(self):
        nbytes = 4 << 20
        whole = plan_wire_bytes(
            Plan(name="one", packing="flat",
                 stages=_two_dimensional_stages("bfloat16")),
            TOPO_2D, nbytes)
        half = plan_wire_bytes(striped_plan(0.5), TOPO_2D, nbytes)
        # two identical stripes at 0.5 sum back to the whole chain
        for scope in whole:
            assert half[scope] == pytest.approx(whole[scope])

    def test_modeled_time_plain_chain_is_sum(self):
        nbytes = 4 << 20
        rates = {"ici": 1.0, "dcn": 0.05}
        p = Plan(name="one", packing="flat",
                 stages=_two_dimensional_stages("bfloat16"))
        costs = plan_wire_bytes(p, TOPO_2D, nbytes)
        want = (costs["intra"] / (rates["ici"] * 1e9)
                + costs["inter"] / (rates["dcn"] * 1e9))
        assert plan_modeled_time_s(p, TOPO_2D, nbytes, rates) == \
            pytest.approx(want)
        # a missing link class is free
        only_dcn = plan_modeled_time_s(p, TOPO_2D, nbytes, {"dcn": 0.05})
        assert only_dcn == pytest.approx(
            costs["inter"] / (rates["dcn"] * 1e9))

    def test_modeled_time_never_beats_link_busy_bound(self):
        nbytes = 4 << 20
        rates = {"ici": 1.0, "dcn": 0.05}
        for r in (0.5, 0.7, 0.9):
            p = striped_plan(r, dcn_comp=dict(INT8_SPEC))
            t = plan_modeled_time_s(p, TOPO_2D, nbytes, rates)
            for (_, link), moved in plan_link_bytes(
                    p, TOPO_2D, nbytes).items():
                assert t >= moved / (rates[link] * 1e9) - 1e-12

    def test_tuned_stripe_beats_both_single_path_endpoints(self):
        """The win mechanism the PLANNER_GATE_STRIPED leg certifies: on
        a 20:1 ICI:DCN bandwidth gap the r=0.5 compressed stripe models
        faster than BOTH the all-bf16 chain and the all-compressed
        chain, because the ICI stripe's hops hide behind the DCN
        stripe's slow hop — and the ladder is genuinely tunable (some
        ratio loses to the best endpoint)."""
        nbytes = 4 << 20
        rates = {"ici": 1.0, "dcn": 0.05}
        plain = Plan(name="plain", packing="flat",
                     stages=_two_dimensional_stages("bfloat16"))
        comp = Plan(name="comp", packing="flat",
                    stages=_two_dimensional_stages(
                        "bfloat16", dcn_comp=dict(INT8_SPEC)))
        t_plain = plan_modeled_time_s(plain, TOPO_2D, nbytes, rates)
        t_comp = plan_modeled_time_s(comp, TOPO_2D, nbytes, rates)
        best_single = min(t_plain, t_comp)
        t_r50 = plan_modeled_time_s(
            striped_plan(0.5, dcn_comp=dict(INT8_SPEC)),
            TOPO_2D, nbytes, rates)
        assert t_r50 < best_single
        ladder = {r: plan_modeled_time_s(
            striped_plan(r, dcn_comp=dict(INT8_SPEC)),
            TOPO_2D, nbytes, rates) for r in (0.5, 0.7, 0.9)}
        assert max(ladder.values()) > best_single


# ---------------------------------------------------------------------------
# Candidate zoo and autotuner
# ---------------------------------------------------------------------------

class TestStripedAutotune:
    def test_candidate_plans_striped_variants(self):
        names = [p.name for p in candidate_plans(
            TOPO_2D, stripe_ratios=(0.5, 0.7, 1.0))]
        assert "striped_r50" in names
        assert "striped_r50_int8" in names
        assert "striped_r70_int8" in names
        # ratio 1.0 has no second stripe to compress
        assert "striped_r100" in names
        assert "striped_r100_int8" not in names
        # default: no striped candidates unless ratios are passed
        assert not any(n.startswith("striped")
                       for n in (p.name for p in candidate_plans(TOPO_2D)))
        # single-axis topologies have no DCN boundary to stripe against
        one = PlanTopology(axes=(("data", 8),))
        assert not any(p.name.startswith("striped")
                       for p in candidate_plans(one,
                                                stripe_ratios=(0.5,)))

    def test_striped_candidates_all_execute(self, devices):
        comm = make_comm()
        n = comm.size
        grads = jnp.tile(jnp.arange(n, dtype=jnp.float32).reshape(n, 1),
                         (1, 64))
        plans = [p for p in candidate_plans(comm.plan_topology(),
                                            stripe_ratios=(0.5, 0.8))
                 if p.is_striped]
        assert len(plans) >= 4
        for plan in plans:
            out = comm.run_spmd(lambda g: execute_plan(plan, comm, g),
                                grads)
            np.testing.assert_allclose(np.asarray(out), (n - 1) / 2.0,
                                       rtol=2e-2, err_msg=plan.name)

    def test_autotune_striped_comparison_lane(self):
        tkey = TOPO_2D.key()
        sp = striped_plan(0.5, dcn_comp=dict(INT8_SPEC))
        rows = [
            {"topology": tkey, "dtype": "float32", "bytes": 1 << 20,
             "plan": "flat", "us": 100.0},
            {"topology": tkey, "dtype": "float32", "bytes": 1 << 20,
             "plan": "two_dimensional", "us": 80.0},
            {"topology": tkey, "dtype": "float32", "bytes": 1 << 20,
             "plan": sp.name, "us": 50.0, "plan_spec": sp.to_dict()},
            # small bucket: a single-path plan wins -> no striped lane
            {"topology": tkey, "dtype": "float32", "bytes": 2048,
             "plan": "flat", "us": 10.0},
            {"topology": tkey, "dtype": "float32", "bytes": 2048,
             "plan": sp.name, "us": 15.0, "plan_spec": sp.to_dict()},
        ]
        table, comparison = autotune_from_rows(rows)
        by_bucket = {c["bucket"]: c for c in comparison}
        big = by_bucket["<=1MiB"]
        assert big["tuned_striped"] is True
        assert big["best_single_plan"] == "two_dimensional"
        assert big["striped_speedup"] == pytest.approx(80.0 / 50.0)
        small = by_bucket["<=4KiB"]
        assert small["tuned_striped"] is False
        assert small["striped_speedup"] is None
        # the striped spec survives the table round-trip
        tuned = PlanTable.from_dict(table.to_dict()).lookup(
            TOPO_2D, "float32", 1 << 20)
        assert tuned.is_striped
        assert tuned.groups[1].stages[1].compression["name"] == "int8"

    def test_lookup_tie_breaks_toward_smaller_bucket(self):
        """Equidistant bucket neighbors resolve to the SMALLER bucket,
        independent of insertion order (the pinned bugfix)."""
        for order in ("small-first", "large-first"):
            table = PlanTable()
            puts = [("<=64KiB", flavor_plan("flat")),
                    ("<=16MiB", flavor_plan("two_dimensional"))]
            if order == "large-first":
                puts.reverse()
            for bucket, plan in puts:
                table.put(TOPO_2D, "float32", bucket, plan)
            # 600 KiB is the <=1MiB bucket: one hop from each entry
            assert table.lookup(TOPO_2D, "float32",
                                600 << 10).name == "flat", order


# ---------------------------------------------------------------------------
# Lint: interleaving census and group-walking wire check
# ---------------------------------------------------------------------------

class TestStripedLint:
    def test_interleaves_dp(self):
        assert _interleaves([("a", "b"), ("c",)], ("a", "c", "b"))
        assert _interleaves([("a", "b"), ("c",)], ("c", "a", "b"))
        assert not _interleaves([("a", "b"), ("c",)], ("b", "a", "c"))
        assert not _interleaves([("a", "b")], ("a",))      # short
        assert not _interleaves([("a",)], ("a", "a"))      # long
        assert _interleaves([], ())
        # custom matcher (the dtype-lane tolerance seam)
        assert _interleaves([(1, 2)], ("1", "2"),
                            match=lambda w, g: str(w) == g)

    def test_census_drift_accepts_clean_striped_plan(self, devices):
        comm = make_comm()
        plan = striped_plan(0.5, dcn_comp=dict(INT8_SPEC))
        hlo = allreduce_hlo(comm, plan=plan)
        ctx = SimpleNamespace(
            census_schedule=schedule_from_hlo(hlo), plan=plan, comm=comm,
            inter_size=2, flavor=None, name="striped")
        assert not get_rule("census-drift").run(ctx)

    def test_census_drift_rejects_wrong_striped_schedule(self, devices):
        comm = make_comm()
        plan = striped_plan(0.5, dcn_comp=dict(INT8_SPEC))
        # the compiled program is a flat all-reduce: not an interleaving
        # of the two declared 3-stage stripes
        hlo = allreduce_hlo(make_comm("xla"))
        ctx = SimpleNamespace(
            census_schedule=schedule_from_hlo(hlo), plan=plan, comm=comm,
            inter_size=2, flavor=None, name="striped")
        findings = get_rule("census-drift").run(ctx)
        assert [f.rule for f in findings] == ["census-drift"]
        assert "interleaving" in findings[0].message
        assert findings[0].details["expected_groups"] == [
            ["reduce-scatter", "all-reduce", "all-reduce"]] * 2

    def test_census_drift_catches_group_order_violation(self, devices):
        """Kinds that interleave but a dtype lane that cannot: declare
        the COMPRESSED stripe where the program runs the plain one."""
        comm = make_comm()
        ran = striped_plan(0.5)                       # both stripes plain
        declared = striped_plan(0.5, dcn_comp=dict(INT8_SPEC))
        hlo = allreduce_hlo(comm, plan=ran)
        ctx = SimpleNamespace(
            census_schedule=schedule_from_hlo(hlo), plan=declared,
            comm=comm, inter_size=2, flavor=None, name="striped")
        findings = get_rule("census-drift").run(ctx)
        assert [f.rule for f in findings] == ["census-drift"]
        assert "wire" in findings[0].message

    def test_wire_dtype_mismatch_walks_groups(self, devices):
        comm = make_comm("xla")
        hlo = allreduce_hlo(comm)                     # plain f32 program
        sched = schedule_from_hlo(hlo)
        plan = striped_plan(0.5, dcn_comp=dict(INT8_SPEC))
        ctx = SimpleNamespace(hlo_schedule=sched, hlo_text=hlo,
                              plan=plan, fsdp_meta=None, name="t")
        findings = get_rule("wire-dtype-mismatch").run(ctx)
        assert findings, "striped stages must be walked"
        declared = " ".join(f.details["declared"] for f in findings)
        assert "group 1 stage 1" in declared
        assert any(f.details["expected_dtype"] == "s8" for f in findings)

    def test_striped_plan_rules_skip_without_probes(self, devices):
        """The requires/requires_any seam never crashes on a striped
        plan with no census/hlo probes — skipped with a reason."""
        rep = lint_step(lambda x: x * 2, jnp.ones((4,)), hlo=False,
                        plan=striped_plan(0.5), raise_on_error=False)
        assert "census-drift" in rep.skipped
        assert "wire-dtype-mismatch" in rep.skipped


# ---------------------------------------------------------------------------
# Observability: the group label
# ---------------------------------------------------------------------------

class TestStripedObservability:
    def test_span_names_carry_group_tag(self):
        from chainermn_tpu.observability import build_step_trees
        evs = []
        base = dict(plan="striped_r50", op="all-reduce", nbytes=64)
        for seq, (kind, ts, grp) in enumerate([
                ("plan_stage_begin", 1.00, 0),
                ("plan_stage_begin", 1.01, 1),
                ("plan_stage_end", 1.02, 0),
                ("plan_stage_end", 1.04, 1)]):
            evs.append({"kind": kind, "ts": ts, "seq": seq, "stage": 1,
                        "scope": "inter", "link": "dcn", "group": grp,
                        **base})
        evs.append({"kind": "step", "ts": 2.0, "seq": 9, "dur_s": 2.0,
                    "iteration": 1})
        trees = build_step_trees(evs)
        spans = [sp for t in trees for sp in t.walk()
                 if sp.kind == "plan_stage"]
        names = sorted(sp.name for sp in spans)
        assert any("g0:1" in n for n in names), names
        assert any("g1:1" in n for n in names), names
        by_group = {sp.meta.get("group"): sp.dur_s for sp in spans}
        assert by_group[0] == pytest.approx(0.02)
        assert by_group[1] == pytest.approx(0.03)


# ---------------------------------------------------------------------------
# Broadcast plans and the serving seam
# ---------------------------------------------------------------------------

class TestMulticastPlans:
    def test_broadcast_plan_zoo(self):
        names = [p.name for p in broadcast_plans(TOPO_2D)]
        assert "multicast_flat" in names
        assert "multicast_hierarchical" in names
        assert "multicast_flat_bfloat16" in names
        one = PlanTopology(axes=(("data", 8),))
        assert not any("hierarchical" in n
                       for n in (p.name for p in broadcast_plans(one)))

    def test_hierarchical_multicast_root_split(self):
        p = multicast_plan(hierarchical=True, root=6, topology=TOPO_2D)
        assert p.stages[0].root == 2 and p.stages[0].scope == "intra"
        assert p.stages[1].root == 1 and p.stages[1].scope == "inter"
        with pytest.raises(PlanError, match="topology"):
            multicast_plan(hierarchical=True, root=6)

    def test_broadcast_inference_params_plan_seam(self, devices):
        from chainermn_tpu.serving.weights import (
            broadcast_inference_params, weights_multicast_plan)
        comm = make_comm()
        rng = np.random.RandomState(3)
        params = {"w": jnp.asarray(rng.randn(3, 4), jnp.float32),
                  "b": jnp.arange(5, dtype=jnp.float32)}
        hier = weights_multicast_plan(
            root=2, hierarchical=True, topology=comm.plan_topology())
        out = broadcast_inference_params(comm, params, root=2, plan=hier)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), out, params)
        # a flat-packed plan cannot broadcast arbitrary trees
        with pytest.raises(ValueError, match="leaf packing"):
            broadcast_inference_params(
                comm, params, plan=flavor_plan("flat"))

    def test_hierarchical_multicast_execute(self, devices):
        comm = make_comm()
        n = comm.size
        values = jnp.arange(n, dtype=jnp.float32).reshape(n, 1)
        plan = multicast_plan(hierarchical=True, root=5,
                              topology=comm.plan_topology())
        # execute_plan applies the gradient-mean 1/n
        out = comm.run_spmd(lambda g: execute_plan(plan, comm, g), values)
        np.testing.assert_allclose(np.asarray(out), 5.0 / n)


# ---------------------------------------------------------------------------
# Bench flags, perf gate CLI, committed artifacts
# ---------------------------------------------------------------------------

GATE = os.path.join(REPO, "tools", "perf_gate.py")


def _run_gate(args, timeout=120):
    return subprocess.run(
        [sys.executable, GATE] + args, capture_output=True, text=True,
        timeout=timeout, env=dict(os.environ, PYTHONPATH=REPO,
                                  JAX_PLATFORMS="cpu"))


def _striped_sweep_rows(tkey, n_wins):
    sp = striped_plan(0.5, dcn_comp=dict(INT8_SPEC))
    rows = []
    for i in range(max(n_wins, 1)):
        nbytes = 1 << (10 + 5 * i)
        striped_us = 50.0 if i < n_wins else 200.0
        rows += [
            {"topology": tkey, "dtype": "float32", "bytes": nbytes,
             "plan": "flat", "us": 100.0},
            {"topology": tkey, "dtype": "float32", "bytes": nbytes,
             "plan": sp.name, "us": striped_us,
             "plan_spec": sp.to_dict()},
        ]
    return rows


class TestStripedGateCLI:
    def test_parse_link_gbps(self):
        sys.path.insert(0, os.path.join(REPO, "benchmarks"))
        try:
            from bench_allreduce import _parse_link_gbps
        finally:
            sys.path.pop(0)
        assert _parse_link_gbps("ici=0.2,dcn=0.01") == \
            {"ici": 0.2, "dcn": 0.01}
        assert _parse_link_gbps("dcn=0.5") == {"dcn": 0.5}
        with pytest.raises(ValueError):
            _parse_link_gbps("pcie=1.0")
        with pytest.raises(ValueError):
            _parse_link_gbps("ici")

    def test_parse_link_gbps_names_accepted_classes(self):
        """A typo'd link class fails loudly NAMING the accepted
        LINK_CLASS values — the bench flag and plan_modeled_time_s
        share one validator (planner.validate_link_gbps), so a typo
        can never silently price a link class as free."""
        sys.path.insert(0, os.path.join(REPO, "benchmarks"))
        try:
            from bench_allreduce import _parse_link_gbps
        finally:
            sys.path.pop(0)
        from chainermn_tpu.planner import LINK_CLASS
        accepted = sorted(set(LINK_CLASS.values()))
        with pytest.raises(ValueError) as e:
            _parse_link_gbps("icn=0.2,dcn=0.01")
        msg = str(e.value)
        assert "icn" in msg
        for name in accepted:  # ["dcn", "ici"]
            assert name in msg
        with pytest.raises(ValueError, match="negative|>= 0|positive"):
            _parse_link_gbps("ici=-1.0")

    def _doc(self, rows):
        return {"schema": "allreduce_sweep/v1", "backend": "cpu",
                "n_devices": 8, "topology": "inter:2,intra:4",
                "rows": rows}

    def test_require_striped_passes_and_reports(self, tmp_path):
        rows = _striped_sweep_rows("inter:2,intra:4", n_wins=2)
        sweep = tmp_path / "sweep.json"
        sweep.write_text(json.dumps(self._doc(rows)))
        out = tmp_path / "gate.json"
        r = _run_gate(["--planner", str(sweep), "--require-striped", "2",
                       "--out", str(out)])
        assert r.returncode == 0, r.stderr[-2000:]
        doc = json.loads(out.read_text())
        assert doc["ok"] is True
        assert doc["striped"]["wins"] == 2
        assert doc["striped"]["required"] == 2
        assert doc["striped"]["best_speedup"] == pytest.approx(2.0)

    def test_require_striped_fails_short(self, tmp_path):
        rows = _striped_sweep_rows("inter:2,intra:4", n_wins=1)
        sweep = tmp_path / "sweep.json"
        sweep.write_text(json.dumps(self._doc(rows)))
        out = tmp_path / "gate.json"
        r = _run_gate(["--planner", str(sweep), "--require-striped", "2",
                       "--out", str(out)])
        assert r.returncode == 1
        assert "striped" in r.stderr
        doc = json.loads(out.read_text())
        assert doc["ok"] is False and doc["striped"]["wins"] == 1
        # without the striped requirement the same sweep passes
        r2 = _run_gate(["--planner", str(sweep)])
        assert r2.returncode == 0

    def test_committed_striped_artifacts_pass_gate(self):
        """Acceptance: the committed r11 sweep re-gates cleanly — tuned
        striped plans beat the best single-path plan in >= 2 cells
        under the modeled heterogeneous links, and the committed gate
        artifact already says so."""
        gate_doc = json.load(open(os.path.join(
            REPO, "PLANNER_GATE_STRIPED_r11.json")))
        assert gate_doc["ok"] is True
        assert gate_doc["striped"]["wins"] >= 2
        assert gate_doc["striped"]["best_speedup"] > 1.0
        sweep = json.load(open(os.path.join(
            REPO, "ALLREDUCE_SWEEP_STRIPED_r11.json")))
        assert sweep["link_gbps"]
        table, comparison = autotune_from_rows(sweep["rows"])
        wins = [c for c in comparison
                if c.get("striped_speedup") is not None
                and c["striped_speedup"] > 1.0]
        assert len(wins) >= 2, comparison
        # modeled-wire rows keep the raw measurement auditable
        striped_rows = [r for r in sweep["rows"]
                        if r.get("plan_spec", {}) and
                        r["plan_spec"].get("groups")]
        assert striped_rows
        assert all("us_measured" in r and "us_modeled_wire" in r
                   for r in striped_rows)

    def test_committed_striped_table_round_trips(self):
        table = PlanTable.load(os.path.join(
            REPO, "PLAN_TABLE_STRIPED_r11.json"))
        striped = [p for p in table.entries.values() if p.is_striped]
        assert striped, "tuned table must select a striped plan somewhere"
        for p in striped:
            assert Plan.from_dict(p.to_dict()) == p
