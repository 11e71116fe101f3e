"""MoE all-to-all plan tests (round 15 tentpole).

The exchange became a first-class plan stage: ``"all-to-all"`` in the IR
(homogeneous flat-packed chains), ``execute_alltoall`` as its compiler
lowering (flat / hierarchical ICI+DCN / narrow-DCN-wire / striped), an
``alltoall_plans`` zoo the PlanTable can tune over, and ``moe_apply``'s
``plan=`` seam routing the dispatch/combine exchanges through it.

Pinned guarantees:

* the flat plan is BIT-EXACT with raw ``lax.all_to_all`` (the default
  ``plan=None`` path) — both at the executor and through ``moe_apply``;
* the hierarchical decomposition (intra exchange, local re-majoring,
  inter exchange) is bit-exact with the flat exchange;
* the pricing model ships ``(P-1)/P`` of the payload per hop, the
  bf16-DCN hierarchical plan shrinks DCN bytes >= 1.8x vs flat (the
  ``moe_alltoall_dcn_bytes`` budget's invariant);
* plan-lowered MoE emits per-hop ``plan_stage`` spans that attribute to
  the ``ici_comm``/``dcn_comm`` buckets;
* serving expert-parallel decode (``ep_size=2``) produces logits
  identical to ``ep_size=1``, with the dispatch census-visible as an
  all-to-all in the fused forward;
* the lint rules fire on broken fixtures: census-drift on a dropped
  all-to-all stage, wire-dtype-mismatch on a mispriced DCN hop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from chainermn_tpu.parallel.expert import moe_apply, moe_plan_topology
from chainermn_tpu.planner import (
    PlanError,
    PlanTopology,
    STAGE_OPS,
    alltoall_plans,
    candidate_plans,
    execute_alltoall,
    load_plan,
    plan_census_kinds,
    plan_dcn_bytes,
    plan_link_bytes,
    plan_wire_dtypes,
)
from chainermn_tpu.planner.ir import Plan, Stage, StageGroup

TOPO_1D = PlanTopology(axes=(("ep", 8),))
TOPO_2D = PlanTopology(axes=(("inter", 2), ("intra", 4)))


def _zoo(topo, **kw):
    return {p.name: p for p in alltoall_plans(topo, **kw)}


def _mesh_for(topo):
    names = tuple(n for n, _ in topo.axes)
    shape = tuple(s for _, s in topo.axes)
    devs = np.array(jax.devices()[:topo.size]).reshape(shape)
    return Mesh(devs, names), names


def _exchange_pair(plan, topo, n=4, d=3):
    """Per-device [P, n, d] buffers through ``execute_alltoall`` AND raw
    tiled ``lax.all_to_all`` in one SPMD program; returns both stacked
    over devices as numpy."""
    mesh, names = _mesh_for(topo)
    axis_arg = names if len(names) > 1 else names[0]
    p_tot = topo.size

    def body(z):
        me = lax.axis_index(axis_arg)
        key = jax.random.fold_in(jax.random.key(7), me)
        buf = jax.random.uniform(key, (p_tot, n, d), jnp.float32)
        return (execute_alltoall(plan, topo, buf),
                lax.all_to_all(buf, axis_arg, 0, 0, tiled=True))

    out_spec = P(names if len(names) > 1 else names[0])
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(*names),
        out_specs=(out_spec, out_spec), check_vma=False))
    a, b = fn(jnp.zeros(tuple(s for _, s in topo.axes)))
    return np.asarray(a), np.asarray(b)


# ---------------------------------------------------------------------------
# IR: the stage kind and its chain validation
# ---------------------------------------------------------------------------

class TestAlltoallIR:
    def test_stage_op_registered(self):
        assert "all-to-all" in STAGE_OPS
        Stage(op="all-to-all", scope="all")        # constructs

    def test_chain_must_be_homogeneous(self):
        with pytest.raises(PlanError, match="all-to-all stages only"):
            Plan(name="bad", packing="flat", stages=(
                Stage(op="all-to-all", scope="intra"),
                Stage(op="all-reduce", scope="inter")))

    def test_chain_must_be_flat_packed(self):
        with pytest.raises(PlanError, match="flat packing"):
            Plan(name="bad", packing="leaf",
                 stages=(Stage(op="all-to-all", scope="all"),))

    def test_compression_rejected_on_exchange(self):
        # in-wire summed codes are meaningless on a hop with no
        # reduction: the narrow-DCN knob is a wire CAST, never a
        # compression spec
        with pytest.raises(PlanError):
            Plan(name="bad", packing="flat", stages=(
                Stage(op="all-to-all", scope="all",
                      compression={"kind": "int8", "chunk": 256}),))

    def test_serialization_round_trip(self):
        plan = _zoo(TOPO_2D)["alltoall_hier_bfloat16_dcn"]
        again = load_plan(plan.to_dict())
        assert again.to_dict() == plan.to_dict()

    def test_zoo_flat_only_on_one_axis(self):
        names = set(_zoo(TOPO_1D))
        assert "alltoall_flat" in names
        assert "alltoall_flat_bfloat16" in names
        assert not any("hier" in n or "striped" in n for n in names)

    def test_zoo_hierarchical_on_two_axes(self):
        names = set(_zoo(TOPO_2D, stripe_ratios=(0.5,)))
        assert {"alltoall_flat", "alltoall_hierarchical",
                "alltoall_hier_bfloat16_dcn",
                "alltoall_hier_float8_e4m3fn_dcn",
                "alltoall_striped_r50"} <= names

    def test_candidate_plans_dispatches_on_op(self):
        want = [p.name for p in alltoall_plans(TOPO_2D)]
        got = [p.name for p in candidate_plans(TOPO_2D, op="all-to-all")]
        assert got == want
        with pytest.raises(ValueError, match="op"):
            candidate_plans(TOPO_2D, op="all-to-nobody")

    def test_executor_rejects_bad_chains_statically(self):
        buf = np.zeros((8, 2, 2), np.float32)
        wrong_order = Plan(name="w", packing="flat", stages=(
            Stage(op="all-to-all", scope="inter"),
            Stage(op="all-to-all", scope="intra")))
        with pytest.raises(PlanError):
            execute_alltoall(wrong_order, TOPO_2D, buf)
        intra_only = Plan(name="i", packing="flat",
                          stages=(Stage(op="all-to-all", scope="intra"),))
        with pytest.raises(PlanError, match="inter"):
            execute_alltoall(intra_only, TOPO_2D, buf)
        flat = _zoo(TOPO_2D)["alltoall_flat"]
        with pytest.raises(PlanError, match="leading"):
            execute_alltoall(flat, TOPO_2D, np.zeros((4, 2), np.float32))


# ---------------------------------------------------------------------------
# Executor: decompositions vs the raw exchange
# ---------------------------------------------------------------------------

class TestExchangeExecutor:
    def test_flat_plan_bit_exact_one_axis(self, devices):
        a, b = _exchange_pair(_zoo(TOPO_1D)["alltoall_flat"], TOPO_1D)
        assert np.array_equal(a, b)

    def test_flat_plan_bit_exact_two_axes(self, devices):
        a, b = _exchange_pair(_zoo(TOPO_2D)["alltoall_flat"], TOPO_2D)
        assert np.array_equal(a, b)

    def test_hierarchical_bit_exact(self, devices):
        # intra exchange + local re-majoring + inter exchange IS the
        # flat exchange — no tolerance
        a, b = _exchange_pair(_zoo(TOPO_2D)["alltoall_hierarchical"],
                              TOPO_2D)
        assert np.array_equal(a, b)

    def test_hierarchical_degenerates_on_one_axis(self, devices):
        plan = Plan(name="h1", packing="flat", stages=(
            Stage(op="all-to-all", scope="intra"),
            Stage(op="all-to-all", scope="inter")))
        a, b = _exchange_pair(plan, TOPO_1D)
        assert np.array_equal(a, b)

    def test_bf16_dcn_wire_close(self, devices):
        a, b = _exchange_pair(_zoo(TOPO_2D)["alltoall_hier_bfloat16_dcn"],
                              TOPO_2D)
        assert not np.array_equal(a, b)        # the narrow wire rounds
        np.testing.assert_allclose(a, b, atol=8e-3)

    def test_striped_full_precision_bit_exact(self, devices):
        plan = Plan(name="s", packing="flat", groups=(
            StageGroup(name="a", ratio=0.5, stages=(
                Stage(op="all-to-all", scope="intra"),
                Stage(op="all-to-all", scope="inter"))),
            StageGroup(name="b", ratio=0.5,
                       stages=(Stage(op="all-to-all", scope="all"),))))
        a, b = _exchange_pair(plan, TOPO_2D, n=6)
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# Pricing and the derived census
# ---------------------------------------------------------------------------

class TestExchangePricing:
    NBYTES = 1 << 20

    def test_tiled_exchange_ships_all_but_own_block(self):
        # flat all-scope exchange: (P-1)/P of the payload, priced DCN
        flat = _zoo(TOPO_2D)["alltoall_flat"]
        link = plan_link_bytes(flat, TOPO_2D, self.NBYTES)
        assert link[("all", "dcn")] == pytest.approx(
            self.NBYTES * 7 / 8)
        assert sum(b for (_, l), b in link.items() if l == "ici") == 0

    def test_hierarchical_splits_ici_dcn(self):
        hier = _zoo(TOPO_2D)["alltoall_hierarchical"]
        link = plan_link_bytes(hier, TOPO_2D, self.NBYTES)
        assert link[("intra", "ici")] == pytest.approx(
            self.NBYTES * 3 / 4)
        assert link[("inter", "dcn")] == pytest.approx(
            self.NBYTES * 1 / 2)

    def test_bf16_dcn_shrink_at_least_1_8x(self):
        # the acceptance bar the moe_alltoall_dcn_bytes budget enforces
        flat = plan_dcn_bytes(_zoo(TOPO_2D)["alltoall_flat"],
                              TOPO_2D, self.NBYTES)
        hier = plan_dcn_bytes(_zoo(TOPO_2D)["alltoall_hier_bfloat16_dcn"],
                              TOPO_2D, self.NBYTES)
        assert flat / hier >= 1.8

    def test_census_kinds_and_wires_derive(self):
        zoo = _zoo(TOPO_2D)
        assert plan_census_kinds(zoo["alltoall_flat"], TOPO_2D) == \
            ("all-to-all",)
        assert plan_census_kinds(zoo["alltoall_hierarchical"], TOPO_2D) \
            == ("all-to-all", "all-to-all")
        assert plan_wire_dtypes(zoo["alltoall_hier_bfloat16_dcn"],
                                TOPO_2D) == ("float32", "bfloat16")


# ---------------------------------------------------------------------------
# moe_apply: the plan seam and routing properties
# ---------------------------------------------------------------------------

def _moe_pair(plan, topo, expert_fn=lambda t: t * 2.0, top_k=2, n=16,
              d=4, e=8, capacity=None, normalize=None):
    """moe_apply through ``plan`` and through the raw path, same tokens."""
    mesh, names = _mesh_for(topo)
    axis_arg = names if len(names) > 1 else names[0]

    def body(z):
        me = lax.axis_index(axis_arg)
        key = jax.random.fold_in(jax.random.key(3), me)
        x = jax.random.uniform(key, (n, d), jnp.float32)
        g = jax.random.normal(jax.random.fold_in(key, 1), (n, e))
        kw = dict(capacity=capacity, top_k=top_k, num_experts=e,
                  normalize_gates=normalize)
        return (moe_apply(expert_fn, g, x, axis_arg, plan=plan, **kw),
                moe_apply(expert_fn, g, x, axis_arg, **kw), x, g)

    spec = P(names if len(names) > 1 else names[0])
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(*names),
        out_specs=(spec, spec, spec, spec), check_vma=False))
    out = fn(jnp.zeros(tuple(s for _, s in topo.axes)))
    return tuple(np.asarray(o) for o in out)


class TestMoePlanSeam:
    def test_flat_plan_bit_exact_with_raw_path(self, devices):
        # THE pinned acceptance: plan=alltoall_flat is plan=None
        y_plan, y_raw, _, _ = _moe_pair(_zoo(TOPO_1D)["alltoall_flat"],
                                        TOPO_1D)
        assert np.array_equal(y_plan, y_raw)

    def test_hierarchical_plan_matches_raw_tuple_axis(self, devices):
        y_plan, y_raw, _, _ = _moe_pair(
            _zoo(TOPO_2D)["alltoall_hierarchical"], TOPO_2D)
        assert np.array_equal(y_plan, y_raw)

    def test_ample_capacity_is_weighted_permutation(self, devices):
        # capacity >= N*k/E drops nothing: with identity experts and
        # renormalized gates, combine(dispatch(x)) == x — the routing is
        # a weighted permutation whose weights sum to one
        n, e, k = 16, 8, 2
        cap = 2 * n * k // e
        y, _, x, _ = _moe_pair(_zoo(TOPO_1D)["alltoall_flat"], TOPO_1D,
                               expert_fn=lambda t: t, top_k=k, n=n, e=e,
                               capacity=cap, normalize=True)
        np.testing.assert_allclose(y, x, rtol=1e-5, atol=1e-6)

    def test_choice_major_slotting_under_pressure(self, devices):
        # every token's first choice is expert 0: capacity c keeps the
        # FIRST c tokens (slot order is token order within a choice) and
        # the rest fall through the residual unchanged
        n, d, e, cap = 8, 4, 8, 3
        mesh, _ = _mesh_for(TOPO_1D)

        def body(z):
            me = lax.axis_index("ep")
            key = jax.random.fold_in(jax.random.key(5), me)
            x = jax.random.uniform(key, (n, d), jnp.float32)
            g = jnp.zeros((n, e)).at[:, 0].set(9.0)   # all -> expert 0
            y = moe_apply(lambda t: t * 2.0, g, x, "ep", capacity=cap,
                          top_k=1, num_experts=e)
            w = jax.nn.softmax(g.astype(jnp.float32), -1)[:, :1]
            return y, x, w

        y, x, w = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("ep"),
            out_specs=(P("ep"),) * 3, check_vma=False))(jnp.zeros((8,)))
        y = y.reshape(8, n, d)
        x = x.reshape(8, n, d)
        w = np.asarray(w).reshape(8, n, 1)
        np.testing.assert_allclose(y[:, :cap], 2.0 * w[:, :cap]
                                   * x[:, :cap], rtol=1e-5)
        # overflowed choices: residual passthrough, bit-exact
        assert np.array_equal(y[:, cap:], x[:, cap:])

    def test_moe_plan_topology_reads_axis_sizes(self, devices):
        mesh, _ = _mesh_for(TOPO_2D)

        def body(z):
            topo = moe_plan_topology(("inter", "intra"))
            assert topo.axes == (("inter", 2), ("intra", 4))
            return z

        jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("inter", "intra"),
                          out_specs=P("inter", "intra"),
                          check_vma=False))(jnp.zeros((2, 4)))


# ---------------------------------------------------------------------------
# Serving: expert-parallel decode
# ---------------------------------------------------------------------------

def _moe_lm(vocab=32):
    from chainermn_tpu.models.transformer import TransformerLM

    return TransformerLM(vocab=vocab, d_model=16, n_layers=1, n_heads=2,
                         max_len=64, attention_impl="xla",
                         moe_experts=4, moe_top_k=2, moe_axis="ep")


def _moe_lm_params(model):
    mesh = Mesh(np.array(jax.devices()[:2]), ("ep",))
    return jax.jit(jax.shard_map(
        lambda tk: model.init(jax.random.key(0), tk), mesh=mesh,
        in_specs=P(), out_specs=P(),
        check_vma=False))(jnp.zeros((1, 4), jnp.int32))


class TestServingExpertParallel:
    def _run(self, model, params, ep, moe_plan=None):
        from chainermn_tpu.serving import InferenceEngine, ServingConfig

        cfg = ServingConfig(page_size=4, num_pages=16, max_seqs=2,
                            chunk_tokens=4, max_pages_per_seq=4,
                            ep_size=ep, moe_plan=moe_plan,
                            keep_logits=True)
        eng = InferenceEngine(model, params, cfg)
        eng.submit([1, 2, 3], max_new_tokens=4)
        eng.submit([5, 6], max_new_tokens=3)
        logits = []
        while not eng.idle():
            r = eng.step()
            if r.last_logits is not None:
                logits.append(r.last_logits)
        toks = [c.tokens for c in
                sorted(eng.completions, key=lambda c: c.rid)]
        return toks, logits, eng

    def test_ep2_logits_identical_to_ep1(self, devices):
        # pinned: expert parallelism must not change decode numerics
        model = _moe_lm()
        params = _moe_lm_params(model)
        plan = _zoo(PlanTopology(axes=(("ep", 2),)))["alltoall_flat"]
        t2, l2, _ = self._run(model, params, 2, moe_plan=plan)
        t1, l1, _ = self._run(model, params, 1)
        assert t2 == t1
        for a, b in zip(l2, l1):
            assert np.array_equal(a, b)

    def test_dispatch_rides_a_census_visible_all_to_all(self, devices):
        from chainermn_tpu.analysis.hlo import parse_hlo_collectives

        model = _moe_lm()
        params = _moe_lm_params(model)
        plan = _zoo(PlanTopology(axes=(("ep", 2),)))["alltoall_flat"]
        _, _, eng = self._run(model, params, 2, moe_plan=plan)
        hlo = eng._fwd.lower(
            eng._params, eng._ck, eng._cv,
            jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, 4), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        ).compile().as_text()
        kinds = parse_hlo_collectives(hlo).kinds()
        # two exchanges per MoE layer: dispatch + combine
        assert kinds.count("all-to-all") == 2

    def test_ep_config_validation(self, devices):
        from chainermn_tpu.models.transformer import TransformerLM
        from chainermn_tpu.serving import InferenceEngine, ServingConfig

        dense = TransformerLM(vocab=32, d_model=16, n_layers=1,
                              n_heads=2, max_len=64)
        dense_params = dense.init(jax.random.key(0),
                                  jnp.zeros((1, 4), jnp.int32))
        base = dict(page_size=4, num_pages=16, max_seqs=2,
                    chunk_tokens=4, max_pages_per_seq=4)
        with pytest.raises(ValueError, match="MoE model"):
            InferenceEngine(dense, dense_params,
                            ServingConfig(ep_size=2, **base))
        model = _moe_lm()
        params = _moe_lm_params(model)
        with pytest.raises(ValueError, match="divide moe_experts"):
            InferenceEngine(model, params,
                            ServingConfig(ep_size=3, **base))
        with pytest.raises(ValueError, match="spec_k"):
            InferenceEngine(model, params,
                            ServingConfig(ep_size=2, spec_k=1,
                                          chunk_tokens=4, page_size=4,
                                          num_pages=16, max_seqs=2,
                                          max_pages_per_seq=4))


# ---------------------------------------------------------------------------
# Lint: the moe/train entry point and its broken fixtures
# ---------------------------------------------------------------------------

def _exchange_hlo(plan, topo):
    mesh, names = _mesh_for(topo)
    block = topo.size
    buf = jnp.zeros((block * block, 4, 4), jnp.float32)
    return jax.jit(jax.shard_map(
        lambda b: execute_alltoall(plan, topo, b), mesh=mesh,
        in_specs=P(names if len(names) > 1 else names[0]),
        out_specs=P(names if len(names) > 1 else names[0]),
        check_vma=False)).lower(buf).compile().as_text()


class TestMoeLint:
    def test_moe_train_entry_point_clean(self, devices):
        from chainermn_tpu.analysis.entrypoints import (ENTRY_POINTS,
                                                        lint_moe_train)

        assert "moe/train" in ENTRY_POINTS
        reports = lint_moe_train()
        assert len(reports) == 1
        rep = reports[0]
        assert rep.ok, [f.render() for f in rep.findings]
        # the plan census genuinely ran — derived, not skipped
        assert "census-drift" not in rep.skipped
        assert "wire-dtype-mismatch" not in rep.skipped

    def test_census_drift_fires_on_dropped_stage(self, devices):
        # broken fixture: the program compiled the FLAT exchange while
        # the spec says hierarchical — one all-to-all hop was dropped
        from chainermn_tpu.analysis.lint import lint_step

        zoo = _zoo(TOPO_2D)
        flat_hlo = _exchange_hlo(zoo["alltoall_flat"], TOPO_2D)
        rep = lint_step(None, plan=zoo["alltoall_hierarchical"],
                        inter_size=2, census=flat_hlo,
                        rules=["census-drift"], raise_on_error=False)
        (f,) = [x for x in rep.findings if x.rule == "census-drift"]
        assert f.severity == "error"
        assert f.details["expected"] == ["all-to-all", "all-to-all"]
        assert f.details["observed"] == ["all-to-all"]

    def test_wire_dtype_mismatch_fires_on_mispriced_dcn_hop(self,
                                                            devices):
        # broken fixture: the plan prices its DCN hop at bf16 but the
        # compiled program moves f32 — 2x the modeled wire
        from types import SimpleNamespace

        from chainermn_tpu.analysis import schedule_from_hlo
        from chainermn_tpu.analysis.rules import get_rule

        zoo = _zoo(TOPO_2D)
        f32_hlo = _exchange_hlo(zoo["alltoall_hierarchical"], TOPO_2D)
        ctx = SimpleNamespace(
            hlo_schedule=schedule_from_hlo(f32_hlo), hlo_text=f32_hlo,
            plan=zoo["alltoall_hier_bfloat16_dcn"], fsdp_meta=None,
            name="moe-fixture")
        findings = get_rule("wire-dtype-mismatch").run(ctx)
        assert findings, "the mispriced DCN hop must be a finding"
        assert any(f.details["expected_dtype"] == "bf16"
                   for f in findings)
        # and the REAL bf16-DCN program passes the same audit
        bf16_hlo = _exchange_hlo(zoo["alltoall_hier_bfloat16_dcn"],
                                 TOPO_2D)
        ctx.hlo_schedule = schedule_from_hlo(bf16_hlo)
        ctx.hlo_text = bf16_hlo
        assert get_rule("wire-dtype-mismatch").run(ctx) == []


# ---------------------------------------------------------------------------
# Acceptance: 2 controllers x 4 devices, bf16-DCN dispatch vs flat f32
# ---------------------------------------------------------------------------

_MOE_2PROC_WORKER = r"""
import json, os, sys
sys.path.insert(0, os.environ["CHAINERMN_TPU_REPO"])
import chainermn_tpu

chainermn_tpu.init_distributed(local_device_count=4)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu.parallel.expert import ExpertParallelMLP
from chainermn_tpu.planner import alltoall_plans

assert jax.process_count() == 2 and jax.device_count() == 8

comm = chainermn_tpu.create_communicator("hierarchical")
mesh = comm.mesh
topo = comm.plan_topology()
assert tuple(topo.axes) == (("inter", 2), ("intra", 4))
plans = {p.name: p for p in alltoall_plans(topo)}
AX = ("inter", "intra")

# every process holds the full token (replicated), so shard_map inputs
# are proper global arrays; the per-device batches are generated INSIDE
# the region from axis_index
tok = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P()), np.zeros((), np.float32))


def data(me):
    key = jax.random.fold_in(jax.random.key(42), me)
    x = jax.random.uniform(key, (16, 8), jnp.float32) - 0.5
    w = jnp.sin(jnp.arange(64, dtype=jnp.float32).reshape(8, 8) / 7.0)
    return x, jnp.tanh(x @ w)


def run(plan_name):
    model = ExpertParallelMLP(hidden=16, axis_name=AX, top_k=2,
                              num_experts=8, plan=plans[plan_name])

    def fwd(pp, z):
        x, y = data(lax.axis_index(AX))
        out = model.apply(pp, x)
        return lax.pmean(jnp.mean((out - y) ** 2), AX)

    def loss_fn(pp, z):
        return jax.shard_map(fwd, mesh=mesh, in_specs=(P(), P()),
                         out_specs=P(), check_vma=False)(pp, z)

    params = jax.jit(jax.shard_map(
        lambda z: model.init(jax.random.key(0),
                             data(lax.axis_index(AX))[0]),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))(tok)

    @jax.jit
    def step(pp, z):
        l, g = jax.value_and_grad(loss_fn)(pp, z)
        return jax.tree.map(lambda a, b: a - 0.5 * b, pp, g), l

    losses = []
    for _ in range(8):
        params, l = step(params, tok)
        losses.append(float(l))
    return losses


flat = run("alltoall_flat")
hier = run("alltoall_hier_bfloat16_dcn")
print("RESULT " + json.dumps({"flat": flat, "hier_bf16": hier,
                              "rank": comm.host_rank}))
"""


@pytest.mark.slow
def test_two_controller_bf16_dcn_dispatch_tracks_flat():
    """The ISSUE's multi-process acceptance: hierarchical dispatch with a
    bf16 DCN wire trains the same loss trajectory as full-precision flat
    — the narrow inter-host hop is a wire format, not a model change."""
    import os

    from chainermn_tpu.utils.proc_world import spawn_world

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = spawn_world(_MOE_2PROC_WORKER, n_procs=2, local_devices=4,
                          timeout=600, repo=repo)

    for key in ("flat", "hier_bf16"):
        # globally synchronous: both controllers see the same curve
        assert results[0][key] == pytest.approx(results[1][key],
                                                rel=1e-6)
    flat = results[0]["flat"]
    hier = results[0]["hier_bf16"]
    assert flat[-1] < flat[0] and hier[-1] < hier[0]
    np.testing.assert_allclose(hier, flat, rtol=0.1, atol=1e-4)
