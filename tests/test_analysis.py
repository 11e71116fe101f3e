"""cmn-lint static analyzer tests.

Three layers, mirroring docs/static_analysis.md:

* the shared HLO collective parser (multi-line renderings, async
  start/done pairs, unmatched halves);
* the jaxpr ``CollectiveSchedule`` extractor (descends through
  pjit/shard_map/scan/cond bodies);
* one deliberately-broken fixture per rule — each fires exactly once
  with its stable rule ID — plus the clean sweep: zero error findings on
  the mnist step (all seven communicator flavors) and the long-context
  ring-attention step, on the tier-1 CPU mesh with no TPU and no process
  spawn.
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import optax
import pytest

import chainermn_tpu
from chainermn_tpu.analysis import (
    CollectiveSchedule,
    LintError,
    all_reduce_overlap_census,
    extract_schedule,
    get_rule,
    lint_step,
    parse_hlo_collectives,
    schedule_from_hlo,
)
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# HLO parser
# ---------------------------------------------------------------------------

SYNC_HLO = """
HloModule m
ENTRY e {
  p0 = f32[256]{0} parameter(0)
  ar = f32[256]{0} all-reduce(f32[256]{0} p0), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=add
  rs = f32[32]{0} reduce-scatter(f32[256]{0} ar), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, to_apply=add
  ROOT t = tuple(rs)
}
"""

MULTILINE_HLO = """
HloModule m
ENTRY e {
  p0 = f32[256]{0} parameter(0)
  ar = f32[256]{0} all-reduce(f32[256]{0} p0),
      replica_groups={{0,1,2,3},{4,5,6,7}},
      to_apply=add
  ROOT t = tuple(ar)
}
"""

ASYNC_HLO = """
HloModule m
ENTRY e {
  p0 = f32[1024]{0} parameter(0)
  ars = (f32[1024]{0}, f32[1024]{0}) all-reduce-start(f32[1024]{0} p0), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=add
  other = f32[1024]{0} add(f32[1024]{0} p0, f32[1024]{0} p0)
  ard = f32[1024]{0} all-reduce-done((f32[1024]{0}, f32[1024]{0}) ars)
  ROOT t = tuple(ard)
}
"""

UNMATCHED_START_HLO = """
HloModule m
ENTRY e {
  p0 = f32[8]{0} parameter(0)
  orphan = (f32[8]{0}, f32[8]{0}) all-reduce-start(f32[8]{0} p0), replica_groups={{0,1}}, to_apply=add
  ROOT t = tuple(p0)
}
"""

UNMATCHED_DONE_HLO = """
HloModule m
ENTRY e {
  p0 = f32[8]{0} parameter(0)
  ghost = f32[8]{0} all-reduce-done((f32[8]{0}, f32[8]{0}) p0)
  ROOT t = tuple(ghost)
}
"""


def test_hlo_parser_sync_ops():
    p = parse_hlo_collectives(SYNC_HLO)
    assert p.kinds() == ("all-reduce", "reduce-scatter")
    assert p.ops[0].nbytes == 256 * 4 and p.ops[0].dtype == "f32"
    assert p.ops[1].nbytes == 32 * 4
    assert "{0,1,2,3,4,5,6,7}" in p.ops[0].groups
    assert not p.problems


def test_hlo_parser_joins_multiline_renderings():
    """An instruction whose replica_groups wrap onto their own physical
    lines still parses as one collective, with the groups attached."""
    p = parse_hlo_collectives(MULTILINE_HLO)
    assert p.kinds() == ("all-reduce",)
    assert p.ops[0].groups == "{{0,1,2,3},{4,5,6,7}}"
    assert not p.problems


def test_hlo_parser_async_pair_is_one_collective():
    p = parse_hlo_collectives(ASYNC_HLO)
    assert p.kinds() == ("all-reduce",)
    op = p.ops[0]
    assert op.is_async
    # payload from the done's result (the start's tuple double-counts),
    # groups from the start (done ops carry none)
    assert op.nbytes == 1024 * 4
    assert "{0,1,2,3,4,5,6,7}" in op.groups
    assert not p.problems


def test_hlo_parser_flags_unmatched_async_halves():
    p = parse_hlo_collectives(UNMATCHED_START_HLO)
    assert [pr["kind"] for pr in p.problems] == ["unmatched-async-start"]
    assert p.kinds() == ("all-reduce",)  # still issued: stays in schedule

    p2 = parse_hlo_collectives(UNMATCHED_DONE_HLO)
    assert [pr["kind"] for pr in p2.problems] == ["unmatched-async-done"]


# The TPU compiler's own asynchronous form (libtpu 0.0.34, as compiled for a
# described v5e:2x2): ONE all-reduce printed in the computation of its
# ``async-collective-start``, of every step fusion that carries it beside
# other work and of its ``async-collective-done``.  Layouts hold parentheses;
# the variadic all-reduce of the small vectors and the scalar loss block.
ASYNC_FUSION_HLO = """
HloModule jit_inner, is_scheduled=true

%fused_computation.1985 (param_0.1: bf16[2048,2048]) -> (bf16[2048,2048], u32[]) {
  %param_0.1 = bf16[2048,2048]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.842 = bf16[2048,2048]{1,0:T(8,128)(2,1)} all-reduce(%param_0.1), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_0.1, backend_config={"async_collective_fusion_config":{"flag_start":"-1","flag_end":"-1"}}
  ROOT %custom-call.15 = (bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) custom-call(%param_0.1, %all-reduce.842), custom_call_target="AllReduceStart"
}

%async_collective_fusion.1246 (param_0.2: bf16[2048,2048], param_1.2: f32[8192,2048]) -> (bf16[2048,2048], f32[8192,2048]) {
  %param_0.2 = bf16[2048,2048]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1.2 = f32[8192,2048]{1,0:T(8,128)} parameter(1)
  %all-reduce.844 = bf16[2048,2048]{1,0:T(8,128)(2,1)} all-reduce(%param_0.2), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_0.1, backend_config={"async_collective_fusion_config":{"flag_start":"2","flag_end":"17"}}
  %multiply.7 = f32[8192,2048]{1,0:T(8,128)} multiply(%param_1.2, %param_1.2)
  ROOT %tuple.443 = (bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)}, f32[8192,2048]{1,0:T(8,128)}) tuple(%all-reduce.844, %multiply.7)
}

%fused_computation.1987 (param_0.3: bf16[2048,2048]) -> bf16[2048,2048] {
  %param_0.3 = bf16[2048,2048]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.846 = bf16[2048,2048]{1,0:T(8,128)(2,1)} all-reduce(%param_0.3), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_0.1, backend_config={"async_collective_fusion_config":{"flag_start":"2","flag_end":"18"}}
  ROOT %custom-call.17 = bf16[2048,2048]{1,0:T(8,128)(2,1)} custom-call(%param_0.3, %all-reduce.846), custom_call_target="AllReduceDone"
}

ENTRY %main.332_spmd (param.1: bf16[2048,2048], param.2: bf16[2048], param.3: bf16[8192], param.4: f32[8192,2048], param.5: f32[]) -> (bf16[2048,2048], f32[]) {
  %param.1 = bf16[2048,2048]{1,0:T(8,128)(2,1)} parameter(0)
  %param.2 = bf16[2048]{0:T(1024)(128)(2,1)} parameter(1)
  %param.3 = bf16[8192]{0:T(1024)(128)(2,1)} parameter(2)
  %param.4 = f32[8192,2048]{1,0:T(8,128)} parameter(3)
  %param.5 = f32[]{:T(128)} parameter(4)
  %all-reduce.712 = (bf16[2048]{0:T(1024)(128)(2,1)S(1)}, bf16[8192]{0:T(1024)(128)(2,1)}) all-reduce(%param.2, %param.3), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_0.1
  %async-collective-start.51 = (bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) fusion(%param.1), kind=kCustom, calls=%fused_computation.1985
  %fusion.1246 = (bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)}, f32[8192,2048]{1,0:T(8,128)}) fusion(%param.1, %param.4), kind=kLoop, calls=%async_collective_fusion.1246
  %async-collective-done.51 = bf16[2048,2048]{1,0:T(8,128)(2,1)} fusion(%param.1), kind=kCustom, calls=%fused_computation.1987
  %psum_invariant.1015 = f32[]{:T(128)} all-reduce(%param.5), channel_id=2, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_1.2
  ROOT %tuple.1 = (bf16[2048,2048]{1,0:T(8,128)(2,1)}, f32[]{:T(128)}) tuple(%async-collective-done.51, %psum_invariant.1015)
}
"""


# A wire cast as the chip's compiler prints it, inside the fusion that makes
# an all-reduce's operand, beside a cast back under ``chainermn.unpack`` and
# one the model made (neither is counted).
WIRE_CAST_HLO = """
HloModule jit_per_rank, is_scheduled=true

%fused_computation.7 (param_0.24: f32[1,4096,512]) -> bf16[4096,512] {
  %param_0.24 = f32[1,4096,512]{2,1,0:T(8,128)} parameter(0)
  %convert_element_type.134 = bf16[1,4096,512]{2,1,0:T(8,128)(2,1)} convert(%param_0.24), metadata={op_name="jit(per_rank)/shard_map/chainermn.allreduce_grad/chainermn.pack/convert_element_type" stack_frame_id=11}
  ROOT %bitcast.38 = bf16[4096,512]{1,0:T(8,128)(2,1)S(1)} bitcast(%convert_element_type.134), metadata={op_name="jit(per_rank)/shard_map/chainermn.allreduce_grad/chainermn.pack/convert_element_type" stack_frame_id=11}
}

ENTRY %main.1_spmd (param.1: f32[1,4096,512], param.2: bf16[512]) -> f32[4096,512] {
  %param.1 = f32[1,4096,512]{2,1,0:T(8,128)} parameter(0)
  %param.2 = bf16[512]{0:T(512)(128)(2,1)} parameter(1)
  %fusion.7 = bf16[4096,512]{1,0:T(8,128)(2,1)S(1)} fusion(%param.1), kind=kLoop, calls=%fused_computation.7
  %all-reduce.1 = bf16[4096,512]{1,0:T(8,128)(2,1)} all-reduce(%fusion.7), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_0.1
  %convert.9 = f32[512]{0:T(512)} convert(%param.2), metadata={op_name="jit(per_rank)/shard_map/chainermn.grad/transpose(jvp(Model))/convert_element_type"}
  ROOT %convert.3 = f32[4096,512]{1,0:T(8,128)} convert(%all-reduce.1), metadata={op_name="jit(per_rank)/shard_map/chainermn.allreduce_grad/chainermn.unpack/convert_element_type"}
}
"""


@pytest.mark.parametrize("text,want", [
    (SYNC_HLO, {"synchronous": 1, "asynchronous": 0,
                "synchronous_bytes": 1024, "asynchronous_bytes": 0,
                "wire_casts": 0, "asynchronous_byte_share": 0.0}),
    (ASYNC_HLO, {"synchronous": 0, "asynchronous": 1,
                 "synchronous_bytes": 0, "asynchronous_bytes": 4096,
                 "wire_casts": 0, "asynchronous_byte_share": 1.0}),
    (ASYNC_FUSION_HLO, {
        "synchronous": 2, "asynchronous": 1,
        "synchronous_bytes": 2 * (2048 + 8192) + 4,
        "asynchronous_bytes": 2 * 2048 * 2048, "wire_casts": 0,
        "asynchronous_byte_share":
            2 * 2048 * 2048 / (2 * 2048 * 2048 + 2 * (2048 + 8192) + 4)}),
    (WIRE_CAST_HLO, {"synchronous": 1, "asynchronous": 0,
                     "synchronous_bytes": 2 * 4096 * 512,
                     "asynchronous_bytes": 0, "wire_casts": 1,
                     "asynchronous_byte_share": 0.0}),
], ids=["blocking", "start_done_pair", "async_collective_fusion",
        "wire_cast"])
def test_all_reduce_overlap_census(text, want):
    """The engagement counter of the asynchronous gradient exchange: an
    all-reduce counts once however often the compiler prints it, blocking
    unless it is a start/done pair or a chain of asynchronous-collective
    fusions, with the bytes its result holds; ``wire_casts`` counts the
    ``convert`` instructions under ``chainermn.pack``, inside a fusion or
    not, and no other cast."""
    assert all_reduce_overlap_census(text) == want


def test_hlo_parser_reads_a_variadic_all_reduce_through_tpu_layouts():
    """A TPU layout holds parentheses (``T(8,128)(2,1)``): the tuple shape
    of a combined all-reduce ends where the op begins, not at the first
    ``)``."""
    p = parse_hlo_collectives(ASYNC_FUSION_HLO)
    entry = [o for o in p.ops if o.name in ("all-reduce.712",
                                            "psum_invariant.1015")]
    assert [o.nbytes for o in entry] == [2 * (2048 + 8192), 4]


# ---------------------------------------------------------------------------
# jaxpr schedule extractor
# ---------------------------------------------------------------------------

def test_extract_schedule_descends_into_spmd_bodies(devices):
    """Collectives inside jit(jax.shard_map(...)) bodies — the make_train_step
    nesting — are all visible, in issue order, with axes and payload."""
    comm = chainermn_tpu.create_communicator("xla")
    ax = comm.data_axes

    def body(x):
        y = jax.lax.psum(x, ax)
        z = jax.lax.pmax(y, ax)
        return z

    step = jax.jit(jax.shard_map(body, mesh=comm.mesh, in_specs=P(ax),
                             out_specs=P(ax), check_vma=False))
    sched = extract_schedule(step, jnp.ones((comm.size, 4)))
    assert sched.kinds() == ("psum", "pmax")
    assert all(op.axes == tuple(ax) for op in sched.ops)
    assert sched.ops[0].nbytes == 4 * 4  # the local [4] f32 shard


def test_extract_schedule_sees_both_cond_branches(devices):
    """A collective in only ONE cond branch — the desync hazard — appears
    in the schedule (tagged with its branch path)."""
    comm = chainermn_tpu.create_communicator("xla")
    ax = comm.data_axes

    def body(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jax.lax.psum(v, ax),
                            lambda v: v * 2.0, x)

    step = jax.shard_map(body, mesh=comm.mesh, in_specs=P(ax),
                     out_specs=P(ax), check_vma=False)
    sched = extract_schedule(step, jnp.ones((comm.size, 4)))
    assert sched.kinds() == ("psum",)
    assert any("cond" in tag for tag in sched.ops[0].path), sched.ops[0]


def test_schedule_diff_reports_first_divergence():
    a = CollectiveSchedule(label="a")
    b = CollectiveSchedule(label="b")
    mk = lambda kind: SimpleNamespace(  # noqa: E731
        key=(kind, ("d",), "float32", 4), describe=lambda: kind)
    a.ops = [mk("psum"), mk("pmax")]
    b.ops = [mk("psum"), mk("psum"), mk("pmax")]
    d = a.diff(b)
    assert d["index"] == 1
    assert a.diff(a) is None


# ---------------------------------------------------------------------------
# rules: one deliberately-broken fixture each (stable rule IDs)
# ---------------------------------------------------------------------------

def _only(report, rule_id):
    """Assert the report holds exactly one finding, of the given rule."""
    assert [f.rule for f in report.findings] == [rule_id], (
        report.findings, report.skipped)
    return report.findings[0]


def test_rule_schedule_desync_catches_rank_divergent_order(devices):
    """THE acceptance scenario: a seeded rank-divergent collective order
    (the same bug tests/test_flight_recorder.py catches at runtime after
    the mesh wedges) is caught statically — per-rank traces on the CPU
    mesh, no TPU, no process spawn."""
    comm = chainermn_tpu.create_communicator("xla")
    ax = comm.data_axes

    def make_rank_step(rank):
        # rank-dependent Python branch — each rank traces a DIFFERENT
        # collective order, exactly what wedges a live mesh
        def body(x):
            if rank == 0:
                return jax.lax.pmax(jax.lax.psum(x, ax), ax)
            return jax.lax.psum(jax.lax.pmax(x, ax), ax)
        return jax.shard_map(body, mesh=comm.mesh, in_specs=P(ax),
                         out_specs=P(ax), check_vma=False)

    x = jnp.ones((comm.size, 4))
    rep = lint_step(
        None,
        variants={f"rank{r}": (make_rank_step(r), x) for r in range(4)},
        rules=["schedule-desync"], raise_on_error=False)
    f = _only(rep, "schedule-desync")
    assert f.severity == "error"
    assert f.details["index"] == 0
    assert "identify_desync" in f.message  # runtime cross-link

    # identical traces per rank -> clean
    rep2 = lint_step(
        None,
        variants={f"rank{r}": (make_rank_step(1), x) for r in range(4)},
        rules=["schedule-desync"], raise_on_error=False)
    assert not rep2.findings


def test_rule_census_drift(devices):
    """A communicator whose compiled decomposition does not match its
    flavor's specified census is an error (here: an xla program audited
    against the hierarchical two-level expectation)."""
    comm = chainermn_tpu.create_communicator("xla")
    rep = lint_step(None, comm=comm, flavor="hierarchical", inter_size=2,
                    census=True, rules=["census-drift"],
                    raise_on_error=False)
    f = _only(rep, "census-drift")
    assert f.details["expected"] == ["all-reduce", "all-reduce"]
    assert f.details["observed"] == ["all-reduce"]

    rep2 = lint_step(None, comm=comm, flavor="xla", census=True,
                     rules=["census-drift"], raise_on_error=False)
    assert not rep2.findings


def test_rule_census_drift_per_hop_compressed_dtype(devices):
    """Per-hop census: a compressed plan whose quantized DCN hop runs
    f32 in the compiled program (compression silently off) is an error
    naming the hop — and the real compiled plan passes, per-hop dtypes
    included."""
    from chainermn_tpu.analysis import schedule_from_hlo as _from_hlo
    from chainermn_tpu.planner import PlanTable, PlanTopology, size_bucket
    from chainermn_tpu.planner.plans import compressed_two_dimensional

    plan = compressed_two_dimensional({"name": "int8",
                                       "stochastic": False})
    # clean: an auto communicator whose tuned table pins the compressed
    # plan at the census probe's payload (1024 f32 = 4 KiB) compiles
    # the plan for real, so kinds and per-hop wires (bf16 RS, s8
    # in-wire-summed AR, bf16 gather-back) all line up
    topo = PlanTopology(axes=(("inter", 2), ("intra", 4)))
    table = PlanTable()
    table.put(topo, "float32", size_bucket(1024 * 4), plan)
    comm = chainermn_tpu.create_communicator("auto", intra_size=4,
                                             plan_table=table)
    rep = lint_step(None, comm=comm, plan=plan, census=True,
                    rules=["census-drift"], raise_on_error=False)
    assert not rep.findings, rep.findings
    assert "census-drift" not in rep.skipped, rep.skipped

    # broken fixture: same kinds, but the inter hop moves f32 — the
    # schedule a program with the quantizer silently dropped compiles to
    broken = _from_hlo("""
HloModule m
ENTRY e {
  p0 = f32[1024]{0} parameter(0)
  rs = f32[256]{0} reduce-scatter(f32[1024]{0} p0), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, to_apply=add
  ar1 = f32[256]{0} all-reduce(f32[256]{0} rs), replica_groups={{0,4},{1,5},{2,6},{3,7}}, to_apply=add
  ar2 = f32[1024]{0} all-reduce(f32[1024]{0} ar1), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=add
  ROOT t = tuple(ar2)
}
""")
    ctx = SimpleNamespace(census_schedule=broken, plan=plan, comm=comm,
                          inter_size=2, flavor=None, name="synthetic")
    findings = get_rule("census-drift").run(ctx)
    assert [f.rule for f in findings] == ["census-drift"], findings
    f = findings[0]
    assert f.details["stage"] == 1
    assert f.details["expected_dtype"] == "s8"
    assert f.details["observed_dtype"] == "f32"


def test_rule_census_drift_accepts_hlo_text_and_callable(devices):
    """The census seam takes the program to audit three ways: ``True``
    (the communicator's own allreduce), raw HLO text, or a lazy callable
    — text and callable must feed the same drift check, and a callable
    that blows up must degrade to a skip, never a crash."""
    from chainermn_tpu.analysis.lint import allreduce_hlo

    comm = chainermn_tpu.create_communicator("xla")
    hlo = allreduce_hlo(comm)
    # raw HLO text, right flavor -> clean; wrong flavor -> fires
    rep = lint_step(None, comm=comm, flavor="xla", census=hlo,
                    rules=["census-drift"], raise_on_error=False)
    assert not rep.findings, rep.findings
    rep = lint_step(None, comm=comm, flavor="hierarchical", inter_size=2,
                    census=hlo, rules=["census-drift"],
                    raise_on_error=False)
    f = _only(rep, "census-drift")
    assert f.details["observed"] == ["all-reduce"]
    # callable: invoked lazily, same verdicts
    rep = lint_step(None, comm=comm, flavor="hierarchical", inter_size=2,
                    census=lambda: hlo, rules=["census-drift"],
                    raise_on_error=False)
    _only(rep, "census-drift")

    def boom():
        raise RuntimeError("probe died")
    rep = lint_step(None, comm=comm, flavor="xla", census=boom,
                    rules=["census-drift"], raise_on_error=False)
    assert not rep.findings
    assert "census-drift" in rep.skipped
    assert "probe died" in str(rep.skipped["census-drift"])


def test_rule_census_drift_fires_through_spec_decode_path(devices):
    """Census-drift through the speculative-decoding fused step: the
    tp=2 draft+verify program's own compiled HLO (many Megatron psums —
    draft micro-steps plus the verify pass) rides the ``census=`` text
    seam and is held against a single-allreduce spec, so the rule must
    fire with the spec step's real collective count observed.  Pins that
    the serving entry point's extension did not bypass the drift check.
    """
    from chainermn_tpu.analysis.entrypoints import _serving_spec_target

    fn, args = _serving_spec_target()
    hlo = fn.lower(*args).compile().as_text()
    comm = chainermn_tpu.create_communicator("xla")
    rep = lint_step(None, comm=comm, flavor="xla", census=hlo,
                    rules=["census-drift"], raise_on_error=False)
    f = _only(rep, "census-drift")
    # the fused spec step runs MANY tp psums, never the flavor's one
    assert f.details["expected"] == ["all-reduce"]
    assert len(f.details["observed"]) > 1
    assert set(f.details["observed"]) == {"all-reduce"}


def test_rule_census_drift_serving_weights_multicast(devices):
    """Census-drift through the serving fleet's weight-distribution
    path: the real multicast program (the router's one masked-psum stage
    chain) holds to the plan IR's census, and a broken fixture — a
    replica fan that all-gathers instead — fires with the plan named.
    The broken program rides the ``census=`` callable seam, proving the
    serving entry point's own compiled HLO (not the training allreduce)
    is what the rule audits."""
    from chainermn_tpu.analysis.entrypoints import lint_serving_weights
    from chainermn_tpu.serving import weights_multicast_plan

    reports = lint_serving_weights()
    assert len(reports) == 1
    rep = reports[0]
    assert not rep.findings, rep.findings
    assert "census-drift" not in rep.skipped, rep.skipped

    comm = chainermn_tpu.create_communicator("flat")
    topo = comm.plan_topology()
    plan = weights_multicast_plan(root=0, topology=topo,
                                  name="serving_weights")

    def broken_hlo():
        # a drifted "broadcast": every rank all-gathers the stack — the
        # wrong collective class for the plan's masked-psum multicast
        return comm.compiled_hlo(
            lambda leaf: jax.lax.all_gather(leaf, comm.data_axes,
                                            tiled=True),
            jnp.zeros((comm.size, 64), jnp.float32))

    rep = lint_step(None, comm=comm, plan=plan, census=broken_hlo,
                    rules=["census-drift"], raise_on_error=False)
    f = _only(rep, "census-drift")
    assert "plan 'serving_weights'" in f.message
    assert "all-gather" in f.details["observed"]


def test_rule_wire_dtype_mismatch_per_hop_compressed_plan(devices):
    """A plan stage carrying a per-hop compression spec expects the
    COMPRESSOR's wire among the compiled collective dtypes: the real
    compressed program passes; the same spec audited against an
    uncompressed program fires once per missing wire, s8 included."""
    from chainermn_tpu.analysis.lint import allreduce_hlo
    from chainermn_tpu.analysis import schedule_from_hlo as _from_hlo
    from chainermn_tpu.planner.plans import (compressed_two_dimensional,
                                             flavor_plan)

    comm = chainermn_tpu.create_communicator("two_dimensional",
                                             intra_size=4)
    plan = compressed_two_dimensional({"name": "int8",
                                       "stochastic": False})
    hlo = allreduce_hlo(comm, plan=plan)
    ctx = SimpleNamespace(hlo_schedule=_from_hlo(hlo), hlo_text=hlo,
                          plan=plan, fsdp_meta=None, name="t")
    assert not get_rule("wire-dtype-mismatch").run(ctx)

    # broken fixture: the compiled program is the UNCOMPRESSED 2-D
    # decomposition — no s8 codes (and no bf16 seam) anywhere
    hlo2 = allreduce_hlo(comm, plan=flavor_plan("two_dimensional"))
    ctx2 = SimpleNamespace(hlo_schedule=_from_hlo(hlo2), hlo_text=hlo2,
                           plan=plan, fsdp_meta=None, name="t")
    findings = get_rule("wire-dtype-mismatch").run(ctx2)
    assert {f.details["expected_dtype"] for f in findings} \
        == {"s8", "bf16"}, findings
    s8 = [f for f in findings if f.details["expected_dtype"] == "s8"]
    assert len(s8) == 1 and "compressor 'int8'" in s8[0].details["declared"]


def test_rule_unpinned_transpose(devices):
    """A raw allreduce of the per-rank loss, differentiated inside the
    SPMD body (the PR 1 bug class: gradients inflate by world size),
    shows up as a backward psum with no primal counterpart.  The pinned
    path (functions.allreduce custom VJP) stays clean."""
    from chainermn_tpu import functions as F

    comm = chainermn_tpu.create_communicator("xla")
    params = {"w": jnp.ones((4, 4))}
    batch = jnp.ones((comm.size * 2, 4))

    def raw_loss(p, x):
        return comm.allreduce((x @ p["w"]).mean(), "mean")

    def pinned_loss(p, x):
        return F.allreduce(comm, (x @ p["w"]).mean(), "mean")

    rep = lint_step(None, comm=comm, loss=raw_loss,
                    loss_args=(params, batch),
                    rules=["unpinned-transpose"], raise_on_error=False)
    f = _only(rep, "unpinned-transpose")
    assert f.details["extra_backward_psums"] >= 1
    assert "functions.allreduce" in f.message  # names the fix

    rep2 = lint_step(None, comm=comm, loss=pinned_loss,
                     loss_args=(params, batch),
                     rules=["unpinned-transpose"], raise_on_error=False)
    assert not rep2.findings


def test_rule_captured_constant(devices):
    big = jnp.ones((64, 64))  # 16 KiB > the 4 KiB threshold

    def step(x):
        return (x * big).sum()

    rep = lint_step(step, jnp.ones((64, 64)), hlo=False,
                    rules=["captured-constant"], raise_on_error=False)
    f = _only(rep, "captured-constant")
    assert f.details["constants"][0]["nbytes"] == 64 * 64 * 4

    def clean(x, c):
        return (x * c).sum()

    rep2 = lint_step(clean, jnp.ones((64, 64)), big, hlo=False,
                     rules=["captured-constant"], raise_on_error=False)
    assert not rep2.findings


def test_rule_donation_alias(devices):
    a = jnp.ones((8,))
    step = jax.jit(lambda u, v: (u + v, v), donate_argnums=(0,))

    rep = lint_step(step, a, a, donate_argnums=(0,), hlo=False,
                    rules=["donation-alias"], raise_on_error=False)
    f = _only(rep, "donation-alias")
    assert f.details["donated"] == [0]

    rep2 = lint_step(step, a, jnp.ones((8,)), donate_argnums=(0,),
                     hlo=False, rules=["donation-alias"],
                     raise_on_error=False)
    assert not rep2.findings


def test_rule_wire_dtype_mismatch(devices):
    """An FSDP bucket whose layout claims a wire dtype the compiled
    program never moves (compression silently off — or numerics silently
    narrowed) is an error."""
    from chainermn_tpu.parallel.fsdp import fsdp_init, make_fsdp_train_step

    comm = chainermn_tpu.create_communicator("xla")
    params = {"a": jnp.ones((512,)), "b": jnp.ones((512,))}
    opt = optax.sgd(1e-2)
    state, meta = fsdp_init(comm, params, opt, num_buckets=2,
                            bucket_compressors=["int8", None])

    def loss(p, x):
        return (x @ p["a"].reshape(8, 64) @ p["b"].reshape(64, 8)).mean()

    step = make_fsdp_train_step(comm, loss, opt, meta)
    batch = jnp.ones((comm.size * 2, 8))

    rep = lint_step(step, state, batch, fsdp_meta=meta,
                    rules=["wire-dtype-mismatch"], raise_on_error=False)
    assert not rep.findings, rep.findings  # int8 bucket's s8 RS is there

    lying = list(meta.buckets)
    lying[1] = lying[1]._replace(wire_dtype="float8_e4m3fn")
    rep2 = lint_step(step, state, batch,
                     fsdp_meta=meta._replace(buckets=tuple(lying)),
                     rules=["wire-dtype-mismatch"], raise_on_error=False)
    f = _only(rep2, "wire-dtype-mismatch")
    assert f.details["bucket"] == 1
    assert f.details["expected_dtype"] == "f8e4m3fn"


def test_rule_async_pair():
    """An unmatched all-reduce-start in a compiled schedule is an error
    finding (the guaranteed-wedge shape the watchdog sees at runtime)."""
    sched = schedule_from_hlo(UNMATCHED_START_HLO)
    ctx = SimpleNamespace(hlo_schedule=sched, name="synthetic")
    findings = get_rule("async-pair").run(ctx)
    assert [f.rule for f in findings] == ["async-pair"]
    assert findings[0].details["kind"] == "unmatched-async-start"

    clean = schedule_from_hlo(SYNC_HLO)
    assert not get_rule("async-pair").run(
        SimpleNamespace(hlo_schedule=clean, name="synthetic"))


def _flight(kind_begin, kind_end, t0, t1, **f):
    return [{"kind": kind_begin, "ts": t0, "seq": 0, **f},
            {"kind": kind_end, "ts": t1, "seq": 1, **f}]


def test_rule_overlapping_collectives_fires_on_contended_link():
    """An FSDP gather and a MoE all-to-all hop concurrent on the ici
    link are independently tuned -> one warning finding naming both
    identities and the contended seconds.  Warning severity: the report
    stays ok (contention is a throughput bug, not a wedge)."""
    events = (
        _flight("fsdp_gather_begin", "fsdp_gather_end", 10.010, 10.030,
                bucket=0, link="ici", nbytes=1 << 20)
        + _flight("plan_stage_begin", "plan_stage_end", 10.020, 10.040,
                  plan="alltoall_hier", op="all_to_all", stage=0,
                  scope="intra", link="ici", nbytes=1 << 16))
    for i, e in enumerate(events):
        e["seq"] = i
    rep = lint_step(None, flight_events={0: events},
                    rules=["overlapping-collectives"], hlo=False,
                    raise_on_error=False, name="synthetic")
    assert rep.ok  # warning, not error
    assert [f.rule for f in rep.findings] == ["overlapping-collectives"]
    f = rep.findings[0]
    assert f.severity == "warning"
    assert f.details["link"] == "ici"
    assert f.details["identities"] == ["fsdp", "plan:alltoall_hier"]
    assert f.details["contended_s"] == pytest.approx(0.010)
    assert f.details["ranks"] == [0]


def test_rule_overlapping_collectives_fires_on_full_nesting():
    """One identity's span fully time-containing another's is the
    worst-contended case (the inner transfer runs entirely under
    contention), not a parent/child — the rule fires for the inner
    span's whole duration."""
    events = (
        _flight("fsdp_gather_begin", "fsdp_gather_end", 20.000, 20.100,
                bucket=0, link="ici", nbytes=1 << 20)
        + _flight("plan_stage_begin", "plan_stage_end", 20.020, 20.080,
                  plan="alltoall_hier", op="all_to_all", stage=0,
                  scope="intra", link="ici", nbytes=1 << 16))
    for i, e in enumerate(events):
        e["seq"] = i
    rep = lint_step(None, flight_events={0: events},
                    rules=["overlapping-collectives"], hlo=False,
                    raise_on_error=False, name="synthetic")
    assert [f.rule for f in rep.findings] == ["overlapping-collectives"]
    f = rep.findings[0]
    assert f.details["identities"] == ["fsdp", "plan:alltoall_hier"]
    assert f.details["contended_s"] == pytest.approx(0.060)


def test_rule_overlapping_collectives_exempts_plan_decomposition():
    """A trace-time collective wrapper over its OWN plan stages is one
    decomposed transfer, not two contending ones — no finding."""
    events = (
        _flight("collective_begin", "collective_end", 30.000, 30.100,
                op="allreduce_grad", op_seq=1, nbytes=1 << 20)
        + _flight("plan_stage_begin", "plan_stage_end", 30.020, 30.080,
                  plan="hier", op="all-reduce", stage=0,
                  scope="intra", link="ici", nbytes=1 << 20))
    for i, e in enumerate(events):
        e["seq"] = i
    rep = lint_step(None, flight_events={0: events},
                    rules=["overlapping-collectives"], hlo=False,
                    raise_on_error=False)
    assert rep.ok and rep.findings == []


def test_rule_overlapping_collectives_ignores_cotuned_stripes():
    """Concurrent groups of ONE striped plan share a tuning identity
    (their link split is a single co-tuned decision) and never fire."""
    stripe = dict(plan="striped_bf16", op="all-reduce", stage=0,
                  scope="intra", link="ici", nbytes=1 << 18)
    events = (
        _flight("plan_stage_begin", "plan_stage_end", 5.000, 5.020,
                group=0, **stripe)
        + _flight("plan_stage_begin", "plan_stage_end", 5.005, 5.025,
                  group=1, **stripe))
    for i, e in enumerate(events):
        e["seq"] = i
    rep = lint_step(None, flight_events=events,
                    rules=["overlapping-collectives"], hlo=False,
                    raise_on_error=False)
    assert rep.ok and rep.findings == []


def test_rule_overlapping_collectives_exempts_cotuned_workload():
    """Two DIFFERENT plans whose names carry the same ``@wl:<sig>``
    workload tag were priced together by the global scheduler
    (planner.schedule.jointly_tune) — their overlap is the joint plan,
    not accidental contention, so the rule must not fire."""
    events = (
        _flight("plan_stage_begin", "plan_stage_end", 40.000, 40.030,
                plan="striped_r90@wl:ab12cd34ef56", op="all-reduce",
                stage=0, scope="intra", link="ici", nbytes=1 << 20)
        + _flight("plan_stage_begin", "plan_stage_end", 40.010, 40.040,
                  plan="alltoall_hier@wl:ab12cd34ef56", op="all_to_all",
                  stage=0, scope="intra", link="ici", nbytes=1 << 18))
    for i, e in enumerate(events):
        e["seq"] = i
    rep = lint_step(None, flight_events={0: events},
                    rules=["overlapping-collectives"], hlo=False,
                    raise_on_error=False, name="synthetic")
    assert rep.ok and rep.findings == [], rep.findings


def test_rule_overlapping_collectives_fires_across_workloads():
    """Broken fixture: the same two plans overlapping WITHOUT a shared
    workload signature (different tags, or one untagged) are still
    independently tuned — the exemption must not swallow them."""
    different_sig = (
        _flight("plan_stage_begin", "plan_stage_end", 41.000, 41.030,
                plan="striped_r90@wl:ab12cd34ef56", op="all-reduce",
                stage=0, scope="intra", link="ici", nbytes=1 << 20)
        + _flight("plan_stage_begin", "plan_stage_end", 41.010, 41.040,
                  plan="alltoall_hier@wl:999999999999", op="all_to_all",
                  stage=0, scope="intra", link="ici", nbytes=1 << 18))
    one_untagged = (
        _flight("plan_stage_begin", "plan_stage_end", 42.000, 42.030,
                plan="striped_r90@wl:ab12cd34ef56", op="all-reduce",
                stage=0, scope="intra", link="ici", nbytes=1 << 20)
        + _flight("plan_stage_begin", "plan_stage_end", 42.010, 42.040,
                  plan="alltoall_hier", op="all_to_all", stage=0,
                  scope="intra", link="ici", nbytes=1 << 18))
    for events, identities in (
            (different_sig, ["workload:999999999999",
                             "workload:ab12cd34ef56"]),
            (one_untagged, ["plan:alltoall_hier",
                            "workload:ab12cd34ef56"])):
        for i, e in enumerate(events):
            e["seq"] = i
        rep = lint_step(None, flight_events={0: events},
                        rules=["overlapping-collectives"], hlo=False,
                        raise_on_error=False, name="synthetic")
        assert [f.rule for f in rep.findings] == \
            ["overlapping-collectives"]
        assert sorted(rep.findings[0].details["identities"]) == identities


def test_rule_overlapping_collectives_skips_without_events(devices):
    rep = lint_step(lambda x: x * 2, jnp.ones((4,)), hlo=False,
                    raise_on_error=False)
    assert "overlapping-collectives" in rep.skipped
    assert "flight_events" in rep.skipped["overlapping-collectives"]


# ---------------------------------------------------------------------------
# lint_step API / fixture behavior
# ---------------------------------------------------------------------------

def test_lint_step_raises_on_error_findings(lint_step):
    big = jnp.ones((64, 64))
    with pytest.raises(LintError) as ei:
        lint_step(lambda x: (x * big).sum(), jnp.ones((64, 64)), hlo=False)
    assert "captured-constant" in str(ei.value)
    assert ei.value.report.errors


def test_lint_step_skips_rules_without_inputs(devices):
    """With only a step function, the comm/fsdp-bound rules are skipped
    with a reason — never crashed, never silently passed."""
    rep = lint_step(lambda x: x * 2, jnp.ones((4,)), hlo=False,
                    raise_on_error=False)
    assert rep.ok
    for rule_id in ("schedule-desync", "census-drift", "unpinned-transpose",
                    "wire-dtype-mismatch"):
        assert rule_id in rep.skipped, rep.skipped
    assert "captured-constant" not in rep.skipped


def test_unknown_rule_id_is_an_error():
    with pytest.raises(ValueError, match="unknown lint rule"):
        lint_step(lambda x: x, jnp.ones(()), rules=["no-such-rule"])


def test_report_json_shape(devices):
    rep = lint_step(lambda x: x * 2, jnp.ones((4,)), hlo=False,
                    raise_on_error=False, name="t")
    doc = rep.to_json()
    assert doc["suite"] == "cmn_lint" and doc["target"] == "t"
    assert doc["ok"] is True and doc["findings"] == []
    json.dumps(doc)  # must be serializable as-is


# ---------------------------------------------------------------------------
# clean sweeps: the example steps hold zero error findings
# ---------------------------------------------------------------------------

def test_clean_sweep_mnist_all_flavors(devices):
    """Acceptance: zero error-severity findings on the mnist step across
    all seven communicator flavors, with the census, desync, and
    gradient-transpose probes all actually running (not skipped)."""
    from chainermn_tpu.analysis.entrypoints import MNIST_FLAVORS, lint_mnist

    reports = lint_mnist()
    assert len(reports) == len(MNIST_FLAVORS) == 7
    for rep in reports:
        assert rep.ok, rep.render_text()
        for rule_id in ("schedule-desync", "census-drift",
                        "unpinned-transpose", "captured-constant",
                        "donation-alias", "async-pair"):
            assert rule_id not in rep.skipped, (rep.target, rep.skipped)


def test_clean_sweep_long_context(devices):
    """Zero error findings on the long-context ring-attention step (the
    ppermute ring + explicit psums trace clean through shard_map)."""
    from chainermn_tpu.analysis.entrypoints import lint_long_context

    (rep,) = lint_long_context()
    assert rep.ok, rep.render_text()
    assert "schedule-desync" not in rep.skipped
    assert "captured-constant" not in rep.skipped


def test_clean_sweep_resnet_fused(devices):
    """Zero error findings on the fused-norm resnet train step: the
    Pallas kernels inside the shard_map'd loss contribute no
    collectives, so the census holds the compiled schedule to the xla
    gradient-allreduce plan, and differentiating through the fused
    custom VJP adds no unpinned backward psum."""
    from chainermn_tpu.analysis.entrypoints import lint_resnet_fused

    (rep,) = lint_resnet_fused()
    assert rep.ok, rep.render_text()
    for rule_id in ("schedule-desync", "census-drift",
                    "unpinned-transpose", "captured-constant",
                    "donation-alias", "async-pair"):
        assert rule_id not in rep.skipped, (rep.target, rep.skipped)


def test_rules_still_fire_through_fused_norm(devices):
    """The broken-fixture counterpart of the fused clean sweep: routing
    the body through the fused_norm Pallas kernels must not blind the
    analyzer.  A seeded rank-divergent collective order around the fused
    op is still a schedule-desync error, and a raw (unpinned) allreduce
    of a fused-norm loss still shows the PR 1 gradient-inflation
    transpose."""
    from chainermn_tpu.ops import fused_norm

    comm = chainermn_tpu.create_communicator("xla")
    ax = comm.data_axes
    scale = jnp.ones((8,), jnp.float32)
    bias = jnp.zeros((8,), jnp.float32)

    def make_rank_step(rank):
        def body(x):
            y, _, _ = fused_norm(x, scale, bias)
            if rank == 0:
                return jax.lax.pmax(jax.lax.psum(y, ax), ax)
            return jax.lax.psum(jax.lax.pmax(y, ax), ax)
        return jax.shard_map(body, mesh=comm.mesh, in_specs=P(ax),
                         out_specs=P(ax), check_vma=False)

    x = jnp.ones((comm.size * 2, 8))
    rep = lint_step(
        None,
        variants={f"rank{r}": (make_rank_step(r), x) for r in range(2)},
        rules=["schedule-desync"], raise_on_error=False)
    f = _only(rep, "schedule-desync")
    assert f.severity == "error"
    assert f.details["index"] == 0  # pallas calls contribute no collectives

    params = {"w": jnp.ones((8, 8))}

    def raw_fused_loss(p, xb):
        y, _, _ = fused_norm(xb @ p["w"], scale, bias)
        return comm.allreduce(y.mean(), "mean")

    rep2 = lint_step(None, comm=comm, loss=raw_fused_loss,
                     loss_args=(params, x),
                     rules=["unpinned-transpose"], raise_on_error=False)
    f2 = _only(rep2, "unpinned-transpose")
    assert f2.details["extra_backward_psums"] >= 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cmn_lint_cli_json(tmp_path):
    """The CLI lints a named entry point on a virtual mesh it bootstraps
    itself, exits 0 on a clean sweep, and writes the findings JSON the
    obs_report --lint lane renders."""
    out = tmp_path / "lint.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "cmn_lint.py"),
         "examples/mnist", "--flavors", "xla", "--json",
         "--out", str(out)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    doc = json.loads(r.stdout)
    assert doc["suite"] == "cmn_lint" and doc["ok"] is True
    assert doc["reports"][0]["target"] == "examples/mnist[xla]"
    assert json.loads(out.read_text())["ok"] is True

    # the obs_report lint lane renders that artifact
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--lint", str(out)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"))
    assert r2.returncode == 0, r2.stderr[-1500:]
    assert "cmn-lint static analysis" in r2.stdout
    assert "CLEAN" in r2.stdout


def test_cmn_lint_cli_exit_code_on_findings(tmp_path):
    """--rules census-drift with a deliberately wrong flavor expectation
    is not reachable from the CLI (entry points are the clean builds), so
    exercise the nonzero-exit path via --list + unknown entry point."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "cmn_lint.py"),
         "--list"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-800:]
    for rule_id in ("schedule-desync", "census-drift", "unpinned-transpose",
                    "captured-constant", "donation-alias",
                    "wire-dtype-mismatch", "async-pair"):
        assert rule_id in r.stdout, r.stdout


# ---------------------------------------------------------------------------
# deprecation shim
# ---------------------------------------------------------------------------

def test_jaxpr_audit_reexport_still_works():
    """The old utils.jaxpr_audit import path keeps working (thin
    re-export of analysis.captured) — the long-context example and any
    external caller survive the move."""
    from chainermn_tpu.utils.jaxpr_audit import (
        CapturedConstantError, assert_no_captured_constants)
    from chainermn_tpu.analysis import captured

    assert assert_no_captured_constants is captured.assert_no_captured_constants
    big = jnp.ones((64, 64))
    with pytest.raises(CapturedConstantError, match="explicit argument"):
        assert_no_captured_constants(lambda x: x * big, jnp.ones((64, 64)))
