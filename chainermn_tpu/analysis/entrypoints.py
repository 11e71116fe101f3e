"""Named lintable entry points — the programs ``tools/cmn_lint.py`` (and
the CI clean sweep) hold to zero error findings.

Each entry point rebuilds the example's train step the way the example
itself does — same builder (:func:`make_train_step` / the long-context
jit), same loss structure, same donation — but at toy sizes, because the
lint only reads the *schedule*: collective structure is invariant to
width, so a 16-unit MLP proves the same theorem as the 1000-unit one at
a fraction of the trace/compile cost.

Everything here runs on the tier-1 CPU mesh: no TPU, no process spawn.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chainermn_tpu.analysis.lint import LintReport, lint_step

#: the seven communicator flavors the mnist sweep must hold clean
#: (pure_nccl is the xla alias and is accepted as a spelling)
MNIST_FLAVORS = ("naive", "flat", "hierarchical", "two_dimensional",
                 "single_node", "non_cuda_aware", "xla")

#: flavors whose decomposition needs a two-level topology on 8 devices
_NEEDS_INTRA = {"hierarchical": 4, "two_dimensional": 4}


def _mnist_target(flavor: str):
    """The mnist example's step at toy width: MLP + multi-node Adam +
    ``make_train_step(has_aux=True)`` (donating params/opt_state exactly
    like the example's hot loop)."""
    import chainermn_tpu
    from chainermn_tpu.models import MLP
    from chainermn_tpu.optimizers import init_opt_state, make_train_step

    comm = chainermn_tpu.create_communicator(
        flavor, intra_size=_NEEDS_INTRA.get(flavor))
    model = MLP(16, 10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 784)))
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(1e-3), comm)
    opt_state = init_opt_state(comm, optimizer, params)

    def loss_fn(p, batch):
        x, y = batch
        logits = model.apply(p, x)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        acc = (logits.argmax(-1) == y).mean()
        return loss, {"accuracy": acc}

    step = make_train_step(comm, loss_fn, optimizer, has_aux=True)
    batch = (jnp.zeros((comm.size * 4, 784), jnp.float32),
             jnp.zeros((comm.size * 4,), jnp.int32))
    return comm, step, (params, opt_state, batch), loss_fn


def lint_mnist(flavors: Optional[Sequence[str]] = None,
               rules: Optional[Sequence[str]] = None,
               hlo: bool = True) -> List[LintReport]:
    """One report per communicator flavor for the mnist step.  Every rule
    runs: schedule-desync over two independent traces (every rank runs
    this same builder, so identical traces ARE the invariant),
    census-drift over the flavor's compiled allreduce, the gradient
    probe over the example's loss, captured-constant/donation-alias/
    async-pair over the traced + compiled step."""
    reports = []
    for flavor in (flavors or MNIST_FLAVORS):
        comm, step, args, loss_fn = _mnist_target(flavor)
        params, opt_state, batch = args
        reports.append(lint_step(
            step, *args,
            name=f"examples/mnist[{flavor}]",
            comm=comm, flavor=flavor,
            loss=loss_fn, loss_args=(params, batch),
            donate_argnums=(0, 1),
            variants={"rank0": (step,) + args, "rank1": (step,) + args},
            census=True, hlo=hlo, rules=rules,
            raise_on_error=False))
    return reports


def _long_context_target():
    """The long-context example's non-FSDP ring-attention step at toy
    size: seq 128 over the 8-way ``sp`` mesh, loss traced through the
    example's own shard_map (ppermute ring + explicit psums)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from chainermn_tpu.models import TransformerLM

    devices = jax.devices()
    n_sp = len(devices)
    seq_len = 16 * n_sp
    t_local = seq_len // n_sp
    kw = dict(vocab=32, d_model=16, n_layers=1, n_heads=2,
              max_len=seq_len)
    model = TransformerLM(attention_impl="ring", axis_name="sp", **kw)
    ref_init = TransformerLM(attention_impl="xla", **kw)
    mesh = Mesh(np.array(devices[:n_sp]), ("sp",))
    toks = jnp.zeros((2, seq_len), jnp.int32)
    params = ref_init.init(jax.random.key(0), toks[:, :8])
    opt = optax.sgd(1e-2)
    opt_state = opt.init(params)

    def sp_body(pp, tkk):
        me = jax.lax.axis_index("sp")
        logits = model.apply(pp, tkk, pos_offset=me * t_local)
        nxt = jax.lax.ppermute(
            tkk[:, :1], "sp",
            perm=[(i, (i - 1) % n_sp) for i in range(n_sp)])
        targets = jnp.concatenate([tkk[:, 1:], nxt], axis=1)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets)
        mask = jnp.ones_like(ce).at[:, -1].set(
            jnp.where(me == n_sp - 1, 0.0, 1.0))
        total = jax.lax.psum((ce * mask).sum(), "sp")
        count = jax.lax.psum(mask.sum(), "sp")
        return total / count

    def loss_fn(p_, tk):
        return jax.shard_map(sp_body, mesh=mesh,
                         in_specs=(P(), P(None, "sp")),
                         out_specs=P(), check_vma=False)(p_, tk)

    @jax.jit
    def step(p_, s_, tk):
        l, g = jax.value_and_grad(loss_fn)(p_, tk)
        updates, s_ = opt.update(g, s_, p_)
        return optax.apply_updates(p_, updates), s_, l

    return step, (params, opt_state, toks)


def lint_long_context(rules: Optional[Sequence[str]] = None,
                      hlo: bool = True) -> List[LintReport]:
    """One report for the long-context ring-attention step.  No
    communicator object is in play (the example drives shard_map
    directly), so the comm-bound rules (census-drift, the gradient
    probe) report as skipped; schedule-desync, captured-constant,
    donation-alias, and async-pair all run."""
    step, args = _long_context_target()
    return [lint_step(
        step, *args,
        name="examples/long_context[ring]",
        variants={"rank0": (step,) + args, "rank1": (step,) + args},
        hlo=hlo, rules=rules, raise_on_error=False)]


def _resnet_fused_target(flavor: str = "xla"):
    """The resnet example's step with the fused normalization path
    (``ops.FusedBatchNormAct`` at every BN boundary) at toy width — the
    program the fusednorm probe variant and the remat autotuner time.
    The Pallas kernels ride inside the shard_map'd loss via their custom
    VJP; they contain no collectives, so the lintable schedule must stay
    exactly the flavor's gradient-allreduce plan (census-drift) and the
    backward must add no unpinned psum (the custom VJP *is* the pin)."""
    import chainermn_tpu
    from chainermn_tpu.models import ResNet
    from chainermn_tpu.models.resnet import BasicBlock
    from chainermn_tpu.ops import FusedBatchNormAct
    from chainermn_tpu.optimizers import (
        init_model_state, init_opt_state, make_train_step)

    comm = chainermn_tpu.create_communicator(
        flavor, intra_size=_NEEDS_INTRA.get(flavor))
    model = ResNet(stage_sizes=(1,), block_cls=BasicBlock, num_filters=8,
                   num_classes=10, norm_cls=FusedBatchNormAct)
    x0 = jnp.zeros((1, 16, 16, 3), jnp.float32)
    variables = model.init(jax.random.key(0), x0)
    params = variables["params"]
    stats0 = variables["batch_stats"]
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm)
    model_state = init_model_state(comm, stats0)
    opt_state = init_opt_state(comm, optimizer, params)

    def loss_fn(p, state, batch):
        x, y = batch
        logits, mut = model.apply(
            {"params": p, "batch_stats": state}, x, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, mut["batch_stats"]

    # The gradient probe wants loss(params, *sharded_rest): close over the
    # (tiny, replicated) initial stats so only the batch is sharded.
    def probe_loss(p, batch):
        return loss_fn(p, stats0, batch)[0]

    step = make_train_step(comm, loss_fn, optimizer, with_model_state=True)
    batch = (jnp.zeros((comm.size * 2, 16, 16, 3), jnp.float32),
             jnp.zeros((comm.size * 2,), jnp.int32))
    args = (params, model_state, opt_state, batch)
    return comm, step, args, probe_loss


def lint_resnet_fused(rules: Optional[Sequence[str]] = None,
                      hlo: bool = True) -> List[LintReport]:
    """One report for the fused-norm resnet train step (xla flavor).
    Every rule runs: the desync variants trace the builder twice, the
    census holds the compiled collectives to the flavor's plan (the
    Pallas calls must contribute zero), and the gradient probe
    differentiates through the fused kernels' custom VJP inside the SPMD
    region — a regrown stats-path psum would land here as
    unpinned-transpose."""
    comm, step, args, probe_loss = _resnet_fused_target()
    params, _, _, batch = args
    return [lint_step(
        step, *args,
        name="examples/resnet_fused[xla]",
        comm=comm, flavor="xla",
        loss=probe_loss, loss_args=(params, batch),
        donate_argnums=(0, 1, 2),
        variants={"rank0": (step,) + args, "rank1": (step,) + args},
        census=True, hlo=hlo, rules=rules,
        raise_on_error=False)]


def _moe_train_target():
    """The MoE LM example's train step at toy size over the 2x4
    ``("ep", "data")`` mesh: tokens sharded over ``data``, the expert
    MLPs dispatched over ``ep`` through a planner all-to-all plan
    (``moe_plan=``), loss differentiated outside the shard_map exactly
    like the example.  The census spec is the plan itself: the
    ``census=`` callable compiles ONE ``execute_alltoall`` over the ep
    axis, so census-drift holds the compiled exchange to
    ``plan_census_kinds`` of the MoE dispatch plan — expected kinds are
    DERIVED from the IR, never hand-written."""
    from jax.sharding import Mesh, PartitionSpec as P

    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.planner.compiler import execute_alltoall
    from chainermn_tpu.planner.ir import PlanTopology
    from chainermn_tpu.planner.plans import alltoall_plans

    devices = jax.devices()
    if len(devices) < 8:
        raise RuntimeError(
            f"moe/train needs 8 devices for the 2x4 ep x data mesh, "
            f"have {len(devices)}")
    mesh = Mesh(np.array(devices[:8]).reshape(2, 4), ("ep", "data"))
    topo = PlanTopology(axes=(("ep", 2),))
    plan = next(p for p in alltoall_plans(topo)
                if p.name == "alltoall_flat_bfloat16")
    model = TransformerLM(vocab=32, d_model=16, n_layers=1, n_heads=2,
                          max_len=32, attention_impl="xla",
                          moe_experts=4, moe_top_k=2, moe_axis="ep",
                          moe_plan=plan)
    toks = jnp.zeros((8, 16), jnp.int32)

    # init inside the SPMD region (router/expert shapes bind the ep axis)
    params = jax.jit(jax.shard_map(
        lambda tk: model.init(jax.random.key(0), tk), mesh=mesh,
        in_specs=P("data"), out_specs=P(), check_vma=False))(toks)
    opt = optax.sgd(1e-2)
    opt_state = opt.init(params)

    def loss_fn(p_, tk):
        def body(pp, tkk):
            logits, mut = model.apply(pp, tkk, mutable=["moe_stats"])
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tkk[:, 1:]).mean()
            aux = mut["moe_stats"]["block_0"]["aux_loss"][0]
            return jax.lax.pmean(ce, ("ep", "data")) + 1e-2 * aux

        return jax.shard_map(body, mesh=mesh, in_specs=(P(), P(None, "data")),
                         out_specs=P(), check_vma=False)(p_, tk)

    @jax.jit
    def step(p_, s_, tk):
        l, g = jax.value_and_grad(loss_fn)(p_, tk)
        updates, s_ = opt.update(g, s_, p_)
        return optax.apply_updates(p_, updates), s_, l

    # the census program: ONE plan execution over the ep axis — the
    # exchange the MoE layer rides twice per application
    buf = jnp.zeros((4, 8, 16), jnp.float32)

    def census_hlo():
        return jax.jit(jax.shard_map(
            lambda b: execute_alltoall(plan, topo, b), mesh=mesh,
            in_specs=P("ep"), out_specs=P("ep"),
            check_vma=False)).lower(buf).compile().as_text()

    return step, (params, opt_state, toks), plan, census_hlo


def lint_moe_train(rules: Optional[Sequence[str]] = None,
                   hlo: bool = True) -> List[LintReport]:
    """One report for the MoE transformer train step (2x4 ep x data
    mesh).  census-drift holds the compiled token exchange to the MoE
    dispatch plan's derived kinds and per-hop wire dtypes;
    wire-dtype-mismatch checks the plan's declared bf16 wire actually
    appears in the step's compiled program; schedule-desync,
    captured-constant, donation-alias and async-pair run over the full
    step.  No communicator object is in play (the example drives
    shard_map directly), so the gradient-probe rule reports as
    skipped."""
    step, args, plan, census_hlo = _moe_train_target()
    return [lint_step(
        step, *args,
        name="examples/moe_lm[ep2xdata4]",
        plan=plan, census=census_hlo,
        variants={"rank0": (step,) + args, "rank1": (step,) + args},
        hlo=hlo, rules=rules, raise_on_error=False)]


def _serving_decode_target(tp: int = 2):
    """The serving engine's fused prefill+decode forward at toy size,
    tensor-parallel over 2 devices — the jitted program every serving
    step replays.  The interesting schedule is the tp > 1 one: Megatron
    row-parallel psums over the ``"tp"`` axis inside shard_map (tp=1
    compiles to a collective-free program)."""
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import InferenceEngine, ServingConfig

    model = TransformerLM(vocab=32, d_model=16, n_layers=1, n_heads=2,
                          max_len=64, attention_impl="xla")
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    cfg = ServingConfig(page_size=4, num_pages=8, max_seqs=2,
                        chunk_tokens=4, max_pages_per_seq=4, tp_size=tp)
    eng = InferenceEngine(model, params, cfg)
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.scheduler.apply_plan(eng.scheduler.build_plan())
    batch = eng.scheduler.step_batch()
    args = (eng._params, eng._ck, eng._cv,
            jnp.asarray(batch["page_table"]),
            jnp.asarray(batch["tokens"]), jnp.asarray(batch["pos0"]),
            jnp.asarray(batch["n_new"]))
    return eng._fwd, args


def _serving_spec_target(tp: int = 2):
    """The serving engine's fused draft+verify speculative step at toy
    size, tensor-parallel over 2 devices.  Self-draft (the 1-layer toy
    is its own draft): the schedule theorem — k greedy draft micro-steps
    plus one target verify pass, all Megatron psums over ``"tp"`` inside
    ONE jitted program — is invariant to which weights the draft loads.
    """
    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import InferenceEngine, ServingConfig

    model = TransformerLM(vocab=32, d_model=16, n_layers=1, n_heads=2,
                          max_len=64, attention_impl="xla")
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    cfg = ServingConfig(page_size=4, num_pages=8, max_seqs=2,
                        chunk_tokens=4, max_pages_per_seq=4, tp_size=tp,
                        spec_k=2)
    eng = InferenceEngine(model, params, cfg,
                          draft_model=model, draft_params=params)
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.scheduler.apply_plan(eng.scheduler.build_plan())
    batch = eng.scheduler.step_batch()
    args = (eng._params, eng._dparams, eng._ck, eng._cv,
            eng._dck, eng._dcv,
            jnp.asarray(batch["page_table"]),
            jnp.asarray(batch["tokens"]), jnp.asarray(batch["pos0"]),
            jnp.asarray(batch["n_new"]), jnp.asarray(batch["decode"]),
            jnp.asarray(batch["prev"]))
    return eng._fwd_spec, args


def lint_serving_decode(rules: Optional[Sequence[str]] = None,
                        hlo: bool = True) -> List[LintReport]:
    """Two reports for the serving forward programs (tp=2): the plain
    fused prefill+decode step and the fused draft+verify speculative
    step.  Lockstep serving has the same SPMD obligation as training —
    every controller must trace the identical schedule from the
    broadcast plan (for the spec step: including the accept/reject
    computation, whose decisions ride that plan's envelope) — so the
    schedule-desync variants run each builder twice, exactly as a rank
    pair would.  No communicator object is in play (the engine drives
    shard_map directly), so the comm-bound rules report as skipped."""
    step, args = _serving_decode_target()
    reports = [lint_step(
        step, *args,
        name="serving/decode[tp2]",
        variants={"rank0": (step,) + args, "rank1": (step,) + args},
        hlo=hlo, rules=rules, raise_on_error=False)]
    spec_step, spec_args = _serving_spec_target()
    reports.append(lint_step(
        spec_step, *spec_args,
        name="serving/decode[tp2,spec]",
        variants={"rank0": (spec_step,) + spec_args,
                  "rank1": (spec_step,) + spec_args},
        hlo=hlo, rules=rules, raise_on_error=False))
    return reports


def _serving_weights_target():
    """The router's multicast weight-distribution program: the per-leaf
    staged broadcast (``planner.compiler._run_stages_leaf``) the fleet
    replicates params through, compiled on the flat 8-way communicator,
    with the plan IR as the census spec."""
    import chainermn_tpu
    from chainermn_tpu.planner.compiler import _run_stages_leaf
    from chainermn_tpu.serving import weights_multicast_plan

    comm = chainermn_tpu.create_communicator("flat")
    topo = comm.plan_topology()
    plan = weights_multicast_plan(root=0, topology=topo,
                                  name="serving_weights")
    leaf = jnp.zeros((comm.size, 64), jnp.float32)

    def program(stacked):
        return _run_stages_leaf(plan, topo, stacked)

    def census_hlo():
        return comm.compiled_hlo(program, leaf)

    fn = comm._spmd_program(program, jit=True)
    return comm, plan, fn, ((leaf,),), census_hlo


def lint_serving_weights(rules: Optional[Sequence[str]] = None,
                         hlo: bool = True) -> List[LintReport]:
    """One report for the fleet weight-distribution multicast.  The
    census here is NOT the training allreduce: the ``census=`` callable
    compiles the router's own broadcast program and census-drift holds
    its collective decomposition to ``plan_census_kinds`` of the
    multicast plan — params must reach every replica through the plan's
    ONE masked-psum stage chain, never a fan of point-to-point sends."""
    comm, plan, fn, args, census_hlo = _serving_weights_target()
    return [lint_step(
        fn, *args,
        name="serving/weights[multicast]",
        comm=comm, plan=plan, census=census_hlo,
        variants={"rank0": (fn,) + args, "rank1": (fn,) + args},
        hlo=hlo, rules=rules, raise_on_error=False)]


ENTRY_POINTS: Dict[str, dict] = {
    "examples/mnist": {
        "fn": lint_mnist,
        "flavors": MNIST_FLAVORS,
        "help": "MLP data-parallel step, one report per communicator "
                "flavor (census + gradient probe + desync variants)",
    },
    "examples/long_context": {
        "fn": lint_long_context,
        "flavors": None,
        "help": "ring-attention sequence-parallel LM step (schedule, "
                "captured-constant, donation, async rules)",
    },
    "examples/resnet_fused": {
        "fn": lint_resnet_fused,
        "flavors": None,
        "help": "resnet train step with the fused BN(+ReLU) Pallas "
                "kernels at every norm boundary (census + gradient probe "
                "through the custom VJP + desync variants)",
    },
    "moe/train": {
        "fn": lint_moe_train,
        "flavors": None,
        "help": "MoE transformer train step over the 2x4 ep x data mesh: "
                "census-drift holds the compiled token exchange to the "
                "dispatch plan's derived kinds and wire dtypes (plus "
                "schedule/captured-constant/donation/async rules)",
    },
    "serving/decode": {
        "fn": lint_serving_decode,
        "flavors": None,
        "help": "serving engine fused forwards, tp=2 Megatron shard_map: "
                "plain prefill+decode AND the draft+verify speculative "
                "step (schedule, captured-constant, async rules)",
    },
    "serving/weights": {
        "fn": lint_serving_weights,
        "flavors": None,
        "help": "fleet weight-distribution multicast: census-drift holds "
                "the compiled broadcast program to the multicast plan IR "
                "(plus schedule/async rules)",
    },
}


def lint_entry_point(name: str, flavors: Optional[Sequence[str]] = None,
                     rules: Optional[Sequence[str]] = None,
                     hlo: bool = True) -> List[LintReport]:
    """Run a named entry point's lint sweep, returning its reports."""
    try:
        entry = ENTRY_POINTS[name]
    except KeyError:
        raise ValueError(
            f"unknown entry point {name!r}; available: "
            f"{sorted(ENTRY_POINTS)}") from None
    if entry["flavors"] is not None:
        return entry["fn"](flavors=flavors, rules=rules, hlo=hlo)
    if flavors:
        raise ValueError(f"{name} takes no --flavors")
    return entry["fn"](rules=rules, hlo=hlo)


__all__ = ["ENTRY_POINTS", "MNIST_FLAVORS", "lint_entry_point",
           "lint_long_context", "lint_mnist", "lint_moe_train",
           "lint_resnet_fused", "lint_serving_decode",
           "lint_serving_weights"]
