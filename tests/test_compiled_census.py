"""``analysis.compiled_step_census``: what the compiler added to a compiled
step, counted from ``compiled.as_text()`` (PR 40): XLA's rematerialised
clones and ``jax.checkpoint``'s recomputation by module, copies by kind, and
values placed in fast memory.  On a text in the TPU compiler's rendering, by
hand; and on a small program compiled here that is made to rematerialise."""

import jax
import jax.numpy as jnp
import pytest
from flax import linen as nn

from chainermn_tpu.analysis import compiled_step_census
from chainermn_tpu.analysis.compiled import _module

OP = 'metadata={op_name="jit(inner)/chainermn.grad/%s" stack_frame_id=5}'
FORWARD = OP % "jvp(LFM2MoE)/layer_%d/%s"
TPU_TEXT = f"""HloModule jit_inner, is_scheduled=true

%fused_computation.959.clone (param_0.239: bf16[24576,2048], param_1.4: s32[61440]) -> bf16[24576,2048] {{
  %param_0.239 = bf16[24576,2048]{{1,0:T(8,128)(2,1)S(1)}} parameter(0)
  %param_1.4 = s32[61440]{{0:T(1024)S(1)}} parameter(1)
  ROOT %copy.77 = bf16[24576,2048]{{1,0:T(8,128)(2,1)}} copy(%param_0.239)
}}

%async_computation.24 (param_0.6721: bf16[8192,2304]) -> bf16[2048,2304] {{
  %param_0.6721 = bf16[8192,2304]{{1,0:T(8,128)(2,1)}} parameter(0)
  ROOT %slice.1624 = bf16[2048,2304]{{1,0:T(8,128)(2,1)S(1)}} slice(%param_0.6721), slice={{[0:2048], [0:2304]}}
}}

%branch_1 (p: f32[8,128]) -> f32[8,128] {{
  %p = f32[8,128]{{1,0:T(8,128)}} parameter(0)
  ROOT %copy.9 = f32[8,128]{{0,1:T(8,128)}} copy(%p)
}}

ENTRY %main.1 (Arg_0.1: bf16[24576,2048], Arg_1.2: s32[61440]) -> f32[8,128] {{
  %Arg_0.1 = bf16[24576,2048]{{1,0:T(8,128)(2,1)}} parameter(0)
  %Arg_1.2 = s32[61440]{{0:T(1024)}} parameter(1)
  %copy-start.3 = (bf16[24576,2048]{{1,0:T(8,128)(2,1)S(1)}}, bf16[24576,2048]{{1,0:T(8,128)(2,1)}}, u32[]{{:S(2)}}) copy-start(%Arg_0.1)
  %copy-done.3 = bf16[24576,2048]{{1,0:T(8,128)(2,1)S(1)}} copy-done(%copy-start.3)
  %fusion.557 = bf16[24576,2048]{{1,0:T(8,128)(2,1)}} fusion(%copy-done.3, %Arg_1.2), kind=kLoop, calls=%fused_computation.959.clone, {FORWARD % (0, "conv/in_proj/dot_general")}
  %fusion.557.remat = bf16[24576,2048]{{1,0:T(8,128)(2,1)}} fusion(%copy-done.3, %Arg_1.2), kind=kLoop, calls=%fused_computation.959.clone, {FORWARD % (0, "conv/in_proj/dot_general")}
  %fusion.558.remat2 = bf16[24576,2048]{{1,0:T(8,128)(2,1)}} fusion(%copy-done.3, %Arg_1.2), kind=kLoop, calls=%fused_computation.959.clone, {FORWARD % (2, "conv/in_proj/dot_general")}
  %slice-start.24 = ((bf16[8192,2304]{{1,0:T(8,128)(2,1)}}), bf16[2048,2304]{{1,0:T(8,128)(2,1)S(1)}}, s32[]{{:S(2)}}) async-start(%fusion.557), calls=%async_computation.24
  %slice-done.24 = bf16[2048,2304]{{1,0:T(8,128)(2,1)S(1)}} async-done(%slice-start.24)
  %copy.223.remat_compressed = f32[3,8,64]{{1,2,0:T(8,128)}} copy(%fusion.557)
  %copy.12 = f32[8,128]{{0,1:T(8,128)S(1)}} copy(%fusion.557), {OP % "transpose(jvp(LFM2MoE))/layer_1/attn/transpose"}
  %moe.47 = bf16[24576,2048]{{1,0:T(8,128)(2,1)}} custom-call(%fusion.557.remat), custom_call_target="tpu_custom_call", {FORWARD % (4, "moe/pallas_call")}
  ROOT %conditional.1 = f32[8,128]{{1,0:T(8,128)}} conditional(%Arg_1.2, %copy.12), branch_computations={{%branch_1}}
}}
"""
WIDE = 24576 * 2048 * 2


def test_census_of_a_text_in_the_tpu_compilers_rendering():
    census = compiled_step_census(TPU_TEXT)
    # the entry's thirteen and the branch's two; not the fusion's three nor
    # the two that an ``async-start`` wraps (the chip's own rendering)
    assert census["instructions"] == 15
    assert census["remat_clones"] == 3
    assert census["remat_clones_by_module"] == {
        "layer_*/conv/in_proj": 2, "": 1}
    assert census["checkpoint_recomputed"] == 0
    assert census["copies"] == {"copy": 3, "copy-start": 1, "copy-done": 1}
    # the compiler's own: the pair, the compressed clone, the branch's copy
    assert census["copies_without_op_name"] == 4
    assert census["copy_bytes"] == WIDE + 3 * 8 * 64 * 4 + 2 * 8 * 128 * 4
    # the copy pair's destination and done, the slice pair's, ``copy.12``;
    # not the fusion's parameters, and S(2) is another memory
    assert census["fast_memory_values"] == 5
    # the one kernel a ``pallas_call`` lowered to, under its module's name
    assert census["pallas_kernels_by_module"] == {"moe": 1}


def test_census_of_no_text_is_all_zeros():
    census = compiled_step_census("")
    assert census["instructions"] == census["remat_clones"] == 0
    assert census["copies"] == {"copy": 0, "copy-start": 0, "copy-done": 0}
    assert census["fast_memory_values"] == 0
    assert census["pallas_kernels_by_module"] == {}


@pytest.mark.parametrize("op_name,module", [
    ("jit(inner)/chainermn.grad/jvp(LFM2MoE)/layer_4/moe/"
     "chainermn.moe.experts/mul", "layer_*/moe/chainermn.moe.experts"),
    ("jit(inner)/chainermn.grad/transpose(jvp(AfmoeMoE))/layer_12/swa/"
     "gate_proj/dot_general", "layer_*/swa/gate_proj"),
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/rematted_computation/M/"
     "in_proj/dot_general", "M/in_proj"),
    ("jit(inner)/chainermn.update/add", "chainermn.update"),
    ("jit(inner)/chainermn.grad/jvp(TransformerLM)/block_3/qkv/dot_general",
     "block_*/qkv"),
    ("reduce_sum", ""), ("", ""),
])
def test_a_module_is_the_path_between_the_wrappers_and_the_primitive(
        op_name, module):
    assert _module(op_name) == module


class TwoProducts(nn.Module):
    @nn.compact
    def __call__(self, x):
        hidden = jnp.tanh(nn.Dense(64, name="in_proj")(x))
        return nn.Dense(8, name="out_proj")(hidden).sum()


@pytest.mark.parametrize("checkpointed", [True, False])
def test_census_of_a_compiled_program_made_to_rematerialise(checkpointed):
    """``jax.checkpoint`` keeps no activation, so the backward pass runs
    ``in_proj`` and the ``tanh`` again under ``rematted_computation``; the
    same program without it runs nothing twice."""
    model, x = TwoProducts(), jnp.ones((4, 16))
    params = model.init(jax.random.key(0), x)
    forward = jax.checkpoint(model.apply) if checkpointed else model.apply
    text = jax.jit(jax.grad(forward)).lower(params, x).compile().as_text()
    census = compiled_step_census(text)
    assert census["instructions"] > 5
    assert census["remat_clones"] == 0      # XLA's CPU pipeline has no pass
    if checkpointed:
        assert census["checkpoint_recomputed"] >= 2
        modules = census["checkpoint_recomputed_by_module"]
        assert any(key.endswith("in_proj") for key in modules)
        assert not any(key.endswith("out_proj") for key in modules)
        assert sum(modules.values()) == census["checkpoint_recomputed"]
    else:
        assert census["checkpoint_recomputed"] == 0
        assert census["checkpoint_recomputed_by_module"] == {}


def test_census_of_the_lfm2_toy_step_names_what_the_layer_makes_again():
    """PR 47's mechanism engaged, and only where it was put: what
    ``LFM2MoE``'s checkpoints run again in the compiled gradient lies under
    a norm, the short convolution's scope, the rotation or the dense
    layer's module (``silu(gate) * up``), never under a product's module
    (a fusion is named after its root: a product that ran again would show
    as ``in_proj``, ``w1``, ``q_proj``, ...); the one entry without a module
    is the expert layer's own checkpointed remainder."""
    import optax
    from chainermn_tpu.models import lfm2

    sizes = dict(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_dense_layers=1, num_attention_heads=4,
        layer_types=("conv", "full_attention", "conv"), num_experts=3,
        num_key_value_heads=2, num_experts_per_tok=2, first_expert=2)
    model = lfm2.LFM2MoE(lfm2.LFM2Config(**sizes, num_experts_routed=8))
    tokens = jax.random.randint(jax.random.key(0), (2, 24), 0, 96)
    params = model.init(jax.random.key(1), tokens)

    def loss(params, tokens):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(params, tokens)[:, :-1], tokens[:, 1:]).mean()

    text = jax.jit(jax.grad(loss)).lower(params, tokens).compile().as_text()
    census = compiled_step_census(text)
    assert census["checkpoint_recomputed"] > 0
    modules = census["checkpoint_recomputed_by_module"]
    last = {key.rsplit("/", 1)[-1] for key in modules}
    assert last <= {"operator_norm", "ffn_norm", "q_layernorm", "k_layernorm",
                    "chainermn.rope", "chainermn.shortconv", "ffn", ""}
    assert {"operator_norm", "ffn_norm", "chainermn.shortconv"} <= last
    # each under the function of the layer that was checkpointed
    assert {key.split("/")[0] for key in modules if key} == {
        "layer_*.operator", "layer_*.feed_forward", "layer_*.moe_input",
        "attn.in_front"}
    assert "pallas_call" not in text and "tpu_custom_call" not in text


KERNEL = ('  %%%s = %s custom-call(%%p), custom_call_target="%s", '
          'metadata={op_name="jit(inner)/chainermn.grad/%s" '
          'stack_frame_id=1}')
WIDE_Q = "bf16[1,32,8192,128]{3,2,1,0:T(8,128)(2,1)}"


@pytest.mark.parametrize("name,shape,target,op_name,counted_as", [
    # PR 41's kernels, forward and (two results) backward
    ("chainermn.rope.16", WIDE_Q, "tpu_custom_call",
     "jvp(MellumMoE)/layer_0/sliding/chainermn.rope/pallas_call",
     "chainermn.rope"),
    ("chainermn.rope.31", f"({WIDE_Q}, f32[1,16,8,128]{{3,2,1,0}})",
     "tpu_custom_call",
     "transpose(jvp(MellumMoE))/layer_3/full/chainermn.rope/pallas_call",
     "chainermn.rope"),
    ("sliding.9", f"({WIDE_Q}, f32[32,1,8192]{{2,1,0}})", "tpu_custom_call",
     "jvp(MellumMoE)/layer_0/sliding/pallas_call", "sliding"),
    ("block_7.4", WIDE_Q, "tpu_custom_call",
     "transpose(jvp(TransformerLM))/block_7/pallas_call", "block_7"),
    # a name without a serial number is its own key
    ("moe", WIDE_Q, "tpu_custom_call", "jvp(M)/layer_1/moe/pallas_call",
     "moe"),
    # XLA's own grouped product is a tpu_custom_call and no Pallas kernel
    ("ragged-dot-none.3", WIDE_Q, "tpu_custom_call",
     "jvp(M)/layer_1/moe/cond/branch_1_fun/ragged_dot_general", None),
    ("custom-call.7", WIDE_Q, "Sharding", "jvp(M)/layer_1/moe/pallas_call",
     None),
])
def test_pallas_kernels_are_counted_by_the_name_a_trace_shows(
        name, shape, target, op_name, counted_as):
    text = "\n".join([
        "HloModule jit_inner", "",
        f"ENTRY %main.1 (p: {WIDE_Q}) -> {WIDE_Q} {{",
        f"  %p = {WIDE_Q} parameter(0)",
        KERNEL % (name, shape, target, op_name),
        KERNEL % ("moe.47", WIDE_Q, "tpu_custom_call",
                  "jvp(M)/layer_4/moe/pallas_call"),
        f"  ROOT %copy.1 = {WIDE_Q} copy(%p)", "}", ""])
    want = {"moe": 1}
    if counted_as:
        want[counted_as] = want.get(counted_as, 0) + 1
    assert compiled_step_census(text)["pallas_kernels_by_module"] == want
