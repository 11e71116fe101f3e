"""The main path's Pallas kernels, compiled for a described v5e — no chip.

Interpret-mode tests cannot see what the TPU compiler refuses: a slice not
aligned to the tiling, more VMEM than a kernel may use, a kernel that
cannot be partitioned.  libtpu compiles for a chip that is described and
not attached (on-chip-measurement guide, section 2), so each kernel is
compiled here at the widths the main path runs — about two seconds a case —
and must come out as a ``tpu_custom_call``.  ``jax.default_backend`` is
steered to ``"tpu"`` while tracing so the entries leave interpret mode;
nothing runs, so this says nothing about results or times (``chip_smoke.py``
does, on the chip).  Whole step programs: ``tools/compile_for_chip.py``.
"""

import os
import re
import unittest.mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chainermn_tpu.ops.flash_attention import flash_attention  # noqa: E402
from chainermn_tpu.ops.fused_norm import fused_norm  # noqa: E402
from chainermn_tpu.ops.grouped_matmul import grouped_matmul  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / cannot describe
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


def _flash_loss(q, k, v, segment_ids=None):
    out = flash_attention(q, k, v, causal=True, q_segment_ids=segment_ids,
                          kv_segment_ids=segment_ids)
    return out.astype(jnp.float32).sum()


_flash_grad = jax.grad(_flash_loss, argnums=(0, 1, 2))
_windowed_grad = jax.grad(
    lambda q, k, v: flash_attention(q, k, v, causal=True, window=2048).astype(
        jnp.float32).sum(), argnums=(0, 1, 2))
_one_tile_window_grad = jax.grad(
    lambda q, k, v: flash_attention(q, k, v, causal=True, window=1024).astype(
        jnp.float32).sum(), argnums=(0, 1, 2))


def _norm_grad(x, scale, bias):
    return jax.grad(
        lambda *a: fused_norm(*a)[0].astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(x, scale, bias)


_Q = ((1, 8192, 16, 128), jnp.bfloat16)     # the LM's [B, T, H, D]
_KV_GQA = ((1, 8192, 4, 128), jnp.bfloat16)
# starcoder1b-t2048: four rows of 2048, multi-query
_Q2048 = ((4, 2048, 16, 128), jnp.bfloat16)
_KV2048 = ((4, 2048, 1, 128), jnp.bfloat16)
# starcoder1b-t8192's own multi-query shape
_KV_MQA = ((1, 8192, 1, 128), jnp.bfloat16)
_SEG = ((1, 8192), jnp.int32)
# LFM2-8B-A1B: 32 query / 8 kv heads of 64, three rows
_Q64 = ((3, 8192, 32, 64), jnp.bfloat16)
_KV64 = ((3, 8192, 8, 64), jnp.bfloat16)
# Trinity-Mini: 32 query / 4 kv heads of 128, one row, a window of 2048
_Q32 = ((1, 8192, 32, 128), jnp.bfloat16)
# Kimi-VL-A3B's latent attention: 16 heads, keys of 192 beside values of 128
_QK192 = ((1, 8192, 16, 192), jnp.bfloat16)
# its expert layer: tokens x top-4 rows through 8 held experts of 1792
_ROWS = ((3 * 8192 * 4, 2048), jnp.bfloat16)
_EXPERTS_UP = ((8, 2048, 1792), jnp.bfloat16)
_EXPERTS_DOWN = ((8, 1792, 2048), jnp.bfloat16)
_GROUPS = ((8,), jnp.int32)
# Mellum2-12B-A2.5B: a window of 1024 (one tile) at Trinity-Mini's heads; its
# expert layer's bound of 24,576 rows through 16 held experts, 2304 x 896
_ROWS_2304 = ((24576, 2304), jnp.bfloat16)
_EXPERTS_UP_896 = ((16, 2304, 896), jnp.bfloat16)
_EXPERTS_DOWN_896 = ((16, 896, 2304), jnp.bfloat16)
_GROUPS_16 = ((16,), jnp.int32)


def _expert_grad(rows, up, down, sizes):
    """Two grouped products and their VJPs: forward, dlhs and drhs each."""
    return jax.grad(
        lambda r, u, d: grouped_matmul(
            grouped_matmul(r, u, sizes), d, sizes).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(rows, up, down)


def _norm_args(shape):
    return [(shape, jnp.bfloat16), ((shape[-1],), jnp.float32),
            ((shape[-1],), jnp.float32)]


# name -> (function, [(shape, dtype)], tpu_custom_calls expected at least)
CASES = {
    "flash_fwd": (lambda q, k, v: flash_attention(q, k, v, causal=True),
                  [_Q, _Q, _Q], 1),
    "flash_fwd_bwd": (_flash_grad, [_Q, _Q, _Q], 3),
    "flash_gqa_fwd_bwd": (_flash_grad, [_Q, _KV_GQA, _KV_GQA], 3),
    "flash_mqa_fwd_bwd": (_flash_grad, [_Q, _KV_MQA, _KV_MQA], 3),
    "flash_mqa_t2048_batch4_fwd_bwd": (
        _flash_grad, [_Q2048, _KV2048, _KV2048], 3),
    "flash_segment_ids_fwd_bwd": (_flash_grad, [_Q, _Q, _Q, _SEG], 3),
    "flash_gqa_head_dim_64_fwd_bwd": (_flash_grad, [_Q64, _KV64, _KV64], 3),
    "flash_gqa_window_fwd_bwd": (_windowed_grad, [_Q32, _KV_GQA, _KV_GQA], 3),
    "flash_keys_of_192_values_of_128_fwd_bwd": (
        _flash_grad, [_QK192, _QK192, _Q], 3),
    "flash_gqa_window_of_one_tile_fwd_bwd": (
        _one_tile_window_grad, [_Q32, _KV_GQA, _KV_GQA], 3),
    "grouped_matmul_2304_by_896_fwd_bwd": (
        _expert_grad,
        [_ROWS_2304, _EXPERTS_UP_896, _EXPERTS_DOWN_896, _GROUPS_16], 5),
    # 53 x 128: no divisor to tile by and too long to hold whole, so equal
    # tiles with a ragged last one, as K (masked), as N and in drhs
    "grouped_matmul_6784_in_ragged_tiles_fwd_bwd": (
        _expert_grad,
        [((1024, 6784), jnp.bfloat16), ((2, 6784, 1024), jnp.bfloat16),
         ((2, 1024, 6784), jnp.bfloat16), ((2,), jnp.int32)], 5),
    "grouped_matmul_fwd": (grouped_matmul, [_ROWS, _EXPERTS_UP, _GROUPS], 1),
    "grouped_matmul_fwd_bwd": (
        # the second forward is dead under a sum: 1 + 2 x (dlhs, drhs)
        _expert_grad, [_ROWS, _EXPERTS_UP, _EXPERTS_DOWN, _GROUPS], 5),
    "fused_norm_fwd_stage1": (
        lambda *a: fused_norm(*a)[0], _norm_args((256, 112, 112, 64)), 2),
    "fused_norm_fwd_bwd_stage1": (
        _norm_grad, _norm_args((256, 112, 112, 64)), 3),
    "fused_norm_fwd_stage4": (
        lambda *a: fused_norm(*a)[0], _norm_args((256, 7, 7, 2048)), 2),
    "fused_norm_fwd_bwd_stage4": (
        _norm_grad, _norm_args((256, 7, 7, 2048)), 3),
}


def _compile(fn, args):
    with unittest.mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = jax.jit(fn).lower(*args)
    return lowered.compile().as_text()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_described_v5e(topo, name):
    fn, specs, want = CASES[name]
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    text = _compile(fn, args)
    assert text.count("tpu_custom_call") >= want, (
        f"{name}: {text.count('tpu_custom_call')} tpu_custom_call in the "
        f"compiled program, expected at least {want}")


def test_grouped_matmul_compiles_inside_shard_map(topo):
    """Where the train step runs it: inside ``shard_map`` with varying
    axes checked, on device-varying rows, weights and group sizes (the
    kernels type their results as their operands are)."""
    mesh = Mesh(topo.devices[:1], ("d",))
    shapes = [jax.ShapeDtypeStruct((1,) + shape, dtype,
                                   sharding=NamedSharding(mesh, P("d")))
              for shape, dtype in (_ROWS, _EXPERTS_UP, _EXPERTS_DOWN,
                                   _GROUPS)]

    def step(*stacked):
        return jax.shard_map(
            lambda *a: jax.tree.map(
                lambda g: g[None], _expert_grad(*(x[0] for x in a))),
            mesh=mesh, in_specs=P("d"), out_specs=P("d"))(*stacked)

    assert _compile(step, shapes).count("tpu_custom_call") >= 5


def _small_tree(leaf):
    return {"emb": leaf(4096, 512), "up": leaf(512, 2048),
            "down": leaf(2048, 512), "qkv": leaf(512, 640),
            "ln": [leaf(512) for _ in range(6)], "bias": leaf(2048)}


def _resnet50_tree(leaf):
    """ResNet-50's real gradient tree: 161 leaves, 25.6 M elements."""
    from chainermn_tpu.models import ResNet50

    params = jax.eval_shape(
        ResNet50().init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32))["params"]
    tree = jax.tree.map(lambda p: leaf(*p.shape), params)
    sizes = [p.size for p in jax.tree.leaves(params)]
    assert len(sizes) == 161 and sum(sizes) == 25_557_032
    return tree


def _exchange_program(topo, body_name, make_tree=_small_tree, chips=4,
                      held=True):
    """A double-buffered step's exchange: the ``pending`` gradients of
    ``chips`` chips through the bf16-wire ``allreduce_grad`` into an
    SGD-momentum update in float32.  ``pending`` is as the optimizer holds
    it since PR 46, matrices in the wire's bfloat16 and vectors in float32,
    and the means come back like the momentum; ``held=False`` (and the
    legacy body, which knows nothing else) hands the exchange float32
    gradients, as the optimizer did before and as an exchange of fresh
    gradients still does."""
    import chainermn_tpu
    from chainermn_tpu.parallel.topology import init_topology

    comm = chainermn_tpu.create_communicator(
        "xla", topology=init_topology(devices=list(topo.devices)[:chips]),
        allreduce_grad_dtype="bfloat16")
    assert comm.size == chips
    stacked = NamedSharding(comm.mesh, P(comm.data_axes))

    held = held and body_name == "allreduce_grad"

    def tree(dtype_of):
        return make_tree(lambda *shape: jax.ShapeDtypeStruct(
            (comm.size,) + shape, dtype_of(shape), sharding=stacked))

    def step(pending, momentum):
        mean = (comm.allreduce_grad(pending, like=momentum) if held
                else getattr(comm, body_name)(pending))
        return jax.tree.map(lambda m, g: 0.9 * m + g, momentum, mean)

    pending = tree(lambda shape: jnp.bfloat16 if held and len(shape) > 1
                   else jnp.float32)
    return comm._spmd_program(step).lower(
        (pending, tree(lambda shape: jnp.float32))).compile().as_text()


def _instructions(text, *ops):
    pattern = re.compile(r"= .*\b(%s)\(" % "|".join(map(re.escape, ops)))
    return [line for line in text.splitlines() if pattern.search(line)]


def _wire_all_reduces(text):
    """How many all-reduces a compiled gradient exchange holds, each over
    the bf16 wire, as ``all_reduce_overlap_census`` counts them: a blocking
    one once, and an asynchronous one once however many computations of its
    start - steps - done chain print it."""
    from chainermn_tpu.analysis.hlo import all_reduce_overlap_census

    reduced = [line for line in _instructions(
        text, "all-reduce", "all-reduce-start", "all-reduce-done")
        if "chainermn.report" not in line]      # the loss, a float32 scalar
    assert all("bf16[" in line.split(" all-reduce")[0]
               for line in reduced), reduced
    return all_reduce_overlap_census(text)


@pytest.mark.parametrize("body,packs", [
    ("allreduce_grad", False),
    # the flat reference that stays: shows that the test sees a buffer
    ("_legacy_allreduce_grad_traced", True)])
def test_four_chip_exchange_builds_no_buffer(topo, body, packs):
    """Compiled for four described chips, the gradient mean of an
    all-reduce-only plan holds no ``dynamic-update-slice`` (what gathering
    the leaves into one buffer becomes) and no relayout ``copy`` named
    under ``chainermn.pack``: on the chip those cost more than the
    all-reduce they served (PERF.md, PR 25)."""
    text = _exchange_program(topo, body)
    gathers = _instructions(text, "dynamic-update-slice")
    pack_copies = [line for line in _instructions(text, "copy")
                   if "chainermn.pack" in line]
    assert bool(gathers or pack_copies) == packs, (gathers, pack_copies)
    assert "tpu_custom_call" not in text
    # the combiner, not a buffer, merges the leaves: fewer operations than
    # leaves (every small vector rides with a matrix), and with XLA's own
    # options (``_spmd_program`` is not a train step) every one blocks
    census = _wire_all_reduces(text)
    assert 0 < census["synchronous"] < 11 and not census["asynchronous"]


@pytest.mark.parametrize("held,casts", [(True, 7), (False, 11)])
def test_four_chip_exchange_casts_only_what_is_not_held_in_the_wire_dtype(
        topo, held, casts):
    """``all_reduce_overlap_census``'s ``wire_casts`` on the compiled
    exchange: handed float32 gradients it casts each of the 11 leaves before
    the collective; handed ``pending`` as the optimizer holds it, only the 7
    vectors (which keep float32), and no matrix."""
    from chainermn_tpu.analysis.hlo import all_reduce_overlap_census

    text = _exchange_program(topo, "allreduce_grad", held=held)
    assert all_reduce_overlap_census(text)["wire_casts"] == casts


@pytest.mark.parametrize("chips", [4, 1])
def test_resnet50_exchange_is_xla_alone(topo, chips):
    """The exchange of the ``resnet50-b256`` cell's model over its real
    gradient tree.  Four described chips: the casts are XLA's fusions (no
    ``tpu_custom_call``), no leaf is gathered into a buffer, and the
    combiner leaves fewer all-reduces than leaves.  One described chip,
    the cell itself: no collective at all."""
    text = _exchange_program(topo, "allreduce_grad", _resnet50_tree, chips)
    assert "tpu_custom_call" not in text
    assert not _instructions(text, "dynamic-update-slice")
    census = _wire_all_reduces(text)
    reduced = census["synchronous"] + census["asynchronous"]
    if chips == 1:
        assert not reduced and not _instructions(
            text, "all-gather", "reduce-scatter", "collective-permute",
            "all-to-all"), census
    else:
        assert 0 < reduced < 161, census


_STEP_SHAPES = {"in": (2048, 2048), "up": (2048, 4096), "down": (4096, 2048),
                "bias": (2048,), "gain": (2048,)}
_STEP_PARAMETERS = 2048 * 2048 + 2 * 2048 * 4096 + 2 * 2048


def _double_buffered_step(topo, chips):
    """``make_train_step`` over the double-buffered optimizer and the bf16
    wire, as every benchmark cell builds it, on ``chips`` described chips: a
    three-matrix model whose matrices (8 and 16 MB on the wire) lie over the
    combiner threshold of ``exchange_compiler_options`` and whose two
    vectors lie under it.  The state's shapes and dtypes are the
    optimizer's own (``init``), stacked as ``init_opt_state`` stacks them.
    Returns the communicator, the keywords ``jax.jit`` was called with and
    the compiled step."""
    import optax

    import chainermn_tpu
    from chainermn_tpu.optimizers import make_train_step
    from chainermn_tpu.parallel.topology import init_topology

    comm = chainermn_tpu.create_communicator(
        "xla", topology=init_topology(devices=list(topo.devices)[:chips]),
        allreduce_grad_dtype="bfloat16")
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm, double_buffering=True)

    def loss_fn(p, batch):
        (x,) = batch
        hidden = jnp.tanh(x @ p["in"] + p["bias"])
        return jnp.mean((hidden @ p["up"] @ p["down"] * p["gain"]) ** 2)

    replicated = NamedSharding(comm.mesh, P())
    stacked = NamedSharding(comm.mesh, P(comm.data_axes))
    state = jax.eval_shape(optimizer.init, {
        k: jax.ShapeDtypeStruct(v, jnp.float32)
        for k, v in _STEP_SHAPES.items()})
    on_every_chip = lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=replicated)
    params = {k: jax.ShapeDtypeStruct(v, jnp.float32, sharding=replicated)
              for k, v in _STEP_SHAPES.items()}
    opt_state = state._replace(
        inner=jax.tree.map(on_every_chip, state.inner),
        pending=jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            (chips,) + a.shape, a.dtype, sharding=stacked), state.pending),
        step=on_every_chip(state.step))
    batch = (jax.ShapeDtypeStruct((8 * chips, 2048), jnp.float32,
                                  sharding=stacked),)
    with unittest.mock.patch.object(jax, "jit", wraps=jax.jit) as jit:
        step = make_train_step(comm, loss_fn, optimizer)
    (_, keywords), = jit.call_args_list
    return comm, keywords, step.lower(params, opt_state, batch).compile()


def _assert_ten_bytes_a_parameter(compiled):
    """The step's arguments on a chip: the parameters and the momentum in
    float32 and ``pending`` in the wire's bfloat16, 10 bytes a parameter
    (12 with a float32 ``pending``: 2 bytes a parameter more, 42 MB here),
    beside the chip's 8 rows of the batch; the counter and the two vectors
    of ``pending``, which keep float32, are the few KB over."""
    held = compiled.memory_analysis().argument_size_in_bytes
    over = held - (10 * _STEP_PARAMETERS + 8 * 2048 * 4)
    assert 0 <= over < 32 * 1024, (held, over)


def _assert_nothing_casts_pending_for_the_wire(text):
    """Everything that reads a MATRIX of the step's ``pending`` argument (the
    ENTRY computation's parameters named ``opt_state.pending[...]``: the
    three matrices bfloat16, the two vectors float32), followed through a
    ``bitcast`` or a ``copy`` into the fusion that takes it, holds no
    ``convert`` of a matrix: the buffer is what the all-reduce sends, and
    the census counts no wire cast but the two vectors' (one fusion)."""
    from chainermn_tpu.analysis.hlo import all_reduce_overlap_census

    entry = text[text.index("\nENTRY "):].splitlines()
    pending, vectors = set(), 0
    for line in entry:
        held = re.match(r"\s+%([\w.\-]+) = (\w+)\[([\d,]+)\].* parameter\("
                        r"\d+\).*op_name=\"opt_state\.pending", line)
        if held and held.group(3).count(",") == 2:      # [1, rows, columns]
            assert held.group(2) == "bf16", line
            pending.add(held.group(1))
        elif held:
            assert held.group(2) == "f32", line
            vectors += 1
    assert len(pending) == 3 and vectors == 2, (pending, vectors)
    readers = set()
    for _ in range(2):                      # a bitcast of it is still it
        for line in entry:
            made = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (.*)", line)
            if made and made.group(1) not in pending and pending & set(
                    re.findall(r"%([\w.\-]+)", made.group(2))):
                if re.search(r" (bitcast|copy)\(", line):
                    pending.add(made.group(1))
                else:
                    readers.add(line)
    assert readers
    for line in readers:
        assert " convert(" not in line, line
        called = re.search(r"calls=%([\w.\-]+)", line)
        body = re.search(r"^%" + re.escape(called.group(1)) + r" \(.*?^\}",
                         text, re.M | re.S).group(0) if called else ""
        assert not re.search(
            r"= \w+\[(\d+,)*\d{3,},\d{3,}\]\S* convert\(", body), line
    assert 0 < all_reduce_overlap_census(text)["wire_casts"] <= vectors


def test_four_chip_double_buffered_exchange_compiles_asynchronous(topo):
    """On four described chips the step is jitted with the communicator's
    options and the exchange of ``pending`` (which nothing the step computes
    feeds) compiles to asynchronous all-reduces: each matrix alone, as a
    start - steps - done chain of fusions that the scheduler spreads over
    the step; the two vectors ride together in one blocking all-reduce of
    8 KB and the loss in another.  On the dp4 cell's real step the same
    options give 43 asynchronous all-reduces carrying 99.96 % of the wire
    bytes (PERF.md, PR 29), with ``pending`` held in the wire's dtype as
    before (PR 46): nothing casts a matrix of it on its way to the
    all-reduce, and the optimizer's select on the counter is the fusion each
    chain starts in (without it the all-reduce that reads the step's
    argument itself stays blocking: the first matrix here, 11 of 43
    there)."""
    comm, keywords, compiled = _double_buffered_step(topo, 4)
    text = compiled.as_text()
    assert keywords["compiler_options"] == comm.exchange_compiler_options()
    assert keywords["compiler_options"]["xla_enable_async_all_reduce"]
    census = _wire_all_reduces(text)
    assert census["asynchronous"] == 3, census
    assert census["synchronous"] == 2, census
    assert census["asynchronous_bytes"] == 2 * (2048 * 2048 + 2 * 2048 * 4096)
    assert census["asynchronous_byte_share"] > 0.999, census
    assert text.count("calls=%async_collective_fusion") >= 3
    _assert_nothing_casts_pending_for_the_wire(text)
    _assert_ten_bytes_a_parameter(compiled)


def test_one_chip_double_buffered_step_keeps_xla_defaults(topo):
    """The same step on ONE described chip, as eight of the nine benchmark
    cells run it: nothing to exchange, so ``jax.jit`` gets no compile
    options (the compiled program, and its key in the persistent cache, stay
    what they were before PR 29) and the text holds no collective in either
    form.  Its state is 10 bytes a parameter."""
    comm, keywords, compiled = _double_buffered_step(topo, 1)
    text = compiled.as_text()
    assert comm.exchange_compiler_options() is None
    assert keywords["compiler_options"] is None
    census = _wire_all_reduces(text)
    assert not census["synchronous"] and not census["asynchronous"], census
    assert "async_collective_fusion" not in text
    assert "async-collective-start" not in text
    _assert_ten_bytes_a_parameter(compiled)


@pytest.mark.parametrize("cell,pairs,rows", [
    ("lfm2-8b-a1b-ep4share-t8192", 98304, 36864),
    ("trinity-mini-ep8share-t8192", 65536, 12288),
    ("kimi-vl-a3b-ep8share-t8192", 49152, 9216)])
def test_moe_layer_main_pass_kernels_keep_their_names(topo, cell, pairs, rows):
    """The expert layer at a benchmark cell's sizes, forward and backward,
    alone: a quarter of the experts held (lfm2) and an eighth (Trinity-Mini:
    the even share and a half, 12,288 of 65,536 rows; Kimi-VL-A3B: 9,216 of
    49,152 through experts 1,408 wide, a dimension the grouped kernels hold
    whole, so that this compile is also the check that their blocks fit the
    VMEM they ask for, two shared experts beside).  The trace's readers
    find the grouped-matmul kernels by name (``moe.<k>``:
    ``chipbench/layer_metrics/moe_gmm_ms.py::is_gmm``), and a Pallas call is
    named after the innermost entry of its name stack: the main pass's nine
    must stay directly under the module, whatever the remainder (under its
    ``cond``, its ``checkpoint`` or its loops) runs."""
    import flax.linen as nn

    from chainermn_tpu.models.afmoe import AfmoeConfig
    from chainermn_tpu.models.deepseek_v3 import DeepseekV3Config
    from chainermn_tpu.models.lfm2 import LFM2Config, SparseMoE
    from chipbench import reduce_trace, spec
    from chipbench.layer_metrics.moe_gmm_ms import is_gmm

    sizes = spec.resolve(cell).sizes
    config = {"lfm2": LFM2Config, "trinity": AfmoeConfig,
              "kimi": DeepseekV3Config}[cell.split("-")[0]].from_dict(
        sizes, num_experts_routed=sizes["num_experts_published"],
        dtype=jnp.dtype(sizes["compute_dtype"]))
    assert config.moe_matmul_impl == "pallas"

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, u):
            return SparseMoE(config, name="moe")(u)[0]

    one_chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                             sharding=one_chip)
    tokens = jax.ShapeDtypeStruct(
        (sizes["batch_per_chip"], sizes["seq_len"], config.hidden_size),
        config.dtype)
    params = jax.eval_shape(
        Layer().init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 128, config.hidden_size), config.dtype))
    grad = jax.value_and_grad(lambda p, u: Layer().apply(p, u).astype(
        jnp.float32).sum(), argnums=(0, 1))
    text = _compile(grad, jax.tree.map(on_chip, (params, tokens)))
    kernels = [reduce_trace.short_name(line.strip().removeprefix("ROOT "))
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    main_pass = [name for name in kernels if is_gmm(name)]
    # gate, up and down, each forward, dlhs and drhs, over the bound's rows
    assert pairs == (tokens.shape[0] * tokens.shape[1]
                     * config.num_experts_per_tok)
    assert len(main_pass) == 9, kernels
    assert sum(f"[{rows}," in name for name in main_pass) == 6, main_pass
    # the remainder takes XLA's own grouped product, ``W1`` and ``W3`` as
    # one (``SparseMoE``'s ``remainder_fn``: no Pallas kernel to trace, and
    # as few kernels to load as the mathematics allows): two in the forward,
    # two recomputed and four derivatives in the backward, kernels the
    # compiler makes and names itself, over all the other rows under a
    # ``cond`` (lfm2) or a chunk's 2,048 under the loops (Trinity-Mini)
    remainder = [name for name in kernels if not is_gmm(name)]
    assert all(name.startswith("ragged-dot") for name in remainder), kernels
    assert sum(name.startswith("ragged-dot-none")
               for name in remainder) == 8, remainder


_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_FLOAT32 = re.compile(r" = f32\[([0-9,]+)\]")


@pytest.mark.parametrize("cell,two_layers,kernels", [
    ("mellum2-ep4share-t8192",
     dict(layer_types=["sliding_attention", "full_attention"],
          mlp_layer_types=["sparse"] * 2), 8),
    # one layer that rotates (and is dense), one ``nope`` that does not
    ("trinity-mini-ep8share-t8192",
     dict(layer_types=["sliding_attention", "full_attention"]), 8),
    # heads of 64: the plain form, no such kernel
    ("lfm2-8b-a1b-ep4share-t8192",
     dict(layer_types=["conv", "full_attention"]), 0)])
def test_qk_norm_and_rotation_kernels_are_read_as_norm_rope(
        topo, cell, two_layers, kernels):
    """Two layers of a MoE cell's model at the cell's widths, loss and
    gradient, with the Pallas kernels on.  Where a head fills the lanes
    (mellum, Trinity-Mini) QK-norm and rotation are ONE kernel a pass and a
    tensor (q and k, forward and backward: four a layer, a layer that does
    not rotate too), named ``chainermn.rope.<k>`` after the scope they are
    called under: the benchmark's account of a step (``chipbench/parts.py``)
    puts each in ``norm_rope_ms``, no flash reader takes one for its own,
    nothing of q's size is left in float32 under ``chainermn.rope`` or a
    QK-norm module, and ``compiled_step_census`` counts them; lfm2's heads
    of 64 keep the plain form and the census reads none."""
    from chainermn_tpu.analysis import compiled_step_census
    from chipbench import parts, reduce_trace, spec

    found = spec.resolve(cell)
    sizes = dict(found.sizes, vocab_size=4096, **two_layers)
    assert sizes["attention_impl"] == "flash"
    one_chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                             sharding=one_chip)
    tokens = jax.ShapeDtypeStruct(
        (sizes["batch_per_chip"], sizes["seq_len"]), jnp.int32)
    grad = jax.value_and_grad(found.family.loss_fn(sizes))
    text = _compile(grad, jax.tree.map(
        on_chip, (found.family.param_shapes(sizes), (tokens,))))
    lines = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()]
    named = {reduce_trace.short_name(line): _OP_NAME.search(line).group(1)
             for line in lines
             if 'custom_call_target="tpu_custom_call"' in line}
    new = {name: path for name, path in named.items()
           if name.startswith("chainermn.rope.")}
    assert len(new) == kernels, sorted(named)
    flash_readers = [is_kernel for is_kernel, flash, _
                     in parts.kernel_readers().values() if flash]
    for name, path in new.items():
        assert parts.part_of(name, path) == "norm_rope_ms", (name, path)
        assert not any(is_flash(name) for is_flash in flash_readers), name
    census = compiled_step_census(text)["pallas_kernels_by_module"]
    assert census.get("chainermn.rope", 0) == kernels, census
    # the flash kernels keep their names and their count beside them
    assert sum(any(is_flash(name) for is_flash in flash_readers)
               for name in named) == 3 * (
        len([kind for kind in sizes["layer_types"] if kind != "conv"]))
    if not kernels:
        return
    q_elements = (sizes["batch_per_chip"] * sizes["seq_len"]
                  * sizes["num_attention_heads"] * sizes["head_dim"])
    for line in lines:
        wide, path = _FLOAT32.search(line), _OP_NAME.search(line)
        if wide and path and re.search(r"chainermn\.rope|[qk]_norm",
                                       path.group(1)):
            count = 1
            for dim in wide.group(1).split(","):
                count *= int(dim)
            assert count < q_elements, line[:300]
