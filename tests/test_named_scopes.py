"""The train step names its parts from inside: every scope of the naming
rule (docs/observability.md) is in the compiled program's ``op_name``
metadata, whatever the optimizer wrapper, and flax's module scopes are there
beside them (this pins ``flax_profile``).  There is no switch to test: a
scope is metadata of the one program."""

import re

import jax
import jax.numpy as jnp
import optax
import pytest

import chainermn_tpu
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.optimizers import init_opt_state, make_train_step
from chainermn_tpu.parallel.fsdp import fsdp_init, make_fsdp_train_step
from chainermn_tpu.planner import execute_plan
from chainermn_tpu.planner.plans import compressed_two_dimensional
from chainermn_tpu.training.trainer import put_global_batch

OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# optimizer wrapper -> (communicator arguments, create_multi_node_optimizer
# arguments, the scope the gradient's collective must sit under, whether
# anything runs under chainermn.pack)
WRAPPERS = {
    # an all-reduce-only plan reduces the leaves where they lie: with no
    # wire dtype there is no cast, so nothing is left of "pack" (PR 25)
    "plain": ({}, {}, r"chainermn\.plan\.0\.all_reduce", False),
    "double_buffered": ({}, {"double_buffering": True},
                        r"chainermn\.plan\.0\.all_reduce", False),
    # ... and with one, pack is the wire cast
    "bf16_wire": ({"allreduce_grad_dtype": "bfloat16"}, {},
                  r"chainermn\.plan\.0\.all_reduce", True),
    # ZeRO-1 runs no plan: reduce-scatter and gather-back are its exchange,
    # over the packed buffer they shard
    "zero1": ({}, {"zero": True}, None, True),
}


def _compiled_text(comm_args, optimizer_args):
    comm = chainermn_tpu.create_communicator("xla", **comm_args)
    model = TransformerLM(vocab=64, d_model=32, n_layers=2, n_heads=2,
                          max_len=16, attention_impl="xla")
    tokens = jnp.zeros((comm.size, 16), jnp.int32)
    params = comm.bcast_data(model.init(jax.random.key(0), tokens[:1]))
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm, **optimizer_args)
    state = init_opt_state(comm, optimizer, params)

    def loss_fn(p, batch):
        (t,) = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, t)[:, :-1], t[:, 1:]).mean()

    step = make_train_step(comm, loss_fn, optimizer, donate=False)
    batch = put_global_batch(comm, (tokens,))
    return step.lower(params, state, batch).compile().as_text()


@pytest.mark.parametrize("wrapper", sorted(WRAPPERS))
def test_every_scope_is_in_the_compiled_step(wrapper):
    comm_args, optimizer_args, stage, packs = WRAPPERS[wrapper]
    text = _compiled_text(comm_args, optimizer_args)
    names = set(OP_NAME.findall(text))

    def some(pattern):
        return any(re.search(pattern, name) for name in names)

    # forward and backward under chainermn.grad, told apart by transpose(
    assert some(r"/chainermn\.grad/jvp\(")
    assert some(r"/chainermn\.grad/transpose\(jvp\(")
    # flax's module scopes, inside the program's
    assert some(r"/chainermn\.grad/.*\bblock_1/qkv/dot_general")
    assert some(r"/chainermn\.grad/.*\bhead/")
    assert some(r"/chainermn\.allreduce_grad/chainermn\.pack/") == packs
    assert some(r"/chainermn\.allreduce_grad/chainermn\.unpack/")
    assert some(r"/chainermn\.update/")
    assert some(r"/chainermn\.report/")
    # a scope never opens inside another top-level one
    assert not some(r"chainermn\.(grad|update|report)/.*chainermn\.")
    # the gradient's collective is named by the exchange it belongs to
    collectives = [line for line in text.splitlines() if re.search(
        r"= .*\b(all-reduce|reduce-scatter)(-start)?\(", line)]
    under = [OP_NAME.search(line).group(1) for line in collectives
             if OP_NAME.search(line)]
    assert under, collectives
    if stage is None:
        assert some(r"/chainermn\.allreduce_grad/(?!chainermn\.plan)")
        assert any("/chainermn.allreduce_grad/" in name for name in under)
    else:
        assert any(re.search(
            r"/chainermn\.allreduce_grad/" + stage + "/", name)
            for name in under), under


def test_a_leaf_packed_plan_names_its_stages_too():
    """The naive flavor reduces leaf by leaf: each leaf's psum still sits
    under the stage's scope."""
    comm = chainermn_tpu.create_communicator("naive")
    grads = {"w": jnp.ones((comm.size, 4)), "b": jnp.ones((comm.size, 2))}
    text = comm.compiled_hlo(comm.allreduce_grad, grads)
    assert re.search(
        r'op_name="[^"]*/chainermn\.allreduce_grad/'
        r'chainermn\.plan\.0\.all_reduce/', text)


def test_no_argument_or_variable_turns_the_scopes_off():
    """The naming rule has no switch: every ``named_scope`` of the package
    is opened unconditionally, by a plain ``with`` (or the one helper that
    names a plan stage)."""
    import os

    root = os.path.dirname(chainermn_tpu.__file__)
    opened = []
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as handle:
                    opened += [line.strip() for line in handle
                               if "jax.named_scope(" in line]
    assert len(opened) >= 12
    assert all(re.match(r"(with|return) jax\.named_scope\($|"
                        r'with jax\.named_scope\("chainermn\.\w+"\):$', line)
               for line in opened), opened


def _fsdp_text(**init_args):
    comm = chainermn_tpu.create_communicator("naive", intra_size=4)
    params = {"a": jnp.ones((16, 16)), "b": jnp.ones((16,)),
              "c": jnp.ones((16, 8))}
    state, meta = fsdp_init(comm, params, optax.adam(0.01), num_buckets=2,
                            **init_args)
    step = make_fsdp_train_step(
        comm,
        lambda p, b: jnp.mean(
            (jnp.tanh(b[0] @ p["a"] + p["b"]) @ p["c"]) ** 2),
        optax.adam(0.01), meta, donate=False)
    batch = put_global_batch(comm, (jnp.ones((comm.size * 2, 16)),))
    return step.lower(state, batch).compile().as_text()


def _int8_allreduce_grad_text():
    comm = chainermn_tpu.create_communicator("naive", intra_size=4)
    grads = {"w": jnp.ones((comm.size, 3, 5)), "b": jnp.ones((comm.size, 7))}
    state = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (comm.size,) + a.shape),
        comm.init_compression_state(grads, "int8"))
    return comm.compiled_hlo(
        lambda g, s: comm.allreduce_grad(g, compressor="int8", state=s),
        grads, state)


def _per_hop_int8_text():
    comm = chainermn_tpu.create_communicator("naive", intra_size=4)
    plan = compressed_two_dimensional({"name": "int8"})
    return comm.compiled_hlo(lambda g: execute_plan(plan, comm, g),
                             jnp.ones((comm.size, 2048)))


# a bucket's collective, by the instruction it must name
_FSDP_LEGS = [leg for i in (0, 1) for leg in (
    ("all-gather", r"/jvp\(chainermn\.fsdp\.gather\.%d\)/" % i),
    ("reduce-scatter",
     r"/transpose\(jvp\(chainermn\.fsdp\.scatter\.%d\)\)/" % i))]
# program -> (its compiled text, [(instruction or None, op_name pattern)])
SCOPED = {
    "fsdp": (_fsdp_text, _FSDP_LEGS),
    "fsdp_int8": (
        lambda: _fsdp_text(bucket_compressors="int8"),
        _FSDP_LEGS + [(None, r"jvp\(chainermn\.compress\)\)/"),
                      (None, r"jvp\(chainermn\.decompress\)\)/")]),
    "int8_allreduce_grad": (
        _int8_allreduce_grad_text,
        [(None, r"/chainermn\.allreduce_grad/chainermn\.compress/"),
         (None, r"/chainermn\.allreduce_grad/chainermn\.decompress/")]),
    # inside the plan stage's own scope
    "per_hop_int8_plan": (
        _per_hop_int8_text,
        [(None, r"/chainermn\.plan\.1\.all_reduce/chainermn\.compress/"),
         (None, r"/chainermn\.plan\.1\.all_reduce/chainermn\.decompress/"),
         ("all-reduce", r"/chainermn\.plan\.1\.all_reduce/psum")]),
}


@pytest.mark.parametrize("program", sorted(SCOPED))
def test_fsdp_buckets_and_the_quantizer_are_named(program):
    """What the device trace reads where a host-clock emitter used to sit:
    a bucket's all-gather and (in the transpose) its reduce-scatter, and
    the quantizer's two halves around the collective that carries the
    codes."""
    build, wanted = SCOPED[program]
    text = build()
    for kind, pattern in wanted:
        lines = [line for line in text.splitlines() if kind is None
                 or re.search(r"= .*\b%s(-start)?\(" % kind, line)]
        names = [m.group(1) for m in map(OP_NAME.search, lines) if m]
        assert any(re.search(pattern, name) for name in names), (
            kind, pattern, sorted(set(names))[:40])
