"""The packed flat buffer is built only by plans that need one (ISSUE 25).

A gradient mean whose every stage is an all-reduce is elementwise in every
leaf, so the plan compiler reduces the leaves where they lie: cast to the
wire dtype, ``psum``, cast back, scale.  Pinned here:

1. **Parity** — the leaf-wise lowering equals the flat reference that stays
   (``_legacy_allreduce_grad_traced``: ``_packing.pack`` -> one ``psum`` a
   buffer -> ``_packing.unpack``) to the bit on the 8-device CPU mesh, for
   every wire dtype, on awkward trees; ``NoCompression(wire)`` is the
   ``allreduce_grad_dtype`` program by construction; the 1/size scale is
   applied after the cast back.
2. **Structure** — ``plan_needs_buffer`` over the plan zoo, and what the
   lowered programs hold (no ``concatenate`` without a buffer, one with).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chainermn_tpu
from chainermn_tpu.compression import NoCompression
from chainermn_tpu.parallel.topology import init_topology
from chainermn_tpu.planner import (
    FLAVOR_NAMES,
    Plan,
    PlanTopology,
    Stage,
    StageGroup,
    candidate_plans,
    execute_plan,
    flavor_plan,
    plan_needs_buffer,
    striped_plan,
)
from chainermn_tpu.planner.plans import compressed_two_dimensional

TOPO_2D = PlanTopology(axes=(("inter", 2), ("intra", 4)))
TOPO_1D = PlanTopology(axes=(("data", 8),))
INT8 = {"name": "int8", "stochastic": False}
WIRES = [None, "bfloat16", "float16"]


def _comm(name, **kwargs):
    return chainermn_tpu.create_communicator(name, intra_size=4, **kwargs)


def _all_reduce_plan(name, wire):
    return Plan(name=name, packing="flat", wire_dtype=wire,
                stages=(Stage(op="all-reduce", scope="all"),))


# ---------------------------------------------------------------------------
# Parity with the flat reference
# ---------------------------------------------------------------------------

def _tree(kind, n):
    """Per-rank stacked gradients ([n, ...] leaves) of random values, so
    that equality to the bit means the same sums of the same elements."""
    rng = np.random.RandomState(25)

    def f32(*shape):
        return jnp.asarray(rng.randn(n, *shape).astype(np.float32))

    if kind == "empty_tree":
        return {}
    if kind == "mixed_dtypes":
        return {"w": f32(5, 3),
                "h": f32(7).astype(jnp.bfloat16),
                "q": f32(2, 2).astype(jnp.float16),
                "v": f32(4)}
    if kind == "scalar_and_zero_size":
        return {"s": f32(), "z": jnp.zeros((n, 0, 3), jnp.float32),
                "w": f32(6)}
    if kind == "many_tiny_leaves":
        return {"bn": [f32(2) for _ in range(40)], "w": f32(33, 9)}
    raise AssertionError(kind)


TREES = ["empty_tree", "mixed_dtypes", "scalar_and_zero_size",
         "many_tiny_leaves"]


def _assert_same_bits(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)))


@pytest.mark.parametrize("tree", TREES)
@pytest.mark.parametrize("flavor", ["xla", "flat"])
@pytest.mark.parametrize("wire", WIRES)
def test_leafwise_lowering_equals_the_flat_reference(devices, wire, flavor,
                                                     tree):
    """``xla`` runs its own plan at its wire dtype; ``flat`` (which takes no
    wire dtype as a flavor) runs the tuned ``flat_<dtype>`` plan a plan
    table would hand it.  The reference is the packed program that stays."""
    reference = _comm("xla", allreduce_grad_dtype=wire)
    grads = _tree(tree, reference.size)
    want = reference.run_spmd(
        lambda g: reference._legacy_allreduce_grad_traced(g), grads)
    if flavor == "xla":
        comm = reference
        got = comm.run_spmd(lambda g: comm.allreduce_grad(g), grads)
    else:
        comm = _comm("flat")
        plan = comm.plan() if wire is None \
            else _all_reduce_plan(f"flat_{wire}", wire)
        assert not plan_needs_buffer(plan, comm.plan_topology())
        got = comm.run_spmd(lambda g: execute_plan(plan, comm, g), grads)
    _assert_same_bits(got, want)


def _lowered(comm, f, grads):
    return comm._spmd_program(f).lower((grads,)).as_text()


@pytest.mark.parametrize("flavor", ["xla", "naive", "hierarchical", "flat"])
@pytest.mark.parametrize("wire", ["bfloat16", "float16"])
def test_nocompression_is_the_dtype_knob_program(devices, wire, flavor):
    """``allreduce_grad(compressor=NoCompression(wire))`` executes the xla
    flavor's plan at that wire dtype through the one compiler, on any
    flavor: the SAME lowered program as the ``allreduce_grad_dtype`` knob,
    not a second lowering that is kept equal by hand."""
    knob = _comm("xla", allreduce_grad_dtype=wire)
    plain = _comm(flavor)
    grads = _tree("mixed_dtypes", knob.size)
    codec = NoCompression(wire_dtype=wire)
    assert _lowered(knob, lambda g: knob.allreduce_grad(g), grads) == \
        _lowered(plain, lambda g: plain.allreduce_grad(g, compressor=codec),
                 grads)
    _assert_same_bits(
        plain.run_spmd(lambda g: plain.allreduce_grad(g, compressor=codec),
                       grads),
        knob.run_spmd(lambda g: knob.allreduce_grad(g), grads))


@pytest.mark.parametrize("wire", ["bfloat16", "float16"])
def test_scale_is_applied_after_the_cast_back(devices, wire):
    """``test_unpack_scale_applied_after_cast``'s property for the leaf-wise
    path: the 1/size multiply runs in the leaf's own dtype on the raw
    reduced wire values.  Over THREE devices (1/3 is not a power of two),
    with every rank but the first sending zeros, so the wire sum is exact."""
    comm = chainermn_tpu.create_communicator(
        "xla", topology=init_topology(devices=jax.devices()[:3]),
        allreduce_grad_dtype=wire)
    assert comm.size == 3
    vals = np.asarray([1.0, 2.0, 3.141592, 1e-3, 255.0], np.float32)
    stacked = np.zeros((3, vals.size), np.float32)
    stacked[0] = vals
    out = comm.run_spmd(lambda g: comm.allreduce_grad(g),
                        {"w": jnp.asarray(stacked)})["w"]
    assert out.dtype == jnp.float32
    on_wire = jnp.asarray(vals).astype(wire)
    expect = np.asarray(on_wire.astype(jnp.float32)) * np.float32(1.0 / 3.0)
    for row in np.asarray(out):
        np.testing.assert_array_equal(row, expect)
    wrong = np.asarray((on_wire * jnp.asarray(1.0 / 3.0, on_wire.dtype))
                       .astype(jnp.float32))
    assert not np.array_equal(wrong, expect)


# ---------------------------------------------------------------------------
# Structure: who needs the buffer, and what the lowered program holds
# ---------------------------------------------------------------------------

def _candidate(name):
    return next(p for p in candidate_plans(TOPO_2D) if p.name == name)


# case -> (plan factory, topology, needs the buffer)
NEEDS_BUFFER = {
    # the fixed flavors: only the 2-D decomposition shards an index space
    **{flavor: ((lambda f=flavor: flavor_plan(f)), TOPO_2D,
                flavor == "two_dimensional") for flavor in FLAVOR_NAMES},
    "xla_bfloat16": (lambda: flavor_plan("xla", wire_dtype="bfloat16"),
                     TOPO_2D, False),
    # a tuned plan a plan table hands the auto communicator
    "table_flat_bfloat16": (lambda: _candidate("flat_bfloat16"), TOPO_2D,
                            False),
    "table_two_dimensional_bfloat16": (
        lambda: _candidate("two_dimensional_bfloat16"), TOPO_2D, True),
    # per-stage wire casts and identity codecs are still elementwise
    "stage_wires": (lambda: Plan(name="hier_wires", packing="flat", stages=(
        Stage(op="all-reduce", scope="intra"),
        Stage(op="all-reduce", scope="inter", wire_dtype="bfloat16"))),
        TOPO_2D, False),
    "identity_codec": (lambda: Plan(name="ident", packing="flat", stages=(
        Stage(op="all-reduce", scope="all",
              compression={"name": "none", "wire_dtype": "bfloat16"}),)),
        TOPO_2D, False),
    # stripes: ratio boundaries are offsets into the buffer
    "striped_r60": (lambda: striped_plan(0.6), TOPO_2D, True),
    "striped_r60_int8": (lambda: striped_plan(0.6, dcn_comp=INT8), TOPO_2D,
                         True),
    "two_all_reduce_stripes": (lambda: Plan(name="ar_stripes", groups=(
        StageGroup(stages=(Stage(op="all-reduce", scope="all"),), ratio=0.5),
        StageGroup(stages=(Stage(op="all-reduce", scope="all"),),
                   ratio=0.5))), TOPO_2D, True),
    # ... but ONE ratio-1.0 group is a plain chain
    "single_all_reduce_group": (lambda: Plan(name="one_group", groups=(
        StageGroup(stages=(Stage(op="all-reduce", scope="all"),),
                   ratio=1.0),)), TOPO_2D, False),
    "single_two_dimensional_group": (lambda: striped_plan(1.0), TOPO_2D,
                                     True),
    # a quantizer's error-feedback state maps onto the buffer
    "int8_dcn_hop": (lambda: compressed_two_dimensional(INT8), TOPO_2D,
                     True),
    "int8_all_reduce_only": (lambda: Plan(name="q", packing="flat", stages=(
        Stage(op="all-reduce", scope="intra"),
        Stage(op="all-reduce", scope="inter", compression=INT8))), TOPO_2D,
        True),
    # ... unless its scope resolves to no axes: the compiler emits no hop
    "int8_hop_not_emitted": (lambda: Plan(name="q", packing="flat", stages=(
        Stage(op="all-reduce", scope="intra"),
        Stage(op="all-reduce", scope="inter", compression=INT8))), TOPO_1D,
        False),
}


@pytest.mark.parametrize("case", sorted(NEEDS_BUFFER))
def test_plan_needs_buffer(case):
    make_plan, topology, want = NEEDS_BUFFER[case]
    assert plan_needs_buffer(make_plan(), topology) is want


def _three_leaves(n):
    return {"w": jnp.ones((n, 6, 4)), "b": jnp.ones((n, 5)),
            "g": jnp.ones((n, 3))}


@pytest.mark.parametrize("flavor", ["xla", "xla_bfloat16", "flat",
                                    "non_cuda_aware", "pure_nccl"])
def test_an_all_reduce_only_flavor_lowers_without_a_buffer(devices, flavor):
    """No concatenate and an all-reduce a leaf in the LOWERED program (the
    compiled one is the combiner's business), under the stage's scope."""
    comm = _comm("xla", allreduce_grad_dtype="bfloat16") \
        if flavor == "xla_bfloat16" else _comm(flavor)
    text = _lowered(comm, lambda g: comm.allreduce_grad(g),
                    _three_leaves(comm.size))
    assert "stablehlo.concatenate" not in text
    assert len(re.findall(r"stablehlo\.all_reduce", text)) == 3
    # every leaf travels in the wire dtype, and only there
    assert ("xbf16>" in text) == (flavor == "xla_bfloat16")


@pytest.mark.parametrize("case", ["two_dimensional", "striped_r60",
                                  "int8_dcn_hop", "flat_reference"])
def test_a_plan_that_needs_the_buffer_still_builds_it(devices, case):
    comm = _comm("two_dimensional" if case == "two_dimensional" else "xla")
    grads = _three_leaves(comm.size)
    if case == "two_dimensional":
        body = comm.allreduce_grad
    elif case == "flat_reference":
        body = comm._legacy_allreduce_grad_traced
    else:
        plan = NEEDS_BUFFER[case][0]()
        assert plan_needs_buffer(plan, comm.plan_topology())

        def body(g):
            return execute_plan(plan, comm, g)
    assert "stablehlo.concatenate" in _lowered(comm, body, grads)
