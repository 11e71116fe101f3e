"""Bucketed FSDP tests — partitioner properties, single-bucket parity,
HLO schedule pinning (K gathers / K reduce-scatters / prefetch barriers),
bucketed checkpoint round-trip + config refusal, and the per-bucket
observability lane (chainermn_tpu/parallel/buckets.py + fsdp.py)."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu.parallel import buckets as bucket_mod
from chainermn_tpu.parallel.fsdp import (
    fsdp_full_params, fsdp_init, fsdp_layout, make_fsdp_train_step)
from chainermn_tpu.training import put_global_batch


@pytest.fixture
def comm():
    return chainermn_tpu.create_communicator("flat")


def _mlp_params(n_layers=6, width=16, seed=0):
    rng = np.random.RandomState(seed)
    return {f"layer{i}": {
        "w": jnp.asarray(rng.randn(width, width) / 4.0, jnp.float32),
        "b": jnp.asarray(rng.randn(width) / 4.0, jnp.float32)}
        for i in range(n_layers)}, rng


def _mlp_problem(comm, n_layers=6, width=16, seed=0):
    params, rng = _mlp_params(n_layers, width, seed)

    def loss_fn(p, batch):
        x, y = batch
        for i in range(n_layers):
            x = jnp.tanh(x @ p[f"layer{i}"]["w"] + p[f"layer{i}"]["b"])
        return jnp.mean((x - y) ** 2)

    xs = np.asarray(rng.randn(comm.size * 4, width), np.float32)
    ys = np.asarray(rng.randn(comm.size * 4, width), np.float32)
    return params, loss_fn, (xs, ys)


# ---- partitioner properties -------------------------------------------------

class TestPartitioner:
    @pytest.mark.parametrize("seed", range(8))
    def test_every_leaf_in_exactly_one_bucket(self, seed):
        rng = np.random.RandomState(seed)
        n = rng.randint(1, 40)
        leaves = [np.zeros(tuple(rng.randint(1, 6)
                                 for _ in range(rng.randint(0, 3))),
                           np.float32) for _ in range(n)]
        k = rng.randint(1, 10)
        assignments = bucket_mod.partition_buckets(leaves, num_buckets=k)
        # contiguous cover: [0, n) split with no gaps, overlaps, or empties
        assert assignments[0].start == 0
        assert assignments[-1].stop == n
        for a, b in zip(assignments, assignments[1:]):
            assert a.stop == b.start
        assert all(a.n_leaves >= 1 for a in assignments)
        assert len(assignments) == min(k, n)
        assert sum(a.n_leaves for a in assignments) == n

    @pytest.mark.parametrize("seed", range(8))
    def test_rank_order_determinism(self, seed):
        """The partition is a pure function of shapes/dtypes — two 'ranks'
        flattening structurally identical pytrees (different array
        instances, different backing) compute identical buckets."""
        rng = np.random.RandomState(seed)
        shapes = [tuple(rng.randint(1, 8) for _ in range(rng.randint(0, 3)))
                  for _ in range(rng.randint(1, 20))]
        dtypes = [np.float32, np.float16, np.int32]
        dts = [dtypes[rng.randint(3)] for _ in shapes]
        rank0 = [np.zeros(s, d) for s, d in zip(shapes, dts)]
        rank1 = [jnp.asarray(np.ones(s, d)) for s, d in zip(shapes, dts)]
        k = rng.randint(1, 6)
        assert bucket_mod.partition_buckets(rank0, num_buckets=k) \
            == bucket_mod.partition_buckets(rank1, num_buckets=k)

    @pytest.mark.parametrize("seed", range(8))
    def test_size_balance_within_2x_of_target(self, seed):
        """When no single leaf exceeds the ideal target, every bucket
        stays within 2x of it (the half-item greedy bound)."""
        rng = np.random.RandomState(seed)
        n = rng.randint(8, 60)
        leaves = [np.zeros((rng.randint(1, 32),), np.float32)
                  for _ in range(n)]
        total = sum(l.nbytes for l in leaves)
        k = rng.randint(2, 8)
        target = total / k
        if max(l.nbytes for l in leaves) > target:
            pytest.skip("a single leaf exceeds the target: bound waived")
        assignments = bucket_mod.partition_buckets(leaves, num_buckets=k)
        for a in assignments:
            assert a.nbytes <= 2 * target + 1e-9

    def test_scalar_and_mixed_dtype_leaves(self):
        leaves = [np.float32(1.0), np.zeros((7,), np.float16),
                  np.zeros((3, 3), np.int32), np.float64(2.0),
                  np.zeros((1,), np.float32)]
        assignments = bucket_mod.partition_buckets(leaves, num_buckets=3)
        assert sum(a.n_leaves for a in assignments) == len(leaves)
        assert sum(a.nbytes for a in assignments) \
            == sum(bucket_mod.leaf_nbytes(l) for l in leaves)
        # scalar leaves count their itemsize
        assert bucket_mod.leaf_nbytes(np.float64(2.0)) == 8
        assert bucket_mod.leaf_nbytes(np.float32(1.0)) == 4

    def test_resolve_knobs(self):
        # num_buckets wins over bucket_bytes; both clamp to [1, n_leaves]
        assert bucket_mod.resolve_num_buckets(1000, 10, 3, 100) == 3
        assert bucket_mod.resolve_num_buckets(1000, 10, None, 250) == 4
        assert bucket_mod.resolve_num_buckets(1000, 10, None, 1) == 10
        assert bucket_mod.resolve_num_buckets(1000, 2, 64, None) == 2
        assert bucket_mod.resolve_num_buckets(1000, 10, None, None) == 1
        assert bucket_mod.resolve_num_buckets(0, 0, None, None) == 1
        with pytest.raises(ValueError):
            bucket_mod.resolve_num_buckets(1000, 10, 0, None)
        with pytest.raises(ValueError):
            bucket_mod.resolve_num_buckets(1000, 10, None, 0)

    def test_bucket_bytes_knob_reaches_fsdp_init(self, comm):
        params, _, _ = _mlp_problem(comm)
        total = sum(l.size * l.dtype.itemsize
                    for l in jax.tree.leaves(params))
        state, meta = fsdp_init(comm, params, optax.sgd(0.1),
                                bucket_bytes=total // 3)
        assert meta.num_buckets == 3
        assert len(state.shards) == 3


# ---- single-bucket parity and K>1 trajectory equality -----------------------

class TestParity:
    def test_k1_and_k4_trajectories_match(self, comm):
        """The bucketed schedule is a pure reordering: K=4 with prefetch
        reproduces the K=1 (monolithic, no-barrier) trajectory step by
        step, bit for bit."""
        params, loss_fn, data = _mlp_problem(comm)
        batch = put_global_batch(comm, data)
        trajs = {}
        for K in (1, 4):
            state, meta = fsdp_init(comm, params, optax.adam(0.01),
                                    num_buckets=K)
            step = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01),
                                        meta, donate=False, prefetch=1)
            losses = []
            for _ in range(5):
                state, loss = step(state, batch)
                losses.append(float(loss))
            trajs[K] = (losses, fsdp_full_params(state, meta))
        assert trajs[1][0] == trajs[4][0]
        for a, b in zip(jax.tree.leaves(trajs[1][1]),
                        jax.tree.leaves(trajs[4][1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_full_params_round_trip_bucketed(self, comm):
        """fsdp_full_params restores the exact pytree (values, dtypes,
        shapes) from a bucketed layout with scalar and mixed-dtype
        leaves crossing bucket boundaries."""
        params = {"s": jnp.asarray(3.25, jnp.float32),
                  "w": jnp.arange(13, dtype=jnp.float32),
                  "h": jnp.ones((3, 5), jnp.bfloat16),
                  "z": jnp.arange(29, dtype=jnp.float32)}
        state, meta = fsdp_init(comm, params, optax.sgd(0.1),
                                num_buckets=3)
        assert meta.num_buckets >= 2
        out = fsdp_full_params(state, meta)
        assert jax.tree.structure(out) == jax.tree.structure(params)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(params)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_per_bucket_wire_dtype(self, comm):
        """bucket_wire_dtypes overrides the step-wide wire per bucket:
        the lowered program gathers one bucket on a bf16 wire while the
        other stays f32, and training still converges on the same
        problem."""
        params, loss_fn, data = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.adam(0.01),
                                num_buckets=2,
                                bucket_wire_dtypes=["bfloat16", None])
        assert meta.buckets[0].wire_dtype == "bfloat16"
        assert meta.buckets[1].wire_dtype is None
        step = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01), meta,
                                    donate=False)
        batch = put_global_batch(comm, data)
        text = step.lower(state, batch).as_text()
        gathers = [l for l in text.splitlines()
                   if "stablehlo.all_gather" in l]
        assert len(gathers) == 2
        assert sum("bf16" in l for l in gathers) == 1
        losses = []
        for _ in range(5):
            state, loss = step(state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        # master shards stay full precision
        for b in jax.tree.leaves(state.shards):
            assert b.dtype == jnp.float32

    def test_bucket_wire_dtypes_length_mismatch_raises(self, comm):
        params, _, _ = _mlp_problem(comm)
        with pytest.raises(ValueError, match="bucket_wire_dtypes"):
            fsdp_init(comm, params, optax.sgd(0.1), num_buckets=3,
                      bucket_wire_dtypes=["bfloat16"])


# ---- HLO schedule pinning (the fast-tier smoke) -----------------------------

def _counts(step, state, batch):
    lowered = step.lower(state, batch)
    shlo = lowered.as_text()
    hlo = lowered.compile().as_text()
    return (len(re.findall(r"all-gather(?:-start)?\(", hlo)),
            len(re.findall(r"reduce-scatter(?:-start)?\(", hlo)),
            shlo.count("stablehlo.optimization_barrier"))


class TestSchedule:
    @pytest.mark.parametrize("K,D", [(1, 0), (3, 0), (3, 1), (4, 0),
                                     (4, 1), (4, 2), (4, 5)])
    def test_hlo_has_k_collectives_and_pinned_window(self, comm, K, D):
        """num_buckets=K compiles to exactly K all-gathers and K
        reduce-scatters; the prefetch window leaves 2*max(0, K-1-D)
        optimization barriers in the lowered program (each pin counted
        once forward + once on the backward via the custom VJP)."""
        params, loss_fn, data = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.adam(0.01),
                                num_buckets=K)
        assert meta.num_buckets == K
        step = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01), meta,
                                    donate=False, prefetch=D)
        batch = put_global_batch(comm, data)
        n_ag, n_rs, n_bar = _counts(step, state, batch)
        assert n_ag == K and n_rs == K
        assert n_bar == (2 * max(0, K - 1 - D) if K > 1 else 0)

    def test_prefetch_validation(self, comm):
        params, loss_fn, _ = _mlp_problem(comm)
        _, meta = fsdp_init(comm, params, optax.sgd(0.1))
        with pytest.raises(ValueError, match="prefetch"):
            make_fsdp_train_step(comm, loss_fn, optax.sgd(0.1), meta,
                                 prefetch=-1)


# ---- bucketed checkpoint layout ---------------------------------------------

class TestCheckpoint:
    def test_bucketed_state_roundtrips(self, comm, tmp_path):
        """A K=3 FsdpState survives the multi-node checkpointer and
        training continues bit-for-bit from the restored state."""
        from chainermn_tpu.extensions import create_multi_node_checkpointer
        from chainermn_tpu.parallel.fsdp import FsdpState

        params, loss_fn, data = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.adam(1e-2),
                                num_buckets=3)
        step = make_fsdp_train_step(comm, loss_fn, optax.adam(1e-2), meta,
                                    donate=False)
        batch = put_global_batch(comm, data)
        state, _ = step(state, batch)
        layout = fsdp_layout({"fsdp": state})
        assert layout["num_buckets"] == 3

        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "fsdpb")
        ckpt.save({"fsdp": state}, 1)
        restored, gen = ckpt.resume(
            jax.tree.map(jnp.zeros_like, {"fsdp": state}))
        assert gen == 1 and isinstance(restored["fsdp"], FsdpState)
        s2, l2 = step(restored["fsdp"], batch)
        s3, l3 = step(state, batch)
        assert float(l2) == float(l3)
        for a, b in zip(jax.tree.leaves(s2), jax.tree.leaves(s3)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bucket_config_mismatch_refused(self, comm, tmp_path):
        """A checkpoint saved under num_buckets=3 refuses to resume into
        a num_buckets=1 state with an error naming the bucket config."""
        from chainermn_tpu.extensions import create_multi_node_checkpointer

        params, _, _ = _mlp_problem(comm)
        state3, _ = fsdp_init(comm, params, optax.adam(1e-2),
                              num_buckets=3)
        state1, _ = fsdp_init(comm, params, optax.adam(1e-2),
                              num_buckets=1)
        ckpt = create_multi_node_checkpointer(comm, str(tmp_path), "fsdpb")
        ckpt.save({"fsdp": state3}, 1)
        with pytest.raises(ValueError, match="num_buckets"):
            ckpt.resume(jax.tree.map(jnp.zeros_like, {"fsdp": state1}))


# ---- observability: the step carries no host callback ----------------------

class TestObservability:
    @pytest.fixture(autouse=True)
    def clean(self):
        from chainermn_tpu import observability as obs
        from chainermn_tpu.observability import (
            get_registry, reset_flight_recorder)

        reset_flight_recorder()
        obs.disable()
        get_registry().reset()
        yield
        reset_flight_recorder()
        obs.disable()
        get_registry().reset()

    def test_disabled_observability_keeps_program_clean(self, comm):
        """Zero-cost-when-disabled: with recorder and registry off, the
        lowered program contains no host callbacks."""
        params, loss_fn, data = _mlp_problem(comm)
        state, meta = fsdp_init(comm, params, optax.adam(0.01),
                                num_buckets=2)
        step = make_fsdp_train_step(comm, loss_fn, optax.adam(0.01), meta,
                                    donate=False)
        batch = put_global_batch(comm, data)
        assert hasattr(step, "lower")  # bare jitted step, no wrapper
        assert "callback" not in step.lower(state, batch).as_text()


# ---- the sweep as a subprocess (slow tier) ----------------------------------

@pytest.mark.slow
def test_bench_fsdp_overlap_sweep_runs():
    """End-to-end: the bucket x prefetch sweep passes its own structural
    schedule asserts on the 8-device CPU mesh and emits valid JSON."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks",
                                      "bench_fsdp_overlap.py"),
         "--json", "--iters", "2", "--warmup", "1",
         "--layers", "4", "--width", "32",
         "--buckets", "1,2,4", "--prefetch", "0,1"],
        capture_output=True, text=True, timeout=480, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    assert len(rows) == 6
    assert all(r["schedule_ok"] for r in rows)
    assert {r["num_buckets"] for r in rows} == {1, 2, 4}
