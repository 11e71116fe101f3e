"""The ``lfm2_moe`` family in the benchmark: the comparison that decides
``correct`` fails the int8 control and passes a sound run at the toy sizes,
``flops_lfm2.py`` agrees with a count by hand for one layer of each kind,
the four kernel readers read the recorded chip trace as they did on the
chip, and the configuration keeps every published width."""

import json
import os

import jax
import pytest

from chipbench import (check, flops, flops_lfm2, generator, harness,
                       reduce_trace, run, spec, weights)

CELL = "lfm2-8b-a1b-ep4share-t8192"
FIXTURE = os.path.join(spec.HERE, "fixtures", CELL + ".two-steps.json.gz")
PEAKS = {"bf16_tflops": 197.0, "hbm_gbytes_per_s": 819.0}


def _batches(cell, seed):
    comm = cell.family.make_comm(cell.sizes, jax.devices()[:1])
    return generator.make_ring(dict(cell.sizes, ring=3), 1,
                               weights.seed_key(seed, 1), comm.mesh,
                               comm.data_axes)


@pytest.mark.parametrize("seed", [101, 202, 303])
def test_the_control_in_int8_is_not_correct(seed):
    cell = spec.resolve(CELL, rehearse=True)
    device = jax.devices()[:1]
    batches = _batches(cell, seed)
    reference = harness.reference_readings(cell, seed, batches, device)
    control = harness.reference_readings(cell, seed, batches, device, "int8")
    rows, within = check.judge(check.numbers(control, reference), cell.limits)
    assert within is False, rows
    # the lower precision fails the gradient, the number set to catch it
    assert "grad_norm" in {r["check"] for r in rows if not r["within"]}


def test_a_sound_run_in_this_process_is_correct(capsys):
    code = run.main(["--workload", CELL, "--seed", "77", "--seconds", "1",
                     "--trace", "0", "--rehearse"])
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert code == 0 and lines[-1]["correct"] is True, lines[-8:]
    checks = {l["check"]: l for l in lines if l.get("phase") == "check"}
    assert checks["loss_step1"]["value"] < 1e-5


def test_the_family_builds_a_step_that_reports_the_counters():
    """``build(with_counters=True)``: the step PERF.md's ``held_share`` by
    layer was read from; the timed step is built without them."""
    cell = spec.resolve(CELL, rehearse=True)
    comm = cell.family.make_comm(cell.sizes, jax.devices()[:1])
    params = cell.family.make_params(cell.sizes, weights.seed_key(5, 0))
    step, state = cell.family.build(comm, cell.sizes, params,
                                    with_counters=True)
    (batch,) = _batches(cell, 5)[:1]
    *_, loss, counters = step(*state, batch)
    assert set(counters) == {"layer_1", "layer_2"} and float(loss) > 0
    for counted in counters.values():
        assert float(counted["dropped_pairs"]) == 0.0
        assert counted["tokens_per_held_expert"].shape == (2,)
        assert 0.0 <= float(counted["held_share"]) <= 1.0


def test_the_configuration_keeps_every_published_width():
    sizes = spec.resolve(CELL).sizes
    assert (sizes["hidden_size"], sizes["intermediate_size"],
            sizes["moe_intermediate_size"], sizes["num_attention_heads"],
            sizes["num_key_value_heads"], sizes["num_experts_per_tok"],
            sizes["conv_L_cache"], sizes["seq_len"]) == (
                2048, 7168, 1792, 32, 8, 4, 3, 8192)
    assert sizes["num_experts_published"] == 32 == 4 * sizes["num_experts"]
    assert sizes["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                    "conv"]
    assert sizes["num_dense_layers"] == 1
    # a whole period of the pattern, four layers after the dense one, an
    # eighth of the vocabulary at least (model-configs guide, section 4)
    assert sizes["vocab_size"] * 8 >= sizes["vocab_size_published"]
    shapes = spec.resolve(CELL).family.param_shapes(sizes)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 507_820_288
    moe = shapes["params"]["layer_1"]["moe"]
    assert moe["w1"].shape == (8, 2048, 1792)
    assert moe["gate"]["kernel"].shape == (2048, 32)
    assert spec.resolve(CELL).family.min_kernels(sizes) == 3 + 4 * 9


def test_flops_by_hand_for_one_layer_of_each_kind():
    sizes = spec.resolve(CELL).sizes
    d, t = 2048, 8192
    conv = flops_lfm2.layer_forward_flop_per_token(sizes, "conv", True)
    assert conv == {"operator": 2 * d * 6144 + 2 * d * d,      # 33.6 M
                    "feed_forward": 3 * 2 * d * 7168}          # 88.1 M
    attention = flops_lfm2.layer_forward_flop_per_token(
        sizes, "full_attention", False)
    assert attention["operator"] == 2 * d * (2048 + 512 + 512) + 2 * d * d
    assert attention["attention"] == 2 * 2 * (t / 2) * 32 * 64  # 33.6 M
    # one expert visit a token at the expected load: 4 x 8 / 32
    assert attention["experts"] == 3 * 2 * d * 1792             # 22.0 M
    assert attention["router"] == 2 * d * 32
    forward = (sum(conv.values()) + sum(attention.values()) + 3 * (
        conv["operator"] + attention["experts"] + attention["router"])
        + 2 * d * 16384)
    assert flops_lfm2.lm_train_flop_per_token(sizes) == 3 * forward
    assert 1.29e9 < 3 * forward < 1.31e9
    assert spec.resolve(CELL).family.flop_per_unit(sizes) == 3 * forward


def test_kernel_needs_by_hand_and_their_bounds():
    sizes = spec.resolve(CELL).sizes
    rows = sizes["batch_per_chip"] * 8192 * 4 * 8 / 32
    assert flops_lfm2.expected_pairs(sizes["batch_per_chip"] * 8192,
                                     sizes) == rows
    assert flops_lfm2.grouped_matmul_train_flop(rows, 2048, 1792) == (
        3 * 2 * rows * 2048 * 1792)
    one = flops_lfm2.grouped_matmul_train_bytes(rows, 8, 2048, 1792)
    assert one == 2 * 3 * (rows * 2048 + rows * 1792 + 8 * 2048 * 1792)
    flop, nbytes = flops_lfm2.moe_gmm_train_flop_and_bytes(sizes)
    assert flop == 4 * 3 * 3 * 2 * rows * 2048 * 1792
    assert nbytes == 4 * 3 * one
    assert flops.roofline_seconds(flop, nbytes, PEAKS)[1] == "compute"
    flash, moved = flops_lfm2.gqa_flash_train_flop_and_bytes(sizes)
    assert flash == flops.flash_train_flop(sizes["batch_per_chip"], 8192, 32,
                                           64)
    assert flops.roofline_seconds(flash, moved, PEAKS)[1] == "compute"
    # attention's share of the step's required operations
    share = flash / (flops_lfm2.lm_train_flop_per_token(sizes)
                     * sizes["batch_per_chip"] * 8192)
    assert 0.07 < share < 0.09


def test_the_readers_match_kernels_by_the_names_the_chip_gives():
    from chipbench.layer_metrics import flash_ms, gqa_flash_ms, moe_gmm_ms

    gmm = "moe.41 (bf16[98304,1792] tpu_custom_call"
    attention = "attn.3 (bf16[96,8192,64] tpu_custom_call"
    assert moe_gmm_ms.is_gmm(gmm) and not moe_gmm_ms.is_gmm(attention)
    assert gqa_flash_ms.is_flash(attention)
    assert not gqa_flash_ms.is_flash(gmm)
    # a fusion named after the module is not a kernel; TransformerLM's
    # reader does not take these for its own
    assert not moe_gmm_ms.is_gmm("moe.41 bf16[98304,1792]")
    assert not flash_ms.is_flash(attention) and not flash_ms.is_flash(gmm)


def _read(name, events, sizes):
    host = {"steps": 2, "dispatch_s": [], "compile_info": {}}
    context = {"sizes": sizes, "chips": 1, "peaks": PEAKS}
    return spec.resolve(CELL).layer_reader(name).read(events, host, context)


def test_the_four_readers_on_the_recorded_chip_trace():
    """Two of the ten traced steps of the cell (my chip run, PR 26)."""
    from chipbench.layer_metrics import gqa_flash_ms, moe_gmm_ms

    events = reduce_trace.load_events(FIXTURE)
    sizes = spec.resolve(CELL).sizes
    ops = reduce_trace.first_device(events)
    # 4 MoE layers x 3 products x (forward, dlhs, drhs), twice; one
    # attention layer x (forward, dk/dv, dq), twice
    assert len([n for n, _, _ in ops if moe_gmm_ms.is_gmm(n)]) == 2 * 36
    assert len([n for n, _, _ in ops if gqa_flash_ms.is_flash(n)]) == 2 * 3
    readings = {name: _read(name, events, sizes) for name in (
        "moe_gmm_ms", "moe_gmm_roofline", "gqa_flash_ms",
        "gqa_flash_roofline")}
    assert readings["moe_gmm_ms"] == pytest.approx(RECORDED["moe_gmm_ms"],
                                                   abs=0.01)
    assert readings["gqa_flash_ms"] == pytest.approx(
        RECORDED["gqa_flash_ms"], abs=0.01)
    for name in ("moe_gmm_roofline", "gqa_flash_roofline"):
        assert readings[name] == pytest.approx(RECORDED[name], abs=0.01)
        assert 0 < readings[name] < 100
    # the roofline is the need over the time, nothing else
    flop, nbytes = flops_lfm2.moe_gmm_train_flop_and_bytes(sizes)
    assert readings["moe_gmm_roofline"] == pytest.approx(
        100 * flops.roofline_seconds(flop, nbytes, PEAKS)[0]
        / (readings["moe_gmm_ms"] / 1e3))


# what the four readers gave on the recorded steps
RECORDED = {"moe_gmm_ms": 46.517, "moe_gmm_roofline": 70.866,
            "gqa_flash_ms": 65.091, "gqa_flash_roofline": 19.293}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_a_reader_finds_nothing_to_read_without_its_kernels(name):
    """On the CPU, on another implementation, or in a trace of a program
    that has no such kernel, a reader returns None and does not raise."""
    sizes = spec.resolve(CELL).sizes
    assert _read(name, {"devices": {}, "host_spans": []}, sizes) is None
    toy = spec.resolve(CELL, rehearse=True).sizes
    events = {"devices": {"/device:TPU:0": [["fusion.1 f32[8]", 0, 10]]},
              "host_spans": []}
    assert _read(name, events, toy) is None
    assert _read(name, events, sizes) in (None, 0.0)
