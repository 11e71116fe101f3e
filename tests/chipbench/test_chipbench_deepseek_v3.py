"""The ``deepseek_v3`` family (Kimi-VL-A3B's decoder) in the benchmark: the
comparison that decides ``correct`` fails ALL THREE controls (the reference
in int8, and the float32 reference with each piece of latent attention's
mathematics left wrong: the scores over sqrt(128), the shared key head
unrotated) and passes a sound run at the toy sizes (24-wide keys beside
16-wide values, a dense layer and two sparse, a share of the experts, two
shared experts), ``flops_deepseek_v3.py`` agrees with a count by hand for
one layer of each kind and the kernels' needs with one at this cell's
shapes, the four new readers read the recorded chip trace as they did on
the chip and nothing on any cell's that ``BENCHMARK.json`` does not list for
them, the configuration keeps every published width, and a program from
before the model fails at once."""

import importlib.util
import json
import os

import jax
import pytest

from chipbench import (check, flops, flops_deepseek_v3, flops_lfm2,
                       generator, harness, parts, reduce_trace, run, spec,
                       weights)

CELL = "kimi-vl-a3b-ep8share-t8192"
SCOPED = os.path.join(spec.HERE, "fixtures_scoped")
PEAKS = {"bf16_tflops": 197.0, "hbm_gbytes_per_s": 819.0}
NEW_READERS = ("mla_flash_ms", "mla_flash_roofline", "mla_latent_ms",
               "moe_shared_experts_ms")


def _batches(cell, seed):
    comm = cell.family.make_comm(cell.sizes, jax.devices()[:1])
    return generator.make_ring(dict(cell.sizes, ring=3), 1,
                               weights.seed_key(seed, 1), comm.mesh,
                               comm.data_axes)


@pytest.fixture(scope="module")
def toy_reference():
    """The toy cell, its first batches and the float32 reference's first
    three steps on them (once for all the controls)."""
    cell = spec.resolve(CELL, rehearse=True)
    batches = _batches(cell, 101)
    return cell, batches, harness.reference_readings(
        cell, 101, batches, jax.devices()[:1])


@pytest.mark.parametrize("control", ["int8", "nope_scale", "unrotated_key"])
def test_a_control_is_not_correct(toy_reference, control):
    """A precision below the configuration's, the softmax scale of plain
    128-wide heads, or the shared key head left unrotated, fails the
    gradient: the limits can tell each from a sound run."""
    cell, batches, reference = toy_reference
    other = harness.reference_readings(cell, 101, batches, jax.devices()[:1],
                                       control)
    rows, within = check.judge(check.numbers(other, reference), cell.limits)
    assert within is False, rows
    assert "grad_norm" in {r["check"] for r in rows if not r["within"]}


def test_a_sound_run_in_this_process_is_correct(capsys):
    toy = spec.resolve(CELL, rehearse=True).sizes
    # the toy keeps the mechanism: keys wider than values, a rotated part,
    # a dense layer before the sparse ones, a share, two shared experts
    assert (toy["qk_nope_head_dim"] + toy["qk_rope_head_dim"],
            toy["v_head_dim"]) == (24, 16)
    assert toy["mlp_layer_types"] == ["dense", "sparse", "sparse"]
    assert toy["num_experts"] < toy["num_experts_published"]
    assert toy["n_shared_experts"] == 2
    code = run.main(["--workload", CELL, "--seed", "77", "--seconds", "1",
                     "--trace", "0", "--rehearse"])
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert code == 0 and lines[-1]["correct"] is True, lines[-8:]
    checks = {l["check"]: l for l in lines if l.get("phase") == "check"}
    assert checks["loss_step1"]["value"] < 1e-5


def test_the_family_builds_a_step_that_reports_the_counters():
    cell = spec.resolve(CELL, rehearse=True)
    comm = cell.family.make_comm(cell.sizes, jax.devices()[:1])
    params = cell.family.make_params(cell.sizes, weights.seed_key(5, 0))
    # one draw of the weights for every seed, another for another
    # ``weights_key``; the tokens are the seed's
    head = lambda tree: tree["params"]["lm_head"]["kernel"]
    same = cell.family.make_params(cell.sizes, weights.seed_key(6, 0))
    other = cell.family.make_params(
        dict(cell.sizes, weights_key=cell.sizes["weights_key"] + 1),
        weights.seed_key(5, 0))
    assert bool((head(params) == head(same)).all())
    assert not bool((head(params) == head(other)).any())
    assert not bool((_batches(cell, 5)[0][0] == _batches(cell, 6)[0][0]).all())
    rows = params["params"]["embed_tokens"]["embedding"]
    assert cell.sizes["embedding_std"] == 1.0
    assert float(rows.std()) == pytest.approx(1.0, rel=0.05)
    assert float(head(params).std()) == pytest.approx(
        cell.sizes["initializer_range"], rel=0.05)
    bias = params["params"]["layer_1"]["moe"]["expert_bias"]
    assert bias.shape == (8,) and 0 < float(abs(bias).max()) < 0.1
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
    cell = spec.resolve(CELL)
    sizes = cell.sizes
    assert (sizes["hidden_size"], sizes["num_attention_heads"],
            sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
            sizes["qk_rope_head_dim"], sizes["v_head_dim"],
            sizes["intermediate_size"], sizes["moe_intermediate_size"],
            sizes["num_experts_per_tok"], sizes["num_experts_published"],
            sizes["n_shared_experts"], sizes["routed_scaling_factor"]) == (
                2048, 16, 512, 128, 64, 128, 11264, 1408, 6, 64, 2, 2.446)
    assert (sizes["q_lora_rank"], sizes["rope_scaling"], sizes["rope_theta"],
            sizes["rms_norm_eps"], sizes["num_key_value_heads"],
            sizes["scoring_func"], sizes["topk_method"], sizes["n_group"],
            sizes["topk_group"], sizes["norm_topk_prob"],
            sizes["first_k_dense_replace"], sizes["moe_layer_freq"],
            sizes["seq_len"], sizes["batch_per_chip"]) == (
                None, None, 800000, 1e-5, 16, "sigmoid", "noaux_tc", 1, 1,
                True, 1, 1, 8192, 1)
    # every number of the catalog's config under its own key, but the three
    # that are reduced
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Kimi-VL-A3B-Instruct"]
    assert row["source_url"] == sizes["source"]
    differs = sorted(k for k, v in row["config"].items() if sizes[k] != v)
    assert differs == ["n_routed_experts", "vocab_size"]
    assert sizes["reduced"] == ["mlp_layer_types", "n_routed_experts",
                                "vocab_size"]
    # published layers 1-6: the one dense layer and five sparse
    assert sizes["mlp_layer_types"] == sizes["mlp_layer_types_published"][
        :6] == ["dense"] + ["sparse"] * 5
    assert (len(sizes["mlp_layer_types_published"]),
            sizes["num_hidden_layers"]) == (27, 27)
    # 8 of 64 experts and an eighth of the vocabulary: a chip's share of 8
    assert sizes["n_routed_experts"] * 8 == sizes[
        "n_routed_experts_published"] == 64
    assert sizes["vocab_size"] * 8 == sizes["vocab_size_published"] == 163840
    assert "8 chips" in sizes["deployment"]
    # what the benchmark's shared readers go by mirrors the published keys
    assert (sizes["num_experts"], sizes["num_experts_published"],
            sizes["num_dense_layers"], len(sizes["layer_types"])) == (
                sizes["n_routed_experts"], 64,
                sizes["first_k_dense_replace"], 6)
    assert "n_layer" not in sizes       # ``block_ms`` and ``head_loss_ms``
    assert "head_dim" not in sizes      # no one head size: 192 and 128
    stated = " ".join(sizes["assumed"])
    for said in ("rotate half", "1e-20", "initializer_range",
                 "kv_a_layernorm", "multi-token-prediction"):
        assert said in stated, said
    assert "VISION TOWER IS NOT BUILT" in " ".join(sizes["departures"])
    shapes = cell.family.param_shapes(sizes)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 668_890_432
    mla = shapes["params"]["layer_3"]["mla"]
    assert {k: v["kernel"].shape for k, v in mla.items()
            if "kernel" in v} == {
        "q_proj": (2048, 16 * 192), "kv_a_proj_with_mqa": (2048, 576),
        "kv_b_proj": (512, 16 * 256), "o_proj": (2048, 2048)}
    assert mla["kv_a_layernorm"]["scale"].shape == (512,)
    moe = shapes["params"]["layer_1"]["moe"]
    assert set(moe) == {"expert_bias", "gate", "shared", "w1", "w2", "w3"}
    assert moe["w1"].shape == (8, 2048, 1408)
    assert moe["gate"]["kernel"].shape == (2048, 64)
    assert moe["shared"]["w1"]["kernel"].shape == (2048, 2816)
    assert shapes["params"]["layer_0"]["ffn"]["w1"]["kernel"].shape == (
        2048, 11264)
    assert "moe" not in shapes["params"]["layer_0"]
    assert shapes["params"]["lm_head"]["kernel"].shape == (2048, 20480)
    assert cell.family.min_kernels(sizes) == 6 * 3 + 5 * 9 == 63


def test_flops_by_hand_for_one_layer_of_each_kind():
    sizes = spec.resolve(CELL).sizes
    d, t = 2048, 8192
    sparse = flops_deepseek_v3.layer_forward_flop_per_token(sizes, "sparse")
    dense = flops_deepseek_v3.layer_forward_flop_per_token(sizes, "dense")
    # q 2048 -> 16 x 192, the latent and the shared key 2048 -> 576, k_nope
    # and v 512 -> 16 x 256, the output 2048 -> 2048: 27.5 M
    assert sparse["latent_projections"] == dense["latent_projections"] == (
        2 * d * 3072 + 2 * d * 576 + 2 * 512 * 4096 + 2 * d * d)
    # scores at 192, values at 128, (T + 1) / 2 keys a query: 41.9 M
    assert sparse["attention"] == 2 * (t + 1) / 2 * 16 * (192 + 128)
    assert dense["feed_forward"] == 3 * 2 * d * 11264
    assert set(dense) == {"latent_projections", "attention", "feed_forward"}
    # 0.75 expert visits a token at the expected load (6 x 8 / 64); the two
    # shared experts see every token
    assert flops_lfm2.expected_pairs(1, sizes) == 0.75
    assert sparse["experts"] == 0.75 * 3 * 2 * d * 1408
    assert sparse["shared_experts"] == 2 * 3 * 2 * d * 1408
    assert sparse["router"] == 2 * d * 64
    forward = (sum(dense.values()) + 5 * sum(sparse.values())
               + 2 * d * 20480)
    assert flops_deepseek_v3.lm_train_flop_per_token(sizes) == 3 * forward
    assert 2.63e9 < 3 * forward < 2.65e9
    assert spec.resolve(CELL).family.flop_per_unit(sizes) == 3 * forward
    # ISSUE 42's shares: the flash kernels 29 %, latent attention as a whole
    # 48 % of what the step requires
    attention = 6 * sparse["attention"]
    latent = attention + 6 * sparse["latent_projections"]
    assert 0.28 < attention / forward < 0.30
    assert 0.47 < latent / forward < 0.49


def test_kernel_needs_by_hand_and_their_bounds():
    sizes = spec.resolve(CELL).sizes
    t = 8192
    flop, moved = flops_deepseek_v3.mla_flash_train_flop_and_bytes(sizes)
    # six layers of 3 x 2 x T (T + 1) / 2 x (192 + 128) a head: 1.03 TFLOP
    # a layer, 5.2 ms at the bf16 peak
    assert flop == 6 * 3 * 2 * 16 * (t * (t + 1) / 2) * 320
    assert 1.03e12 < flop / 6 < 1.04e12
    # less than the same pairs cost at 192-wide values (what a padded v
    # would be counted as), more than at 128-wide keys
    assert (flops.flash_train_flop(1, t, 16, 128) < flop / 6
            < flops.flash_train_flop(1, t, 16, 192))
    # q, k, dq, dk at 192 and v, o, do, dv at 128, each read or written
    # as often as ``flops.flash_train_bytes`` counts them at one size
    assert (flops.flash_train_bytes(1, t, 16, 16, 128) < moved / 6
            < flops.flash_train_bytes(1, t, 16, 16, 192))
    assert flops.roofline_seconds(flop, moved, PEAKS)[1] == "compute"
    # the grouped products' need at 2048 x 1408, by the reader the cell
    # shares with the other MoE cells: 6,144 rows a layer, five layers
    rows = 8192 * 6 * 8 / 64
    assert flops_lfm2.expected_pairs(8192, sizes) == rows == 6144
    flop, _ = flops_lfm2.moe_gmm_train_flop_and_bytes(sizes)
    assert flop == 5 * 3 * 3 * 2 * rows * 2048 * 1408


def test_the_readers_match_kernels_by_the_names_the_chip_gives():
    from chipbench.layer_metrics import (flash_ms, full_flash_ms,
                                         gqa_flash_ms, mla_flash_ms,
                                         moe_gmm_ms, nope_flash_ms,
                                         sliding_flash_ms, swa_flash_ms)

    kernel = "mla.4 (bf16[16,8192,128] tpu_custom_call"
    assert mla_flash_ms.is_flash(kernel) and mla_flash_ms.MODULE == "mla"
    # a fusion named after the module is not a kernel, and no other
    # family's reader takes these kernels for its own
    assert not mla_flash_ms.is_flash("mla.3 bf16[16,8192,192]")
    for other in (flash_ms.is_flash, gqa_flash_ms.is_flash,
                  swa_flash_ms.is_flash, nope_flash_ms.is_flash,
                  full_flash_ms.is_flash, sliding_flash_ms.is_flash,
                  moe_gmm_ms.is_gmm):
        assert not other(kernel)
    # ``parts.py`` takes the module for an attention module by the file
    assert "mla" in parts.attention_modules()
    assert parts.part_of(kernel, "") == "mla_flash_ms"
    inside = "jit(step)/chainermn.grad/jvp(DeepseekV3)/layer_2/mla/"
    assert parts.part_of("fusion.1", inside + "q_proj/dot_general") == (
        "attn_proj_ms")
    assert parts.part_of("fusion.2", inside + "kv_a_layernorm/mul") == (
        "norm_rope_ms")
    assert parts.part_of(
        "fusion.3", "jit(step)/chainermn.grad/jvp(DeepseekV3)/layer_2/moe/"
        "chainermn.moe.shared_experts/shared/w1/dot_general") == "moe_rest_ms"


def _context(cell_name):
    cell = spec.resolve(cell_name)
    return {"sizes": cell.sizes, "chips": cell.chips, "peaks": PEAKS}


HOST = {"steps": 2, "dispatch_s": [], "compile_info": {}}


def test_the_new_readers_on_the_recorded_chip_trace():
    """Two of the ten traced steps of the cell (my chip run, PR 42)."""
    from chipbench.layer_metrics import mla_flash_ms, moe_gmm_ms

    events = reduce_trace.load_events(
        os.path.join(SCOPED, CELL + ".two-steps.json.gz"))
    ops = reduce_trace.first_device(events)
    count = lambda match: len([n for n, _, _ in ops if match(n)])
    # (forward, dk/dv, dq) x 6 layers; 5 MoE layers x 3 products x
    # (forward, dlhs, drhs); twice
    assert count(mla_flash_ms.is_flash) == 2 * 18
    assert count(moe_gmm_ms.is_gmm) == 2 * 45
    cell = spec.resolve(CELL)
    read = lambda name, document=events: cell.layer_reader(name).read(
        document, HOST, _context(CELL))
    got = {name: read(name) for name in NEW_READERS + SHARED_READERS}
    assert got == {name: pytest.approx(value, abs=0.01)
                   for name, value in RECORDED.items()}
    for name in ("mla_flash_roofline", "moe_gmm_roofline"):
        assert 0 < got[name] < 100
    # the roofline is the need over the time, nothing else
    flop, nbytes = flops_deepseek_v3.mla_flash_train_flop_and_bytes(
        cell.sizes)
    assert got["mla_flash_roofline"] == pytest.approx(
        100 * flops.roofline_seconds(flop, nbytes, PEAKS)[0]
        / (got["mla_flash_ms"] / 1e3))
    # the two "of which" figures lie inside the parts they are taken from
    assert got["mla_latent_ms"] < got["attn_proj_ms"] + got["norm_rope_ms"]
    assert got["moe_shared_experts_ms"] < got["moe_rest_ms"]
    # the parts leave nothing out that ``scope_unnamed_share`` does not count
    assert parts.unmeasured_share(events) <= read("scope_unnamed_share") + 1
    # this model opens none of these scopes, and calls no such module
    for absent in ("moe_route_ms", "moe_shared_ms", "moe_softmax_route_ms",
                   "nope_flash_ms", "swa_flash_ms", "full_flash_ms",
                   "sliding_flash_ms", "gqa_flash_ms", "shortconv_ms"):
        assert read(absent) is None, absent
    # by scope: nothing without the key
    keyless = {k: v for k, v in events.items() if k != "scopes"}
    for name in ("mla_latent_ms", "moe_shared_experts_ms"):
        assert read(name, keyless) is None


# what the readers gave on the recorded steps
SHARED_READERS = ("moe_gmm_ms", "moe_gmm_roofline", "moe_dispatch_combine_ms",
                  "attn_proj_ms", "norm_rope_ms", "dense_ffn_ms",
                  "moe_rest_ms", "lm_head_loss_ms")
RECORDED = {"mla_flash_ms": 72.998, "mla_flash_roofline": 43.013,
            "mla_latent_ms": 7.094, "moe_shared_experts_ms": 27.839,
            "moe_gmm_ms": 27.431, "moe_gmm_roofline": 29.506,
            "moe_dispatch_combine_ms": 18.943, "attn_proj_ms": 29.782,
            "norm_rope_ms": 9.53, "dense_ffn_ms": 21.317,
            "moe_rest_ms": 36.045, "lm_head_loss_ms": 17.481}
OTHER_CELLS = sorted(name.split(".")[0] for name in os.listdir(SCOPED)
                     if not name.startswith(CELL))


@pytest.mark.parametrize("other", OTHER_CELLS)
def test_the_new_readers_read_nothing_on_another_cells_trace(other):
    """None, never 0, and no exception, on every other cell's recording
    under that cell's own sizes (which lack this family's keys), on a trace
    with no device and on the toy sizes (another implementation).  A cell
    that ``BENCHMARK.json`` lists for a reader is one that reads it
    (``spec.applies``): a later cell of this family is appended there and
    this file stays as it is."""
    events = reduce_trace.load_events(
        os.path.join(SCOPED, other + ".two-steps.json.gz"))
    cell = spec.resolve(CELL)
    toy = dict(_context(CELL), sizes=spec.resolve(CELL, rehearse=True).sizes)
    entries = {m["name"]: m for m in
               spec.load_benchmark(spec.CHECKOUT)["per_layer"]}
    for name in NEW_READERS:
        read = cell.layer_reader(name).read
        if not spec.applies(entries[name], other):
            assert read(events, HOST, _context(other)) is None, name
        assert read({"devices": {}, "host_spans": []}, HOST,
                    _context(CELL)) is None, name
    for name in ("mla_flash_ms", "mla_flash_roofline"):
        assert cell.layer_reader(name).read(events, HOST, toy) is None, name


def test_a_program_from_before_the_model_fails_at_once(monkeypatch):
    """On the parent's program the family raises ``SpecError`` at import, so
    ``chipbench.run`` exits with 2 before it touches a device."""
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "chainermn_tpu.models.deepseek_v3"
        else real(name, *a))
    path = os.path.join(spec.HERE, "families", "deepseek_v3.py")
    module_spec = importlib.util.spec_from_file_location("_before", path)
    with pytest.raises(spec.SpecError, match="no chainermn_tpu.models."
                                             "deepseek_v3"):
        module_spec.loader.exec_module(
            importlib.util.module_from_spec(module_spec))
