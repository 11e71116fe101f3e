"""chipbench/scopes.py and the readers built on it: on a compiled program's
text, on hand-made EVENTS documents with a ``"scopes"`` key, on PR 23's
fixtures (which have none), and on the short scoped recordings of chip runs
under ``chipbench/fixtures_scoped/``, one a cell.  EVERY per-layer entry of
``BENCHMARK.json`` is held to two rules, whatever reads it and whichever PR
brought it: (1) which cells a reader reads is said in one place, the
``workloads`` list of its entry: it gives a number exactly on the cells
listed for it; (2) it needs the ``"scopes"`` key (None without it) or never
sees it (the same number without it).  ``EXPECTED`` below holds this file's
hand-made cases; a later PR's reader brings its case in a file of its own.
The last test adds a cell WITH a reader by new files and appended names
alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import reduce_trace as rt
from chipbench import scopes, spec

FIXTURES = os.path.join(spec.HERE, "fixtures")
SCOPED = os.path.join(spec.HERE, "fixtures_scoped")
STEP = "jit(inner)/shard_map/"
FWD = STEP + "chainermn.grad/jvp(TransformerLM)/"
BWD = STEP + "chainermn.grad/transpose(jvp(TransformerLM))/"
EXCHANGE = STEP + "chainermn.allreduce_grad/"


def reader(name, root=spec.CHECKOUT):
    return spec.load_module(root, "layer_metrics", name)


# ---- one op_name -----------------------------------------------------------

def test_a_path_splits_into_scopes_whatever_wraps_it():
    path = BWD + "block_1/qkv/dot_general"
    assert scopes.segments(path) == [
        "jit", "inner", "shard_map", "chainermn.grad", "transpose", "jvp",
        "TransformerLM", "block_1", "qkv", "dot_general"]
    assert scopes.under(path, "block_*") and scopes.under(path, "qkv")
    assert not scopes.under(path, "block_2", "head")
    assert scopes.is_backward(path) and not scopes.is_backward(FWD + "head/x")
    assert scopes.top_level(path) == "chainermn.grad"
    assert scopes.top_level(EXCHANGE + "chainermn.pack/concatenate") == (
        "chainermn.allreduce_grad")
    assert scopes.top_level(STEP + "chainermn.pack/concatenate") == "other"
    assert scopes.top_level(STEP + "squeeze") is None
    assert scopes.top_level("") is None
    # an inherited name reads like the name it was taken from
    assert scopes.top_level(scopes.INHERITED + path) == "chainermn.grad"
    # ... also where the path starts with the scope (PR 29's chains)
    assert scopes.top_level(scopes.INHERITED + "chainermn.allreduce_grad/"
                            "chainermn.plan.0.all_reduce/psum") == (
        "chainermn.allreduce_grad")


# ---- from a compiled program's text ----------------------------------------

HLO = """HloModule jit_inner, entry_computation_layout={()->f32[]}

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %convert.1 = bf16[8]{0} convert(%p0), metadata={op_name="jit(inner)/chainermn.allreduce_grad/chainermn.unpack/convert_element_type" stack_frame_id=3}
  ROOT %add.1 = f32[8]{0} add(%p0, %p0), metadata={op_name="jit(inner)/chainermn.update/add" stack_frame_id=4}
}

%late_fusion (p1: f32[8]) -> f32[8] {
  %p1 = f32[8]{0} parameter(0)
  %mul.7 = f32[8]{0} multiply(%p1, %p1), metadata={op_name="jit(inner)/chainermn.grad/jvp(M)/block_0/mul"}
  ROOT %bitcast.2 = f32[8]{0} bitcast(%mul.7)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%Arg_0.1)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %fusion.3 = f32[8]{0} fusion(%copy-done.1), kind=kLoop, calls=%late_fusion
  %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(inner)/chainermn.update/add" stack_frame_id=4}
  %copy.5 = f32[8]{0} copy(%fusion.4)
  ROOT %tuple.6 = (f32[8]{0}) tuple(%copy.5)
}
"""


def test_the_compiled_text_gives_every_instruction_its_scope():
    program = scopes.parse(HLO)
    table = scopes.instruction_scopes(program)
    assert table["fusion.4"] == "jit(inner)/chainermn.update/add"
    assert table["Arg_0.1"] == "params['w']"
    # a fusion made late has no metadata: the commonest op_name inside
    assert table["fusion.3"] == "jit(inner)/chainermn.grad/jvp(M)/block_0/mul"
    # compiler-made copies take their nearest named consumer's, marked
    assert table["copy-done.1"] == scopes.INHERITED + table["fusion.3"]
    assert table["copy-start.1"] == table["copy-done.1"]
    # what feeds only the program's result has nobody to take a name from
    assert "copy.5" not in table and "tuple.6" not in table
    # fusion.4 holds an unpack convert and the optimizer's add
    assert scopes.mixed_fusions(program) == ["fusion.4"]
    events = {"devices": {"/device:TPU:0": [
        ["fusion.4 f32[8]", 0.0, 5.0], ["copy.5 f32[8]", 5.0, 1.0]]}}
    assert scopes.event_scopes(events, table) == {
        "fusion.4 f32[8]": "jit(inner)/chainermn.update/add",
        "copy.5 f32[8]": ""}


# ---- readers on a hand-made document ---------------------------------------

MS = 1e6
LM_OPS = [  # name, start, duration (milliseconds here), op_name
    ["fwd.1 bf16[8]", 0, 100, FWD + "block_0/qkv/dot_general"],
    ["block_0.3 (bf16[8] tpu_custom_call", 100, 50,
     FWD + "block_0/pallas_call"],
    ["head.1 f32[8]", 150, 40, FWD + "head/dot_general"],
    ["loss.1 f32[8]", 190, 10, STEP + "chainermn.grad/jvp()/reduce_max"],
    ["emb.1 f32[8]", 200, 20, BWD + "tok_emb/jit(_take)/scatter-add"],
    ["bwd.1 bf16[8]", 220, 200, BWD + "block_0/qkv/dot_general"],
    ["pack.1 bf16[8]", 420, 20, EXCHANGE + "chainermn.pack/concatenate"],
    # an asynchronous pair: in flight 440..500, the update runs under it
    ["all-reduce-start.1 bf16[8]", 440, 5,
     EXCHANGE + "chainermn.plan.0.all_reduce/psum"],
    ["opt.1 f32[8]", 445, 40, STEP + "chainermn.update/add"],
    ["all-reduce-done.1 bf16[8]", 490, 10,
     EXCHANGE + "chainermn.plan.0.all_reduce/psum"],
    ["unpack.1 f32[8]", 500, 15,
     scopes.INHERITED + EXCHANGE + "chainermn.unpack/mul"],
    ["report.1 f32[]", 515, 5, STEP + "chainermn.report/psum"],
    ["copy.9 f32[8]", 520, 10, ""],
    # a loop spans its body: 40 of its own, 60 in the body
    ["while.1 (f32[8])", 540, 100, FWD + "block_1/while"],
    ["body.1 f32[8]", 550, 60, FWD + "block_1/up/dot_general"],
]
RESNET_OPS = [
    ["conv.1 bf16[8]", 0, 50,
     STEP + "chainermn.grad/jvp(ResNet)/Conv_0/conv_general_dilated"],
    ["bn.1 bf16[8]", 50, 30, STEP + "chainermn.grad/jvp(ResNet)/"
                                    "BottleneckBlock_0/BatchNorm_1/mul"],
]
MOE = "chainermn.grad/jvp(LFM2MoE)/layer_1/moe/"
MOE_BWD = "chainermn.grad/transpose(jvp(LFM2MoE))/layer_1/moe/"
LFM2_OPS = [
    ["fusion.1 f32[8]", 0, 14,
     STEP + "chainermn.grad/jvp(LFM2MoE)/layer_0/conv/chainermn.shortconv/mul"],
    ["fusion.2 bf16[8]", 14, 30,
     STEP + "chainermn.grad/jvp(LFM2MoE)/layer_0/conv/in_proj/dot_general"],
    ["fusion.3 f32[8]", 44, 6, STEP + "chainermn.grad/transpose(jvp(LFM2MoE))"
                                      "/layer_0/conv/chainermn.shortconv/mul"],
    ["fusion.4 f32[8]", 50, 8, STEP + MOE + "chainermn.moe.route/top_k"],
    ["sort.5 s32[8]", 58, 12,
     STEP + MOE + "chainermn.moe.dispatch/jit(argsort)/sort"],
    ["moe.36 (bf16[8] tpu_custom_call", 70, 20,
     STEP + MOE + "chainermn.moe.experts/pallas_call"],
    ["fusion.6 f32[8]", 90, 22, STEP + MOE + "chainermn.moe.combine/scatter-add"],
    # named by its consumer: counts under it all the same
    ["copy.7 bf16[8]", 112, 4,
     scopes.INHERITED + STEP + MOE_BWD + "chainermn.moe.dispatch/scatter-add"],
    ["iota.8 s32[8]", 116, 2, scopes.INHERITED + STEP + MOE_BWD
     + "chainermn.moe.route/jit(take_along_axis)/scatter-add"],
]
# PR 29's asynchronous collective fusions, an XLA pair and a blocking one
WIRE_OPS = [
    ["async-collective-start.3 (bf16[8]", 0, 1, EXCHANGE + "psum"],
    ["fusion.70 bf16[8]", 1, 9, EXCHANGE + "psum"],     # a chain's step
    ["opt.1 f32[8]", 10, 30, STEP + "chainermn.update/add"],
    ["async-collective-done.3 bf16[8]", 40, 16, EXCHANGE + "psum"],
    ["all-reduce-start.1 bf16[8]", 56, 2, EXCHANGE + "psum"],
    ["all-reduce-done.1 bf16[8]", 58, 6, EXCHANGE + "psum"],
    ["all-reduce.9 f32[]", 64, 4, STEP + "chainermn.report/psum"],
    ["all-gather.2 bf16[8]", 68, 10, STEP + "chainermn.grad/all_gather"],
    # one the compiler left as it came keeps the JAX primitive's name
    ["psum_invariant.7 f32[]", 78, 2, STEP + "chainermn.report/psum_invariant"],
]


def document(rows, with_scopes=True):
    events = {"devices": {"/device:TPU:0": [
        [name, start * MS, duration * MS] for name, start, duration, _ in rows
    ]}, "host_spans": []}
    if with_scopes:
        events["scopes"] = {name: path for name, _, _, path in rows}
    return events


HOST = {"steps": 2, "dispatch_s": [0.002, 0.003],
        "compile_info": {"argument_bytes": 3, "temp_bytes": 5}}
LM = {"sizes": {"n_layer": 2, "family": "transformer_lm"}, "chips": 4,
      "peaks": None}
RESNET = {"sizes": {"family": "resnet"}, "chips": 1, "peaks": None}
LFM2 = {"sizes": {"family": "lfm2_moe"}, "chips": 1, "peaks": None}
EXPECTED = {  # per step: the totals of the rows above, halved
    "forward_ms": (LM_OPS, LM, (100 + 50 + 40 + 10 + 40 + 60) / 2),
    "backward_ms": (LM_OPS, LM, (20 + 200) / 2),
    "optimizer_ms": (LM_OPS, LM, 40 / 2),
    # 420..515 with no hole: pack, the pair's 440..500, unpack
    "allreduce_grad_ms": (LM_OPS, LM, 95 / 2),
    # less the 40 the update covers: exposed < total
    "allreduce_grad_exposed_ms": (LM_OPS, LM, 55 / 2),
    "block_ms": (LM_OPS, LM, (100 + 50 + 200 + 40 + 60) / 2 / 2),
    "head_loss_ms": (LM_OPS, LM, (40 + 10) / 2),
    "scope_unnamed_share": (LM_OPS, LM, 100 * 10 / 625),
    "moe_route_ms": (LFM2_OPS, LFM2, (8 + 2) / 2),
    "moe_dispatch_combine_ms": (LFM2_OPS, LFM2, (12 + 22 + 4) / 2),
    "shortconv_ms": (LFM2_OPS, LFM2, (14 + 6) / 2),
    # the dones and the blocking ones; no start, no step of a chain
    "collective_wait_ms": (WIRE_OPS, LM, (16 + 6 + 4 + 10 + 2) / 2),
}
# matched by instruction name: reads the same with the key as without
BY_INSTRUCTION_NAME = {"collective_wait_ms"}


def entries(root=spec.CHECKOUT):
    """Every per-layer entry of ``BENCHMARK.json``.  The rules below are
    asked of each, so a later PR's entry is held to them by being there: no
    list of names in this file decides it."""
    return spec.load_benchmark(root)["per_layer"]


def test_every_case_is_of_an_entry():
    assert set(EXPECTED) <= {m["name"] for m in entries()}


HAND_MADE = [(LM_OPS, LM), (WIRE_OPS, LM), (LFM2_OPS, LFM2),
             (RESNET_OPS, RESNET)]


@pytest.mark.parametrize("name", [m["name"] for m in entries()])
def test_a_reader_needs_the_key_or_never_sees_it(name):
    """Rule (2), on the hand-made documents; ``check_recording`` asks the
    same of every chip recording."""
    for rows, context in HAND_MADE:
        keyed = reader(name).read(document(rows), HOST, context)
        keyless = reader(name).read(
            document(rows, with_scopes=False), HOST, context)
        assert keyless is None or keyless == keyed, (name, keyed, keyless)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_made_document(name):
    rows, context, expected = EXPECTED[name]
    assert reader(name).read(document(rows), HOST, context) == (
        pytest.approx(expected))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_without_the_key(name):
    rows, context, expected = EXPECTED[name]
    keyless = reader(name).read(
        document(rows, with_scopes=False), HOST, context)
    if name in BY_INSTRUCTION_NAME:
        assert keyless == pytest.approx(expected)
        assert reader(name).read(document(LM_OPS[:7]), HOST, LM) is None
    else:
        assert keyless is None


def test_readers_keep_to_their_cells():
    lm, resnet = document(LM_OPS), document(RESNET_OPS)
    for name in ("moe_route_ms", "moe_dispatch_combine_ms", "shortconv_ms"):
        assert reader(name).read(lm, HOST, LM) is None
        assert reader(name).read(resnet, HOST, RESNET) is None
    assert reader("block_ms").read(document(LFM2_OPS), HOST, LFM2) is None
    assert reader("block_ms").read(resnet, HOST, RESNET) is None
    assert reader("head_loss_ms").read(resnet, HOST, RESNET) is None
    # on one chip nobody answers: the scope holds a cast's round trip
    one_chip = dict(LM, chips=1)
    assert reader("allreduce_grad_exposed_ms").read(lm, HOST, one_chip) is None
    assert reader("allreduce_grad_ms").read(lm, HOST, one_chip) is None


def test_top_level_scopes_partition_the_self_time():
    events = document(LM_OPS)
    totals = scopes.by_top_level(events)
    assert {k: v / MS for k, v in totals.items()} == pytest.approx({
        "chainermn.grad": 520, "chainermn.allreduce_grad": 50,
        "chainermn.update": 40, "chainermn.report": 5, "other": 0,
        "none": 10})
    own = rt.self_times(rt.first_device(events))
    assert sum(totals.values()) == pytest.approx(sum(own.values()))
    alone = scopes.exposed(
        events, lambda path: scopes.under(path, scopes.ALLREDUCE_GRAD))
    assert [[a / MS, b / MS] for a, b in alone] == [[420, 445], [485, 515]]


# ---- PR 23's fixtures: no key, and a key changes no reader by name ------------

def readings(events, context, root=spec.CHECKOUT):
    return {m["name"]: reader(m["name"], root).read(
        events, HOST, context) for m in entries(root)}


@pytest.mark.parametrize("name", sorted(os.listdir(FIXTURES)))
def test_the_readers_the_benchmark_had_do_not_see_the_key(name):
    events = rt.load_events(os.path.join(FIXTURES, name))
    cell = spec.resolve(name.split(".")[0])
    peaks = spec.load_peaks("TPU v5 lite")
    context = {"sizes": cell.sizes, "chips": cell.chips, "peaks": peaks}
    assert "scopes" not in events
    before = readings(events, context)
    for case in set(EXPECTED) - BY_INSTRUCTION_NAME:
        assert before[case] is None
    assert sum(value is not None for value in before.values()) >= 4
    keyed = dict(events, scopes=scopes.event_scopes(
        events, {"fusion.9": FWD + "head/dot_general"}))
    after = readings(keyed, context)
    assert {k: v for k, v in after.items() if before[k] is not None} == {
        k: v for k, v in before.items() if v is not None}
    assert json.dumps(rt.breakdown(keyed)) == json.dumps(rt.breakdown(events))


# ---- the scoped recordings, one a cell ---------------------------------------

RECORDED = sorted(name for name in os.listdir(SCOPED)
                  if name.endswith(".json.gz"))


def cells_and_recordings(root):
    cells = {w["name"] for w in spec.load_benchmark(root)["workloads"]}
    recorded = {name.split(".")[0] for name in os.listdir(
        os.path.join(root, "chipbench", "fixtures_scoped"))}
    return cells, recorded


def test_every_cell_has_a_scoped_recording():
    cells, recorded = cells_and_recordings(spec.CHECKOUT)
    assert recorded == cells


def check_recording(root, name):
    """Both rules for every entry of the benchmark under ``root``, and the
    numbers hang together."""
    events = rt.load_events(
        os.path.join(root, "chipbench", "fixtures_scoped", name))
    cell = spec.resolve(name.split(".")[0], root)
    assert len(events["devices"]) == cell.chips
    context = {"sizes": cell.sizes, "chips": cell.chips,
               "peaks": spec.load_peaks("TPU v5 lite", root)}
    # the top-level scopes partition the first device's self time
    totals = scopes.by_top_level(events)
    own = sum(rt.self_times(rt.first_device(events)).values())
    assert sum(totals.values()) == pytest.approx(own, rel=1e-3)
    assert totals["other"] == 0
    assert totals["chainermn.grad"] > 0.5 * own
    read = readings(events, context, root)
    keyless = readings(
        {k: v for k, v in events.items() if k != "scopes"}, context, root)
    values = {}
    for metric in entries(root):
        value = read[metric["name"]]
        # (1) a number exactly on the cells the entry lists
        if spec.applies(metric, cell.name):
            assert value is not None and value >= 0, metric["name"]
            values[metric["name"]] = value
        else:
            assert value is None, metric["name"]
        # (2) the key is needed, or never seen
        assert keyless[metric["name"]] in (None, value), metric["name"]
    assert sorted(values) == sorted(m["name"] for m in cell.per_layer)
    assert values["backward_ms"] > values["forward_ms"] > 0
    assert values["scope_unnamed_share"] < 10
    in_step = (values["forward_ms"] + values["backward_ms"]
               + values["optimizer_ms"])
    assert 0.8 * own < in_step * 2e6 <= own
    if cell.chips > 1:
        # the wait is part of what the exchange costs, which is part of
        # the time it is in flight
        assert 0 < values["collective_wait_ms"] < (
            values["allreduce_grad_exposed_ms"]) <= values["allreduce_grad_ms"]
        stage = scopes.ms_per_step(
            events, HOST, lambda path: scopes.under(
                path, "chainermn.plan.0.all_reduce"))
        assert 0 < stage <= values["allreduce_grad_ms"]
    if "moe_route_ms" in values:
        assert 0 < values["moe_route_ms"] < values["moe_dispatch_combine_ms"]
        assert values["shortconv_ms"] > 0
    return events, values


@pytest.mark.parametrize("name", RECORDED)
def test_scoped_recording_reads_by_scope(name):
    check_recording(spec.CHECKOUT, name)


def test_the_four_chip_recording_holds_asynchronous_collective_fusions():
    """Two steps of this tree's dp4 step (PR 30's chip run): the exchange is
    PR 29's chains, and the readers read it as they did on the chip."""
    from chipbench.layer_metrics import collective_wait_ms

    events, values = check_recording(
        spec.CHECKOUT, "starcoder1b-dp4-t8192.two-steps.json.gz")
    names = [name for name, _, _ in rt.first_device(events)]
    starts = [n for n in names if n.startswith("async-collective-start")]
    dones = [n for n in names if n.startswith("async-collective-done")]
    assert len(starts) == len(dones) == 2 * 43
    # the two blocking ones left: the 83 vectors as one, and the loss
    assert [n.split(".")[0] for n in names if n.startswith(
        ("all-reduce", "psum"))] == 2 * ["all-reduce", "psum_invariant"]
    waits = {n for n in names if collective_wait_ms.is_wait(n)}
    assert set(dones) < waits and not set(starts) & waits
    assert RECORDED_DP4 == {
        name: pytest.approx(values[name], abs=0.01) for name in RECORDED_DP4}


# what the readers give on the two kept steps (the run's ten read 15.48,
# 40.55, 9.29 and 6.17: PERF.md section 5)
RECORDED_DP4 = {"allreduce_grad_exposed_ms": 15.387,
                "allreduce_grad_ms": 40.539, "collective_wait_ms": 9.280,
                "optimizer_ms": 6.175}


# ---- chipbench.run carries the scopes itself ----------------------------------

def test_only_a_traced_compile_is_keyed_on_the_names(capsys):
    import jax

    from chipbench import harness, run

    option = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, option)
    assert before is False
    with pytest.raises(RuntimeError):
        with harness._cache_keyed_on_names():
            assert getattr(jax.config, option) is True
            raise RuntimeError("a compile that fails")
    assert getattr(jax.config, option) is before
    assert run.main(["--workload", "no-such-cell", "--seed", "1",
                     "--seconds", "1", "--trace", "1"]) == 2
    assert "no cell 'no-such-cell'" in capsys.readouterr().err


def _rehearse(arguments, chips):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    env.pop("JAX_NUM_CPU_DEVICES", None)
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--seconds", "2", "--trace",
         "1", "--rehearse"] + arguments,
        cwd=spec.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1]["correct"] is True and lines[-1]["metrics"] == {}
    numbers = [l for l in lines if l.get("phase") == "rehearsal_numbers"][0]
    described = [l for l in lines if l.get("phase") == "scopes"][0]
    return numbers, described


def test_traced_rehearsal_reads_every_scoped_metric(tmp_path):
    """``chipbench.run --trace 1`` end to end on the CPU: every scoped
    metric of the cell beside the others (under ``cpu_`` names: a
    rehearsal), the line that says how far the names reach, and a kept
    trace that carries ``"scopes"``."""
    kept = tmp_path / "events.json.gz"
    cell = "starcoder1b-dp4-t8192"
    numbers, described = _rehearse(
        ["--workload", cell, "--seed", str(2**31 + 24), "--keep-trace",
         str(kept)], chips=4)
    for metric in entries():
        if not spec.applies(metric, cell):
            assert "cpu_" + metric["name"] not in numbers
        elif metric["name"] in EXPECTED:
            assert numbers["cpu_" + metric["name"]] >= 0
    assert "cpu_device_step_ms" in numbers
    assert numbers["cpu_allreduce_grad_exposed_ms"] <= (
        numbers["cpu_allreduce_grad_ms"])
    assert described["instructions_with_op_name"] > 100
    by_scope = described["window_ms_by_top_level"]
    assert by_scope["chainermn.grad"] > by_scope["chainermn.update"] > 0
    assert by_scope["chainermn.allreduce_grad"] > 0 and by_scope["other"] == 0
    events = rt.load_events(str(kept))
    assert any(scopes.under(path, "chainermn.plan.0.all_reduce")
               for path in events["scopes"].values())
    assert set(events["scopes"]) == {
        name for ops in events["devices"].values() for name, _, _ in ops}


# ---- the contract for the next PR: a cell is new files and appended names ----

def _files(root):
    held = {}
    for folder, _, names in os.walk(root):
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as handle:
                held[os.path.relpath(path, root)] = handle.read()
    return held


MLP_MS = '''"""``mlp_ms``: self time per step under the blocks' ``up`` and ``down``
modules (layer: models).  Read where a module of either name ran.  Needs
the EVENTS document's ``"scopes"``."""

from chipbench import scopes


def read(events, host, context):
    return scopes.ms_per_step(
        events, host, lambda path: scopes.under(path, "up", "down")) or None
'''


def test_a_cell_is_added_by_new_files_and_appended_names_alone(tmp_path):
    """What a PR that adds a cell does, and nothing else: a traffic file, its
    limits, two traced steps under the cell's name and a reader by scope of
    its own; in ``BENCHMARK.json`` the cell's and the reader's entries, and
    the cell's name appended to the lists of the metrics that read it.  Then
    the cell resolves, EVERY recording reads by both rules as that benchmark
    says (the new reader a number on the cells it lists, None on the
    others), and the cell rehearses; no file that was there has changed."""
    root = str(tmp_path / "checkout")
    bench = spec.load_benchmark()
    for path in bench["paths"]:
        shutil.copytree(os.path.join(spec.CHECKOUT, path),
                        os.path.join(root, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(root)
    like, new = "starcoder1b-t2048", "starcoder1b-t4096"
    held = os.path.join(root, "chipbench")
    with open(os.path.join(held, "traffic", "b2-t4096.json"), "w") as handle:
        json.dump({"name": "b2-t4096", "kind": "train_steps",
                   "layout": "data_parallel", "batch_per_chip": 2,
                   "seq_len": 4096, "ring": 8, "warmup_steps": 6,
                   "interval_steps": 1, "trace_steps": 10,
                   "toy": {"batch_per_chip": 2, "seq_len": 96,
                           "trace_steps": 3}}, handle)
    shutil.copy(os.path.join(held, "limits", like + ".json"),
                os.path.join(held, "limits", new + ".json"))
    recording = new + ".two-steps.json.gz"
    shutil.copy(
        os.path.join(held, "fixtures_scoped", like + ".two-steps.json.gz"),
        os.path.join(held, "fixtures_scoped", recording))
    with open(os.path.join(held, "layer_metrics", "mlp_ms.py"), "w") as handle:
        handle.write(MLP_MS)
    bench["workloads"].append({
        "name": new, "config": "starcoderbase-1b", "traffic": "b2-t4096",
        "chips": 1, "why": "2 rows of 4096 tokens: a test's cell"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if like in metric.get("workloads", ()):
            metric["workloads"].append(new)
    block_ms = [m for m in bench["per_layer"] if m["name"] == "block_ms"][0]
    bench["per_layer"].append(dict(block_ms, name="mlp_ms"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as handle:
        json.dump(bench, handle)

    cell, model = spec.resolve(new, root), spec.resolve(like)
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in model.per_layer] + ["mlp_ms"]
    assert cell.sizes["seq_len"] == 4096 and cell.sizes["n_layer"] == 10
    cells, recorded = cells_and_recordings(root)
    assert recorded == cells and new in cells
    for name in sorted(os.listdir(os.path.join(held, "fixtures_scoped"))):
        _, values = check_recording(root, name)
        assert ("mlp_ms" in values) == name.startswith("starcoder1b")
        if name == recording:
            assert 0 < values["mlp_ms"] < 10 * values["block_ms"]
    numbers, described = _rehearse(
        ["--workload", new, "--seed", str(2**31 + 30), "--root", root],
        chips=1)
    for metric in entries(root):
        if not spec.applies(metric, new):
            assert "cpu_" + metric["name"] not in numbers
        elif metric["name"] in set(EXPECTED) | {"mlp_ms"}:
            assert numbers["cpu_" + metric["name"]] >= 0, metric["name"]
    assert "cpu_device_step_ms" in numbers and "cpu_block_ms" in numbers
    assert described["instructions_with_op_name"] > 100
    after = _files(root)
    assert {path: data for path, data in after.items()
            if path in before} == before
    assert sorted(set(after) - set(before)) == sorted([
        "BENCHMARK.json", "chipbench/traffic/b2-t4096.json",
        f"chipbench/limits/{new}.json", "chipbench/layer_metrics/mlp_ms.py",
        f"chipbench/fixtures_scoped/{recording}"])
