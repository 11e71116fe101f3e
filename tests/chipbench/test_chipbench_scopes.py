"""chipbench/scopes.py and the readers built on it: on a compiled program's
text, on hand-made EVENTS documents with a ``"scopes"`` key, on PR 23's
fixtures (which have none: every reader returns None, and the readers the
benchmark had give the same bytes with the key as without), and on the
short scoped recordings of PR 24's chip runs under
``chipbench/fixtures_scoped/``."""

import json
import os

import pytest

from chipbench import reduce_trace as rt
from chipbench import scopes, spec

FIXTURES = os.path.join(spec.HERE, "fixtures")
SCOPED = os.path.join(spec.HERE, "fixtures_scoped")
with open(os.path.join(spec.HERE, "scoped_metrics.json")) as _handle:
    SCOPED_METRICS = json.load(_handle)["per_layer"]
OLD_READERS = [m["name"] for m in spec.load_benchmark()["per_layer"]]
STEP = "jit(inner)/shard_map/"
FWD = STEP + "chainermn.grad/jvp(TransformerLM)/"
BWD = STEP + "chainermn.grad/transpose(jvp(TransformerLM))/"
EXCHANGE = STEP + "chainermn.allreduce_grad/"


def reader(name):
    return spec.load_module(spec.CHECKOUT, "layer_metrics", name)


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
    ["bn.2 bf16[8]", 80, 10,
     STEP + "chainermn.grad/transpose(jvp(ResNet))/bn_init/mul"],
    ["bn.3 bf16[8]", 90, 6, STEP + "chainermn.grad/jvp(ResNet)/"
                                   "BottleneckBlock_0/norm_proj/add"],
]


def document(rows, with_scopes=True):
    events = {"devices": {"/device:TPU:0": [
        [name, start * MS, duration * MS] for name, start, duration, _ in rows
    ]}, "host_spans": []}
    if with_scopes:
        events["scopes"] = {name: path for name, _, _, path in rows}
    return events


HOST = {"steps": 2, "dispatch_s": [0.001, 0.001], "compile_info": {}}
LM = {"sizes": {"n_layer": 2, "family": "transformer_lm"}, "chips": 4,
      "peaks": None}
RESNET = {"sizes": {"family": "resnet"}, "chips": 1, "peaks": None}
EXPECTED = {  # per step: the totals of the rows above, halved
    "forward_ms": (LM_OPS, LM, (100 + 50 + 40 + 10 + 40 + 60) / 2),
    "backward_ms": (LM_OPS, LM, (20 + 200) / 2),
    "optimizer_ms": (LM_OPS, LM, 40 / 2),
    # 420..515 with no hole: pack, the pair's 440..500, unpack
    "allreduce_grad_ms": (LM_OPS, LM, 95 / 2),
    # less the 40 the update covers: exposed < total
    "allreduce_grad_exposed_ms": (LM_OPS, LM, 55 / 2),
    "pack_unpack_ms": (LM_OPS, LM, (20 + 15) / 2),
    "block_ms": (LM_OPS, LM, (100 + 50 + 200 + 40 + 60) / 2 / 2),
    "head_loss_ms": (LM_OPS, LM, (40 + 10) / 2),
    "norm_ms": (RESNET_OPS, RESNET, (30 + 10 + 6) / 2),
    "scope_unnamed_share": (LM_OPS, LM, 100 * 10 / 625),
}


def test_every_scoped_metric_has_its_case():
    assert sorted(EXPECTED) == sorted(m["name"] for m in SCOPED_METRICS)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_made_document(name):
    rows, context, expected = EXPECTED[name]
    assert reader(name).read(document(rows), HOST, context) == (
        pytest.approx(expected))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_without_the_key(name):
    rows, context, _ = EXPECTED[name]
    assert reader(name).read(
        document(rows, with_scopes=False), HOST, context) is None


def test_readers_keep_to_their_cells():
    lm, resnet = document(LM_OPS), document(RESNET_OPS)
    assert reader("norm_ms").read(lm, HOST, LM) is None
    assert reader("block_ms").read(resnet, HOST, RESNET) is None
    assert reader("head_loss_ms").read(resnet, HOST, RESNET) is None
    one_chip = dict(LM, chips=1)
    assert reader("allreduce_grad_exposed_ms").read(lm, HOST, one_chip) is None
    assert reader("allreduce_grad_ms").read(lm, HOST, one_chip) == (
        pytest.approx(47.5))


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


# ---- PR 23's fixtures: same bytes with the key as without -------------------

def _old_readings(events, context):
    host = {"steps": 2, "dispatch_s": [0.002, 0.003],
            "compile_info": {"argument_bytes": 3, "temp_bytes": 5}}
    values = {name: reader(name).read(events, host, context)
              for name in OLD_READERS}
    return json.dumps({"metrics": values,
                       "breakdown": rt.breakdown(events)}).encode()


@pytest.mark.parametrize("name", sorted(os.listdir(FIXTURES)))
def test_the_readers_the_benchmark_had_do_not_see_the_key(name):
    events = rt.load_events(os.path.join(FIXTURES, name))
    cell = spec.resolve(name.split(".")[0])
    peaks = spec.load_peaks("TPU v5 lite")
    context = {"sizes": cell.sizes, "chips": cell.chips, "peaks": peaks}
    assert "scopes" not in events
    before = _old_readings(events, context)
    for metric in SCOPED_METRICS:
        assert reader(metric["name"]).read(
            events, {"steps": 2}, context) is None
    keyed = dict(events, scopes=scopes.event_scopes(
        events, {"fusion.9": FWD + "head/dot_general"}))
    assert _old_readings(keyed, context) == before


# ---- PR 24's scoped recordings ---------------------------------------------

RECORDED = sorted(name for name in os.listdir(SCOPED)
                  if name.endswith(".json.gz"))


def test_every_cell_has_a_scoped_recording():
    cells = {w["name"] for w in spec.load_benchmark()["workloads"]}
    assert {name.split(".")[0] for name in RECORDED} == cells


@pytest.mark.parametrize("name", RECORDED)
def test_scoped_recording_reads_by_scope(name):
    events = rt.load_events(os.path.join(SCOPED, name))
    cell = spec.resolve(name.split(".")[0])
    assert len(events["devices"]) == cell.chips
    host = {"steps": 2}
    context = {"sizes": cell.sizes, "chips": cell.chips, "peaks": None}
    # the top-level scopes partition the first device's self time
    totals = scopes.by_top_level(events)
    own = sum(rt.self_times(rt.first_device(events)).values())
    assert sum(totals.values()) == pytest.approx(own, rel=1e-3)
    assert totals["other"] == 0
    assert totals["chainermn.grad"] > 0.5 * own
    values = {}
    for metric in SCOPED_METRICS:
        value = reader(metric["name"]).read(events, host, context)
        if cell.name in metric["workloads"]:
            assert value is not None and value >= 0, metric["name"]
            values[metric["name"]] = value
        else:
            assert value is None, metric["name"]
    assert values["backward_ms"] > values["forward_ms"] > 0
    assert values["scope_unnamed_share"] < 10
    in_step = (values["forward_ms"] + values["backward_ms"]
               + values["optimizer_ms"])
    assert 0.8 * own < in_step * 2e6 <= own
    if cell.chips > 1:
        assert 0 < values["allreduce_grad_exposed_ms"] <= (
            values["allreduce_grad_ms"])
        # the all-reduce the trace names psum_invariant is inside
        stage = scopes.ms_per_step(events, host, lambda path: scopes.under(
            path, "chainermn.plan.0.all_reduce"))
        assert 0 < stage <= values["allreduce_grad_ms"]
    # the readers the benchmark had read the recording as before
    keyless = {k: v for k, v in events.items() if k != "scopes"}
    old = {"sizes": cell.sizes, "chips": cell.chips,
           "peaks": spec.load_peaks("TPU v5 lite")}
    assert _old_readings(events, old) == _old_readings(keyless, old)


# ---- the wrapper that carries the scopes -----------------------------------

def test_scoped_run_leaves_nothing_patched(capsys):
    from chipbench import harness, scoped_run

    before = (harness.Run.compile, rt.reduce_directory, spec.load_benchmark)
    assert scoped_run.main(["--workload", "no-such-cell", "--seed", "1",
                            "--seconds", "1", "--trace", "1"]) == 2
    assert "no cell 'no-such-cell'" in capsys.readouterr().err
    assert before == (harness.Run.compile, rt.reduce_directory,
                      spec.load_benchmark)


def test_scoped_run_rehearsal_reads_every_scoped_metric(tmp_path):
    """``chipbench.scoped_run`` end to end on the CPU: the same lines as
    ``chipbench.run``, the scoped metrics beside the others (under ``cpu_``
    names: a rehearsal), and a kept trace that carries ``"scopes"``."""
    import subprocess
    import sys

    kept = tmp_path / "events.json.gz"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_NUM_CPU_DEVICES", None)
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.scoped_run", "--workload",
         "starcoder1b-dp4-t8192", "--seed", str(2**31 + 24), "--seconds",
         "2", "--trace", "1", "--rehearse", "--keep-trace", str(kept)],
        cwd=spec.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1]["correct"] is True and lines[-1]["metrics"] == {}
    numbers = [l for l in lines if l.get("phase") == "rehearsal_numbers"][0]
    for metric in SCOPED_METRICS:
        if "starcoder1b-dp4-t8192" in metric["workloads"]:
            assert numbers["cpu_" + metric["name"]] >= 0
    assert "cpu_norm_ms" not in numbers and "cpu_device_step_ms" in numbers
    assert numbers["cpu_allreduce_grad_exposed_ms"] <= (
        numbers["cpu_allreduce_grad_ms"])
    described = [l for l in lines if l.get("phase") == "scopes"][0]
    assert described["instructions_with_op_name"] > 100
    by_scope = described["window_ms_by_top_level"]
    assert by_scope["chainermn.grad"] > by_scope["chainermn.update"] > 0
    assert by_scope["chainermn.allreduce_grad"] > 0 and by_scope["other"] == 0
    events = rt.load_events(str(kept))
    assert any(scopes.under(path, "chainermn.plan.0.all_reduce")
               for path in events["scopes"].values())
    assert set(events["scopes"]) == {
        name for ops in events["devices"].values() for name, _, _ in ops}
