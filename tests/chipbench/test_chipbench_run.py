"""The command, end to end on the CPU: ``--rehearse`` of every cell, the
refusal to measure without a chip, and a throw-away cell, configuration and
per-layer metric added by new files and BENCHMARK.json entries alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def _run(arguments, chips=1, cwd=spec.CHECKOUT):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    env.pop("JAX_NUM_CPU_DEVICES", None)
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run"] + arguments, cwd=cwd,
        env=env, capture_output=True, text=True, timeout=900)


def _last(done):
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_ends_in_a_well_formed_line_that_names_the_cpu(name, trace):
    chips = spec.resolve(name).chips
    done = _run(["--workload", name, "--seed", str(2**31 + 11 + trace),
                 "--seconds", "2", "--trace", str(trace), "--rehearse"],
                chips=chips)
    result = _last(done)
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    # a CPU run's numbers are never written under a device metric's name
    assert result["metrics"] == {} and result["rehearsal"] is True
    assert "busy_s" not in result["device"] and "breakdown" not in result
    lines = [json.loads(line) for line in done.stdout.splitlines()
             if line.startswith("{")]
    checks = [line for line in lines if line.get("phase") == "check"]
    assert {c["check"] for c in checks} == {
        "loss_step1", "loss_step2", "loss_step3", "grad_norm", "delta_norm"}
    assert all("limit" in c and "value" in c for c in checks)
    numbers = [line for line in lines
               if line.get("phase") == "rehearsal_numbers"]
    assert numbers and all(k.startswith("cpu_") or k in ("phase", "platform")
                           for k in numbers[0])


def test_without_a_chip_the_command_fails_and_prints_no_result():
    done = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0"])
    assert done.returncode != 0
    assert "no accelerator" in done.stderr
    assert done.stdout.strip() == ""


def test_an_unknown_cell_fails():
    done = _run(["--workload", "no-such-cell", "--seed", "1", "--seconds",
                 "1", "--trace", "0", "--rehearse"])
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_a_directory_with_only_the_benchmark_fails(tmp_path):
    """BENCHMARK.json and the files under ``paths`` alone (no program)."""
    bench = spec.load_benchmark()
    shutil.copy(os.path.join(spec.CHECKOUT, "BENCHMARK.json"), tmp_path)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(spec.CHECKOUT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0", "--rehearse"], cwd=tmp_path)
    assert done.returncode != 0
    assert not any(line.startswith('{"correct"')
                   for line in done.stdout.splitlines())


def test_a_new_cell_config_and_metric_are_files_and_entries_alone(tmp_path):
    """A later PR may add files and entries and edit nothing: a throw-away
    configuration (a wider toy), traffic mix, cell and per-layer metric."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.CHECKOUT, "chipbench"),
                    root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_benchmark()
    with open(os.path.join(spec.HERE, "configs", "starcoderbase-1b.json")) as f:
        config = json.load(f)
    config.update(name="throwaway-lm", n_layer=3)
    config["toy"].update(n_layer=1, n_embd=32, n_inner=128)
    (root / "chipbench" / "configs" / "throwaway-lm.json").write_text(
        json.dumps(config))
    (root / "chipbench" / "traffic" / "b3-t64.json").write_text(json.dumps({
        "name": "b3-t64", "kind": "train_steps", "layout": "data_parallel",
        "batch_per_chip": 3, "seq_len": 64, "ring": 4, "warmup_steps": 4,
        "interval_steps": 1, "trace_steps": 3, "toy": {}}))
    shutil.copy(root / "chipbench" / "limits" / "starcoder1b-t8192.json",
                root / "chipbench" / "limits" / "throwaway.json")
    (root / "chipbench" / "layer_metrics" / "throwaway_steps.py").write_text(
        "def read(events, host, context):\n    return float(host['steps'])\n")
    bench["configs"].append({
        "name": "throwaway-lm", "source": config["source"],
        "file": "chipbench/configs/throwaway-lm.json",
        "reduced": ["n_layer"], "why": "test"})
    bench["workloads"].append({
        "name": "throwaway", "config": "throwaway-lm", "traffic": "b3-t64",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "throwaway_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "entry point / host loop",
        "moves": "mfu", "workloads": ["throwaway"]})
    for metric in bench["end_to_end"]:
        if metric["name"] == "tokens_per_s":
            metric["workloads"].append("throwaway")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    done = _run(["--workload", "throwaway", "--seed", "7", "--seconds", "1",
                 "--trace", "1", "--rehearse", "--root", str(root)])
    result = _last(done)
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["attempted"] == 3
    numbers = [json.loads(line) for line in done.stdout.splitlines()
               if '"rehearsal_numbers"' in line]
    assert numbers[0]["cpu_throwaway_steps"] == 3.0
    assert "cpu_dispatch_ms" in numbers[0]
