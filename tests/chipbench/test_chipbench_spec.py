"""BENCHMARK.json keeps its contract, and every name in it resolves to its
files under the benchmark's directories."""

import json
import os
import re

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head",
               "embd", "inner", "width", "expansion")


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    cells = 24    # the check must fit with the full 24 cells
    runs = 2 + 14 * cells
    assert (runs * (BENCH["run_seconds"] + 60) + cells * 2 * 90 + 1200
            <= 43200)
    assert 1 <= len(BENCH["paths"]) <= 16
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(spec.CHECKOUT, path))
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    size = os.path.getsize(os.path.join(spec.CHECKOUT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_units_and_keys():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [entry["name"] for entry in BENCH[group]]
        assert len(names) == len(set(names))
        for name in names:
            assert NAME.match(name), name
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for metric in metrics:
        assert metric["name"] not in seen
        seen.add(metric["name"])
        assert UNIT.match(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
    for metric in BENCH["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in BENCH["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    for cell in BENCH["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4)
        assert NAME.match(cell["traffic"]) and len(cell["why"]) <= 200
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(c["chips"] == 4 for c in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


def test_configs_name_their_files_and_reduce_no_width():
    used = {c["config"] for c in BENCH["workloads"]}
    files = set()
    for config in BENCH["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert config["name"] in used
        assert config["file"] not in files
        files.add(config["file"])
        assert any(config["file"].startswith(p + "/")
                   for p in BENCH["paths"])
        with open(os.path.join(spec.CHECKOUT, config["file"])) as handle:
            held = json.load(handle)
        assert held["source"] == config["source"]
        assert held["reduced"] == config["reduced"]
        for key in config["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(word in key for word in WIDTH_WORDS), key
        for key in ("family", "assumed", "departures", "toy", "inputs"):
            assert key in held, key


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    for rehearse in (False, True):
        cell = spec.resolve(name, rehearse=rehearse)
        for attribute in ("make_comm", "make_params", "build", "params_of",
                          "first_gradient_of", "first_gradient_after",
                          "units_per_step", "flop_per_unit", "min_kernels",
                          "THROUGHPUT_METRIC"):
            assert hasattr(cell.family, attribute), attribute
        assert hasattr(cell.reference, "make_loss")
        for number in ("loss", "grad_norm", "delta_norm"):
            assert cell.limits[number] > 0
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.family.THROUGHPUT_METRIC in reported
        assert cell.per_layer
        for metric in cell.per_layer:
            assert callable(cell.layer_reader(metric["name"]).read)
    full, toy = spec.resolve(name), spec.resolve(name, rehearse=True)
    assert toy.sizes["batch_per_chip"] <= 32 < 257
    assert full.family.flop_per_unit(full.sizes) > toy.family.flop_per_unit(
        toy.sizes)


def test_the_language_model_keeps_its_published_widths():
    sizes = spec.resolve("starcoder1b-t8192").sizes
    assert (sizes["n_embd"], sizes["n_head"], sizes["n_inner"],
            sizes["vocab_size"], sizes["n_positions"], sizes["seq_len"]) == (
                2048, 16, 8192, 49152, 8192, 8192)
    assert sizes["multi_query"] is True
    assert sizes["n_layer"] < sizes["n_layer_published"] == 24
    one, four = (spec.resolve(n).sizes for n in
                 ("starcoder1b-t8192", "starcoder1b-dp4-t8192"))
    assert one["n_layer"] == four["n_layer"]


def test_peaks_are_keyed_by_device_kind_and_a_missing_kind_is_an_error():
    peaks = spec.load_peaks("TPU v5 lite")
    assert (peaks["bf16_tflops"], peaks["int8_tops"],
            peaks["hbm_gbytes_per_s"], peaks["ici_gbits_per_s"]) == (
                197.0, 393.0, 819.0, 1600.0)
    with pytest.raises(spec.SpecError):
        spec.load_peaks("cpu")
    with pytest.raises(spec.SpecError):
        spec.resolve("no-such-cell")
