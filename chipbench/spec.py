"""From a cell's name to its files.  ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; everything else is found by those names
under the benchmark's directory:

    configs/<config>.json           sizes as run, ``family``, ``toy`` sizes
    traffic/<traffic>.json          batch, sequence, ring, layout, ``toy``
    families/<family>.py            the program behind its public entry points
    references/<family>.py          the plain float32 reference
    limits/<cell>.json              the limit of each number compared
    layer_metrics/<metric>.py       one reader per per-layer metric
    peaks.json                      peaks per ``device_kind``
    fixtures_scoped/<cell>.two-steps.json.gz   two traced steps of a chip run
                                    (``--keep-trace``), which the tests read

A later PR adds a cell, configuration, family or metric by adding files and
``BENCHMARK.json`` entries, and a cell's name to the ``workloads`` lists of
the metrics that read it; nothing here or in ``run.py`` names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _load_json(path):
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    with open(path) as handle:
        return json.load(handle)


def load_module(root, *parts):
    """Import ``<root>/chipbench/<parts...>.py`` by path (so that a test, or
    a later PR, adds one by adding a file)."""
    path = os.path.join(root, "chipbench", *parts) + ".py"
    if not os.path.isfile(path):
        raise SpecError(f"missing module {path}")
    name = "chipbench_found." + ".".join(parts) + "." + str(abs(hash(path)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    root: str
    sizes: dict             # what is run: config + traffic, or their toys
    family: object
    reference: object
    limits: dict
    end_to_end: list        # the end-to-end metric entries this cell reports
    per_layer: list         # the per-layer metric entries this cell may report

    def layer_reader(self, metric_name):
        return load_module(self.root, "layer_metrics", metric_name)


def load_benchmark(root=CHECKOUT):
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_peaks(device_kind, root=CHECKOUT):
    table = _load_json(os.path.join(root, "chipbench", "peaks.json"))
    if device_kind not in table["kinds"]:
        raise SpecError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json "
            f"(known: {sorted(table['kinds'])}): add its published peaks "
            "with their source, a missing kind is an error and not a default")
    return table["kinds"][device_kind]


def applies(metric, cell_name):
    """Whether ``cell_name`` reports the metric: it is in the entry's
    ``workloads`` list, or the entry has none (then every cell does)."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(cell_name, root=CHECKOUT, rehearse=False) -> Cell:
    bench = load_benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == cell_name]
    if not entries:
        raise SpecError(
            f"no cell {cell_name!r} in BENCHMARK.json (cells: "
            f"{[w['name'] for w in bench['workloads']]})")
    entry = entries[0]
    configs = [c for c in bench["configs"] if c["name"] == entry["config"]]
    if not configs:
        raise SpecError(f"cell {cell_name!r} names the unknown configuration "
                        f"{entry['config']!r}")
    config = _load_json(os.path.join(root, configs[0]["file"]))
    traffic = _load_json(os.path.join(
        root, "chipbench", "traffic", entry["traffic"] + ".json"))
    sizes = {k: v for k, v in config.items() if k != "toy"}
    sizes.update({k: v for k, v in traffic.items() if k not in ("toy", "name")})
    if rehearse:
        sizes.update(config.get("toy", {}))
        sizes.update(traffic.get("toy", {}))
    end_to_end = [m for m in bench["end_to_end"] if applies(m, cell_name)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m, cell_name) and m["moves"] in reported]
    limits = _load_json(os.path.join(
        root, "chipbench", "limits", cell_name + ".json"))
    if rehearse:
        limits = limits["toy"]
    return Cell(
        name=cell_name, chips=int(entry["chips"]), why=entry["why"],
        root=root, sizes=sizes,
        family=load_module(root, "families", config["family"]),
        reference=load_module(root, "references", config["family"]),
        limits=limits, end_to_end=end_to_end, per_layer=per_layer)
