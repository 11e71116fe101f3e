"""The scoped metrics' lists of cells, brought up to the cells the benchmark
has now.

``chipbench/scoped_metrics.json`` (PR 24) lists the cells each reader by
scope reads, and ``test_chipbench_scopes.py`` holds every cell of
``BENCHMARK.json`` to a scoped recording that those lists name.  A PR that
adds a cell may edit no file the benchmark already has, so the cells added
since are listed in ``chipbench/scoped_metrics_added.json`` and appended
here, in memory, to the lists the tests of this directory loaded; the
``benchmark`` PR that lets ``chipbench.run`` carry the scopes (PERF.md
section 7) moves them into ``scoped_metrics.json`` and deletes both."""

import json
import os

import pytest

from chipbench import spec


with open(os.path.join(spec.HERE, "scoped_metrics_added.json")) as _handle:
    ADDED = json.load(_handle)["workloads"]


@pytest.fixture(autouse=True)
def scoped_metrics_list_the_cells_added_since(request):
    for metric in getattr(request.module, "SCOPED_METRICS", ()):
        for cell in ADDED.get(metric["name"], []):
            if cell not in metric["workloads"]:
                metric["workloads"].append(cell)
