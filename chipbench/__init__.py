"""chipbench: the chip benchmark of chainermn_tpu (see PERF.md).

One command, driven by data: ``python3 -m chipbench.run --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.  Cells, configurations, traffic,
families, references and per-layer metrics are files found by the names in
``BENCHMARK.json``; ``run.py`` names none of them.
"""
