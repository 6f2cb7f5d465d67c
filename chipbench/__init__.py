"""Chip benchmark of the LIME serving path (see BENCHMARK.json, PERF.md).

`python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell once on the TPU it is started on. Everything
one cell, configuration, traffic mix, client kind or metric needs sits in
a file of its own under this directory, found by the name BENCHMARK.json
gives it (`manifest.py`).
"""
