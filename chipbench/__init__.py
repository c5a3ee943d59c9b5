"""Chip benchmark of the solver: one cell per run, driven by data files.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once; see
``chipbench/README.md``.
"""
