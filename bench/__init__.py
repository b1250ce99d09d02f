"""Chip benchmark of the PRISM serving engine.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints
one JSON line.  Everything a cell needs is found by name: its file in
``workloads/``, its model in ``configs/`` with the plain reference
that ``references/`` holds for it, its traffic mix in ``traffic/``
(read by ``generator.py``), and one reader per metric in ``metrics/``.
"""
