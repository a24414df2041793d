"""On-chip benchmark of the repository: cells, drivers, references, metrics.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell once; see ``bench/run.py``.
"""
