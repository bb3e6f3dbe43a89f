"""On-chip benchmark of the coded-gradient runtime.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Configurations (``configs/``), traffic mixes (``traffic/``) and per-layer
metrics (``metrics/``) are files of their own, found by the names in the
manifest; configuration files name a ``kind`` (``kinds/``: data and work
counts), traffic files a ``driver`` (``drivers/<kind>.<driver>.py``: the
system under test and its comparison) and a straggler process
(``stragglers/``).
"""
