"""Chip benchmark of the trainer: one command, driven by the data files here.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the repository root names the cells. A cell pairs a
configuration (``configs/<name>.json``, which names its reference and its
counts module) with a traffic mix (``traffic/<name>.json``); each metric is
read by ``metrics/<name>.py``.
"""
